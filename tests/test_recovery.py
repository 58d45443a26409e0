"""Whole-system recovery + chaos: kill the process mid-stream, reboot
from the object store, finish, and match an uninterrupted oracle run.

Mirrors the reference's deterministic-simulation stance (SURVEY §4:
madsim Cluster::kill_node + nexmark_recovery.rs) in one process: a
"kill" abandons the session without close() — unsynced shared-buffer
state and unpersisted offsets are genuinely lost — and a reboot
replays the DDL log and resumes from the committed epoch.
"""

import asyncio

from risingwave_tpu.frontend import Frontend
from risingwave_tpu.storage.hummock import HummockLite
from risingwave_tpu.storage.object_store import MemObjectStore

DDL = ("CREATE SOURCE bid WITH (connector='nexmark', "
       "nexmark.table.type='bid', nexmark.event.num=12000, "
       "nexmark.max.chunk.size=512, "
       "nexmark.min.event.gap.in.ns=100000000); "
       "CREATE MATERIALIZED VIEW q7 AS SELECT window_start, "
       "MAX(price) AS max_price, COUNT(*) AS cnt "
       "FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) "
       "GROUP BY window_start")

QUERY = "SELECT window_start, max_price, cnt FROM q7 ORDER BY window_start"

N_BIDS = 12000 * 46 // 50


def _exhausted(fe: Frontend) -> bool:
    return all(r.offset >= N_BIDS
               for rs in fe.readers.values() for r in rs.values())


async def _drive_until_done(fe: Frontend, max_steps: int = 200) -> None:
    for _ in range(max_steps):
        if _exhausted(fe):
            break
        await fe.step(1)
    else:
        raise RuntimeError("sources never exhausted")
    await fe.step(1)          # final checkpoint past the last chunk


def _oracle():
    async def run():
        fe = Frontend(HummockLite(MemObjectStore()), min_chunks=4)
        await fe.execute(DDL)
        await _drive_until_done(fe)
        rows = await fe.execute(QUERY)
        await fe.close()
        return rows

    return asyncio.run(run())


def test_sql_session_kill_restart_resumes():
    obj = MemObjectStore()

    async def phase1():
        fe = Frontend(HummockLite(obj), min_chunks=4)
        await fe.execute(DDL)
        await fe.step(5)
        # KILL: no close(), no stop barrier — tasks die with the loop;
        # anything not checkpointed is lost
        return sum(r.offset for rs in fe.readers.values()
                   for r in rs.values())

    async def phase2():
        fe = Frontend(HummockLite(obj), min_chunks=4)
        replayed = await fe.recover()
        assert replayed == 2
        # offsets resumed from committed state, not from zero
        resumed = sum(r.offset for rs in fe.readers.values()
                      for r in rs.values())
        await _drive_until_done(fe)
        rows = await fe.execute(QUERY)
        names = await fe.execute("SHOW MATERIALIZED VIEWS")
        await fe.close()
        return resumed, rows, names

    offset1 = asyncio.run(phase1())
    assert offset1 > 0
    resumed, rows, names = asyncio.run(phase2())
    assert resumed > 0                    # did not restart from scratch
    assert names == [("q7",)]
    assert rows == _oracle()


def test_kill_with_uploads_in_flight_recovers_and_matches_oracle():
    """Pipelined (run-mode) barrier driving over a SLOW object store:
    kill the session while checkpoint uploads are still in flight —
    recovery resumes from the last FULLY committed epoch (the async
    pipeline's ordered-commit invariant) and the finished result
    equals the uninterrupted oracle."""
    from risingwave_tpu.storage.object_store import DelayedObjectStore

    obj = MemObjectStore()

    async def phase1():
        fe = Frontend(HummockLite(DelayedObjectStore(obj, delay_s=0.2)),
                      min_chunks=4)
        await fe.execute(DDL)
        loop = fe.loop
        # pipelined driving: no uploader drain between
        # barriers, so uploads pile up behind the slow store
        for _ in range(6):
            while loop.in_flight_count < 2:
                await loop.inject(force_checkpoint=True)
            await loop.collect_next()
        assert loop.uploading_count > 0    # in flight at the kill
        # KILL: no close(), no drain — the in-flight epochs' commits
        # never land; only fully committed epochs may survive

    asyncio.run(phase1())

    async def phase2():
        fe = Frontend(HummockLite(obj), min_chunks=4)
        assert await fe.recover() == 2
        # recover() already vacuumed the dead generation's residue
        # (uploaded-but-uncommitted SSTs + its deferred-compaction
        # garbage): nothing unreferenced is left behind
        assert fe.store.vacuum_orphans() == 0
        await _drive_until_done(fe)
        rows = await fe.execute(QUERY)
        await fe.close()
        return rows

    assert asyncio.run(phase2()) == _oracle()


def test_chaos_repeated_kills_match_oracle():
    """Three generations, each killed after a few epochs; the final
    result must still equal the uninterrupted run (nexmark_recovery.rs
    analog)."""
    obj = MemObjectStore()

    async def gen(steps):
        fe = Frontend(HummockLite(obj), min_chunks=4)
        replayed = await fe.recover()
        if replayed == 0:
            await fe.execute(DDL)
        for _ in range(steps):
            if _exhausted(fe):
                break
            await fe.step(1)
        return fe

    async def run_all():
        for steps in (3, 4, 5):
            await gen(steps)              # killed: no close, no stop
        fe = await gen(10**6)
        await _drive_until_done(fe)
        rows = await fe.execute(QUERY)
        await fe.close()
        return rows

    assert asyncio.run(run_all()) == _oracle()


def test_ddl_after_recovery_preserves_log():
    """DDL executed after a recovery must extend — not overwrite — the
    persisted DDL log, or the next recovery loses the catalog."""
    obj = MemObjectStore()

    async def gen1():
        fe = Frontend(HummockLite(obj), min_chunks=4)
        await fe.execute(DDL)                       # source + q7

    async def gen2():
        fe = Frontend(HummockLite(obj), min_chunks=4)
        assert await fe.recover() == 2
        await fe.execute("CREATE MATERIALIZED VIEW extra AS "
                         "SELECT auction FROM bid")
        await fe.step(1)

    async def gen3():
        fe = Frontend(HummockLite(obj), min_chunks=4)
        assert await fe.recover() == 3
        names = await fe.execute("SHOW MATERIALIZED VIEWS")
        await fe.close()
        return names

    asyncio.run(gen1())
    asyncio.run(gen2())
    assert asyncio.run(gen3()) == [("extra",), ("q7",)]


def test_backup_restore_fresh_cluster():
    """meta/backup: a consistent snapshot (DDL log + hummock version +
    SST closure) restores into a FRESH root; a new session recovers
    the catalog, state, and source offsets and keeps streaming
    (backup_restore/ parity)."""
    from risingwave_tpu.meta.backup import (
        create_backup, delete_backup, list_backups, restore_backup,
    )
    from risingwave_tpu.storage.object_store import MemObjectStore

    obj = MemObjectStore()

    async def phase1():
        f = Frontend(HummockLite(obj), rate_limit=2, min_chunks=2)
        await f.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', nexmark.event.num=4000, "
            "nexmark.max.chunk.size=256)")
        await f.execute(
            "CREATE MATERIALIZED VIEW v AS SELECT auction, count(*) "
            "AS c FROM bid GROUP BY auction")
        for _ in range(4):
            await f.step()
        rows = await f.execute("SELECT * FROM v")
        await f.close()
        return rows

    asyncio.run(phase1())
    bid = create_backup(obj)
    assert list_backups(obj) == [bid]

    # what the source-of-truth says AS OF the backup (recover, no
    # steps), then keep running PAST the backup point
    async def as_of_then_go():
        f = Frontend(HummockLite(obj), rate_limit=2, min_chunks=2)
        await f.recover()
        rows = await f.execute("SELECT * FROM v")
        for _ in range(4):
            await f.step()
        await f.close()
        return rows

    mid_rows = asyncio.run(as_of_then_go())

    # restore the backup into a fresh root: state is AS OF the backup
    fresh = MemObjectStore()
    restore_backup(obj, bid, fresh)

    async def phase2():
        f = Frontend(HummockLite(fresh), rate_limit=2, min_chunks=2)
        n = await f.recover()
        assert n >= 2
        rows = await f.execute("SELECT * FROM v")
        # and the restored cluster streams on from the backed-up offset
        for _ in range(20):
            await f.step()
        final = await f.execute("SELECT * FROM v")
        await f.close()
        return rows, final

    restored, final = asyncio.run(phase2())
    assert sorted(restored) == sorted(mid_rows)    # exact as-of state
    n_bids = 4000 * 46 // 50
    assert sum(c for _a, c in final) == n_bids     # streams to the end

    # refuse restoring over a non-empty root
    import pytest
    with pytest.raises(ValueError, match="empty"):
        restore_backup(obj, bid, obj)
    assert delete_backup(obj, bid) > 0
    assert list_backups(obj) == []
