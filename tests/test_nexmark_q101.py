"""RisingWave's NEXmark q101 as upstream writes it (ISSUE 50): the text
of the benchmark's `nexmark-q101` configuration, read from the file,
through a SQL session, compared exactly (NULL rows included) with the
benchmark's plain reference (`benchmark/reference/nexmark_q101.py`) at
every barrier cut; the plan (a LEFT OUTER hash join over the auction
source and a device aggregate, no FALLBACK); the outer half's books
(`join_outer.*`, `join_degree_probe.*`, stage `join.pad`) and their
readers; recovery (`_recover_degrees`), a compaction that carries the
degrees, parallelism 4 on the CPU mesh and 2 over the cluster, the
served path; and the same view over tables whose rows update and
delete.

Epochs are a fixed number of chunks per reader, so nothing here waits
on a clock. Only the two sources' chunk sizes are rewritten, to cut the
stream small: the view's text is the file's.
"""

import asyncio
import collections
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEED = 5000000011
BARRIERS = 10

# (auction chunk rows, bid chunk rows) at one chunk a reader a barrier.
# in_step: the stream's 3:46; bids name the last hundred auctions, so
#   most auctions are NULL-padded in the epoch that brings them and
#   matched in that epoch or the next.
# auctions_ahead: the auction reader runs twice ahead, so an auction
#   waits for its first bid over several barriers: the padded row is in
#   the view at one cut and gone at a later one.
# bids_ahead: the bid reader runs four times ahead: the aggregate holds
#   groups for auctions that are not there yet, and an auction is
#   matched on arrival, never padded (after the first epoch).
CASES = {"in_step": (67, 1024), "auctions_ahead": (134, 1024),
         "bids_ahead": (16, 1024)}


def _run():
    for path in (BENCH, os.path.join(BENCH, "reference")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import run
    return run


def _bench_module(directory: str, name: str):
    return _run().load_module(directory, name)


def _config() -> dict:
    with open(os.path.join(BENCH, "configs", "nexmark-q101.json")) as f:
        return json.load(f)


def _ddl(config: dict, case: str, more: str = "") -> list:
    auction_rows, bid_rows = CASES[case]
    out = []
    for ddl in config["ddl"]:
        rows = auction_rows if "'auction'" in ddl else bid_rows
        ddl, n = re.subn(r"max\.chunk\.size=\d+",
                         f"max.chunk.size={rows}{more}", ddl)
        assert n == ("CREATE SOURCE" in ddl)
        out.append(ddl.format(seed=SEED))
    return out


def _generator(config: dict):
    return _bench_module("reference", "nexmark_gen").GeneratorConfig(
        seed=SEED, **config["generator"])


def _select(ddl: str) -> str:
    return ddl.split("\nAS\n", 1)[1]


def _bare(ex):
    return getattr(ex, "inner", ex)         # under the monitor


def _executors(fe, view: str, kind):
    actor = fe.actors[fe.catalog.mvs[view].actor_id]
    return [_bare(ex) for _p, ex in _run().walk_executors(actor.consumer)
            if isinstance(_bare(ex), kind)]


def _join(fe, view: str):
    from risingwave_tpu.stream.executors.hash_join import HashJoinExecutor
    found = _executors(fe, view, HashJoinExecutor)
    assert len(found) == 1, found
    return found[0]


def _outer_books() -> collections.Counter:
    """The outer half's counters as they stand, summed over joins."""
    from risingwave_tpu.utils.metrics import STREAMING
    out = collections.Counter()
    for labels, v in STREAMING.join_outer_rows.series():
        out[labels["event"]] += v
    return out


def _history(rows) -> dict:
    out = {}
    for _seq, epoch, ts, interval_s, name, value, _dom in rows:
        out.setdefault(epoch, {"ts": ts, "interval_s": interval_s})[
            name] = value
    # the first row after HISTORY.clear() takes the registry's
    # process-wide counter totals, other tests' among them, as its delta
    del out[min(out)]
    return out


async def _explain(fe, select: str) -> str:
    text = "\n".join(r[0] for r in await fe.execute("EXPLAIN " + select))
    return text.split("-- compiled kernel costs")[0]


def _degrees_by_pk(side) -> dict:
    """{state-table pk: match degree} of a tracked side's live rows."""
    pks = list(side.pk_to_ref)
    if not pks:
        return {}
    import numpy as np
    refs = np.fromiter(side.pk_to_ref.values(), dtype=np.int64,
                       count=len(pks))
    degs = side.kernel.read_degrees(refs) if side.dev_degrees \
        else side.degrees[refs]
    return dict(zip(pks, degs.tolist()))


def _compact_at_the_next_barrier(join, done: list) -> None:
    """Both sides compact where the executor compacts, after the next
    barrier's sweep (the epoch's buffers are empty there: a side's
    staged rows carry refs), once; `done` gets the tracked side's
    degrees by pk before and after."""
    for i, side in enumerate(join.sides):
        real = side.compact

        def compact(side=side, real=real, tracked=i == 0):
            before = _degrees_by_pk(side) if tracked else None
            dead = len(side.free)
            real()
            del side.compact, side.COMPACT_MIN_REFS, \
                side.COMPACT_DEAD_RATIO
            done.append({"tracked": tracked, "before": before,
                         "after": _degrees_by_pk(side) if tracked
                         else None, "dead": dead,
                         "next_ref": side.next_ref,
                         "live": len(side.pk_to_ref)})

        side.compact = compact
        side.COMPACT_MIN_REFS = 0        # shadow the class's thresholds
        side.COMPACT_DEAD_RATIO = 0.0


async def _checkpoint(fe, view: str) -> dict:
    run = _run()
    by_id = collections.Counter()
    for table_id, mv, _vnode, n, _bytes in await fe.execute(
            "SELECT * FROM rw_state_topology"):
        if mv == view:
            by_id[table_id] += n
    return {
        "view": collections.Counter(
            tuple(r) for r in await fe.execute(f"SELECT * FROM {view}")),
        "readers": run.checkpointed_rows(run.source_readers(fe, view)),
        "by_id": dict(by_id),
        "books": _outer_books(),
    }


async def _drive(config: dict, case: str, store=None, barriers=BARRIERS,
                 recover=False, parallelism=None, compact_after=None
                 ) -> dict:
    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.state.topology import TOPOLOGY
    from risingwave_tpu.utils.metrics import HISTORY

    HISTORY.clear()
    TOPOLOGY.clear()            # process-wide books of state rows
    more = {} if parallelism is None else {"parallelism": parallelism}
    fe = Frontend(store, rate_limit=1, min_chunks=1, **more)
    try:
        books0 = _outer_books()
        if recover:
            await fe.recover()
        else:
            for ddl in _ddl(config, case):
                await fe.execute(ddl)
        view = config["view"]
        checkpoints, compactions = [], []
        for i in range(barriers):
            if i == compact_after:
                _compact_at_the_next_barrier(_join(fe, view), compactions)
            await fe.step()
            checkpoints.append(await _checkpoint(fe, view))
        join = _join(fe, view)
        return {
            "checkpoints": checkpoints,
            "books0": books0,
            "compactions": compactions,
            "degrees": _degrees_by_pk(join.sides[0]),
            "join_type": join.join_type.value,
            "tracked": [s.track_degrees for s in join.sides],
            "join_label": join._books_table,
            "history": _history(
                await fe.execute("SELECT * FROM rw_metrics_history")),
            "rewrites": await fe.execute(
                "SELECT job, rule, fired, detail FROM rw_plan_rewrites"),
            "explain": await _explain(fe, _select(config["ddl"][-1])),
        }
    finally:
        await fe.close()


@pytest.fixture(scope="module")
def q101():
    config = _config()
    _bench_module("reference", "nexmark_gen")
    return {"config": config,
            **{case: asyncio.run(_drive(config, case)) for case in CASES}}


def _nulls(view: collections.Counter) -> int:
    return sum(n for row, n in view.items() if row[2] is None)


def _window_sum(run_: dict, prefix: str, suffix: str = "") -> float:
    return sum(v for h in run_["history"].values() for k, v in h.items()
               if k.startswith(prefix) and k.endswith(suffix))


# -- the view against the reference -----------------------------------------

@pytest.mark.parametrize("case", list(CASES))
def test_view_equals_the_reference_at_every_checkpoint(q101, case):
    config = q101["config"]
    gen = _generator(config)
    ref = _bench_module("reference", config["reference"])
    for cp in q101[case]["checkpoints"]:
        readers = [dict(r) for r in cp["readers"]]
        want = ref.reference(readers, gen)
        assert cp["view"] == want            # NULL rows included
        # every auction of the prefix has its one row
        assert sum(want.values()) == len(want) == readers[0]["rows"]
        assert max(cp["by_id"].values()) == ref.resident_rows(readers, gen)
    assert not [r for r in q101[case]["rewrites"]
                if str(r[3]).startswith("FALLBACK")]


def test_an_auction_is_padded_at_one_cut_and_matched_at_a_later_one(q101):
    cps = q101["auctions_ahead"]["checkpoints"]
    assert all(_nulls(cp["view"]) > 50 for cp in cps[2:])
    moved = 0
    for a, b in zip(cps, cps[1:]):
        padded = {r[0] for r in a["view"] if r[2] is None}
        moved += sum(1 for r in b["view"]
                     if r[0] in padded and r[2] is not None)
    assert moved > 200
    # in step with the bids few auctions stay padded across a cut, and
    # with the bids ahead none is ever padded
    assert 0 < _nulls(q101["in_step"]["checkpoints"][-1]["view"]) < 40
    assert all(_nulls(cp["view"]) == 0
               for cp in q101["bids_ahead"]["checkpoints"])


def test_the_highest_bid_of_an_auction_rises_across_barriers(q101):
    """So the aggregate sends U-/U+ into the join's tracked side, not
    inserts alone."""
    cps = q101["in_step"]["checkpoints"]
    rose = 0
    for a, b in zip(cps, cps[1:]):
        was = {r[0]: r[2] for r in a["view"] if r[2] is not None}
        rose += sum(1 for r in b["view"]
                    if r[0] in was and r[2] > was[r[0]])
    assert rose > 20
    label = q101["in_step"]["join_label"]
    assert _window_sum(q101["in_step"], f"join_input_rows.{label}.right.",
                       "update_delete") > 20


def test_with_the_bids_ahead_the_aggregate_s_table_is_the_largest(q101):
    """`resident_rows`' second arm: groups for auctions not there yet."""
    config = q101["config"]
    ref = _bench_module("reference", config["reference"])
    cp = q101["bids_ahead"]["checkpoints"][-1]
    readers = [dict(r) for r in cp["readers"]]
    kept = ref.resident_rows(readers, _generator(config))
    assert kept > readers[0]["rows"]
    assert kept == max(cp["by_id"].values())


def test_the_reference_asserts_that_ids_are_unique():
    _run()
    import nexmark_gen
    import nexmark_q101
    cfg = nexmark_gen.GeneratorConfig(seed=SEED)
    real = nexmark_gen.GENERATORS["auction"]

    def folded(k, c):
        out = dict(real(k, c))
        out["id"] = out["id"] // 2 * 2
        return out

    nexmark_gen.GENERATORS["auction"] = folded
    try:
        with pytest.raises(AssertionError, match="not unique"):
            nexmark_q101.highest_bids(50, 500, cfg)
    finally:
        nexmark_gen.GENERATORS["auction"] = real


# -- the plan ---------------------------------------------------------------

def test_the_text_is_upstreams_and_plans_a_left_outer_join(q101):
    config, run_ = q101["config"], q101["in_step"]
    text = config["ddl"][-1]
    assert text == (
        "CREATE MATERIALIZED VIEW nexmark_q101\nAS\nSELECT\n"
        "    a.id AS auction_id,\n"
        "    a.item_name AS auction_item_name,\n"
        "    b.max_price AS current_highest_bid\n"
        "FROM auction a\nLEFT OUTER JOIN (\n    SELECT\n"
        "        b1.auction,\n        MAX(b1.price) max_price\n"
        "    FROM bid b1\n    GROUP BY b1.auction\n"
        ") b ON a.id = b.auction")
    assert config["reduced"] == []
    assert len(config["sets"]) == 2
    assert all(s.startswith(("SET streaming_rate_limit",
                             "SET streaming_min_chunks"))
               for s in config["sets"])
    post = run_["explain"].split("-- rewritten plan")[1]
    assert re.search(
        r"MaterializeExecutor.*\n(\s+\S.*\n)?\s+HashJoinExecutor\("
        r"left_outer", post)
    assert "HashAggExecutor" in post
    assert "FALLBACK" not in run_["explain"]
    assert run_["join_type"] == "left_outer"
    assert run_["tracked"] == [True, False]


def test_the_aggregate_and_both_join_sides_are_device_kernels():
    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.ops.hash_agg import GroupedAggKernel
    from risingwave_tpu.ops.hash_join import JoinSideKernel
    from risingwave_tpu.stream.executors.hash_agg import HashAggExecutor
    config = _config()

    async def drive():
        fe = Frontend(rate_limit=1, min_chunks=1)
        try:
            for ddl in _ddl(config, "in_step"):
                await fe.execute(ddl)
            await fe.step(2)
            view = config["view"]
            join = _join(fe, view)
            agg, = _executors(fe, view, HashAggExecutor)
            return ([type(s.kernel) for s in join.sides],
                    [s.dev_degrees for s in join.sides],
                    [k for k, _occ, _cap in _run().device_tables(fe)],
                    type(getattr(agg, "kernel", None)
                         or getattr(agg, "_kernel", None)))
        finally:
            await fe.close()

    sides, dev_degrees, tables, agg_kernel = asyncio.run(drive())
    assert sides == [JoinSideKernel, JoinSideKernel]
    assert dev_degrees == [True, True]       # the kernel's `deg` array
    assert agg_kernel is GroupedAggKernel
    assert sorted(tables) == ["GroupedAggKernel", "JoinSideKernel",
                              "JoinSideKernel"]


# -- the outer half's books --------------------------------------------------

@pytest.mark.parametrize("case", list(CASES))
def test_padded_inserts_less_deletes_are_the_view_s_null_rows(q101, case):
    run_ = q101[case]
    for cp in run_["checkpoints"]:
        books = cp["books"] - run_["books0"]
        assert books["padded_insert"] - books["padded_delete"] == \
            _nulls(cp["view"])
        # the right side holds one row a key and never deletes one: a
        # stored auction's degree rises once and never falls
        assert books["flip_off"] == 0
        assert books["flip_on"] <= books["padded_delete"]
    last = run_["checkpoints"][-1]["books"] - run_["books0"]
    if case == "bids_ahead":
        # the first epoch's auctions come before the aggregate's first
        # flush; every later one is matched on arrival
        assert last["padded_insert"] == last["padded_delete"] == \
            last["flip_on"] == CASES[case][0]
    else:
        assert last["flip_on"] == last["padded_delete"] > 300
    # and the device's degree array agrees with the view, row for row
    matched = {r[0] for r in run_["checkpoints"][-1]["view"]
               if r[2] is not None}
    # (the readers run ahead: a row ingested for the next epoch has its
    # ref and no degree yet)
    assert len(run_["degrees"]) >= sum(
        run_["checkpoints"][-1]["view"].values())
    assert set(run_["degrees"].values()) <= {0, 1}
    assert sum(run_["degrees"].values()) == len(matched)


def test_the_books_name_the_stages_and_the_readers_read_them(q101):
    run_ = q101["auctions_ahead"]
    label = run_["join_label"]
    names = {k for h in run_["history"].values() for k in h}
    for stage in ("join.pad", "join.degrees", "join.pairs"):
        assert "stage.host_emit." + stage in names
        assert _window_sum(run_, "stage.host_emit." + stage) > 0
    for event in ("padded_insert", "padded_delete", "flip_on"):
        assert f"join_outer.{label}.{event}" in names
    assert f"join_outer.{label}.flip_off" not in names
    # no probe outgrew its buffer (a series another test's join left
    # in the registry reads 0)
    assert _window_sum(run_, "join_degree_probe.") == 0
    record = {"history": run_["history"]}
    share = _bench_module(
        "layer_metrics", "outer_join_host_share").read(record)
    assert 0 < share < 100
    per_row = _bench_module(
        "layer_metrics", "outer_padded_rows_per_left_row").read(record)
    assert 1.0 < per_row <= 2.0
    inserted = _window_sum(run_, f"join_outer.{label}.padded_insert")
    deleted = _window_sum(run_, f"join_outer.{label}.padded_delete")
    left = _window_sum(run_, f"join_input_rows.{label}.left.")
    assert per_row == (inserted + deleted) / left


def test_the_device_reader_sums_the_join_s_two_epoch_programs():
    read = _bench_module("layer_metrics", "join_device_ms_per_barrier").read
    ops = [["jit_hash_join_epoch_apply_aa_1_", 0.10],
           ["jit_hash_agg_apply_fused_2_", 0.50],
           ["jit_hash_join_epoch_probe_aa_3_", 0.03],
           ["jit_hash_join_epoch_probe_bb_4_", 0.02]]
    trace = {"device_ops": ops, "epochs_in_span": 5}
    assert read({"trace": trace}) == pytest.approx(30.0)
    assert read({"trace": {}}) is None       # a rehearsal: no plane
    assert read({"trace": None}) is None
    assert read({"trace": {"device_ops": ops[1:2],
                           "epochs_in_span": 5}}) is None


def test_an_inner_join_writes_none_of_the_names():
    """q4's view: the same two sources through an INNER join."""
    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.utils.metrics import HISTORY, STREAMING
    with open(os.path.join(BENCH, "configs", "nexmark-q4.json")) as f:
        config = json.load(f)

    async def drive():
        HISTORY.clear()
        fe = Frontend(rate_limit=1, min_chunks=1)
        try:
            before = (_outer_books(),
                      STREAMING.join_degree_redispatches.series())
            for ddl in _ddl(config, "in_step"):
                await fe.execute(ddl)
            await fe.step(4)
            after = (_outer_books(),
                     STREAMING.join_degree_redispatches.series())
            return before, after, _history(
                await fe.execute("SELECT * FROM rw_metrics_history"))
        finally:
            await fe.close()

    before, after, history = asyncio.run(drive())
    assert before == after
    names = {k for h in history.values() for k in h}
    assert "stage.host_emit.join.pairs" in names
    assert "stage.host_emit.join.pad" not in names
    assert "stage.host_emit.join.degrees" not in names
    # a series another test's outer join left in the registry reads 0
    assert not any(h[k] for h in history.values() for k in h
                   if k.startswith(("join_outer.", "join_degree_probe.")))
    record = {"history": history}
    for name in ("outer_join_host_share", "outer_padded_rows_per_left_row"):
        value = _bench_module("layer_metrics", name).read(record)
        assert not value                     # None, or 0 of old series


def test_readers_of_a_program_without_the_books_read_nothing():
    record = {"history": {1: {"ts": 1.0, "interval_s": 0.5,
                              "stage.host_emit.join.pairs": 0.1,
                              "stage.host_emit.join.degrees": 0.1,
                              "join_input_rows.t8.left.insert": 9.0},
                          2: {"ts": 1.5, "interval_s": 0.5}},
              "trace": None}
    for name in ("outer_join_host_share", "outer_padded_rows_per_left_row",
                 "join_device_ms_per_barrier"):
        assert _bench_module("layer_metrics", name).read(record) is None


def test_a_tracked_probe_that_outgrows_its_buffer_counts_its_reruns():
    """A probe buffer of 64 rows under epochs of hundreds of
    candidates: the tracked probe doubles it, runs again, files each
    rerun, and the view stays exact."""
    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.utils.metrics import STREAMING
    config = _config()
    ref = _bench_module("reference", config["reference"])

    def reruns():
        return sum(v for _l, v in
                   STREAMING.join_degree_redispatches.series())

    async def drive():
        fe = Frontend(rate_limit=1, min_chunks=1)
        try:
            for ddl in _ddl(config, "in_step"):
                await fe.execute(ddl)
            view = config["view"]
            for side in _join(fe, view).sides:
                side.kernel._probe_cap = 64
            before = reruns()
            await fe.step(3)
            caps = [s.kernel._probe_cap for s in _join(fe, view).sides]
            return (reruns() - before, caps,
                    await _checkpoint(fe, view))
        finally:
            await fe.close()

    counted, caps, cp = asyncio.run(drive())
    assert counted >= 2 and max(caps) > 64
    assert cp["view"] == ref.reference(
        [dict(r) for r in cp["readers"]], _generator(config))


# -- recovery, a compaction, parallelism, the served path --------------------

def test_recovery_recomputes_the_degrees_and_the_stream_goes_on(q101):
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import MemObjectStore
    config = q101["config"]
    gen = _generator(config)
    ref = _bench_module("reference", config["reference"])
    obj = MemObjectStore()
    head = asyncio.run(_drive(config, "auctions_ahead", HummockLite(obj),
                              barriers=4))
    tail = asyncio.run(_drive(config, "auctions_ahead", HummockLite(obj),
                              barriers=5, recover=True))
    assert _nulls(head["checkpoints"][-1]["view"]) > 50
    padded = {r[0] for r in head["checkpoints"][-1]["view"]
              if r[2] is None}
    for cp in tail["checkpoints"]:
        assert cp["view"] == ref.reference(
            [dict(r) for r in cp["readers"]], gen)
    # rows that were padded when the process went are matched by the
    # recovered one: their recomputed degree was 0 and crossed it
    last = tail["checkpoints"][-1]["view"]
    assert sum(1 for r in last
               if r[0] in padded and r[2] is not None) > 50
    assert (tail["checkpoints"][-1]["books"] - tail["books0"])[
        "flip_on"] > 50
    assert tail["join_type"] == "left_outer"
    matched = {r[0] for r in last if r[2] is not None}
    assert sum(tail["degrees"].values()) == len(matched)


def test_a_compaction_between_two_cuts_keeps_the_degrees(q101):
    config = q101["config"]
    run_ = asyncio.run(_drive(config, "auctions_ahead", barriers=8,
                              compact_after=3))
    left, = [c for c in run_["compactions"] if c["tracked"]]
    right, = [c for c in run_["compactions"] if not c["tracked"]]
    assert left["before"] == left["after"]
    assert set(left["before"].values()) == {0, 1}
    assert sum(left["before"].values()) > 100
    # dense again; the aggregate's U-/U+ had left dead refs behind
    assert left["next_ref"] == left["live"] == len(left["after"])
    assert right["dead"] > 0 and right["next_ref"] == right["live"]
    whole = q101["auctions_ahead"]
    for got, want in zip(run_["checkpoints"], whole["checkpoints"]):
        assert got["readers"] == want["readers"]
        assert got["view"] == want["view"]
    assert sum(run_["degrees"].values()) == sum(
        1 for r in run_["checkpoints"][-1]["view"] if r[2] is not None)


def test_at_parallelism_4_the_view_is_exact():
    """On the CPU mesh both join sides and the aggregate are sharded
    and the degrees live in the executor's host arrays."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    config = _config()
    ref = _bench_module("reference", config["reference"])
    run_ = asyncio.run(_drive(config, "auctions_ahead", barriers=6,
                              parallelism=4))
    for cp in run_["checkpoints"][-3:]:
        assert cp["view"] == ref.reference(
            [dict(r) for r in cp["readers"]], _generator(config))
    last = run_["checkpoints"][-1]
    assert _nulls(last["view"]) > 50
    books = last["books"] - run_["books0"]
    assert books["padded_insert"] - books["padded_delete"] == \
        _nulls(last["view"])


def test_over_the_cluster_at_parallelism_2_the_view_is_the_single_process_s(
        tmp_path):
    from risingwave_tpu.cluster.session import DistFrontend
    from risingwave_tpu.frontend.session import Frontend
    config = _config()
    ddl = _ddl(config, "in_step", more=", nexmark.event.num=6000")
    view = config["view"]

    async def single():
        fe = Frontend(min_chunks=8)
        try:
            for stmt in ddl:
                await fe.execute(stmt)
            await fe.step(30)
            return collections.Counter(
                tuple(r) for r in await fe.execute(f"SELECT * FROM {view}"))
        finally:
            await fe.close()

    async def cluster():
        fe = DistFrontend(str(tmp_path), n_workers=2, parallelism=2)
        await fe.start()
        try:
            for stmt in ddl:
                await fe.execute(stmt)
            await fe.step(30)
            job = fe.cluster.jobs[view]
            fi, node = next(
                (fi, n) for fi, f in enumerate(job.graph.fragments)
                for n in f.nodes if n["op"] == "hash_join")
            assert node["join_type"] == "left_outer"
            assert {s for _a, s in job.placements[fi]} == {0, 1}
            return collections.Counter(
                tuple(r) for r in await fe.execute(f"SELECT * FROM {view}"))
        finally:
            await fe.close()

    got, want = asyncio.run(cluster()), asyncio.run(single())
    assert got == want
    # the bounded stream is read to its end: 360 auctions
    assert sum(got.values()) == 360
    ref = _bench_module("reference", config["reference"])
    assert got == ref.reference(
        [{"table": "auction", "side": "left", "rows": 360},
         {"table": "bid", "side": "right", "rows": 5520}],
        _generator(config))


def test_through_serving_and_pgwire_with_the_session_s_defaults(tmp_path):
    """The DDL as the file has it, over pgwire to the served process, no
    SET: NULL comes back as None and the view equals the reference."""
    run = _run()
    from pgclient import PgClient
    from risingwave_tpu import __main__ as main
    config = _config()
    ref = _bench_module("reference", config["reference"])

    async def drive():
        async with main.serving(str(tmp_path), port=0) as (fe, srv, hb), \
                await PgClient.connect(srv.port) as pg:
            heartbeat = run.Heartbeat(fe, hb)
            await heartbeat.pause()          # barriers by FLUSH only
            for ddl in config["ddl"]:
                await pg.query(ddl.format(seed=SEED))
            for _ in range(3):
                await pg.query("FLUSH")
            got = collections.Counter(
                await pg.query(f"SELECT * FROM {config['view']}"))
            readers = run.checkpointed_rows(
                run.source_readers(fe, config["view"]))
            rewrites = await pg.query(
                "SELECT job, rule, fired, detail FROM rw_plan_rewrites")
        await fe.close()
        return got, readers, rewrites

    got, readers, rewrites = asyncio.run(drive())
    gen = _bench_module("reference", "nexmark_gen").GeneratorConfig(
        seed=SEED, **config["generator"])
    assert readers[0]["rows"] > 0
    assert got == ref.reference([dict(r) for r in readers], gen)
    assert _nulls(got) > 0
    assert not [r for r in rewrites if str(r[3]).startswith("FALLBACK")]


# -- a left side that deletes and updates ------------------------------------

def _outer(auctions: dict, bids: dict) -> collections.Counter:
    best = {}
    for auction, price in bids.values():
        best[auction] = max(best.get(auction, price), price)
    return collections.Counter(
        (a, name, best.get(a)) for a, name in auctions.items())


def test_q101_over_tables_that_update_and_delete():
    """The configuration's view over tables instead of the sources: a
    padded auction that is deleted takes its padded row out, one that
    is renamed retracts the old padded row for the new; an auction
    whose last bid goes gets its padded row back (the degree falls to
    zero: flip_off); a deleted matched auction takes its pair out."""
    from risingwave_tpu.frontend.session import Frontend
    view = _config()["ddl"][-1]
    assert view.count("FROM auction a") == view.count("FROM bid b1") == 1
    view = view.replace("FROM auction a", "FROM auc a") \
        .replace("FROM bid b1", "FROM offer b1")

    async def drive():
        fe = Frontend()
        seen = []
        auctions, bids = {}, {}

        async def cut(what):
            await fe.execute("FLUSH")
            got = collections.Counter(
                tuple(r) for r in await fe.execute(
                    "SELECT * FROM nexmark_q101"))
            assert got == _outer(auctions, bids), what
            seen.append((what, _nulls(got), sum(got.values())))

        try:
            await fe.execute(
                "CREATE TABLE auc (id BIGINT PRIMARY KEY, "
                "item_name VARCHAR)")
            await fe.execute(
                "CREATE TABLE offer (auction BIGINT, price BIGINT, "
                "n BIGINT PRIMARY KEY)")
            await fe.execute(view)
            books0 = _outer_books()
            join = _join(fe, "nexmark_q101")
            for a in range(1, 7):
                auctions[a] = f"item{a}"
            await fe.execute("INSERT INTO auc VALUES " + ", ".join(
                f"({a}, '{name}')" for a, name in auctions.items()))
            await cut("six auctions, no bid")
            for n, (a, price) in enumerate(
                    [(1, 100), (1, 300), (2, 50), (3, 70), (7, 10)]):
                bids[n] = (a, price)
            await fe.execute("INSERT INTO offer VALUES " + ", ".join(
                f"({a}, {p}, {n})" for n, (a, p) in bids.items()))
            await cut("bids on 1, 2, 3 and on 7, which is not there")
            del auctions[4]                  # a padded auction goes
            await fe.execute("DELETE FROM auc WHERE id = 4")
            await cut("a padded auction deleted")
            auctions[5] = "renamed"          # a padded auction changes
            await fe.execute(
                "UPDATE auc SET item_name = 'renamed' WHERE id = 5")
            await cut("a padded auction renamed")
            auctions[1] = "first"            # a matched auction changes
            await fe.execute(
                "UPDATE auc SET item_name = 'first' WHERE id = 1")
            await cut("a matched auction renamed")
            del bids[2]                      # auction 2 loses its only bid
            await fe.execute("DELETE FROM offer WHERE n = 2")
            await cut("the only bid of an auction deleted")
            del bids[1]                      # auction 1's highest bid goes
            await fe.execute("DELETE FROM offer WHERE n = 1")
            await cut("the highest bid of an auction deleted")
            del auctions[3]                  # a matched auction goes
            await fe.execute("DELETE FROM auc WHERE id = 3")
            await cut("a matched auction deleted")
            auctions[7] = "late"             # its bid was waiting
            await fe.execute("INSERT INTO auc VALUES (7, 'late')")
            await cut("an auction arrives after its bid")
            bids[9] = (2, 60)                # auction 2 is bid on again
            await fe.execute("INSERT INTO offer VALUES (2, 60, 9)")
            await cut("a padded auction matched again")
            return (seen, _outer_books() - books0,
                    join.join_type.value,
                    sorted(_degrees_by_pk(join.sides[0]).values()))
        finally:
            await fe.close()

    seen, books, join_type, degrees = asyncio.run(drive())
    assert join_type == "left_outer"
    assert [s[1:] for s in seen] == [
        (6, 6), (3, 6), (2, 5), (2, 5), (2, 5), (3, 5), (3, 5), (3, 4),
        (3, 5), (2, 5)]
    assert books["flip_off"] == 1            # auction 2, once
    assert books["flip_on"] == 4             # 1, 2, 3, then 2 again
    assert books["padded_insert"] - books["padded_delete"] == 2
    assert degrees == [0, 0, 1, 1, 1]        # 5, 6 | 1, 2, 7
