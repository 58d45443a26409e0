"""NEXmark q8 at parallelism 4, as the benchmark's `nexmark-q8-mesh4`
configuration writes it (ISSUE 27): the configuration's own DDL through
a SQL session on a 4-device mesh, compared as a multiset with the
benchmark's plain reference (`benchmark/reference/nexmark_q8.py`) and
with the same view at parallelism 1; and the books the mesh path keeps
on the way: the `exchange_route` ledger phase, the exchange's counters
in `rw_metrics_history`, per-shard occupancy in `rw_mesh_tables`.

One run of the two sessions feeds every test of this file (a module
fixture). Epochs are a fixed number of chunks per reader, so nothing
here waits on a clock. The stream is cut small, but it crosses per-shard
growth rungs (the tables start at 4,096 slots a shard) and, where the
epoch doubles from 2 chunks to the configuration's 4, a change of
routing bucket.
"""

import asyncio
import collections
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEED = 2700000027
SMALL_EPOCHS, FULL_EPOCHS = 3, 2      # barriers at 2 chunks, then at 4


def _bench_module(directory: str, name: str):
    """A module of `benchmark/`, loaded the way `run.py` loads it."""
    for path in (BENCH, os.path.join(BENCH, "reference")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import run
    return run.load_module(directory, name)


def _history(rows) -> dict:
    out = {}
    for _seq, epoch, _ts, interval_s, name, value, _dom in rows:
        out.setdefault(epoch, {"interval_s": interval_s})[name] = value
    return out


async def _drive(config: dict, parallelism: int) -> dict:
    import run
    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.utils.metrics import HISTORY

    HISTORY.clear()
    fe = Frontend(parallelism=parallelism)
    tables = []
    try:
        for stmt in config["rehearse"]["sets"]:
            await fe.execute(stmt)
        for ddl in config["ddl"]:
            await fe.execute(ddl.format(seed=SEED))
        await fe.step(SMALL_EPOCHS)
        if parallelism > 1:
            tables.append(await fe.execute("SELECT * FROM rw_mesh_tables"))
        # a deployed source keeps the chunks per barrier it was planned
        # with, so the test sets the configuration's own 4 on the
        # running readers: the epoch doubles, and with it the bucket
        full = int(config["sets"][0].rsplit("=", 1)[1])
        for _name, _side, source in run.source_readers(fe, config["view"]):
            source = getattr(source, "inner", source)  # the monitor's wrap
            source.rate_limit = source.min_chunks = full
        await fe.step(FULL_EPOCHS)
        await fe.execute("FLUSH")
        if parallelism > 1:
            tables.append(await fe.execute("SELECT * FROM rw_mesh_tables"))
        readers = run.checkpointed_rows(
            run.source_readers(fe, config["view"]))
        return {
            "view": collections.Counter(
                tuple(r) for r in await fe.execute(
                    f"SELECT * FROM {config['view']}")),
            "readers": readers,
            "history": _history(
                await fe.execute("SELECT * FROM rw_metrics_history")),
            "tables": tables,
            "rewrites": await fe.execute(
                "SELECT job, rule, fired, detail FROM rw_plan_rewrites"),
            "topology": await fe.execute(
                "SELECT * FROM rw_state_topology"),
        }
    finally:
        await fe.close()


@pytest.fixture(scope="module")
def q8():
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    with open(os.path.join(BENCH, "configs",
                           "nexmark-q8-mesh4.json")) as f:
        config = json.load(f)
    _bench_module("reference", "nexmark_gen")
    # quiet books: a mesh kernel an earlier file of this worker drove
    # leaves its exchange series behind, and the first epoch here
    # would carry them as its own
    from risingwave_tpu.utils.metrics import STREAMING as S
    for metric in (S.mesh_exchange_launches, S.mesh_exchange_slots,
                   S.mesh_exchange_rows_routed,
                   S.mesh_exchange_rows_received, S.mesh_exchange_bucket):
        for labels, _v in metric.series():
            metric.remove(**labels)
    return {"config": config,
            "mesh": asyncio.run(_drive(config, 4)),
            "one": asyncio.run(_drive(config, 1))}


def _routing_epochs(run_: dict) -> list:
    return [h for _e, h in sorted(run_["history"].items())
            if h.get("mesh_exchange.rows_routed", 0) > 0]


def test_view_equals_the_benchmarks_reference(q8):
    config, mesh = q8["config"], q8["mesh"]
    gen = _bench_module("reference", "nexmark_gen").GeneratorConfig(
        seed=SEED, **config["generator"])
    ref = _bench_module("reference", config["reference"])
    want = ref.reference([dict(r) for r in mesh["readers"]], gen)
    assert sum(want.values()) > 1000
    assert mesh["view"] == want
    # every person of the prefix is kept in the view's largest state table
    by_table = collections.Counter()
    for table_id, mv, _vnode, n, _bytes in mesh["topology"]:
        if mv == config["view"]:
            by_table[table_id] += n
    assert max(by_table.values()) == ref.resident_rows(
        [dict(r) for r in mesh["readers"]], gen)


def test_view_equals_parallelism_1(q8):
    assert q8["mesh"]["readers"] == q8["one"]["readers"]
    rows = (SMALL_EPOCHS * 2 + FULL_EPOCHS * 4) * q8["config"]["chunk_rows"]
    assert all(r["rows"] >= rows for r in q8["mesh"]["readers"]), \
        q8["mesh"]["readers"]
    assert q8["mesh"]["view"] == q8["one"]["view"]


def test_no_rewrite_fell_back_and_every_kernel_is_sharded(q8):
    assert not [r for r in q8["mesh"]["rewrites"]
                if str(r[3]).startswith("FALLBACK")]
    kernels = {(r[2], r[3]) for r in q8["mesh"]["tables"][-1]}
    # two GROUP BYs, and both sides of the join with their key tables
    # and row chains
    assert len([k for k in kernels if k[0].startswith("sharded_agg.t")
                and k[1] == "groups"]) == 2
    assert len([k for k in kernels if k[0].startswith("sharded_join.t")
                and k[1] == "keys"]) == 2
    assert len([k for k in kernels if k[0].startswith("sharded_join.t")
                and k[1] == "rows"]) == 2


def test_mesh_tables_per_shard_and_a_growth_rung_crossed(q8):
    early, late = q8["mesh"]["tables"]
    view = q8["config"]["view"]
    assert {r[1] for r in late} == {view}
    assert {r[4] for r in late} == {0, 1, 2, 3}
    assert all(0 <= r[5] <= r[6] for r in late)
    persons = next(r["rows"] for r in q8["mesh"]["readers"]
                   if r["table"] == "person")
    # the person aggregate holds one group per person, split over the
    # four shards; the join's person side links one row per person
    by_kernel = collections.defaultdict(int)
    for _tid, _mv, kernel, part, _shard, occ, _cap in late:
        by_kernel[kernel, part] += occ
    assert persons in [n for (k, p), n in by_kernel.items()
                       if k.startswith("sharded_agg") and p == "groups"]
    assert persons in [n for (k, p), n in by_kernel.items()
                       if k.startswith("sharded_join") and p == "rows"]
    # a per-shard growth rung: some table's capacity grew between the
    # two reads
    cap = {(r[2], r[3], r[4]): r[6] for r in early}
    assert any(r[6] > cap[r[2], r[3], r[4]] for r in late)


def test_rows_routed_equal_rows_received_on_every_epoch(q8):
    epochs = _routing_epochs(q8["mesh"])
    assert len(epochs) >= SMALL_EPOCHS + FULL_EPOCHS
    for h in epochs:
        shards = [v for k, v in h.items()
                  if k.startswith("mesh_exchange.shard_rows.")]
        assert len(shards) == 4
        assert sum(shards) == h["mesh_exchange.rows_routed"]
        assert h["mesh_exchange.rows_max_shard"] == max(shards)
        assert h["mesh_exchange.rows_mean_shard"] == sum(shards) / 4
        assert h["mesh_exchange.launches"] >= 6   # 2 aggs, 2 x 2 join


def test_slots_carried_cover_rows_routed(q8):
    for h in _routing_epochs(q8["mesh"]):
        assert h["mesh_exchange.slots_carried"] >= \
            h["mesh_exchange.rows_routed"]
        # at most every shard receiving every batch whole
        assert h["mesh_exchange.slots_carried"] <= \
            4 * 4 * 4 * 2 * h["mesh_exchange.rows_routed"]


def test_a_bucket_change_shows_in_the_history(q8):
    buckets = collections.defaultdict(set)
    for h in _routing_epochs(q8["mesh"]):
        for k, v in h.items():
            if k.startswith("mesh_exchange.bucket."):
                buckets[k].add(v)
    assert len(buckets) == 4          # two aggregates, two join sides
    assert any(len(seen) > 1 for seen in buckets.values()), buckets


def test_exchange_route_phase_at_parallelism_4_only(q8):
    for h in _routing_epochs(q8["mesh"]):
        assert h["phase.exchange_route"] > 0
    assert q8["one"]["history"]
    for h in q8["one"]["history"].values():
        assert "phase.exchange_route" not in h
        assert "phase.host_pack" in h


def test_named_phases_still_cover_the_epochs(q8):
    from risingwave_tpu.utils.ledger import PHASES, PhaseLedger
    named = total = 0.0
    for h in _routing_epochs(q8["mesh"]):
        phases = {k[6:]: v for k, v in h.items() if k.startswith("phase.")}
        assert set(phases) <= set(PHASES) | {"unattributed"}
        # the books add up: named + unattributed is the interval, or
        # more where concurrent scopes oversum it
        assert sum(phases.values()) >= h["interval_s"] - 1e-6
        if h.get("kernel_recompiles", 0) == 0:
            named += sum(v for k, v in phases.items()
                         if k != "unattributed")
            total += h["interval_s"]
    if total:
        assert named >= (1 - PhaseLedger.GATE_RESIDUAL_FRAC) * total
