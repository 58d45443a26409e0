"""Hand-built Nexmark pipelines: q1 (stateless), q7-core (hash agg on
device), q8 (windowed join on device).

Reference parity: e2e_test/streaming/nexmark/q1|q7|q8 semantics; plan
shapes mirror what the reference's fragmenter produces for these queries
(src/frontend/src/stream_fragmenter/mod.rs) — hand-assembled here until
the SQL frontend lands. Used by tests/test_e2e_q*.py.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

from risingwave_tpu.common.types import DataType, Field, Interval, Schema
from risingwave_tpu.connectors.nexmark import NexmarkConfig, NexmarkSplitReader
from risingwave_tpu.expr.expr import InputRef, lit, tumble_start
from risingwave_tpu.meta.barrier import BarrierLoop
from risingwave_tpu.ops.hash_agg import AggKind
from risingwave_tpu.state.state_table import StateTable
from risingwave_tpu.stream.actor import Actor, LocalBarrierManager
from risingwave_tpu.stream.exchange import channel_for_test
from risingwave_tpu.stream.executor import Executor
from risingwave_tpu.stream.executors.hash_agg import (
    AggCall, HashAggExecutor, agg_state_schema,
)
from risingwave_tpu.stream.executors.hash_join import HashJoinExecutor
from risingwave_tpu.stream.executors.materialize import MaterializeExecutor
from risingwave_tpu.stream.executors.row_id_gen import RowIdGenExecutor
from risingwave_tpu.stream.executors.simple import ProjectExecutor
from risingwave_tpu.stream.executors.source import SourceExecutor

SPLIT_STATE_SCHEMA = Schema([Field("split_id", DataType.VARCHAR),
                             Field("offset", DataType.INT64)])
DEFAULT_WINDOW = Interval(usecs=10_000_000)   # 10 seconds


@dataclass
class Pipeline:
    """A runnable hand-built plan: one actor + its barrier loop."""

    actor: Actor
    loop: BarrierLoop
    mv_table: StateTable
    readers: Dict[int, NexmarkSplitReader]

    @property
    def reader(self) -> NexmarkSplitReader:
        assert len(self.readers) == 1
        return next(iter(self.readers.values()))


def _source(local: LocalBarrierManager, store, actor_id: int,
            cfg: NexmarkConfig, table_id: int,
            rate_limit: Optional[int],
            min_chunks: Optional[int] = None) -> SourceExecutor:
    reader = NexmarkSplitReader(cfg)
    tx, rx = channel_for_test(
        edge=f"barrier:nexmark-{cfg.table_type}-{actor_id}")
    split_state = StateTable(table_id, SPLIT_STATE_SCHEMA, [0], store)
    local.register_sender(actor_id, tx)
    return SourceExecutor(reader, rx, split_state, actor_id=actor_id,
                          rate_limit_chunks_per_barrier=rate_limit,
                          min_chunks_per_barrier=min_chunks)


def _register_freshness(mat: MaterializeExecutor, fragment: str) -> None:
    """Freshness lineage (stream/freshness.py) for a hand-built
    pipeline: name the MV after its fragment and bind the chain's
    source executors' ingest frontiers — the hand-built pipeline
    reports per-MV lag exactly like a SQL-deployed one."""
    from risingwave_tpu.stream.executor import executor_children
    from risingwave_tpu.stream.freshness import FRESHNESS
    mat.mv_name = fragment

    def _source_keys(ex) -> list:
        keys = [ex.freshness_key] if isinstance(ex, SourceExecutor) \
            else []
        for _a, _i, child in executor_children(ex):
            keys += _source_keys(child)
        return keys

    FRESHNESS.register_mv(fragment, _source_keys(mat))


def _finish(local: LocalBarrierManager, store, mat: MaterializeExecutor,
            mv_table: StateTable, actor_id: int,
            readers: Dict[int, NexmarkSplitReader],
            fragment: str = "nexmark",
            fusion: bool = False) -> Pipeline:
    from risingwave_tpu.stream.monitor import install_monitoring
    _register_freshness(mat, fragment)
    if fusion:
        # fragment fusion (frontend/opt/fusion.py): same rule the SQL
        # sessions apply under SET stream_fusion
        from risingwave_tpu.frontend.opt import rewrite_stream_plan
        mat, _report = rewrite_stream_plan(mat, "none", record=False,
                                           fusion=True)
    local.set_expected_actors([actor_id])
    consumer = install_monitoring(mat, fragment=fragment,
                                  actor_id=actor_id)
    actor = Actor(actor_id, consumer, dispatchers=[],
                  barrier_manager=local, fragment=fragment)
    return Pipeline(actor, BarrierLoop(local, store), mv_table, readers)


def build_q1(store, cfg: NexmarkConfig,
             rate_limit: Optional[int] = 3,
             min_chunks: Optional[int] = None,
             fusion: bool = False) -> Pipeline:
    """q1: SELECT auction, bidder, 0.908*price, date_time FROM bid."""
    local = LocalBarrierManager()
    source = _source(local, store, 1, cfg, 1, rate_limit, min_chunks)
    row_id = RowIdGenExecutor(source)
    s = row_id.schema
    project = ProjectExecutor(
        row_id,
        exprs=[InputRef(s.index_of("auction"), DataType.INT64),
               InputRef(s.index_of("bidder"), DataType.INT64),
               lit("0.908", DataType.DECIMAL)
               * InputRef(s.index_of("price"), DataType.INT64),
               InputRef(s.index_of("date_time"), DataType.TIMESTAMP),
               InputRef(s.index_of("_row_id"), DataType.SERIAL)],
        names=["auction", "bidder", "price", "date_time", "_row_id"])
    mv_table = StateTable(2, project.schema, [4], store)  # pk = _row_id
    mat = MaterializeExecutor(project, mv_table)
    return _finish(local, store, mat, mv_table, 1,
                   {1: source.reader}, fragment="nexmark-q1",
                   fusion=fusion)


def build_q7(store, cfg: NexmarkConfig,
             rate_limit: Optional[int] = 4,
             window: Interval = DEFAULT_WINDOW,
             min_chunks: Optional[int] = None,
             watermark_delay: Optional[Interval] = None,
             mesh=None, shard_capacity: int = 1 << 14,
             coalesce_rows: Optional[int] = None,
             tier_cap: Optional[int] = None,
             fusion: bool = False) -> Pipeline:
    """q7-core: MAX(price), COUNT(*) per tumbling window (device agg).

    With ``watermark_delay``, a WatermarkFilter generates event-time
    watermarks on date_time; the projection derives a window_start
    watermark through tumble_start, and the agg retires closed windows
    (bounded state — the honest steady-state configuration).

    With ``mesh``, the aggregation runs vnode-sharded across the mesh
    (parallel/agg.ShardedAggKernel): the reference's hash dispatch to N
    parallel actors (dispatch.rs:582) becomes one SPMD all_to_all."""
    local = LocalBarrierManager()
    source = _source(local, store, 1, cfg, 1, rate_limit, min_chunks)
    s = source.schema
    upstream: "SourceExecutor | WatermarkFilterExecutor" = source
    derivations = None
    if watermark_delay is not None:
        from risingwave_tpu.stream.executors.watermark_filter import (
            WATERMARK_STATE_SCHEMA, WatermarkFilterExecutor,
        )
        wm_state = StateTable(10, WATERMARK_STATE_SCHEMA, [0], store)
        upstream = WatermarkFilterExecutor(
            source, s.index_of("date_time"), watermark_delay, wm_state)
        w = window.exact_usecs()
        derivations = {s.index_of("date_time"): (0, lambda v: v - v % w)}
    project = ProjectExecutor(
        upstream,
        exprs=[tumble_start(
            InputRef(s.index_of("date_time"), DataType.TIMESTAMP), window),
            InputRef(s.index_of("price"), DataType.INT64)],
        names=["window_start", "price"],
        watermark_derivations=derivations)
    calls = [AggCall(AggKind.MAX, 1), AggCall(AggKind.COUNT)]
    agg_schema, agg_pk = agg_state_schema(project.schema, [0], calls)
    agg_state = StateTable(2, agg_schema, agg_pk, store,
                           dist_key_indices=[0])
    kernel = None
    if mesh is not None:
        from risingwave_tpu.parallel.agg import ShardedAggKernel
        from risingwave_tpu.stream.executors.keys import LANES_PER_KEY
        kernel = ShardedAggKernel(
            mesh, key_width=LANES_PER_KEY * 1,
            specs=[c.spec(project.schema) for c in calls],
            capacity=shard_capacity)
    agg_in: Executor = project
    if coalesce_rows:
        # barrier-bounded chunk coalescing in front of the keyed
        # executor (stream/coalesce.py) — the SQL planner inserts this
        # automatically; the hand-built pipeline takes it as a knob so
        # the oracle test can compare on vs off
        from risingwave_tpu.stream.coalesce import CoalesceExecutor
        agg_in = CoalesceExecutor(project, coalesce_rows)
    # tier_cap: resident-group cap for the state-tiering oracle tests
    # (state/tier.py; single-chip only)
    agg = HashAggExecutor(agg_in, [0], calls, agg_state,
                          append_only=True,
                          output_names=["max_price", "bid_count"],
                          kernel=kernel,
                          tier_cap=tier_cap if mesh is None else None)
    mv_table = StateTable(3, agg.schema, [0], store)  # pk = window_start
    mat = MaterializeExecutor(agg, mv_table)
    return _finish(local, store, mat, mv_table, 1,
                   {1: source.reader}, fragment="nexmark-q7",
                   fusion=fusion)


def build_q8(store, cfg_p: NexmarkConfig, cfg_a: NexmarkConfig,
             rate_limit: Optional[int] = 4,
             window: Interval = DEFAULT_WINDOW,
             min_chunks: Optional[int] = None, mesh=None,
             fusion: bool = False) -> Pipeline:
    """q8: persons who created an auction in the same tumbling window.

    two sources → projects → auction-side hash-agg dedup → inner
    HashJoin (device matcher) → project → materialize.

    With ``mesh``, the join runs on the vnode-sharded SPMD matcher
    (parallel/join.ShardedJoinKernel): both sides' state routes to key
    owners over one all_to_all — the reference's hash dispatch to N
    parallel join actors (dispatch.rs:582)."""
    local = LocalBarrierManager()
    persons = _source(local, store, 1, cfg_p, 1, rate_limit, min_chunks)
    ps = persons.schema
    p_proj = ProjectExecutor(
        persons,
        exprs=[InputRef(ps.index_of("id"), DataType.INT64),
               InputRef(ps.index_of("name"), DataType.VARCHAR),
               tumble_start(InputRef(ps.index_of("date_time"),
                                     DataType.TIMESTAMP), window)],
        names=["id", "name", "starttime"])
    auctions = _source(local, store, 2, cfg_a, 2, rate_limit, min_chunks)
    asch = auctions.schema
    a_proj = ProjectExecutor(
        auctions,
        exprs=[InputRef(asch.index_of("seller"), DataType.INT64),
               tumble_start(InputRef(asch.index_of("date_time"),
                                     DataType.TIMESTAMP), window)],
        names=["seller", "starttime"])
    calls = [AggCall(AggKind.COUNT)]
    agg_sch, agg_pk = agg_state_schema(a_proj.schema, [0, 1], calls)
    # capacity presize from the KNOWN nexmark cardinalities (see
    # common/chunk.presize_cap — growth doublings compile mid-run)
    from risingwave_tpu.common.chunk import presize_cap, presize_flush_cap
    n_p = max(cfg_p.event_num // 50, 1)
    n_a = max(cfg_a.event_num * 3 // 50, 1)
    a_dedup = HashAggExecutor(
        a_proj, [0, 1], calls,
        StateTable(3, agg_sch, agg_pk, store, dist_key_indices=[0]),
        append_only=True, output_names=["seller", "starttime", "_cnt"],
        kernel_capacity=presize_cap(n_a, 1 << 18),
        flush_capacity=presize_flush_cap(n_a))
    a_dedup_proj = ProjectExecutor(
        a_dedup,
        exprs=[InputRef(0, DataType.INT64),
               InputRef(1, DataType.TIMESTAMP)],
        names=["seller", "starttime"])
    lt = StateTable(4, p_proj.schema, [0, 2], store, dist_key_indices=[0])
    rt = StateTable(5, a_dedup_proj.schema, [0, 1], store,
                    dist_key_indices=[0])
    join_opts = None if mesh is not None else {
        "key_capacity": presize_cap(max(n_p, n_a)),
        "row_capacity": presize_cap(max(n_p, n_a)),
        "probe_capacity": 1 << 16,
    }
    join = HashJoinExecutor(p_proj, a_dedup_proj,
                            left_keys=[0, 2], right_keys=[0, 1],
                            left_table=lt, right_table=rt, mesh=mesh,
                            shard_opts=join_opts)
    out = ProjectExecutor(
        join,
        exprs=[InputRef(0, DataType.INT64),
               InputRef(1, DataType.VARCHAR),
               InputRef(2, DataType.TIMESTAMP)],
        names=["id", "name", "starttime"])
    mv = StateTable(6, out.schema, [0, 2], store)
    mat = MaterializeExecutor(out, mv)
    return _finish(local, store, mat, mv, 7,
                   {1: persons.reader, 2: auctions.reader},
                   fragment="nexmark-q8", fusion=fusion)


def drive_to_completion(pipeline: Pipeline,
                        targets: Dict[int, int],
                        max_epochs: int = 500,
                        in_flight: int = 2):
    """Async driver: barrier-tick until every reader hits its target
    offset, one final checkpoint, then a Stop barrier.

    Barriers are PIPELINED up to `in_flight` (the reference's
    in_flight_barrier_nums): epoch N+1's data processing overlaps
    epoch N's barrier flush — the flush's device→host fetch hides
    under the next epoch's compute instead of serializing the stream
    (its length on a local chip is not measured). NOTE: recorded
    barrier latency
    is inject→commit and therefore includes queueing behind earlier
    in-flight barriers (the reference's in-flight semantics) — compare
    latencies only across runs with the same window.

    Returns (timed_elapsed_s, timed_rows) measured AFTER a warmup epoch
    (jit compiles land outside the timed window)."""
    import time

    from risingwave_tpu.stream.message import StopMutation

    in_flight_w = max(1, in_flight)

    async def run():
        task = pipeline.actor.spawn()
        loop = pipeline.loop
        readers = pipeline.readers
        await loop.inject_and_collect()      # warmup epoch
        warm_rows = sum(r.offset for r in readers.values())
        warm_epochs = len(loop.stats.latencies_s)
        t0 = time.perf_counter()

        def done() -> bool:
            return all(readers[a].offset >= t
                       for a, t in targets.items())

        injected = 0
        while not done():
            if injected >= max_epochs:
                raise RuntimeError(
                    f"sources stalled: "
                    f"{ {a: readers[a].offset for a in targets} } "
                    f"vs {targets}")
            while loop.in_flight_count < in_flight_w \
                    and injected < max_epochs:
                await loop.inject()
                injected += 1
            await loop.collect_next()
        while loop.in_flight_count:
            await loop.collect_next()
        elapsed = time.perf_counter() - t0
        timed_rows = sum(r.offset for r in readers.values()) - warm_rows
        await loop.inject_and_collect(
            mutation=StopMutation(frozenset(readers.keys())))
        await task
        if pipeline.actor.failure is not None:
            raise pipeline.actor.failure
        loop.stats.latencies_s = loop.stats.latencies_s[warm_epochs:]
        loop.profiler.drop_first(warm_epochs)
        return elapsed, timed_rows

    return run()


def build_q5(store, cfg: NexmarkConfig,
             rate_limit: Optional[int] = 8,
             min_chunks: Optional[int] = None,
             slide: Interval = Interval(usecs=2_000_000),
             size: Interval = Interval(usecs=10_000_000),
             top_per_window: int = 1,
             tier_cap: Optional[int] = None,
             fusion: bool = False) -> Pipeline:
    """q5 (hot items): auctions with the most bids per sliding window.

    source → hop-window expansion → per-(window, auction) device count
    agg → per-window group top-n → materialize (e2e_test/streaming/
    nexmark/q5 semantics; ties kept deterministically by auction id).
    """
    from risingwave_tpu.stream.executors.hop_window import (
        HopWindowExecutor,
    )
    from risingwave_tpu.stream.executors.top_n import GroupTopNExecutor

    local = LocalBarrierManager()
    source = _source(local, store, 1, cfg, 1, rate_limit, min_chunks)
    s = source.schema
    hop = HopWindowExecutor(source, s.index_of("date_time"), slide, size)
    hs = hop.schema
    proj = ProjectExecutor(
        hop,
        exprs=[InputRef(hs.index_of("window_start"), DataType.TIMESTAMP),
               InputRef(hs.index_of("auction"), DataType.INT64)],
        names=["window_start", "auction"])
    calls = [AggCall(AggKind.COUNT)]
    agg_sch, agg_pk = agg_state_schema(proj.schema, [0, 1], calls)
    # tier_cap governs BOTH stateful stages (state/tier.py): resident
    # agg groups and resident TopN group caches
    agg = HashAggExecutor(
        proj, [0, 1], calls,
        StateTable(2, agg_sch, agg_pk, store, dist_key_indices=[0]),
        append_only=True,
        output_names=["window_start", "auction", "bid_count"],
        tier_cap=tier_cap)
    topn_state = StateTable(3, agg.schema, [0, 1], store)
    topn = GroupTopNExecutor(
        agg, order_by=[(2, True), (1, False)], offset=0,
        limit=top_per_window, state=topn_state,
        group_indices=[0], pk_indices=[0, 1], tier_cap=tier_cap)
    mv = StateTable(4, topn.schema, [0, 1], store)
    mat = MaterializeExecutor(topn, mv)
    return _finish(local, store, mat, mv, 1, {1: source.reader},
                   fragment="nexmark-q5", fusion=fusion)
