"""Metrics + tracing + barrier-aligned observability tests."""

import asyncio

from risingwave_tpu.utils.metrics import (
    Counter, Gauge, Histogram, MetricsRegistry, STREAMING,
)
from risingwave_tpu.utils.trace import AwaitRegistry


def test_counter_gauge_histogram_render():
    r = MetricsRegistry()
    c = r.counter("rows_total")
    c.inc(5, actor="1")
    c.inc(2, actor="1")
    c.inc(1, actor="2")
    assert c.get(actor="1") == 7
    g = r.gauge("cap")
    g.set(1024)
    h = r.histogram("lat_seconds", buckets=[0.01, 0.1, 1.0])
    for v in (0.005, 0.05, 0.05, 0.5, 2.0):
        h.observe(v)
    assert h.count() == 5
    assert h.quantile(0.5) == 0.05
    text = r.render()
    assert 'rows_total{actor="1"} 7' in text
    assert "cap 1024" in text
    assert 'lat_seconds_bucket{le="0.01"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 5' in text
    assert "lat_seconds_count 5" in text


def test_pipeline_populates_streaming_metrics():
    from risingwave_tpu.frontend import Frontend

    async def run():
        before_rows = STREAMING.source_rows.get(source="nexmark-0")
        before_cp = STREAMING.checkpoint_count.get()
        fe = Frontend(min_chunks=2)
        await fe.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', nexmark.event.num=5000)")
        await fe.execute(
            "CREATE MATERIALIZED VIEW m AS SELECT window_start, "
            "COUNT(*) AS c FROM TUMBLE(bid, date_time, "
            "INTERVAL '10' SECOND) GROUP BY window_start")
        await fe.step(3)
        await fe.close()
        return (STREAMING.source_rows.get(source="nexmark-0")
                - before_rows,
                STREAMING.checkpoint_count.get() - before_cp,
                STREAMING.barrier_latency.count())

    rows, cps, lat_n = asyncio.run(run())
    assert rows > 0
    assert cps >= 3
    assert lat_n > 0


def test_help_lines_rendered():
    r = MetricsRegistry()
    r.counter("rows_total", "rows through the system").inc(3)
    r.gauge("cap", "capacity").set(7)
    r.histogram("lat", "latency").observe(0.2)
    r.counter("bare").inc()              # no help → no HELP line
    text = r.render()
    assert "# HELP rows_total rows through the system" in text
    assert "# HELP cap capacity" in text
    assert "# HELP lat latency" in text
    assert "# HELP bare" not in text
    # HELP precedes TYPE for each family
    assert text.index("# HELP cap") < text.index("# TYPE cap gauge")


def test_backpressure_on_throttled_edge():
    """A sender outpacing a slow receiver on a tiny permit budget must
    accumulate blocked-send time in the edge's back-pressure series."""
    from risingwave_tpu.common.chunk import StreamChunk
    from risingwave_tpu.common.types import DataType, Field, Schema
    from risingwave_tpu.stream.exchange import channel

    edge = "test:throttled"
    sch = Schema([Field("a", DataType.INT64)])
    chunk = StreamChunk.from_pydict(sch, {"a": list(range(8))})

    async def run():
        tx, rx = channel(chunk_permits=8, barrier_permits=2, edge=edge)

        async def produce():
            for _ in range(5):
                await tx.send(chunk)

        async def consume():
            await asyncio.sleep(0.05)   # park the sender on permits
            for _ in range(5):
                await rx.recv()

        await asyncio.gather(produce(), consume())

    before = STREAMING.exchange_backpressure.get(edge=edge)
    asyncio.run(run())
    blocked = STREAMING.exchange_backpressure.get(edge=edge) - before
    assert blocked > 0.03, blocked
    assert STREAMING.exchange_send_count.get(edge=edge) >= 5


def test_epoch_profile_attributes_slow_executor():
    """A deliberately slow executor shows up in the epoch profile: the
    barrier exceeds the slow threshold, the profile carries the actor
    attribution + await dump, and the executor-level busy counters
    blame the right node."""
    from risingwave_tpu.common.types import DataType, Field, Schema
    from risingwave_tpu.meta.barrier import BarrierLoop
    from risingwave_tpu.state.store import MemoryStateStore
    from risingwave_tpu.stream.actor import Actor, LocalBarrierManager
    from risingwave_tpu.stream.executor import Executor, ExecutorInfo
    from risingwave_tpu.stream.executors.test_utils import MockSource
    from risingwave_tpu.stream.message import StopMutation, is_barrier
    from risingwave_tpu.stream.monitor import install_monitoring

    class SlowPass(Executor):
        def __init__(self, input_):
            super().__init__(ExecutorInfo(
                input_.schema, list(input_.pk_indices), "SlowPass"))
            self.input = input_

        async def execute(self):
            async for msg in self.input.execute():
                if is_barrier(msg):
                    await asyncio.sleep(0.05)
                yield msg

    sch = Schema([Field("a", DataType.INT64)])

    async def run():
        store = MemoryStateStore()
        local = LocalBarrierManager()
        tx, src = MockSource.channel(sch)
        local.register_sender(7, tx)
        consumer = install_monitoring(SlowPass(src),
                                      fragment="slowtest", actor_id=7)
        local.set_expected_actors([7])
        actor = Actor(7, consumer, dispatchers=[],
                      barrier_manager=local, fragment="slowtest")
        loop = BarrierLoop(local, store,
                           slow_barrier_threshold_s=0.02)
        task = actor.spawn()
        await loop.inject_and_collect(force_checkpoint=True)
        await loop.inject_and_collect(force_checkpoint=True)
        prof = loop.profiler.profiles[-1]
        await loop.inject_and_collect(
            mutation=StopMutation(frozenset({7})))
        await task
        assert actor.failure is None
        return prof

    prof = asyncio.run(run())
    assert prof.inject_to_collect_s > 0.03
    assert prof.slowest_actor == 7
    assert prof.await_dump, "slow barrier must attach the await dump"
    assert "epoch" in prof.format()
    busy = STREAMING.executor_busy.get(
        fragment="slowtest", actor="7", executor="SlowPass", node="0")
    assert busy > 0.03, busy
    # teardown removed the live-actor series
    assert not any(labels.get("actor") == "7"
                   and labels.get("fragment") == "slowtest"
                   for labels, _v in STREAMING.actor_count.series())


def test_rw_metric_tables_over_pgwire():
    """The SQL query surface: rw_actor_metrics lists the live actors,
    rw_barrier_latency matches BarrierStats, rw_fragment_backpressure
    carries the labeled edges."""
    from test_pgwire import _Client, _rows

    from risingwave_tpu.frontend import Frontend
    from risingwave_tpu.frontend.pgwire import PgServer

    async def run():
        fe = Frontend(min_chunks=2)
        srv = PgServer(fe)
        await srv.serve(port=0)
        c = await _Client.connect(srv.port)
        await c.query(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', nexmark.event.num=3000)")
        await c.query(
            "CREATE MATERIALIZED VIEW m AS SELECT auction, "
            "count(*) AS c FROM bid GROUP BY auction")
        await fe.step(3)
        actors = _rows(await c.query("SELECT * FROM rw_actor_metrics"))
        barriers = _rows(await c.query(
            "SELECT * FROM rw_barrier_latency"))
        edges = _rows(await c.query(
            "SELECT * FROM rw_fragment_backpressure"))
        stats = list(fe.loop.stats.latencies_s)
        c.close()
        await srv.close()
        await fe.close()
        return actors, barriers, edges, stats

    actors, barriers, edges, stats = asyncio.run(run())
    # live actor rows, with nonzero executor throughput on the MV chain
    m_rows = [r for r in actors if r[1] == "m"]
    assert m_rows, actors
    assert any(int(r[4]) > 0 for r in m_rows), m_rows
    # per-epoch breakdown consistent with BarrierStats: same epochs,
    # and total ≈ the recorded latency (profiling adds only the time
    # between the two monotonic reads)
    assert len(barriers) == len(stats)
    for row, lat in zip(barriers, stats):
        assert abs(float(row[4]) - lat) < 0.05, (row, lat)
        assert float(row[4]) >= float(row[2])     # total ≥ i2c
    # the source's barrier channel is a labeled, metered edge
    assert any(r[0].startswith("barrier:bid") for r in edges), edges


def test_actor_count_series_track_deploy_and_drop():
    from risingwave_tpu.frontend import Frontend

    def live(fragment):
        return [labels for labels, _v in
                STREAMING.actor_count.series()
                if labels.get("fragment") == fragment]

    async def run():
        fe = Frontend(min_chunks=2)
        await fe.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', nexmark.event.num=2000)")
        await fe.execute(
            "CREATE MATERIALIZED VIEW obs_mv AS SELECT auction, "
            "count(*) AS c FROM bid GROUP BY auction")
        await fe.step(1)
        during = live("obs_mv")
        await fe.execute("DROP MATERIALIZED VIEW obs_mv")
        after_drop = live("obs_mv")
        await fe.close()
        return during, after_drop

    during, after_drop = asyncio.run(run())
    assert len(during) == 1
    assert after_drop == []


def test_tracer_spans_and_await_registry():
    # (the span half went with utils/trace.Tracer, which nothing but
    # this test built; spans are utils/spans.py's)
    a = AwaitRegistry()
    a.enter("actor-1", "barrier_align(left)")
    a.enter("actor-2", "state_table.commit")
    dump = a.dump()
    assert "actor-1: barrier_align(left)" in dump
    a.exit("actor-1")
    assert "actor-1" not in a.dump()
