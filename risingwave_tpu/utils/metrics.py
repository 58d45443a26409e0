"""Metrics: counters/gauges/histograms + Prometheus text rendering.

Reference parity: src/common/src/metrics.rs + the per-subsystem
registries (StreamingMetrics src/stream/src/executor/monitor/
streaming_stats.rs, meta barrier_latency src/meta/src/rpc/metrics.rs:57)
— a dependency-free in-process registry with the same exposition
format, so the numbers can feed any Prometheus scraper later.
"""

from __future__ import annotations

import bisect
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

LabelKey = Tuple[Tuple[str, str], ...]

# guards read-modify-write updates (Counter.inc, Histogram.observe):
# the async checkpoint uploader runs object-store PUTs — and their
# op/latency/byte metrics — in worker threads, and an unguarded
# `d[k] = d.get(k) + v` can lose increments across a GIL preemption.
# One uncontended lock acquire is ~100ns; every metered path is
# per-chunk or per-object-store-op, not per-row.
_WRITE_LOCK = threading.Lock()


def _help_lines(name: str, help_: str) -> List[str]:
    """`# HELP` precedes `# TYPE` (Prometheus exposition order); an
    empty help string renders nothing — real scrapers tolerate the
    omission but tooling (promtool lint) wants the line when known."""
    if not help_:
        return []
    text = help_.replace("\\", "\\\\").replace("\n", "\\n")
    return [f"# HELP {name} {text}"]


def _fmt_value(v: float) -> str:
    """Full-precision exposition: '%g' truncates to 6 significant
    digits, freezing large counters in a scraper's eyes."""
    if float(v).is_integer() and abs(v) < 2**63:
        return str(int(v))
    return repr(float(v))


def exact_quantile(xs: Sequence[float], q: float) -> float:
    """Exact quantile over raw observations (shared by Histogram,
    BarrierStats and the epoch profiler — one index convention)."""
    if not xs:
        return 0.0
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(len(xs) * q))]


def _label_key(labels: Dict[str, str]) -> LabelKey:
    return tuple(sorted(labels.items()))


def _fmt_labels(key: LabelKey) -> str:
    if not key:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in key)
    return "{" + inner + "}"


class Series:
    """Cached-label handle onto one series: per-message hot paths
    (exchange sends) skip rebuilding the sorted label key each call."""

    __slots__ = ("_values", "_key")

    def __init__(self, values: Dict[LabelKey, float], key: LabelKey):
        self._values = values
        self._key = key

    def inc(self, amount: float = 1.0) -> None:
        with _WRITE_LOCK:
            self._values[self._key] = \
                self._values.get(self._key, 0.0) + amount

    def set(self, value: float) -> None:
        self._values[self._key] = value


class Counter:
    def __init__(self, name: str, help_: str = ""):
        self.name = name
        self.help = help_
        self._values: Dict[LabelKey, float] = {}

    def inc(self, amount: float = 1.0, **labels: str) -> None:
        k = _label_key(labels)
        with _WRITE_LOCK:
            self._values[k] = self._values.get(k, 0.0) + amount

    def labeled(self, **labels: str) -> Series:
        return Series(self._values, _label_key(labels))

    def get(self, **labels: str) -> float:
        return self._values.get(_label_key(labels), 0.0)

    def series(self) -> List[Tuple[Dict[str, str], float]]:
        """Every labeled series as (labels, value) — the system-table
        read path (rw_actor_metrics and friends)."""
        return [(dict(k), v) for k, v in sorted(self._values.items())]

    def remove(self, **labels: str) -> None:
        """Drop a labeled series (actor teardown)."""
        self._values.pop(_label_key(labels), None)

    def render(self) -> List[str]:
        out = _help_lines(self.name, self.help)
        out.append(f"# TYPE {self.name} counter")
        for k, v in sorted(self._values.items()):
            out.append(f"{self.name}{_fmt_labels(k)} {_fmt_value(v)}")
        return out


class Gauge:
    def __init__(self, name: str, help_: str = ""):
        self.name = name
        self.help = help_
        self._values: Dict[LabelKey, float] = {}

    def set(self, value: float, **labels: str) -> None:
        self._values[_label_key(labels)] = value

    def labeled(self, **labels: str) -> Series:
        return Series(self._values, _label_key(labels))

    def get(self, **labels: str) -> float:
        return self._values.get(_label_key(labels), 0.0)

    def remove(self, **labels: str) -> None:
        """Drop a labeled series (executor teardown — avoids leaking
        stale series in the process-global registry)."""
        self._values.pop(_label_key(labels), None)

    def series(self) -> List[Tuple[Dict[str, str], float]]:
        return [(dict(k), v) for k, v in sorted(self._values.items())]

    def render(self) -> List[str]:
        out = _help_lines(self.name, self.help)
        out.append(f"# TYPE {self.name} gauge")
        for k, v in sorted(self._values.items()):
            out.append(f"{self.name}{_fmt_labels(k)} {_fmt_value(v)}")
        return out


DEFAULT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0)


class Histogram:
    """Fixed-bucket histogram with exact-quantile support for tests
    (keeps raw observations up to a cap)."""

    def __init__(self, name: str, help_: str = "",
                 buckets: Sequence[float] = DEFAULT_BUCKETS,
                 keep_raw: int = 100_000):
        self.name = name
        self.help = help_
        self.buckets = list(buckets)
        self._counts: Dict[LabelKey, List[int]] = {}
        self._sum: Dict[LabelKey, float] = {}
        self._total: Dict[LabelKey, int] = {}
        self._raw: Dict[LabelKey, List[float]] = {}
        self._keep_raw = keep_raw

    def observe(self, value: float, **labels: str) -> None:
        k = _label_key(labels)
        i = bisect.bisect_left(self.buckets, value)
        with _WRITE_LOCK:
            counts = self._counts.setdefault(
                k, [0] * (len(self.buckets) + 1))
            counts[i] += 1
            self._sum[k] = self._sum.get(k, 0.0) + value
            self._total[k] = self._total.get(k, 0) + 1
            raw = self._raw.setdefault(k, [])
            if len(raw) < self._keep_raw:
                raw.append(value)

    def quantile(self, q: float, **labels: str) -> float:
        return exact_quantile(self._raw.get(_label_key(labels), []), q)

    def count(self, **labels: str) -> int:
        return self._total.get(_label_key(labels), 0)

    def sum(self, **labels: str) -> float:
        return self._sum.get(_label_key(labels), 0.0)

    def series(self) -> List[Tuple[Dict[str, str], int, float]]:
        """(labels, observation count, sum) per labeled series."""
        return [(dict(k), self._total.get(k, 0),
                 self._sum.get(k, 0.0))
                for k in sorted(self._counts)]

    def remove(self, **labels: str) -> None:
        k = _label_key(labels)
        for d in (self._counts, self._sum, self._total, self._raw):
            d.pop(k, None)

    def render(self) -> List[str]:
        out = _help_lines(self.name, self.help)
        out.append(f"# TYPE {self.name} histogram")
        for k, counts in sorted(self._counts.items()):
            acc = 0
            for le, c in zip(self.buckets, counts):
                acc += c
                lk = k + (("le", f"{le:g}"),)
                out.append(f"{self.name}_bucket{_fmt_labels(lk)} {acc}")
            acc += counts[-1]
            lk = k + (("le", "+Inf"),)
            out.append(f"{self.name}_bucket{_fmt_labels(lk)} {acc}")
            out.append(f"{self.name}_sum{_fmt_labels(k)} "
                       f"{_fmt_value(self._sum.get(k, 0.0))}")
            out.append(f"{self.name}_count{_fmt_labels(k)} "
                       f"{self._total.get(k, 0)}")
        return out


class MetricsRegistry:
    def __init__(self) -> None:
        self._metrics: Dict[str, object] = {}

    def counter(self, name: str, help_: str = "") -> Counter:
        return self._get(name, lambda: Counter(name, help_))

    def gauge(self, name: str, help_: str = "") -> Gauge:
        return self._get(name, lambda: Gauge(name, help_))

    def histogram(self, name: str, help_: str = "",
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
        return self._get(name, lambda: Histogram(name, help_, buckets))

    def _get(self, name: str, mk):
        m = self._metrics.get(name)
        if m is None:
            m = self._metrics[name] = mk()
        return m

    def render(self) -> str:
        lines: List[str] = []
        for name in sorted(self._metrics):
            lines.extend(self._metrics[name].render())
        return "\n".join(lines) + "\n"


# the process-global registry (per-node registry analog)
GLOBAL = MetricsRegistry()


class StreamingMetrics:
    """The streaming-side metric family (streaming_stats.rs analog)."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        r = registry or GLOBAL
        self.source_rows = r.counter(
            "stream_source_output_rows_counts",
            "rows emitted by sources")
        self.executor_rows = r.counter(
            "stream_executor_row_count", "rows through executors")
        self.barrier_latency = r.histogram(
            "meta_barrier_duration_seconds",
            "inject→commit latency per barrier")
        self.agg_dirty_groups = r.gauge(
            "stream_agg_dirty_groups_count",
            "dirty groups at last flush")
        self.agg_table_capacity = r.gauge(
            "stream_agg_table_capacity", "device hash-table slots")
        # join payload residency (ISSUE 9): which half of a stored
        # join row lives where — device lane + degree HBM bytes vs the
        # host arena's column bytes, per executor, refreshed at every
        # barrier by HashJoinExecutor
        self.join_device_bytes = r.gauge(
            "stream_join_payload_device_bytes",
            "HBM bytes of device-resident join payload lanes + degree "
            "arrays per executor")
        self.join_host_bytes = r.gauge(
            "stream_join_payload_host_bytes",
            "host arena bytes backing join rows per executor "
            "(varchar/host columns + the durable rebuild copy)")
        self.join_rows_evicted = r.counter(
            "stream_join_rows_evicted",
            "join-state rows evicted to the cold (state-table) tier")
        self.state_pk_keys = r.counter(
            "stream_state_pk_keys",
            "state-table keys encoded, by path (columnar: the bulk "
            "encoder, counted by the batch; row: the scalar codec of "
            "the point operations, one key a call)")
        self.agg_multiset = r.counter(
            "stream_agg_multiset",
            "the in-memory value multisets of the aggregates, by event "
            "(rows_written: rows of the once-a-barrier batch writes to "
            "a minput or distinct table; point_reads: rows read back "
            "from one outside recovery and cold-tier reload; "
            "extreme_scans / values_scanned: groups whose MIN/MAX was "
            "recomputed after a retraction, and the values looked at)")
        self.agg_distinct_pairs = r.gauge(
            "stream_agg_distinct_pairs",
            "(group, value) pairs resident in a DISTINCT column's "
            "dedup state at the barrier's seal, by dedup table "
            "(t<state table id>): the rows of that table")
        self.agg_distinct_changed = r.counter(
            "stream_agg_distinct_changed",
            "pairs of which a barrier changed any count: the rows its "
            "write-through inserted, updated and deleted in the dedup "
            "table, by dedup table")
        self.agg_distinct_crossings = r.counter(
            "stream_agg_distinct_crossings",
            "rows the DISTINCT gating made visible to some call (a "
            "count crossed between 0 and 1): what the device kernel "
            "is shown of the DISTINCT calls, by dedup table")
        self.agg_distinct_seconds = r.counter(
            "stream_agg_distinct_seconds",
            "seconds of a dedup table's write-through at the barrier, "
            "by dedup table and stage (write: its StateTable batch "
            "calls, the state.write stages nested there; persist: the "
            "rest of the pass, its part of agg.persist)")
        self.topn_rows = r.counter(
            "stream_topn_rows",
            "a top-N's rows by its state table (t<state table id>) and "
            "event: rows_in (rows of the chunks it was given), rows_out "
            "(rows of the window deltas it emitted), state_writes and "
            "state_deletes (rows it wrote to and deleted from its "
            "state table)")
        self.topn_resident = r.gauge(
            "stream_topn_resident",
            "what a top-N holds at the barrier, by state table: groups "
            "(groups with a row, resident or cold) and cached_rows "
            "(rows in the groups' sorted caches in memory)")
        self.expr_to_char_rows = r.counter(
            "expr_to_char_rows", "valid rows to_char was given")
        self.expr_to_char_formats = r.counter(
            "expr_to_char_formats",
            "strftime calls to_char made: one a distinct value, in a "
            "chunk, of the finest field its pattern proves the text a "
            "function of (the microsecond where it proves none)")
        self.state_cleaned_rows = r.counter(
            "stream_state_cleaned_rows",
            "rows a watermark's range delete took out of a state "
            "table, by table (t<state table id>)")
        self.join_expired_rows = r.counter(
            "stream_join_expired_rows",
            "rows a watermark's expiry took out of a join side, "
            "resident and cold, by the side's state table (t<state "
            "table id>): a side deletes its rows itself, so they are "
            "not among stream_state_cleaned_rows")
        self.state_clean_reads = r.counter(
            "stream_state_clean_reads",
            "rows a watermark's range delete read from the store, by "
            "table: what the scan that seeds the table's clean index "
            "read, at its first clean; a later clean reads none")
        self.state_clean_seeds = r.counter(
            "stream_state_clean_seeds",
            "scans that seeded a state table's clean index (its first "
            "range delete, and the first after its vnodes changed), "
            "by table")
        self.state_clean_index_keys = r.gauge(
            "stream_state_clean_index_keys",
            "keys a state table's clean index holds, set when it is "
            "seeded and at the table's commit: its committed rows, by "
            "table")
        self.state_resident_rows = r.gauge(
            "stream_state_resident_rows",
            "rows of a state table that a watermark cleans, as the "
            "topology's books hold them at the table's commit, by table")
        self.state_watermark = r.gauge(
            "stream_watermark",
            "the watermark a state table was last cleaned to (the "
            "physical value of the column its operator cleans it on: "
            "rows below it are gone), by table")
        self.watermark_late_rows = r.counter(
            "stream_watermark_late_rows",
            "rows a source's watermark filter dropped as late, by "
            "source")
        self.agg_input_rows = r.counter(
            "stream_agg_input_rows",
            "visible rows a HashAggExecutor took in, by op (insert, "
            "delete, update_delete, update_insert): the retractions an "
            "upstream aggregate or join sends into it")
        self.join_to_agg_rows = r.counter(
            "stream_join_to_agg_rows",
            "rows a hash join handed to the aggregate it feeds, by view")
        self.join_to_agg_seconds = r.counter(
            "stream_join_to_agg_seconds",
            "host seconds of the join -> aggregate hand-off, by view: "
            "from the join's probe result on the host (chunk build) "
            "through the aggregate's ingest to its staged batch (pack, "
            "upload); a second cut of the wall time the ledger's "
            "phases partition")
        self.join_input_rows = r.counter(
            "stream_join_input_rows",
            "visible rows a HashJoinExecutor took in, by join "
            "(t<state table id of its left side>), side (left, right) "
            "and op: what retracts in a join's inputs")
        self.join_output_rows = r.counter(
            "stream_join_output_rows",
            "pairs a HashJoinExecutor matched on its keys, by join, "
            "before its condition (and its other rows out)")
        self.join_outer_rows = r.counter(
            "stream_join_outer_rows",
            "what the outer half of a degree-tracked join did, by join "
            "and event: padded_insert and padded_delete (NULL-padded "
            "rows it emitted and retracted: unmatched incoming rows of "
            "an outer side, and stored rows that flipped), flip_on and "
            "flip_off (stored rows of a tracked side whose match degree "
            "rose from zero and fell to it). An inner join files none")
        self.join_degree_redispatches = r.counter(
            "stream_join_degree_probe_redispatches",
            "times a degree-tracking epoch probe outgrew its buffer, "
            "doubled it and ran its program again, by kernel (join.t<"
            "state table id> of the probed side): each is a program of "
            "a size the data chose. Filed only by a join that tracks "
            "degrees")
        self.join_condition_rows = r.counter(
            "stream_join_condition_rows",
            "matched pairs an inner join's own condition (the "
            "conjuncts of its ON / WHERE that are no hash keys) was "
            "evaluated on, by join and result (kept, dropped)")
        self.join_condition_seconds = r.counter(
            "stream_join_condition_seconds",
            "host seconds an inner join spent evaluating its own "
            "condition on its matched pairs, by join "
            "(trace_ctx.join_condition_span)")
        self.hop_rows = r.counter(
            "stream_hop_rows",
            "rows into and out of a HOP window expansion, by dir (in, "
            "out) and table (t<state table id> of the aggregate the "
            "planner put over it), fused into a kernel or not")
        self.join_probe_chain = r.gauge(
            "stream_join_probe_longest_chain",
            "rows of the longest chain the last epoch probe of a join "
            "side probed, visible or not, by kernel (join.t<state table "
            "id> of the probed side), as the device step returned it")
        self.join_probe_walk_steps = r.gauge(
            "stream_join_probe_walk_steps",
            "steps the last epoch probe's walk took, by kernel: a step "
            "a run (the rows one batch gave one key) of the probed key "
            "that has the most")
        self.join_probe_candidates = r.counter(
            "stream_join_probe_candidates",
            "rows of the probed keys' chains the epoch probes expanded "
            "and tested, visible or not, by kernel")
        self.join_probe_pairs = r.counter(
            "stream_join_probe_pairs",
            "rows the epoch probes found visible at their probe row's "
            "sequence, by kernel: candidates over pairs is what "
            "tombstones and rows of a later sequence cost")
        self.batch_skew_rows = r.counter(
            "stream_batch_skew_rows",
            "visible rows a device kernel staged, by kernel "
            "(<kind>.t<state table id>)")
        self.batch_skew_distinct = r.counter(
            "stream_batch_skew_distinct_keys",
            "distinct keys among the rows a kernel staged in an epoch, "
            "summed over epochs")
        self.batch_skew_max_key = r.gauge(
            "stream_batch_skew_max_key_rows",
            "rows of the most frequent key among those a kernel staged "
            "in its last epoch")
        self.probe_insert_rounds = r.counter(
            "stream_probe_insert_rounds",
            "rounds of hash_table.probe_insert's claim loop, by kernel, "
            "as the device step returned them; a round works the rows "
            "still unplaced, in arrays of a rung of the batch's ladder")
        self.probe_insert_batches = r.counter(
            "stream_probe_insert_batches",
            "device steps whose probe_insert rounds were read, by "
            "kernel")
        self.probe_insert_row_rounds = r.counter(
            "stream_probe_insert_row_rounds",
            "rows probe_insert's rounds worked, by kernel: the sum "
            "over the rounds of the size of the arrays each ran over "
            "(a rung of the ladder, not the count still unplaced)")
        self.probe_insert_rows = r.counter(
            "stream_probe_insert_rows",
            "rows of the batches handed to probe_insert (padding "
            "included), by kernel: a single loop over the whole batch "
            "would work rounds x rows")
        self.actor_count = r.gauge("stream_actor_count", "live actors")
        self.checkpoint_count = r.counter(
            "meta_checkpoint_count", "committed checkpoints")
        self.host_state_bytes = r.gauge(
            "stream_host_state_bytes",
            "accounted host-resident state per cache "
            "(EstimateSize analog)")
        # -- per-executor instrumentation (MonitoredExecutor) ---------
        self.executor_chunks = r.counter(
            "stream_executor_chunk_count",
            "chunks emitted per (fragment, actor, executor)")
        self.executor_busy = r.counter(
            "stream_executor_busy_seconds",
            "exclusive processing time per (fragment, actor, "
            "executor) — own pull time minus wrapped inputs'")
        self.executor_epoch_seconds = r.histogram(
            "stream_executor_epoch_processing_seconds",
            "per-epoch exclusive processing time per executor")
        self.executor_empty_chunks = r.counter(
            "stream_executor_empty_chunk_count",
            "zero-visible-row chunks emitted per (fragment, actor, "
            "executor) — should stay 0; the spine suppresses empties")
        # -- chunk compaction + coalescing (stream/coalesce.py) -------
        self.device_dispatch = r.counter(
            "stream_device_dispatch_count",
            "fused device kernel dispatches per executor (the "
            "per-dispatch host cost is what coalescing amortizes)")
        self.rows_per_dispatch = r.histogram(
            "stream_rows_per_device_dispatch",
            "visible rows carried per device dispatch (dense batches "
            "amortize the per-dispatch overhead)",
            buckets=(1.0, 8.0, 32.0, 128.0, 512.0, 2048.0, 8192.0,
                     32768.0))
        self.kernel_recompile = r.counter(
            "stream_kernel_recompile_count",
            "jitted-kernel (re)traces by kernel label — nonzero "
            "during warmup, any steady-state growth is a shape-churn "
            "bug recompiling on the hot path")
        self.trace_spans_dropped = r.counter(
            "stream_trace_spans_dropped",
            "epoch-trace spans dropped over the per-epoch cap "
            "(utils/spans.py flight recorder bound)")
        self.coalesce_chunks_in = r.counter(
            "stream_coalesce_chunks_in",
            "chunks entering coalescers (ratio vs _out is the "
            "amortization factor)")
        self.coalesce_chunks_out = r.counter(
            "stream_coalesce_chunks_out",
            "chunks leaving coalescers after merging")
        self.compaction_rows_saved = r.counter(
            "stream_compaction_rows_saved",
            "padded row slots dropped by chunk compaction (capacity "
            "that no longer ships over exchanges or the wire)")
        # -- plan-rewrite engine (frontend/opt/) ----------------------
        self.rewrite_rule_fired = r.counter(
            "rewrite_rule_fired_total",
            "plan-rewrite rule applications by rule (frontend/opt "
            "fixpoint engine; a FALLBACK records 0 fires)")
        self.plan_columns_pruned = r.counter(
            "plan_columns_pruned",
            "column lanes removed from plans by the column-pruning "
            "rewrite (narrower joins, exchanges and agg feeds)")
        self.plan_exchanges_elided = r.counter(
            "plan_exchanges_elided",
            "hash exchanges removed because the producer's "
            "distribution already satisfied the consumer's keys")
        # -- exchange edges (permit.rs back-pressure analog) ----------
        self.exchange_backpressure = r.counter(
            "stream_exchange_backpressure_seconds",
            "time senders spent acquiring permits per edge "
            "(stream_exchange_backpressure analog)")
        # -- freshness & bottleneck attribution (ISSUE 14) ------------
        self.backpressure_wait = r.counter(
            "stream_backpressure_wait_seconds",
            "sender-side credit park time per channel — wall time a "
            "sender spent BLOCKED for exchange credits (subtracted "
            "from the parking executor's busy time, so straggler "
            "diagnoses stop blaming the victim of a slow consumer)")
        self.executor_utilization = r.gauge(
            "stream_executor_utilization_ratio",
            "utilization tricolor per (fragment, actor, executor, "
            "node) and state=busy|backpressure|idle: the share of the "
            "last barrier interval spent processing / parked on "
            "downstream credits / parked waiting for input; the "
            "triple sums to <= 1.0 (gated in tier-1 strict mode)")
        self.mv_freshness_lag = r.gauge(
            "stream_mv_freshness_lag_seconds",
            "per-MV event-time freshness lag at the last barrier: "
            "source ingest high-watermark minus the event-time "
            "frontier of what the MV has materialized (seconds of "
            "event time the reader is behind the data)")
        self.mv_freshness_wall_lag = r.gauge(
            "stream_mv_freshness_wall_lag_seconds",
            "per-MV wall-clock freshness lag at the last barrier: "
            "now minus the wall stamp of the newest ingested data "
            "visible in the MV")
        self.bottleneck_streak = r.gauge(
            "stream_bottleneck_streak",
            "contiguous barriers the named operator has been its "
            "domain's walked bottleneck (stream/bottleneck.py); the "
            "series resets when the walk names another operator")
        self.exchange_send_count = r.counter(
            "stream_exchange_send_count",
            "messages sent per exchange edge")
        self.exchange_queue_depth = r.gauge(
            "stream_exchange_queue_depth",
            "messages queued per exchange edge")
        # -- barrier-loop breakdown (epoch profiler) ------------------
        self.barrier_inject_to_collect = r.histogram(
            "meta_barrier_inject_to_collect_seconds",
            "inject→collect time per barrier")
        self.barrier_collect_to_commit = r.histogram(
            "meta_barrier_collect_to_commit_seconds",
            "collect→commit (seal+sync) time per barrier")
        self.barrier_in_flight = r.gauge(
            "meta_barrier_in_flight_count",
            "injected-but-uncollected barriers")
        # -- state tiering (state/tier.py cold tier) ------------------
        self.state_tier_resident = r.gauge(
            "state_tier_resident_keys",
            "hot-tier resident keys per registered executor cache")
        self.state_tier_evicted = r.counter(
            "state_tier_evicted_keys",
            "keys evicted to the cold (state-table) tier per executor")
        self.state_tier_reloads = r.counter(
            "state_tier_reload_keys",
            "evicted keys reloaded on touch per executor (the "
            "degrade-to-reload-traffic path)")
        self.state_tier_bytes = r.gauge(
            "state_tier_resident_bytes",
            "accounted host bytes of tier-governed caches per executor")
        # -- async checkpoint pipeline (storage/uploader.py) ----------
        self.barrier_upload = r.histogram(
            "meta_barrier_upload_seconds",
            "seal→durable-commit time per checkpoint epoch (the "
            "async upload tail, overlapped with later barriers)")
        self.uploader_queue_depth = r.gauge(
            "meta_checkpoint_uploader_queue_depth",
            "checkpoint epochs sealed but not yet durably committed")
        # -- exactly-once sinks (meta/sink_coordinator.py) ------------
        self.sink_committed_epoch = r.gauge(
            "sink_committed_epoch",
            "newest manifest-committed epoch per sink — visibility is "
            "manifest-existence, so this IS the sink's read frontier")
        self.sink_rows_total = r.counter(
            "sink_rows_total",
            "records durably staged per sink and mode (append|upsert; "
            "upsert counts post-fold records — one per touched key "
            "per epoch)")
        self.sink_staged_bytes = r.counter(
            "sink_staged_bytes",
            "segment bytes durably staged per sink (committed and "
            "not-yet-committed epochs both count; staging precedes "
            "the checkpoint floor by design)")
        # -- epoch phase ledger (utils/ledger.py) ---------------------
        self.epoch_phase_seconds = r.counter(
            "stream_epoch_phase_seconds",
            "barrier wall-clock attributed per phase "
            "(host_ingest/host_pack/h2d/device_compute/d2h/host_emit/"
            "barrier_wait; the conservation residual publishes as "
            "phase=unattributed)")
        # the ledger's second coordinate: the phases' seconds filed a
        # second time, never added to them (utils/ledger.py docstring)
        self.exec_phase_seconds = r.counter(
            "stream_exec_phase_seconds",
            "each executor kind's exclusive busy seconds by ledger "
            "phase, its residue under host_emit/host_ingest "
            "(rw_metrics_history exec_phase.<Kind>.<phase>)")
        self.phase_stage_seconds = r.counter(
            "stream_phase_stage_seconds",
            "a phase's seconds by the stage that spent them, e.g. "
            "host_emit by agg.persist / join.pairs / state.write "
            "(rw_metrics_history stage.<phase>.<stage>)")
        self.device_host_seconds = r.counter(
            "stream_device_host_seconds",
            "device_compute as the host spent it: stage=launch "
            "(enqueueing a program) or wait (standing still in "
            "fetch), by the kernel label the host stood under "
            "(rw_metrics_history device.<stage>.<kernel>)")
        self.transfer_bytes = r.counter(
            "stream_transfer_bytes_total",
            "host<->device transfer payload bytes by direction "
            "(dir=h2d|d2h) and kernel")
        self.backlog_rows = r.gauge(
            "stream_epoch_backlog_rows",
            "rows carried by the kernel's most recent epoch-batched "
            "dispatch (set at each backlog flush; sampled at every "
            "epoch seal as the Perfetto backlog counter track — the "
            "per-epoch staging volume, not a live queue depth)")
        self.mesh_exchange_launches = r.counter(
            "mesh_exchange_launch_count",
            "SPMD launches that hold an all_to_all exchange "
            "(parallel/exchange.py), by sharded kernel")
        self.mesh_exchange_slots = r.counter(
            "mesh_exchange_slots_total",
            "row slots the exchanges' all_to_alls carried: n_dev x "
            "n_dev x bucket per launch, rows plus padding, by kernel")
        self.mesh_exchange_rows_routed = r.counter(
            "mesh_exchange_rows_routed_total",
            "valid rows the senders put into an exchange")
        self.mesh_exchange_rows_received = r.counter(
            "mesh_exchange_rows_received_total",
            "rows a shard received from the exchanges, by shard")
        self.mesh_exchange_bucket = r.gauge(
            "mesh_exchange_bucket_rows",
            "routing bucket (rows per sender and target) of the "
            "kernel's latest exchange launch; a new value is a new "
            "compiled program")
        self.kernel_flops = r.gauge(
            "device_kernel_flops",
            "XLA cost-analysis flops of the last-compiled program per "
            "kernel label (published lazily: ctl phases)")
        self.kernel_bytes_accessed = r.gauge(
            "device_kernel_bytes_accessed",
            "XLA cost-analysis bytes-accessed of the last-compiled "
            "program per kernel label")
        # -- per-MV cost attribution (stream/costs.py, ISSUE 16) ------
        self.mv_device_seconds = r.counter(
            "stream_mv_device_seconds_total",
            "device_compute seconds attributed to the owning MV "
            "(executor-cell split of the phase ledger's books — sums "
            "to at most the ledgered device_compute per epoch)")
        self.mv_state_bytes = r.gauge(
            "stream_mv_state_bytes",
            "accounted state bytes per MV (per-(table,vnode) topology "
            "rollup, refreshed at each checkpoint)")
        self.mv_transfer_bytes = r.counter(
            "stream_mv_transfer_bytes_total",
            "host<->device transfer payload bytes attributed to the "
            "owning MV, by direction (dir splits like "
            "stream_transfer_bytes_total)")


class ClusterMetrics:
    """Cluster control-plane metric family (meta recovery +
    heartbeat/RPC liveness — the supervisor's evidence trail)."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        r = registry or GLOBAL
        self.recovery_total = r.counter(
            "recovery_total",
            "cluster recoveries by cause and action (respawn = dead "
            "slots restarted in place, full = kill-and-redeploy); "
            "absorbed transient faults do NOT count here")
        self.recovery_duration = r.histogram(
            "recovery_duration_seconds",
            "failure-detected → cluster-recovered time per recovery "
            "(MTTR samples)")
        self.rpc_retry = r.counter(
            "rpc_retry_total",
            "idempotent worker-control RPCs retried after a "
            "reconnect (transient faults absorbed below the "
            "supervisor), by verb")
        self.worker_expired = r.counter(
            "cluster_worker_expired_total",
            "workers evicted by heartbeat lease expiry, by worker id")
        self.autoscaler_decision = r.counter(
            "autoscaler_decision_total",
            "autoscaler scaling decisions by mv and direction "
            "(up/down); every completed action counts here, including "
            "ones later rolled back")
        self.autoscaler_rollback = r.counter(
            "autoscaler_rollback_total",
            "autoscaler actions rolled back to the prior parallelism "
            "(failed, timed-out, or health-failing rescales), by mv")


class StorageMetrics:
    """Storage-tier metric family (state_store/object_store analog)."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        r = registry or GLOBAL
        self.block_cache_hits = r.counter(
            "state_store_block_cache_hit_count",
            "block-cache hits (sstable_store block_cache analog)")
        self.block_cache_misses = r.counter(
            "state_store_block_cache_miss_count",
            "block-cache misses → ranged object-store reads")
        self.sst_upload_count = r.counter(
            "state_store_sst_upload_count",
            "SSTs built and uploaded at checkpoint sync")
        self.sst_upload_bytes = r.counter(
            "state_store_sst_upload_bytes",
            "bytes of SST data uploaded")
        self.sst_build_entries = r.counter(
            "state_store_sst_build_entries",
            "entries the checkpoint build put into SSTs, by path "
            "(columnar: a table's batch encoded by the column inside the "
            "native library; row: value_codec.encode_row per entry, for "
            "a batch whose columns cannot go as arrays, and for every "
            "entry where the native library is not loaded)")
        self.sst_upload_retries = r.counter(
            "state_store_sst_upload_retry_count",
            "checkpoint SST uploads retried after a transient failure")
        self.object_store_retries = r.counter(
            "object_store_retry_total",
            "object-store ops retried after a transient fault "
            "(RetryingObjectStore jittered-backoff absorption), by op")
        self.object_store_ops = r.counter(
            "object_store_operation_count",
            "object-store operations by op (upload/read/read_range)")
        self.object_store_latency = r.histogram(
            "object_store_operation_latency_seconds",
            "object-store operation latency by op")
        self.compaction_bytes_read = r.counter(
            "compaction_bytes_read",
            "bytes of SST input read by compaction merges, by arm "
            "(inline/dedicated) — the write-amplification numerator's "
            "read side")
        self.compaction_bytes_written = r.counter(
            "compaction_bytes_written",
            "bytes of SST output written by compaction merges, by arm "
            "(inline/dedicated); written/ingested = write amplification")
        self.compaction_merge_entries = r.counter(
            "compaction_merge_entries",
            "SST entries read by compaction merges, by path "
            "(native: whole columnar runs inside the native library; "
            "python: the row-at-a-time twin, storage/merge.py)")
        self.compaction_pending_tasks = r.gauge(
            "compaction_pending_tasks",
            "compaction tasks currently pending or running in the "
            "CompactionManager (dedicated arm)")
        self.storage_space_amp = r.gauge(
            "storage_space_amp",
            "space amplification: (manifest-live + retired-on-disk) "
            "bytes / manifest-live bytes — 1.0 when the pin-gated "
            "vacuum is caught up")


STREAMING = StreamingMetrics()
STORAGE = StorageMetrics()
CLUSTER = ClusterMetrics()


def note_join_condition(join: str, rows_in: int, kept: int) -> None:
    """File the pairs a join's own condition saw under that join
    (HashJoinExecutor._pairs_chunk)."""
    if kept:
        STREAMING.join_condition_rows.inc(float(kept), table=join,
                                          result="kept")
    if rows_in > kept:
        STREAMING.join_condition_rows.inc(float(rows_in - kept),
                                          table=join, result="dropped")


def note_hop_rows(table: str, rows_in: int, rows_out: int) -> None:
    """File the rows into and out of one HOP expansion."""
    if rows_in:
        STREAMING.hop_rows.inc(float(rows_in), table=table, dir="in")
    if rows_out:
        STREAMING.hop_rows.inc(float(rows_out), table=table, dir="out")


class MetricsHistory:
    """Bounded per-barrier time series: last N barriers × selected
    counter DELTAS and gauge values (arxiv 1904.03800's concurrent-
    bookkeeping stance: the control loop reads history, not one
    instantaneous scrape). One row lands per sealed barrier
    (utils/ledger.seal), carrying the tracked registry series plus the
    ledger's phase seconds/coverage/bytes as ``extra``. Backs the
    ``rw_metrics_history`` system table and the ROADMAP-item-3
    autoscaler's telemetry feed."""

    def __init__(self, capacity: int = 512):
        self.capacity = capacity
        from collections import deque
        self._ring = deque(maxlen=capacity)
        self._last: Dict[str, float] = {}
        self._seq = 0
        self._lock = threading.Lock()

    def _tracked(self):
        """(series name, read fn, kind) — counters report per-barrier
        deltas, gauges report the value at seal."""
        def csum(metric, **labels):
            if labels:
                return sum(v for l, v in metric.series()
                           if all(l.get(k) == val
                                  for k, val in labels.items()))
            return sum(v for _l, v in metric.series())

        S = STREAMING
        return (
            ("source_rows", lambda: csum(S.source_rows), "counter"),
            ("device_dispatches",
             lambda: csum(S.device_dispatch), "counter"),
            ("h2d_bytes",
             lambda: csum(S.transfer_bytes, dir="h2d"), "counter"),
            ("d2h_bytes",
             lambda: csum(S.transfer_bytes, dir="d2h"), "counter"),
            ("checkpoints",
             lambda: csum(S.checkpoint_count), "counter"),
            ("kernel_recompiles",
             lambda: csum(S.kernel_recompile), "counter"),
            ("exchange_backpressure_s",
             lambda: csum(S.exchange_backpressure), "counter"),
            ("uploader_queue_depth",
             lambda: S.uploader_queue_depth.get(), "gauge"),
            ("barrier_in_flight",
             lambda: S.barrier_in_flight.get(), "gauge"),
            ("backlog_rows", lambda: csum(S.backlog_rows), "gauge"),
            ("state_pk.columnar",
             lambda: S.state_pk_keys.get(path="columnar"), "counter"),
            ("state_pk.row",
             lambda: S.state_pk_keys.get(path="row"), "counter"),
            ("agg_multiset.point_reads",
             lambda: S.agg_multiset.get(event="point_reads"), "counter"),
            ("agg_multiset.rows_written",
             lambda: S.agg_multiset.get(event="rows_written"), "counter"),
            ("agg_multiset.extreme_scans",
             lambda: S.agg_multiset.get(event="extreme_scans"),
             "counter"),
            ("agg_multiset.values_scanned",
             lambda: S.agg_multiset.get(event="values_scanned"),
             "counter"),
        )

    @staticmethod
    def _mesh_exchange():
        """(series name, value now, kind) of the mesh exchange's books
        (parallel/exchange.py): none in a process that never launched
        an exchange, so a single-chip deployment's rows carry no such
        column. Beside the totals, one counter per shard (the rows it
        received) and one gauge per kernel (its routing bucket)."""
        def total(metric):
            return sum(v for _l, v in metric.series())

        S = STREAMING
        if not S.mesh_exchange_launches.series():
            return []
        pre = "mesh_exchange."
        out = [(pre + "launches", total(S.mesh_exchange_launches),
                "counter"),
               (pre + "slots_carried", total(S.mesh_exchange_slots),
                "counter"),
               (pre + "rows_routed", total(S.mesh_exchange_rows_routed),
                "counter")]
        out += [(pre + "shard_rows." + labels.get("shard", "?"), v,
                 "counter")
                for labels, v in S.mesh_exchange_rows_received.series()]
        out += [(pre + "bucket." + labels.get("kernel", "?"), v, "gauge")
                for labels, v in S.mesh_exchange_bucket.series()]
        return out

    @staticmethod
    def _batch_books():
        """(series name, value now, kind) of what the executors count
        by the batch: rows into an aggregate by op, the join ->
        aggregate hand-off, rows into and out of a join and through
        its condition, the padded rows and degree flips of a join's
        outer half and the times its tracked probe ran again, rows
        through a HOP, the pairs a DISTINCT column's
        dedup state holds, changed and made visible and the seconds of
        its write-through, the rows into and out of a top-N with its
        table's writes and what it keeps, the rows to_char was given and the strftime
        calls it made, what a watermark cleaned out
        of the state tables and expired from the join sides and the
        rows the tables keep, the rows a
        watermark filter dropped, the longest chain a join's
        probe walked, the key skew of a staged batch, the rounds of
        probe_insert's loop and the rows they worked. A plan with no
        such executor writes no such series and its rows carry no
        such column."""
        S = STREAMING
        out = [(f"agg_input_rows.{l.get('table', '?')}.{l.get('op', '?')}",
                v, "counter") for l, v in S.agg_input_rows.series()]
        for name, metric in (("join_to_agg.rows", S.join_to_agg_rows),
                             ("join_to_agg.seconds",
                              S.join_to_agg_seconds)):
            series = metric.series()
            if series:
                out.append((name, sum(v for _l, v in series), "counter"))
        for name, metric, kind in (
                ("join_input_rows.{table}.{side}.{op}",
                 S.join_input_rows, "counter"),
                ("join_output.{table}.rows", S.join_output_rows,
                 "counter"),
                ("join_condition.{table}.{result}",
                 S.join_condition_rows, "counter"),
                ("join_condition.{table}.seconds",
                 S.join_condition_seconds, "counter"),
                ("join_outer.{table}.{event}", S.join_outer_rows,
                 "counter"),
                ("join_degree_probe.{kernel}.redispatches",
                 S.join_degree_redispatches, "counter"),
                ("hop_rows.{table}.{dir}", S.hop_rows, "counter"),
                ("agg_distinct.{table}.pairs", S.agg_distinct_pairs,
                 "gauge"),
                ("agg_distinct.{table}.changed", S.agg_distinct_changed,
                 "counter"),
                ("agg_distinct.{table}.crossings",
                 S.agg_distinct_crossings, "counter"),
                ("agg_distinct.{table}.{stage}_s",
                 S.agg_distinct_seconds, "counter"),
                ("topn.{table}.{event}", S.topn_rows, "counter"),
                ("topn.{table}.{what}", S.topn_resident, "gauge"),
                ("expr_to_char.rows", S.expr_to_char_rows, "counter"),
                ("expr_to_char.formats", S.expr_to_char_formats,
                 "counter"),
                ("state_clean.{table}.cleaned", S.state_cleaned_rows,
                 "counter"),
                ("state_clean.{table}.reads", S.state_clean_reads,
                 "counter"),
                ("join_expire.{table}.rows", S.join_expired_rows,
                 "counter"),
                ("state_clean_index.{table}.seeds", S.state_clean_seeds,
                 "counter"),
                ("state_clean_index.{table}.keys",
                 S.state_clean_index_keys, "gauge"),
                ("state_resident.{table}.rows", S.state_resident_rows,
                 "gauge"),
                ("watermark.{table}", S.state_watermark, "gauge"),
                ("watermark_late.{source}.rows", S.watermark_late_rows,
                 "counter"),
                ("join_probe.{kernel}.longest_chain",
                 S.join_probe_chain, "gauge"),
                ("join_probe.{kernel}.walk_steps",
                 S.join_probe_walk_steps, "gauge"),
                ("join_probe.{kernel}.candidates",
                 S.join_probe_candidates, "counter"),
                ("join_probe.{kernel}.pairs", S.join_probe_pairs,
                 "counter")):
            out += [(name.format(**l), v, kind)
                    for l, v in metric.series()]
        for field, metric, kind in (
                ("rows", S.batch_skew_rows, "counter"),
                ("distinct", S.batch_skew_distinct, "counter"),
                ("max_key", S.batch_skew_max_key, "gauge")):
            out += [(f"batch_skew.{l.get('kernel', '?')}.{field}", v, kind)
                    for l, v in metric.series()]
        for field, metric in (("rounds", S.probe_insert_rounds),
                              ("batches", S.probe_insert_batches)):
            out += [(f"probe_insert.{l.get('kernel', '?')}.{field}", v,
                     "counter") for l, v in metric.series()]
        # under a prefix of their own: the reader of the rounds takes
        # every `probe_insert.<kernel>.*` that is not `.rounds` as its
        # count of batches
        for field, metric in (("row_rounds", S.probe_insert_row_rounds),
                              ("rows", S.probe_insert_rows)):
            out += [(f"probe_insert_rows.{l.get('kernel', '?')}.{field}",
                     v, "counter") for l, v in metric.series()]
        return out

    def observe(self, epoch: int, interval_s: float,
                extra: Optional[Dict[str, float]] = None,
                domain: str = "") -> None:
        values: Dict[str, float] = {}
        readings = [(name, float(fn()), kind)
                    for name, fn, kind in self._tracked()]
        for name, v, kind in readings + self._mesh_exchange() \
                + self._batch_books():
            if kind == "counter":
                values[name] = v - self._last.get(name, 0.0)
                self._last[name] = v
            else:
                values[name] = v
        shard_rows = [v for k, v in values.items()
                      if k.startswith("mesh_exchange.shard_rows.")]
        if shard_rows:
            # what the fullest shard received of the epoch's exchanges,
            # and what a shard received on average
            values["mesh_exchange.rows_max_shard"] = max(shard_rows)
            values["mesh_exchange.rows_mean_shard"] = \
                sum(shard_rows) / len(shard_rows)
        if extra:
            values.update(extra)
        with self._lock:
            self._seq += 1
            self._ring.append((self._seq, int(epoch), time.time(),
                               float(interval_s), values, domain))

    def amend(self, epoch: int, values: Dict[str, float]) -> None:
        """Add names to the row(s) of an epoch already in the ring:
        what is known only after the seal (the checkpoint's stage
        times land with its durable commit). An epoch the ring has
        rolled past is left alone."""
        with self._lock:
            self._values_of(epoch).update(values)

    def value(self, epoch: int, name: str) -> Optional[float]:
        """One name of one epoch's row; None where the ring has no
        such row or the row no such name."""
        with self._lock:
            return self._values_of(epoch).get(name)

    def _values_of(self, epoch: int) -> Dict[str, float]:
        """The values of the epoch's row (epochs are unique in the
        ring), or a throw-away dict. Caller holds the lock."""
        for row in reversed(self._ring):
            if row[1] == epoch:
                return row[4]
        return {}

    def rows(self) -> List[tuple]:
        """(seq, epoch, ts, interval_s, name, value, domain)
        long-format rows — the rw_metrics_history system-table
        payload. ``domain`` names the barrier domain whose seal
        produced the row ("" = the global domain), so the ROADMAP-3
        autoscaler can see WHICH domain is behind, not just the
        cluster aggregate."""
        with self._lock:
            snap = list(self._ring)
        out = []
        for seq, epoch, ts, interval_s, values, domain in snap:
            for name in sorted(values):
                out.append((seq, epoch, ts, interval_s, name,
                            float(values[name]), domain))
        return out

    def domain_rows(self, domain: str) -> List[tuple]:
        """The rows of one barrier domain (autoscaler convenience)."""
        return [r for r in self.rows() if r[6] == domain]

    def barriers(self) -> int:
        return len(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._last.clear()
            self._seq = 0


# the process-global per-barrier history ring (fed at ledger seal)
HISTORY = MetricsHistory()
