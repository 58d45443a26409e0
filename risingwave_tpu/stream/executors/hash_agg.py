"""HashAggExecutor: streaming GROUP BY on device-resident state.

Reference parity: src/stream/src/executor/hash_agg.rs:67 (executor),
:329 (apply_chunk), :445 (flush_data) and the value-state encoding of
aggregation/agg_group.rs. The TPU re-design moves the per-row group map
into HBM (ops/hash_agg.py); this executor is the thin host driver:

  chunk    → build int32 key/input lanes (ops/lanes.py codecs), one
             jitted device step
  barrier  → one device gather of dirty groups → emit change chunk,
             persist physical rows through the StateTable, commit epoch

Emission semantics match flush_data: first touch of a group emits Insert,
subsequent changes emit an UpdateDelete/UpdateInsert pair, a group whose
row count drops to zero emits Delete. Outputs are compared against the
device-resident emitted snapshot, so repeated no-op touches emit nothing.

Value-state row layout (physical): group keys | group_rows | per call
(value [+ non-null count]). Recovery reloads the table and re-encodes it
into the kernel (``GroupedAggKernel.rebuild``).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import (
    AsyncIterator, Dict, List, Optional, Sequence, Tuple,
)

import numpy as np

from risingwave_tpu.common.chunk import (
    Column, Op, StreamChunk, next_pow2,
)
from risingwave_tpu.common.types import DataType, Field, Schema
from risingwave_tpu.ops.hash_agg import (
    HOST_AGG_KINDS, AggKind, AggSpec, GroupedAggKernel, acc_dtypes,
)
from risingwave_tpu.state.state_table import StateTable
from risingwave_tpu.stream.executor import Executor, ExecutorInfo
from risingwave_tpu.stream.executors.keys import (
    LANES_PER_KEY as _LANES_PER_KEY, KeyCodec,
)
from risingwave_tpu.stream.executors.value_multiset import (
    DistinctCounts, ValueMultiset, pylist, value_order,
)
from risingwave_tpu.stream.message import (
    Barrier, Message, Watermark, is_barrier, is_chunk, is_watermark,
)
from risingwave_tpu.stream import hotkeys as _hotkeys
from risingwave_tpu.stream.trace_ctx import join_to_agg_handoff
from risingwave_tpu.utils.ledger import staged
from risingwave_tpu.utils.metrics import STREAMING as _METRICS

_MULTISET_READS = _METRICS.agg_multiset.labeled(event="point_reads")
_MULTISET_WRITTEN = _METRICS.agg_multiset.labeled(event="rows_written")
_EXTREME_SCANS = _METRICS.agg_multiset.labeled(event="extreme_scans")
_VALUES_SCANNED = _METRICS.agg_multiset.labeled(event="values_scanned")

_SUM_OUT = {
    DataType.INT16: DataType.INT64, DataType.INT32: DataType.INT64,
    DataType.INT64: DataType.INT64, DataType.DECIMAL: DataType.DECIMAL,
    DataType.FLOAT32: DataType.FLOAT64, DataType.FLOAT64: DataType.FLOAT64,
}


def _group_rows(mat: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Equal rows of an integer matrix, gathered: ``order`` sorts the
    rows by the column, first column first, equal rows in their given
    order; ``starts`` are the positions in ``order`` where a new row
    value begins. (``np.unique(axis=0)`` sorts records: ten times the
    time of a sort by the column.)"""
    order = np.lexsort(mat.T[::-1])
    by_row = mat[order]
    new = np.ones(len(order), dtype=bool)
    new[1:] = (by_row[1:] != by_row[:-1]).any(axis=1)
    return order, np.flatnonzero(new)


def agg_result_type(kind: AggKind,
                    input_type: Optional[DataType]) -> DataType:
    """Result type of one aggregate call — the ONE copy of these
    rules (AggCall.out_type and the binder's post-agg typing both
    call it; agg/mod.rs return-type derivation analog)."""
    if kind in (AggKind.COUNT, AggKind.APPROX_COUNT_DISTINCT):
        return DataType.INT64
    if kind == AggKind.STRING_AGG:
        return DataType.VARCHAR
    if kind == AggKind.ARRAY_AGG:
        return DataType.LIST
    if kind == AggKind.SUM:
        try:
            return _SUM_OUT[input_type]
        except KeyError:
            raise TypeError(f"sum over {input_type} unsupported")
    return input_type


@dataclass(frozen=True)
class AggCall:
    """Logical aggregate call (agg/mod.rs AggCall analog)."""

    kind: AggKind
    input_idx: Optional[int] = None      # None ⇒ count(*)
    # DISTINCT dedup (aggregation/distinct.rs analog): the executor
    # keeps a per-(group, value) multiset and gates the device kernel
    # so each distinct value contributes once. MIN/MAX ignore it
    # (semantically identity).
    distinct: bool = False
    # string_agg separator (ignored by other kinds)
    delimiter: str = ","
    # FILTER (WHERE c) of a DISTINCT call, as the call's own: the
    # BOOLEAN input column that holds c (NULL counts as false). It is
    # not folded into the distinct argument, so the calls DISTINCT on
    # one column share that column's dedup table and the filter decides
    # only whether a row moves this call's count of its (group, value)
    # pair. A call without DISTINCT carries none: the binder turns its
    # FILTER into a CASE over its argument.
    filter_idx: Optional[int] = None

    def out_type(self, input_schema: Schema) -> DataType:
        in_t = None if self.input_idx is None \
            else input_schema[self.input_idx].data_type
        return agg_result_type(self.kind, in_t)

    def spec(self, input_schema: Schema) -> AggSpec:
        if self.kind == AggKind.COUNT and self.input_idx is None:
            return AggSpec(AggKind.COUNT, None)
        in_t = input_schema[self.input_idx].data_type
        if self.kind in HOST_AGG_KINDS:
            return AggSpec(self.kind, np.dtype(object))
        if not in_t.is_device:
            raise TypeError(f"agg over host type {in_t} needs the host path")
        return AggSpec(self.kind, np.dtype(in_t.np_dtype))


def agg_output_schema(input_schema: Schema, group_indices: Sequence[int],
                      agg_calls: Sequence[AggCall],
                      names: Optional[Sequence[str]] = None) -> Schema:
    """Output schema: group keys then one column per agg call."""
    fields = [input_schema[i] for i in group_indices]
    for j, call in enumerate(agg_calls):
        name = names[j] if names else f"agg{j}"
        fields.append(Field(name, call.out_type(input_schema)))
    return Schema(fields)


def cleanable_type(dt: DataType) -> bool:
    """Can a watermark on a group column of this type clean state?
    Integer family only (timestamps included): the device compare runs
    on the bijective (hi, lo) i64 split, which is order-preserving for
    ints/timestamps but not for bit-cast floats."""
    nd = np.dtype(dt.np_dtype)
    return bool(np.issubdtype(nd, np.integer) or nd == np.dtype(bool))


def state_key_order(n_group: int, key_lead: int = 0) -> List[int]:
    """Group positions in the order the aggregate's state tables key
    them: ``key_lead`` first, the others as written. The planner puts
    the group column that carries a watermark there (generic/agg.rs
    ``window_col_idx``: upstream does the same whatever order GROUP BY
    was written in), because a watermark's range delete covers the
    key's first column only; 0, the written order, where none does."""
    return [key_lead] + [i for i in range(n_group) if i != key_lead]


def agg_state_schema(input_schema: Schema, group_indices: Sequence[int],
                     agg_calls: Sequence[AggCall], key_lead: int = 0
                     ) -> Tuple[Schema, List[int]]:
    """Value-state table schema + pk indices (pk = the group keys, in
    ``state_key_order``; the columns stay in the written order)."""
    fields = [input_schema[i] for i in group_indices]
    fields.append(Field("_group_rows", DataType.INT64))
    specs = [c.spec(input_schema) for c in agg_calls]
    for j, dt in enumerate(acc_dtypes(specs)):
        lt = DataType.FLOAT64 if np.issubdtype(dt, np.floating) \
            else DataType.INT64
        fields.append(Field(f"_acc{j}", lt))
    return Schema(fields), state_key_order(len(group_indices), key_lead)


def minput_state_schema(input_schema: Schema,
                        group_indices: Sequence[int], call: AggCall,
                        key_lead: int = 0
                        ) -> Tuple[Schema, List[int], List[int]]:
    """Materialized-input table for ONE retractable MIN/MAX call
    (aggregation/minput.rs analog, value-multiset form): rows are
    (group keys..., value, _cnt) with pk = (group keys in
    ``state_key_order``, value) so a prefix scan over the group yields
    the surviving values.

    Returns (schema, pk_indices, dist_key_indices)."""
    fields = [input_schema[i] for i in group_indices]
    fields.append(Field("_value", input_schema[call.input_idx].data_type))
    fields.append(Field("_cnt", DataType.INT64))
    g = len(group_indices)
    return (Schema(fields), state_key_order(g, key_lead) + [g],
            list(range(g)))


def distinct_calls(agg_calls: Sequence[AggCall]) -> Dict[int, List[int]]:
    """The calls that dedup, by the input column they are DISTINCT on,
    each list in call order and the columns by their first call."""
    out: Dict[int, List[int]] = {}
    for j, c in enumerate(agg_calls):
        if c.distinct and c.kind in (AggKind.COUNT, AggKind.SUM):
            out.setdefault(c.input_idx, []).append(j)
    return out


def distinct_state_schema(input_schema: Schema,
                          group_indices: Sequence[int],
                          agg_calls: Sequence[AggCall],
                          calls_on_col: Sequence[int], key_lead: int = 0
                          ) -> Tuple[Schema, List[int], List[int]]:
    """Dedup table of ONE distinct input column
    (aggregation/distinct.rs): rows are (group keys..., value, one
    count per DISTINCT call on the column, in call order), pk = (group
    keys in ``state_key_order``, value). Count column ``_cnt<j>`` is how
    many of the pair's rows passed call ``j``'s filter; a pair whose
    counts are all 0 is no row. Where none of the column's calls has a
    filter their counts are one number, and the table is the
    ``(..., value, _cnt)`` of ``minput_state_schema``: the layout from
    before a call carried a filter, so a state written then recovers.

    Returns (schema, pk_indices, dist_key_indices)."""
    first = agg_calls[calls_on_col[0]]
    sch, pk, dk = minput_state_schema(input_schema, group_indices, first,
                                      key_lead)
    if all(agg_calls[j].filter_idx is None for j in calls_on_col):
        return sch, pk, dk
    fields = list(sch)[:-1] + [Field(f"_cnt{j}", DataType.INT64)
                               for j in calls_on_col]
    return Schema(fields), pk, dk


def hll_state_schema(input_schema: Schema,
                     group_indices: Sequence[int], key_lead: int = 0
                     ) -> Tuple[Schema, List[int], List[int]]:
    """Dense-HLL sketch table for ONE approx_count_distinct call:
    (group keys..., _sketch BYTEA) — one packed register file per
    group, upserted per barrier for dirty groups
    (approx_count_distinct/mod.rs:35-42 parity, 2^16 registers)."""
    fields = [input_schema[i] for i in group_indices]
    fields.append(Field("_sketch", DataType.BYTEA))
    g = len(group_indices)
    return Schema(fields), state_key_order(g, key_lead), list(range(g))


def agg_aux_tables(input_schema: Schema,
                   group_indices: Sequence[int],
                   agg_calls: Sequence["AggCall"], append_only: bool,
                   store, dedup_table_id, minput_table_id,
                   key_lead: int = 0
                   ) -> Tuple[Dict[int, StateTable],
                              Dict[int, StateTable]]:
    """Build the aux state tables HashAggExecutor needs:
    per-DISTINCT-column dedup tables and per-call materialized-input
    tables (retractable MIN/MAX + host aggs). The ONE selection rule
    shared by the planner and the shipped-plan factory — both callers
    must agree or the same query gets different state tables.
    ``key_lead`` is the value-state table's (``state_key_order``): every
    table of one aggregate is cleaned on the same column.

    ``dedup_table_id(input_idx)`` / ``minput_table_id(call_idx)``
    supply ids. Iteration order is dedup tables first (call order,
    first DISTINCT occurrence per column), then minput tables in call
    order — the planner's sequential-id replay contract (ALTER
    PARALLELISM re-plans from a recorded id base) depends on it. A
    column has ONE dedup table however many calls are DISTINCT on it
    and whatever their filters (``distinct_state_schema``).

    Returns (distinct_tables, minput_tables)."""
    distinct_tables: Dict[int, StateTable] = {}
    for col, js in distinct_calls(agg_calls).items():
        dsch, dpk, ddk = distinct_state_schema(
            input_schema, group_indices, agg_calls, js, key_lead)
        distinct_tables[col] = StateTable(
            dedup_table_id(col), dsch, dpk, store, dist_key_indices=ddk)
    minput_tables: Dict[int, StateTable] = {}
    for j, c in enumerate(agg_calls):
        if c.kind == AggKind.APPROX_COUNT_DISTINCT:
            hsch, hpk, hdk = hll_state_schema(input_schema,
                                              group_indices, key_lead)
            # sanity off: sketch rows are blind upserts (same pk,
            # newer epoch shadows)
            minput_tables[j] = StateTable(
                minput_table_id(j), hsch, hpk, store,
                dist_key_indices=hdk, sanity_check=False)
    for j, c in enumerate(agg_calls):
        # retractable MIN/MAX need the value multiset; host aggs
        # (string_agg/array_agg) ARE their value multiset
        if ((c.kind in (AggKind.MIN, AggKind.MAX)
             and not append_only) or c.kind in HOST_AGG_KINDS):
            msch, mpk, mdk = minput_state_schema(
                input_schema, group_indices, c, key_lead)
            minput_tables[j] = StateTable(
                minput_table_id(j), msch, mpk, store,
                dist_key_indices=mdk)
    return distinct_tables, minput_tables


class HashAggExecutor(Executor):
    """Streaming hash aggregation over a device kernel (hash_agg.rs:67)."""

    def __init__(self, input_: Executor, group_indices: Sequence[int],
                 agg_calls: Sequence[AggCall], table: StateTable,
                 append_only: bool = False,
                 output_names: Optional[Sequence[str]] = None,
                 minput_tables: Optional[Dict[int, StateTable]] = None,
                 actor_id: int = 0,
                 kernel: Optional[object] = None,
                 distinct_tables: Optional[Dict[int, StateTable]] = None,
                 kernel_capacity: Optional[int] = None,
                 flush_capacity: Optional[int] = None,
                 tier_cap: Optional[int] = None,
                 fused_stages=None):
        self.input = input_
        self.group_indices = list(group_indices)
        self.agg_calls = list(agg_calls)
        self.table = table
        # the label of this executor's per-batch books (utils/metrics
        # MetricsHistory._batch_books)
        self._books_table = f"t{table.table_id}"
        # the planner's mark: this aggregate was planned over a hash
        # join, so its ingest is a leg of the join -> aggregate hand-off
        self.fed_by_join = False
        self.append_only = append_only
        # fragment fusion (ops/fused.py): when set, `input_` is the RAW
        # upstream and the filter/project run in `fused_stages` inlines
        # into the kernel's jitted apply (one dispatch per batch, state
        # donated). Every index below (group, call inputs, schemas)
        # lives in the POST-stage column space.
        self.fused_stages = fused_stages
        in_schema = input_.schema if fused_stages is None \
            else fused_stages.out_schema
        self.group_types = [in_schema[i].data_type
                            for i in self.group_indices]
        # varchar/host-typed group keys go through the exact interning
        # codec (keys.py KeyCodec; key.rs:647 KeySerialized parity)
        self.key_codec = KeyCodec(self.group_types)
        self.specs = [c.spec(in_schema) for c in self.agg_calls]
        # retractable MIN/MAX: device extremes go stale on deletes; the
        # value multisets (minput.rs analog) let the flush recompute
        # and patch them (see _recompute_extremes). Each multiset lives
        # in memory (value_multiset.py) and is written through to its
        # materialized-input table once a barrier
        self.minput: Dict[int, StateTable] = dict(minput_tables or {})
        self._deleted_lanes: set = set()
        # per-epoch buffered value-multiset deltas: call → (group,
        # value) → delta, applied to the multiset where it is written
        # through (a host agg's PREV output reads the multiset as of
        # the last barrier)
        self._minput_pending: Dict[int, Dict[tuple, int]] = {}
        # DISTINCT dedup (distinct.rs): ONE durable table of (group,
        # value, counts) rows + its copy in memory per distinct INPUT
        # COLUMN — count(DISTINCT x) and sum(DISTINCT x) share it, and
        # so do the calls that differ in their FILTER only, each with a
        # count of its own (``distinct_state_schema``), like the
        # reference's per-column dedup tables
        self.distinct_tables: Dict[int, StateTable] = dict(
            distinct_tables or {})
        self._distinct_cols = distinct_calls(self.agg_calls)
        missing_d = [col for col in self._distinct_cols
                     if col not in self.distinct_tables]
        if missing_d:
            raise ValueError(
                f"DISTINCT column(s) {missing_d} need dedup state "
                "tables — pass distinct_tables keyed by input column "
                "(distinct_state_schema shape)")
        # per column, the filter column of each count of its table (None:
        # every row counts) and the count each of its calls reads: the
        # calls of a column none of whose calls has a filter share one
        self._distinct_filters: Dict[int, List[Optional[int]]] = {}
        self._distinct_slot: Dict[int, List[int]] = {}
        for col, js in self._distinct_cols.items():
            filters = [self.agg_calls[j].filter_idx for j in js]
            shared = all(f is None for f in filters)
            self._distinct_filters[col] = [None] if shared else filters
            self._distinct_slot[col] = [0] * len(js) if shared \
                else list(range(len(js)))
        self._distinct_mult: Dict[int, DistinctCounts] = {
            col: DistinctCounts(len(self._distinct_filters.get(col, [None])))
            for col in self.distinct_tables}
        # the label of each dedup table's books (stream_agg_distinct_*)
        self._distinct_label = {col: f"t{t.table_id}"
                                for col, t in self.distinct_tables.items()}
        # per barrier and column: pair → its counts as of the last
        # barrier, for the pairs a chunk of this epoch moved
        self._distinct_pending: Dict[int, Dict[tuple, tuple]] = {}
        # incremental live-group count (gates interner GC cheaply)
        self._live_groups = 0
        # host-state accounting (memory_manager.rs analog)
        import weakref

        from risingwave_tpu.utils import memory as _mem
        mem_name = f"HashAggExecutor#{id(self)}"   # identity not set yet
        wref = weakref.ref(self)

        def _nbytes() -> int:
            s = wref()
            if s is None:
                _mem.GLOBAL.unregister(mem_name)
                return 0
            distinct = sum(120 * len(m)
                           for m in s._distinct_mult.values())
            minput = sum(120 * len(m)
                         for m in s._minput_mult.values())
            pend = sum(120 * len(m)
                       for m in s._minput_pending.values())
            from risingwave_tpu.ops.hash_agg import HLL_M as _M
            sketches = sum((_M + 120) * len(d)
                           for d in s._hll_regs.values())
            cold = 120 * len(getattr(s, "_cold_groups", ()))
            return (s.key_codec.interner_nbytes() + distinct + minput
                    + pend + sketches + cold)

        _mem.GLOBAL.register(mem_name, _nbytes)
        # dense-HLL calls: sketch registry host-side, one BYTEA aux
        # table per call (transported in the minput dict by
        # agg_aux_tables; split here — the multiset write paths must
        # never touch a sketch table)
        self._hll_calls = [j for j, s in enumerate(self.specs)
                           if s.kind == AggKind.APPROX_COUNT_DISTINCT]
        self.hll_tables: Dict[int, StateTable] = {
            j: self.minput.pop(j) for j in self._hll_calls
            if j in self.minput}
        self._minput_mult: Dict[int, ValueMultiset] = {
            j: ValueMultiset() for j in self.minput}
        missing_s = [j for j in self._hll_calls
                     if j not in self.hll_tables]
        if missing_s:
            raise ValueError(
                "approx_count_distinct needs a sketch state table per "
                f"call ({missing_s}) — pass minput_tables from "
                "agg_aux_tables (hll_state_schema)")
        # per-call: group tuple → uint8[HLL_M] registers; prev emitted
        # estimate; groups dirty since the last barrier
        self._hll_regs: Dict[int, Dict[tuple, np.ndarray]] = {
            j: {} for j in self._hll_calls}
        self._hll_prev: Dict[int, Dict[tuple, int]] = {
            j: {} for j in self._hll_calls}
        self._hll_dirty: Dict[int, set] = {
            j: set() for j in self._hll_calls}
        # host aggs (string_agg/array_agg) always need the value
        # multiset — their output IS the multiset
        self._host_calls = [j for j, s in enumerate(self.specs)
                            if s.kind in HOST_AGG_KINDS]
        missing_h = [j for j in self._host_calls if j not in self.minput]
        if missing_h:
            raise ValueError(
                f"{[self.specs[j].kind.value for j in missing_h]} need "
                "materialized-input state tables — pass minput_tables "
                "(see minput_state_schema)")
        if not append_only:
            need = [j for j, s in enumerate(self.specs)
                    if s.kind in (AggKind.MIN, AggKind.MAX)]
            missing = [j for j in need if j not in self.minput]
            if missing:
                raise ValueError(
                    "retractable min/max needs materialized-input state "
                    f"tables for call(s) {missing} — pass minput_tables "
                    "(see minput_state_schema) or append_only=True")
            if any(s.kind == AggKind.APPROX_COUNT_DISTINCT
                   for s in self.specs):
                raise ValueError(
                    "approx_count_distinct needs an append-only "
                    "upstream — an HLL sketch cannot retract")
        # kernel injection: the planner passes a vnode-sharded kernel
        # (parallel/agg.ShardedAggKernel) when parallelism > 1 — same
        # host surface, SPMD launch shape (dispatch.rs:582's hash
        # exchange becomes the in-kernel all_to_all)
        # capacity/flush presize: growth doublings and flush-buffer
        # bumps each cost a fresh XLA compile — builders that know
        # their cardinality pass hints and skip the ladder entirely.
        # Construction is LAZY (first data touch): building device
        # state here would initialize the JAX backend — and claim the
        # TPU — in processes that only PLAN (the distributed frontend
        # serializes this executor to IR and throws it away)
        self._kern_kw = {}
        if kernel_capacity is not None:
            self._kern_kw["capacity"] = kernel_capacity
        if flush_capacity is not None:
            self._kern_kw["flush_capacity"] = flush_capacity
        self._kernel = kernel
        if kernel is not None and hasattr(kernel, "table_id"):
            # an injected sharded kernel goes by its state table in
            # the exchange's books and in rw_mesh_tables
            kernel.table_id = table.table_id
        # watermark-driven state cleaning (state_table.rs:894 analog):
        # the group positions in the order the state tables key them
        # (the planner's choice, ``state_key_order``: a group column
        # that carries a watermark leads, whatever order GROUP BY was
        # written in), the latest watermark seen on the leading one —
        # the only column a range delete covers, the reference's prefix
        # rule — and the last value already applied to kernel and tables
        self._key_order = list(table.pk_indices)
        self.key_lead = self._key_order[0]
        self._clean_wm: Optional[int] = None
        self._cleaned_wm: Optional[int] = None
        out_schema = agg_output_schema(in_schema, group_indices, agg_calls,
                                       output_names)
        super().__init__(ExecutorInfo(
            out_schema, list(range(len(group_indices))),
            f"HashAggExecutor(actor={actor_id})"))
        # cold-tier participation (state/tier.py): groups past the cap
        # evict — device slots + host mirrors (distinct multisets, HLL
        # registers) drop, the value-state/aux tables stay durable —
        # and a later touch of an evicted group reloads it before the
        # chunk applies. Agg state is FULLY durable, so reload-on-touch
        # is retraction-safe (a delete touching a cold group reloads
        # first, then retracts normally). Single-chip lazy kernel only:
        # the sharded kernel's vnode routing has no targeted-evict path.
        self._tier = None
        self._tier_part = None
        self._cold_groups: Dict[tuple, tuple] = {}
        self._tier_seq = 0            # barrier counter = LRU clock
        self.tier_cap = tier_cap      # fragmenter ships this in the IR
        if tier_cap is not None:
            if kernel is not None:
                raise ValueError(
                    "tier_cap needs the single-chip lazy kernel "
                    "(sharded kernels have no targeted-evict path)")
            from risingwave_tpu.state import tier as _tier
            self._tier = _tier.GLOBAL
            # registration is DEFERRED to execute(): plan-only
            # executors (EXPLAIN, distributed CREATEs that serialize
            # to IR and discard) must leave no ghost entries in the
            # process-global registry
            self._tier_nbytes = _nbytes
        if fused_stages is not None:
            # fusion eligibility — the rewrite rule refuses these
            # before ever mutating the plan; failing loud here guards
            # the IR-rebuild path too. THE one predicate lives in
            # opt/fusion.py (rule, checker and both executor guards
            # all call it — no drifting copies).
            from risingwave_tpu.frontend.opt.fusion import (
                agg_ineligible_reason,
            )
            r = agg_ineligible_reason(self)
            if r is not None:
                raise ValueError(f"agg is not fusion-eligible: {r}")

    @property
    def kernel(self):
        """Device kernel, built on first touch (see __init__ note —
        plan-only processes must not initialize a JAX backend)."""
        if self._kernel is None:
            kw = dict(self._kern_kw)
            if self.fused_stages is not None:
                from risingwave_tpu.ops.fused import (
                    build_agg_prelude, raw_width,
                )
                kw["prelude"] = build_agg_prelude(
                    self.fused_stages, self.group_indices,
                    self.agg_calls, self.specs)
                kw["raw_width"] = raw_width(
                    len(self.fused_stages.ref_cols))
                kw["metrics_label"] = self.identity
                if self.fused_stages.hop is not None:
                    # in-trace hop expansion: keep per-dispatch
                    # POST-expansion rows near the normal batch size
                    kw["expand_units"] = self.fused_stages.hop.units
            self._kernel = GroupedAggKernel(
                key_width=_LANES_PER_KEY * len(self.group_indices),
                specs=self.specs, **kw)
            # dispatch spans carry the executor identity even when the
            # metrics_label is unset (unfused mode counts dispatches at
            # the executor, but trace spans always stamp the kernel at
            # its real jit sites)
            self._kernel._span_label = self.identity
        elif self.fused_stages is not None and \
                getattr(self._kernel, "supports_prelude", False) and \
                self._kernel._prelude is None:
            # injected SHARDED kernel + fused plan (ISSUE 10): install
            # the prelude on first touch — the absorbed run then
            # traces ahead of the vnode routing inside the SPMD step
            from risingwave_tpu.ops.fused import (
                build_agg_prelude, raw_width,
            )
            self._kernel.set_prelude(
                build_agg_prelude(self.fused_stages,
                                  self.group_indices, self.agg_calls,
                                  self.specs),
                raw_width(len(self.fused_stages.ref_cols)),
                metrics_label=self.identity,
                prelude_key=(
                    f"{self.fused_stages.trace_key()}"
                    f"|g={self.group_indices}"
                    f"|c={[(c.kind.value, c.input_idx) for c in self.agg_calls]}"))
        return self._kernel

    @kernel.setter
    def kernel(self, k) -> None:
        self._kernel = k

    # -- fragment fusion (frontend/opt/fusion.py mutates in place) -------
    def adopt_fused_stages(self, fs, raw_input) -> None:
        """Absorb a filter/project run: `raw_input` becomes the direct
        input and `fs` (whose out_schema must equal the input schema
        this executor was planned against) runs inside the kernel's
        jitted apply. Only valid before the kernel is built."""
        from risingwave_tpu.frontend.opt.fusion import (
            agg_fusable_reason,
        )
        r = agg_fusable_reason(self)
        if r is not None:
            raise ValueError(f"agg is not fusion-eligible: {r}")
        got = [f.data_type for f in fs.out_schema]
        # fused_stages is None here (agg_fusable_reason refused
        # re-fusing above), so the planned-against schema IS the input
        want = [f.data_type for f in self.input.schema]
        if got != want:
            raise ValueError(
                f"fused stage chain emits {got}, agg planned on {want}")
        self.fused_stages = fs
        self.input = raw_input

    def drain_stage_metrics(self):
        """Per-logical-stage (identity, rows, chunks) attribution for
        the monitor; empty when unfused."""
        if self.fused_stages is None:
            return []
        return self.fused_stages.drain_stage_metrics()

    @property
    def _fused_raw_key_cols(self):
        """Raw input columns carrying the group-key VALUES through the
        absorbed run (None when any key is a computed expression) —
        cached; drives the sharded kernel's host-side owner counts."""
        if not hasattr(self, "_fused_raw_keys_cache"):
            self._fused_raw_keys_cache = None if \
                self.fused_stages is None else \
                self.fused_stages.input_positions(self.group_indices)
        return self._fused_raw_keys_cache

    # -- chunk path ------------------------------------------------------
    def _inputs(self, chunk: StreamChunk) -> Tuple:
        """Per call: (host input lane arrays, valid mask) — the kernel
        packs everything into one int32 matrix (one transfer)."""
        out = []
        for call, spec in zip(self.agg_calls, self.specs):
            if call.input_idx is None:          # count(*)
                out.append(((), None))
                continue
            c = chunk.columns[call.input_idx]
            in_lanes = spec.encode_input(np.asarray(c.values))
            ok = np.ones(chunk.capacity, dtype=bool) \
                if c.validity is None else np.asarray(c.validity)
            out.append((in_lanes, ok))
        return tuple(out)

    @staged("agg.ingest")
    def _apply_chunk(self, chunk: StreamChunk) -> None:
        ops = np.asarray(chunk.ops)[np.asarray(chunk.visibility)]
        for op, n in enumerate(np.bincount(ops, minlength=5)):
            if n:
                _METRICS.agg_input_rows.inc(
                    float(n), table=self._books_table,
                    op=Op(op).name.lower())
        if self.fused_stages is not None:
            self.fused_stages.note_rows_in(len(ops))
            # fused fragment path: the RAW chunk ships as one int64
            # matrix; filter/project/key-encode/lane-encode all run
            # inside the kernel's jitted apply. Dispatch metrics are
            # counted by the kernel at REAL dispatch sites (one per
            # backlog flush), not per chunk: one dispatch per backlog
            # flush is what fusion buys.
            from risingwave_tpu.ops.fused import encode_raw_chunk
            raw = encode_raw_chunk(chunk, self.fused_stages.ref_cols)
            # when the group keys map to raw input columns, host-side
            # lanes serve two consumers: the heavy-hitter sketch (a
            # pre-filter superset of the grouped rows — safe when the
            # traced filter drops rows) and, for sharded kernels, the
            # skew-exact per-row owner routing bucket
            sharded = getattr(self.kernel, "counts_own_dispatches",
                              False)
            raw_keys = self._fused_raw_key_cols
            lanes = None
            if raw_keys is not None:
                lanes = self.key_codec.build(chunk, raw_keys)
                _hotkeys.HOTKEYS.observe(
                    self.identity, lanes,
                    np.asarray(chunk.visibility), self.key_codec)
            if sharded:
                owners = None if lanes is None \
                    else self.kernel.owners_of(lanes)
                self.kernel.apply_raw(raw, chunk.cardinality(),
                                      owners=owners)
            else:
                self.kernel.apply_raw(raw, chunk.cardinality())
            return
        key_lanes = self.key_codec.build(chunk, self.group_indices)
        signs = np.asarray(chunk.signs())
        vis = np.asarray(chunk.visibility)
        # heavy-hitter sketch over the agg's group keys: the lanes
        # are already built for the kernel — the sketch adds one
        # hash+unique pass over the visible rows
        _hotkeys.HOTKEYS.observe(self.identity, key_lanes, vis,
                                 self.key_codec)
        if self._tier is not None:
            self._tier_touch(key_lanes, vis)
        # one kernel.apply below = one fused device dispatch: the
        # metric pair the coalescing layer optimizes — fewer
        # dispatches, denser rows per dispatch.
        # Sharded kernels count at their own jit sites instead
        # (kernel="sharded_agg", real epoch-batched launches).
        if not getattr(self.kernel, "counts_own_dispatches", False):
            _METRICS.device_dispatch.inc(1, executor=self.identity)
            _METRICS.rows_per_dispatch.observe(float(vis.sum()),
                                               executor=self.identity)
        inputs = list(self._inputs(chunk))
        if self.minput:
            self._apply_minput(chunk, key_lanes, signs, vis)
        for col, js in self._distinct_cols.items():
            _in_lanes0, ok0 = inputs[js[0]]
            masks = self._apply_distinct(col, chunk, key_lanes, signs,
                                         vis & ok0)
            for j, slot in zip(js, self._distinct_slot[col]):
                inputs[j] = (inputs[j][0], masks[slot])
        self.kernel.apply(key_lanes, signs, vis, tuple(inputs))
        for j in self._hll_calls:
            self._apply_hll(j, chunk, key_lanes, signs, vis)

    def _apply_hll(self, j: int, chunk: StreamChunk,
                   key_lanes: np.ndarray, signs: np.ndarray,
                   vis: np.ndarray) -> None:
        """Scatter-max this chunk's rows into the per-group dense
        register files (vectorized; python work is O(groups in
        chunk))."""
        from risingwave_tpu.ops.hash_agg import hll_lanes
        from risingwave_tpu.stream.executors.keys import to_i64

        call = self.agg_calls[j]
        c = chunk.columns[call.input_idx]
        ok = vis if c.validity is None \
            else (vis & np.asarray(c.validity))
        rows = np.flatnonzero(ok)
        if not len(rows):
            return
        if (signs[rows] < 0).any():
            raise ValueError(
                "approx_count_distinct saw a retraction — the sketch "
                "is append-only (guarded at construction)")
        reg, rho = hll_lanes(to_i64(np.asarray(c.values)[rows]))
        rho8 = rho.astype(np.uint8)
        _uniq, inverse = np.unique(key_lanes[rows], axis=0,
                                   return_inverse=True)
        order = np.argsort(inverse, kind="stable")
        starts = np.searchsorted(inverse[order],
                                 np.arange(len(_uniq), dtype=np.int64))
        ends = np.append(starts[1:], len(order))
        g_cols = [(np.asarray(chunk.columns[i].values),
                   None if chunk.columns[i].validity is None
                   else np.asarray(chunk.columns[i].validity))
                  for i in self.group_indices]
        regs_d, dirty = self._hll_regs[j], self._hll_dirty[j]
        from risingwave_tpu.ops.hash_agg import HLL_M
        for u in range(len(_uniq)):
            r0 = int(rows[order[starts[u]]])
            gkey = tuple(
                None if (okc is not None and not okc[r0])
                else (gv[r0].item() if hasattr(gv[r0], "item")
                      else gv[r0])
                for gv, okc in g_cols)
            arr = regs_d.get(gkey)
            if arr is None:
                arr = regs_d[gkey] = np.zeros(HLL_M, dtype=np.uint8)
            sel = order[starts[u]:ends[u]]
            np.maximum.at(arr, reg[sel], rho8[sel])
            dirty.add(gkey)

    # -- per-(group, value) multisets (minput + distinct) ----------------
    def _multiset_groups(self, chunk: StreamChunk, key_lanes: np.ndarray,
                         signs: np.ndarray, ok: np.ndarray,
                         input_idx: int, vals_override=None):
        """Vectorized grouping of visible rows by (group key, value).

        Returns (rows, deltas, keys_of, order, starts) — python work
        is O(distinct keys), not O(rows) (hash_agg.rs minput/distinct
        parity without the per-row loop). ``deltas`` is the net sign of
        each unique key; ``keys_of(us)`` gives the multiset keys
        ``(group, value)`` of the unique keys ``us``, by the column;
        ``rows[order[starts[u]:starts[u + 1]]]`` are key ``u``'s chunk
        rows in chunk order.
        """
        from risingwave_tpu.stream.executors.keys import to_i64

        rows = np.flatnonzero(ok)
        if not len(rows):
            return None
        c = chunk.columns[input_idx]
        vals = vals_override if vals_override is not None \
            else np.asarray(c.values)
        comp = np.empty((len(rows), key_lanes.shape[1] + 1),
                        dtype=np.int64)
        comp[:, :key_lanes.shape[1]] = key_lanes[rows]
        if vals.dtype == object:
            # host-typed values (string_agg/array_agg): EXACT local
            # interning for the grouping image only — ids live for this
            # call alone, so nothing accumulates across the stream (a
            # hash image could merge distinct values; a sort cannot
            # order mixed None/str)
            local: Dict[object, int] = {}
            comp[:, -1] = np.fromiter(
                (local.setdefault(v, len(local))
                 for v in vals[rows].tolist()),
                dtype=np.int64, count=len(rows))
        else:
            comp[:, -1] = to_i64(vals[rows])
        order, starts = _group_rows(comp)
        deltas = np.add.reduceat(
            signs[rows][order].astype(np.int64), starts)
        # first chunk-row index per unique key (stable order)
        first_rows = rows[order[starts]]
        g_cols = [(np.asarray(chunk.columns[i].values),
                   None if chunk.columns[i].validity is None
                   else np.asarray(chunk.columns[i].validity))
                  for i in self.group_indices]

        def keys_of(us: np.ndarray) -> List[tuple]:
            sel = first_rows[us]
            return list(zip(self._group_tuples(g_cols, sel),
                            pylist(vals[sel])))

        return rows, deltas, keys_of, order, starts

    def _apply_minput(self, chunk: StreamChunk, key_lanes: np.ndarray,
                      signs: np.ndarray, vis: np.ndarray) -> None:
        """Maintain the per-call value multisets; remember which groups
        saw deletes (only those can have stale device extremes)."""
        del_rows = np.flatnonzero(vis & (signs < 0))
        if len(del_rows):
            lanes = key_lanes[del_rows]
            order, starts = _group_rows(lanes)
            self._deleted_lanes.update(
                map(tuple, lanes[order[starts]].tolist()))
        for j in self.minput:
            call = self.agg_calls[j]
            c = chunk.columns[call.input_idx]
            vals_override = None
            if call.kind == AggKind.ARRAY_AGG:
                # pg array_agg PRESERVES NULL elements: feed them into
                # the multiset (string_agg and MIN/MAX skip NULLs); a
                # device-typed column needs an object view so NULL
                # slots carry None instead of buffer fill
                ok = vis
                if c.validity is not None and c.data_type.is_device:
                    vo = np.asarray(c.values).astype(object)
                    vo[~np.asarray(c.validity)] = None
                    vals_override = vo
            else:
                ok = vis if c.validity is None \
                    else vis & np.asarray(c.validity)
            ms = self._multiset_groups(chunk, key_lanes, signs, ok,
                                       call.input_idx,
                                       vals_override=vals_override)
            if ms is None:
                continue
            _rows, deltas, keys_of, _order, _starts = ms
            pend = self._minput_pending.setdefault(j, {})
            us = np.flatnonzero(deltas != 0)
            for key, d in zip(keys_of(us), deltas[us].tolist()):
                pend[key] = pend.get(key, 0) + d

    @staged("agg.distinct")
    def _apply_distinct(self, col: int, chunk: StreamChunk,
                        key_lanes: np.ndarray, signs: np.ndarray,
                        ok: np.ndarray) -> List[np.ndarray]:
        """DISTINCT gating (aggregation/distinct.rs), once per distinct
        column and chunk. For a row ``(op, g, v)`` with ``v`` not NULL
        and a count ``s`` of the column's table with filter ``f_s``
        (``true`` where the call has none): if ``f_s(row)`` then
        ``cnt_s[g, v] += sign(op)``; the row is visible to the calls
        that read count ``s`` iff ``cnt_s[g, v]`` crossed between 0 and
        1 in the direction of ``op``. Over a chunk that is one
        representative row of the matching sign per pair whose count
        crossed zero by the chunk's net; a pair whose counts are all 0
        leaves the table, a negative count is an error. Returns the
        valid mask per count; ``_distinct_slot`` says which a call
        takes."""
        filters = self._distinct_filters[col]
        masks = [np.zeros(chunk.capacity, dtype=bool) for _ in filters]
        ms = self._multiset_groups(chunk, key_lanes, signs, ok, col)
        if ms is None:
            return masks
        rows, net, keys_of, order, starts = ms
        srt = rows[order]          # chunk rows, a pair's rows together
        sg = signs[srt].astype(np.int64)
        passes: List[Optional[np.ndarray]] = []     # None: every row
        for f in filters:
            if f is None:
                passes.append(None)
                continue
            c = chunk.columns[f]
            p = np.asarray(c.values).astype(bool)
            if c.validity is not None:
                p = p & np.asarray(c.validity)
            passes.append(p[srt])
        deltas = np.stack(
            [net if p is None
             else np.add.reduceat(np.where(p, sg, 0), starts)
             for p in passes], axis=1)
        us = np.flatnonzero(deltas.any(axis=1))
        if not len(us):
            return masks
        keys = keys_of(us)
        mult = self._distinct_mult[col]
        old = np.asarray([mult.count(g, v) for g, v in keys],
                         dtype=np.int64)
        new = old + deltas[us]
        if (new < 0).any():
            bad = int(np.flatnonzero((new < 0).any(axis=1))[0])
            raise ValueError(
                f"distinct retract below zero for {keys[bad]}")
        pend = self._distinct_pending.setdefault(col, {})
        for (g, v), o, n in zip(keys, old.tolist(), new.tolist()):
            pend.setdefault((g, v), tuple(o))
            mult.put(g, v, tuple(n))
        eff = (new > 0).astype(np.int8) - (old > 0)
        pos = np.arange(len(srt))
        for mask, p, e in zip(masks, passes, eff.T):
            for direction in (1, -1):
                hit = us[e == direction]
                if len(hit):
                    # a row of the pair that passed the filter with the
                    # crossing's sign (there is one: the net of such
                    # rows moved the count that way)
                    cand = sg == direction
                    if p is not None:
                        cand &= p
                    first = np.minimum.reduceat(
                        np.where(cand, pos, len(srt)), starts)[hit]
                    mask[srt[first]] = True
        _METRICS.agg_distinct_crossings.inc(
            float(sum(int(m.sum()) for m in masks)),
            table=self._distinct_label[col])
        return masks

    @staticmethod
    @staged("agg.persist")
    def _write_multiset_pending(pending: Dict[int, Dict[tuple, int]],
                                tables: Dict[int, StateTable],
                                mults: Dict[int, ValueMultiset]) -> None:
        """Write one barrier's net multiset deltas through to the
        StateTables: new pairs, pairs whose count changed and pairs
        that reached zero, one batch call each. No row is read: the
        old count is the in-memory multiset's, which takes the deltas
        here (a recompute after this then sees them)."""
        for j, table in tables.items():
            deltas, mult = pending.get(j, {}), mults[j]
            ins: List[tuple] = []
            upd_old: List[tuple] = []
            upd_new: List[tuple] = []
            dels: List[tuple] = []
            for (group, value), d in deltas.items():
                if d == 0:
                    continue
                old = mult.count(group, value)
                new = old + d
                key = group + (value,)
                if old == 0:
                    assert new > 0, f"retract of unseen value {key}"
                    ins.append(key + (new,))
                elif new == 0:
                    dels.append(key + (old,))
                else:
                    upd_old.append(key + (old,))
                    upd_new.append(key + (new,))
                mult.put(group, value, new)
            table.insert_rows(ins)
            table.update_rows(upd_old, upd_new)
            table.delete_rows(dels)
            _MULTISET_WRITTEN.inc(len(ins) + len(upd_old) + len(dels))
        pending.clear()

    @staged("agg.persist")
    def _write_distinct_pending(self) -> None:
        """Write the pairs this barrier's chunks moved through to the
        dedup tables, one row a pair with all its counts: new pairs,
        pairs of which a count changed and pairs whose counts all
        reached zero, one batch call each. No row is read: a pair's old
        row is what ``_apply_distinct`` found in memory at its first
        touch this epoch, its new one what memory holds now. The
        seconds are filed by table besides the stage they fall in
        (``stream_agg_distinct_seconds``): the table's own writes
        (``state.write`` nested here) and the rest of the pass."""
        for col, table in self.distinct_tables.items():
            t0 = time.perf_counter()
            label = self._distinct_label[col]
            mult = self._distinct_mult[col]
            zero = mult.zero
            ins: List[tuple] = []
            upd_old: List[tuple] = []
            upd_new: List[tuple] = []
            dels: List[tuple] = []
            for (group, value), old in self._distinct_pending.pop(
                    col, {}).items():
                new = mult.count(group, value)
                if new == old:
                    continue
                key = group + (value,)
                if old == zero:
                    ins.append(key + new)
                elif new == zero:
                    dels.append(key + old)
                else:
                    upd_old.append(key + old)
                    upd_new.append(key + new)
            t1 = time.perf_counter()
            table.insert_rows(ins)
            table.update_rows(upd_old, upd_new)
            table.delete_rows(dels)
            t2 = time.perf_counter()
            changed = len(ins) + len(upd_old) + len(dels)
            _MULTISET_WRITTEN.inc(changed)
            _METRICS.agg_distinct_changed.inc(float(changed), table=label)
            _METRICS.agg_distinct_seconds.inc(t1 - t0, table=label,
                                              stage="persist")
            _METRICS.agg_distinct_seconds.inc(t2 - t1, table=label,
                                              stage="write")

    # -- cold tier (state/tier.py) ---------------------------------------
    def _tier_register(self) -> None:
        """Register with the global tier at execute() start — only
        executors that actually RUN appear in the registry."""
        import weakref
        tref = weakref.ref(self)

        def _evict_cb(keys):
            s = tref()
            return 0 if s is None else s._tier_evict(keys)

        self._tier_part = self._tier.register(
            f"{self.identity}#{id(self)}", _evict_cb,
            cap=int(self.tier_cap), nbytes=self._tier_nbytes)

    @staticmethod
    def _group_tuples(cols, idx) -> List[tuple]:
        """Group key tuples (None is NULL) of rows ``idx`` of per-column
        (values, valid mask or None): the keys of the host mirrors and
        the pk prefixes of the aux tables."""
        out = []
        for vals, ok in cols:
            col = pylist(vals[idx])
            if ok is not None and not ok[idx].all():
                col = [v if o else None
                       for v, o in zip(col, ok[idx].tolist())]
            out.append(col)
        return list(zip(*out))

    def _tier_touch(self, key_lanes: np.ndarray,
                    vis: np.ndarray) -> None:
        """LRU recency + reload-on-touch: the chunk's distinct group
        keys refresh the tier clock, and any that are COLD reload from
        their committed state rows BEFORE this chunk's device apply."""
        rows = np.flatnonzero(vis)
        if not len(rows):
            return
        uniq = np.unique(key_lanes[rows], axis=0)
        tuples = list(map(tuple, uniq.tolist()))
        self._tier.touch(self._tier_part, tuples, self._tier_seq)
        if self._cold_groups:
            need = [t for t in tuples if t in self._cold_groups]
            if need:
                self._reload_groups(need)

    def _reload_groups(self, lanes_ts: List[tuple]) -> None:
        """Reload evicted groups (the _reload_cold analog): device
        accumulators from the value-state row, the value multisets
        (minput and distinct) and the HLL registers from their aux
        tables, one prefix scan a group: with the init barrier the only
        place a multiset's table is read. Fully durable state makes
        this retraction-safe — a delete touching a cold group reloads
        first, then retracts against exact state."""
        from risingwave_tpu.ops.hash_agg import hll_estimate_dense
        ng = len(self.group_indices)
        rows: List[tuple] = []
        lanes_keep: List[tuple] = []
        groups: List[tuple] = []
        for lt in lanes_ts:
            vt = self._cold_groups.pop(lt)
            row = self.table.get_row(self._state_pk(vt))
            if row is None:
                continue       # retired under a watermark while cold
            rows.append(row)
            lanes_keep.append(lt)
            groups.append(vt)
        if not rows:
            return
        keys = np.asarray(lanes_keep, dtype=np.int32)
        grows = np.asarray([int(r[ng]) for r in rows], dtype=np.int64)
        acc_cols = [
            np.asarray([0 if r[ng + 1 + j] is None else r[ng + 1 + j]
                        for r in rows], dtype=dt)
            for j, dt in enumerate(acc_dtypes(self.specs))]
        self.kernel.load_groups(keys, grows, acc_cols)
        for tables, mults in ((self.minput, self._minput_mult),
                              (self.distinct_tables, self._distinct_mult)):
            for j, t in tables.items():
                for vt in groups:
                    mults[j].load(
                        row for _pk, row
                        in t.iter_prefix(self._state_pk(vt)))
        for j, t in self.hll_tables.items():
            for vt in groups:
                row = t.get_row(self._state_pk(vt))
                if row is not None:
                    arr = np.frombuffer(row[-1], dtype=np.uint8).copy()
                    self._hll_regs[j][vt] = arr
                    self._hll_prev[j][vt] = int(
                        hll_estimate_dense(arr)[0])
        self._tier.note_reload(self._tier_part, len(rows))

    def _tier_evict(self, lanes_ts: List[tuple]) -> int:
        """Tier sweep callback (checkpoint barriers only, post-flush):
        move the given groups to the cold tier — device slots rebuild
        away, host mirrors drop, durable rows stay. Groups with NO
        durable row (retracted to zero, watermark-cleaned) are
        phantoms: skipped, not marked cold, not counted — the tier's
        counters are in keys ACTUALLY evicted."""
        mat = np.asarray(lanes_ts, dtype=np.int32)
        gk = self._group_key_host(mat)
        kept_lanes: List[tuple] = []
        kept_groups: List[tuple] = []
        for lt, vt in zip(lanes_ts, self._group_tuples(
                gk, np.arange(len(lanes_ts)))):
            if self.table.get_row(self._state_pk(vt)) is None:
                continue
            kept_lanes.append(lt)
            kept_groups.append(vt)
        if not kept_lanes:
            return 0
        self.kernel.evict_keys(np.asarray(kept_lanes, dtype=np.int32))
        for lt, vt in zip(kept_lanes, kept_groups):
            self._cold_groups[lt] = vt
        gset = set(kept_groups)
        for mult in (*self._minput_mult.values(),
                     *self._distinct_mult.values()):
            mult.drop_groups(gset)
        for j in self._hll_calls:
            self._hll_regs[j] = {k: v for k, v in
                                 self._hll_regs[j].items()
                                 if k not in gset}
            self._hll_prev[j] = {k: v for k, v in
                                 self._hll_prev[j].items()
                                 if k not in gset}
        self._deleted_lanes -= set(kept_lanes)
        return len(kept_lanes)

    def _tier_forget_expired(self, phys: int) -> None:
        """Watermark cleaning retired groups below `phys`: drop their
        cold markers (rows already range-deleted) and their resident
        tier entries (retired on device by retire_below)."""
        lead = self.key_lead
        if self._cold_groups:
            self._cold_groups = {
                lt: vt for lt, vt in self._cold_groups.items()
                if vt[lead] is None or vt[lead] >= phys}
        part = self._tier_part
        if part is None or not part.keys:
            return
        from risingwave_tpu.ops import lanes as _lanes
        keys_list = list(part.keys)
        mat = np.asarray(keys_list, dtype=np.int64)
        at = _LANES_PER_KEY * lead
        ok = mat[:, at + 2] != 0
        v = _lanes.merge_i64(mat[:, at].astype(np.int32),
                             mat[:, at + 1].astype(np.int32))
        dead = ok & (v < phys)
        if dead.any():
            self._tier.forget(part, [
                k for k, d in zip(keys_list, dead.tolist()) if d])

    # -- watermark state cleaning ----------------------------------------
    def _state_pk(self, group: Sequence) -> list:
        """A group's values in the state tables' key order."""
        return [group[i] for i in self._key_order]

    def _clean_state(self) -> None:
        """Clean to the newest watermark, where it has advanced. Runs
        after flush/advance (a dirty group must emit its last change
        before retirement); late rows for a retired group restart it
        from scratch — the same contract as the reference's cleaned
        state tables."""
        wm = self._clean_wm
        if wm is not None and (self._cleaned_wm is None
                               or wm > self._cleaned_wm):
            self._clean_to(int(wm))
            self._cleaned_wm = wm

    @staged("agg.clean")
    def _clean_to(self, phys: int) -> None:
        """Retire the groups below ``phys`` on the state key's leading
        group column (``key_lead``): device rebuild + range delete
        from every state table, each by the keys its clean index
        holds (cold groups' rows too, which no multiset in memory
        holds)."""
        lead = self.key_lead
        self.kernel.retire_below(lead, phys)
        n, _read = self.table.delete_below_prefix(phys)
        self._live_groups = max(0, self._live_groups - n)
        for tables, mults in ((self.minput, self._minput_mult),
                              (self.distinct_tables, self._distinct_mult)):
            for j, t in tables.items():
                # a table's first range delete reads it once, to seed
                # its index; the ones after read nothing
                _n, read = t.delete_below_prefix(phys)
                _MULTISET_READS.inc(read)
                mults[j].cut_below(lead, phys)
        for j, t in self.hll_tables.items():
            t.delete_below_prefix(phys)
            self._hll_regs[j] = {
                k: v for k, v in self._hll_regs[j].items()
                if k[lead] is None or k[lead] >= phys}
            self._hll_prev[j] = {
                k: v for k, v in self._hll_prev[j].items()
                if k[lead] is None or k[lead] >= phys}
        if self._tier is not None:
            self._tier_forget_expired(phys)

    INTERNER_GC_MIN = 4096

    def _maybe_gc_interner(self) -> None:
        """Retire group-key interner entries no live group references
        (bounded-by-live-state, VERDICT r3 weak #6). Runs every
        barrier; the gate uses the INCREMENTALLY-tracked live-group
        count (see _flush) so the O(live) table scan only happens when
        at least half the entries are provably dead."""
        codec = self.key_codec
        if not codec.interners:
            return
        total = codec.interner_entries()
        if total < self.INTERNER_GC_MIN or \
                total <= 2 * max(self._live_groups, 1) * \
                len(codec.interners):
            return
        live_cols: Dict[int, list] = {j: [] for j in codec.interners}
        n_live = 0
        for _pk, row in self.table.iter_rows():
            n_live += 1
            for j in live_cols:
                v = row[j]
                if v is not None:
                    live_cols[j].append(v)
        self._live_groups = n_live     # re-sync the incremental count
        for j, it in codec.interners.items():
            it.gc(live_cols[j])

    # -- barrier path ----------------------------------------------------
    @staged("agg.decode")
    def _group_key_host(self, keys: np.ndarray
                        ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Key lanes → per group col (values in col dtype, valid mask)."""
        return self.key_codec.decode(keys)

    def _flush(self) -> Optional[StreamChunk]:
        # the hand-off's last leg: the backlog packed, uploaded and
        # dispatched (the kernel's flush below finds it empty)
        with join_to_agg_handoff(self.fed_by_join):
            self.kernel.dispatch_backlog()
        own = getattr(self.kernel, "counts_own_dispatches", False)
        if not own:
            _METRICS.device_dispatch.inc(1, executor=self.identity)
        fr = self.kernel.flush()
        if self.fused_stages is not None:
            # flush synchronized the queue — the per-stage row vectors
            # are landed DMAs; attribute them to the logical executors
            # inside the fused block (monitor drains at the barrier)
            sr = self.kernel.drain_stage_rows()
            if sr is not None:
                self.fused_stages.note_stage_rows(sr, 0)
        # the flush dispatch gathers the dirty groups — observe them so
        # the histogram count tracks the dispatch counter exactly
        if not own:
            _METRICS.rows_per_dispatch.observe(float(fr.n),
                                               executor=self.identity)
        _METRICS.agg_dirty_groups.set(fr.n, executor=self.identity)
        _METRICS.agg_table_capacity.set(self.kernel.capacity,
                                        executor=self.identity)
        gk = None
        host_prev = None
        if self._host_calls and fr.n:
            # host-agg PREV outputs come from the value multisets as
            # of the LAST barrier — read before this epoch's deltas
            # are applied to them (where they are written through)
            gk = self._group_key_host(fr.keys)
            host_prev = self._host_agg_outputs(fr, gk)
        if self.minput:
            self._write_multiset_pending(
                self._minput_pending, self.minput, self._minput_mult)
        if self.distinct_tables:
            self._write_distinct_pending()
        if fr.n == 0:
            self._deleted_lanes.clear()
            self.kernel.advance()
            return None
        if gk is None:
            gk = self._group_key_host(fr.keys)   # decode lanes once
        if self.minput and self._deleted_lanes:
            self._recompute_extremes(fr, gk)
        if self._host_calls:
            host_new = self._host_agg_outputs(fr, gk)
            for j in self._host_calls:
                fr.prev_outs[j], fr.prev_nulls[j] = host_prev[j]
                fr.outs[j], fr.nulls[j] = host_new[j]
        if self._hll_calls:
            self._overwrite_hll_outputs(fr, gk)
            self._persist_hll_dirty()
        self._deleted_lanes.clear()
        return self._emit_changes(fr, gk)

    @staged("agg.emit")
    def _emit_changes(self, fr, gk) -> Optional[StreamChunk]:
        """From the flushed groups' changed-mask to the chunk that goes
        downstream; the state rows are persisted on the way."""
        outs, nulls = fr.outs, fr.nulls
        pouts, pnulls = fr.prev_outs, fr.prev_nulls
        cur_live = fr.group_rows > 0
        was = fr.was_emitted
        changed = np.zeros(fr.n, dtype=bool)
        for o, po, nu, pnu in zip(outs, pouts, nulls, pnulls):
            changed |= (nu != pnu) | (~nu & (o != po))
        ins_i = np.flatnonzero(cur_live & ~was)
        upd_i = np.flatnonzero(cur_live & was & changed)
        del_i = np.flatnonzero(~cur_live & was)
        # incremental live-group count (cheap gate for interner GC)
        self._live_groups += len(ins_i) - len(del_i)
        # persistence must also cover groups whose outputs are unchanged
        # but whose internal state (row/non-null counts) moved — otherwise
        # recovery reloads a stale row count
        state_moved = fr.group_rows != fr.prev_rows
        for nn, pnn in zip(fr.nns, fr.prev_nns):
            if nn is not None:
                state_moved |= nn != pnn
        persist_upd_i = np.flatnonzero(
            cur_live & was & (changed | state_moved))
        self._persist(fr, gk, ins_i, persist_upd_i, del_i)
        self.kernel.advance()
        t = len(ins_i) + 2 * len(upd_i) + len(del_i)
        if t == 0:
            return None
        cap = next_pow2(t)

        def emit_col(cur: np.ndarray, prev: np.ndarray, dtype) -> np.ndarray:
            out = np.zeros(cap, dtype=dtype)
            k = len(ins_i)
            out[:k] = cur[ins_i]
            out[k:k + 2 * len(upd_i):2] = prev[upd_i]
            out[k + 1:k + 2 * len(upd_i):2] = cur[upd_i]
            out[k + 2 * len(upd_i):t] = prev[del_i]
            return out

        columns: List[Column] = []
        for (vals, ok), dt in zip(gk, self.group_types):
            v = emit_col(vals, vals, dt.np_dtype)
            okc = emit_col(ok, ok, bool)
            columns.append(Column(dt, v, None if okc.all() else okc))
        for j, (o, po, nu, pnu) in enumerate(zip(outs, pouts, nulls,
                                                 pnulls)):
            dt = self.schema[len(self.group_indices) + j].data_type
            v = emit_col(o.astype(dt.np_dtype), po.astype(dt.np_dtype),
                         dt.np_dtype)
            nuc = emit_col(nu, pnu, bool)
            columns.append(Column(dt, v, None if not nuc.any() else ~nuc))
        ops = np.full(cap, int(Op.INSERT), dtype=np.int8)
        k = len(ins_i)
        ops[k:k + 2 * len(upd_i):2] = int(Op.UPDATE_DELETE)
        ops[k + 1:k + 2 * len(upd_i):2] = int(Op.UPDATE_INSERT)
        ops[k + 2 * len(upd_i):t] = int(Op.DELETE)
        vis = np.zeros(cap, dtype=bool)
        vis[:t] = True
        return StreamChunk(self.schema, columns, vis, ops)

    def _overwrite_hll_outputs(self, fr, gk) -> None:
        """Replace the placeholder approx outputs with estimates from
        the dense sketches (and exact prev estimates for update
        pairs)."""
        from risingwave_tpu.ops.hash_agg import HLL_M, hll_estimate_dense

        gkeys = [tuple(
            None if not ok[r]
            else (vals[r].item() if hasattr(vals[r], "item")
                  else vals[r])          # interned VARCHAR keys decode
            for vals, ok in gk)          # to plain python strings
                 for r in range(fr.n)]
        for j in self._hll_calls:
            regs_d, prev_d = self._hll_regs[j], self._hll_prev[j]
            dirty = self._hll_dirty[j]
            # estimate ONLY dirty sketches (64KB register files: a
            # full re-stack per flushed row would move gigabytes per
            # barrier at scale); clean groups reuse the cached value
            fresh = [g for g in dict.fromkeys(gkeys) if g in dirty]
            ests = {}
            if fresh:
                mat = np.stack([regs_d[g] for g in fresh])
                for g, e in zip(fresh,
                                hll_estimate_dense(mat).tolist()):
                    ests[g] = int(e)
            for r, g in enumerate(gkeys):
                prev = prev_d.get(g)
                new = ests.get(g)
                if new is None:
                    new = prev if prev is not None else 0
                fr.outs[j][r] = new
                fr.nulls[j][r] = False
                fr.prev_outs[j][r] = 0 if prev is None else prev
                fr.prev_nulls[j][r] = prev is None
                prev_d[g] = new

    def _persist_hll_dirty(self) -> None:
        """Upsert dirty register files (one BYTEA row per group; the
        sketch table is sanity-off so same-pk rewrites shadow)."""
        for j in self._hll_calls:
            table, regs_d = self.hll_tables[j], self._hll_regs[j]
            for gkey in self._hll_dirty[j]:
                table.insert(gkey + (regs_d[gkey].tobytes(),))
            self._hll_dirty[j].clear()

    @staged("agg.extremes")
    def _recompute_extremes(self, fr, gk) -> None:
        """Correct stale device MIN/MAX for groups that saw deletes
        from their surviving values in the in-memory multiset (this
        epoch's deltas written through and applied before), then patch
        the device accumulators (hash_agg.rs + minput.rs flush
        semantics)."""
        deleted = self._deleted_lanes
        need = np.asarray(
            [r for r, lt in enumerate(map(tuple, fr.keys.tolist()))
             if lt in deleted], dtype=np.int64)
        if not len(need):
            return
        groups = self._group_tuples(gk, need)
        scanned = 0
        for j, mult in self._minput_mult.items():
            if self.specs[j].kind in HOST_AGG_KINDS:
                continue       # host outputs recompute separately
            best_of = max if self.specs[j].kind == AggKind.MAX else min
            outs, nulls, nns = fr.outs[j], fr.nulls[j], fr.nns[j]
            for r, group in zip(need.tolist(), groups):
                vals = mult.values(group)
                scanned += len(vals)
                if nns[r] == 0 or not vals:
                    nulls[r] = True
                    nns[r] = 0
                else:
                    outs[r] = best_of(vals)
                    nulls[r] = False
            _EXTREME_SCANS.inc(len(need))
        _VALUES_SCANNED.inc(scanned)
        decoded = [
            (fr.outs[j], fr.nns[j])
            if j in self.minput
            and self.specs[j].kind not in HOST_AGG_KINDS else None
            for j in range(len(self.specs))]
        self.kernel.patch_accs(decoded, raw_accs=fr.raw_accs)

    def _host_agg_outputs(self, fr, gk):
        """string_agg/array_agg outputs for the flushed groups, read
        from the in-memory value multisets. Values compose in VALUE
        order, the table's pk order (the multiset has no arrival order
        and pg leaves the order unspecified without an in-agg ORDER BY;
        value order is the deterministic, recovery-stable choice)."""
        out: Dict[int, tuple] = {}
        groups = self._group_tuples(gk, np.arange(fr.n))
        for j in self._host_calls:
            call = self.agg_calls[j]
            mult = self._minput_mult[j]
            vals_col = np.empty(fr.n, dtype=object)
            nulls_col = np.zeros(fr.n, dtype=bool)
            for r, group in enumerate(groups):
                counts = mult.values(group)
                items: List = []
                for v in sorted(counts, key=value_order):
                    items.extend([v] * counts[v])
                if not items:
                    nulls_col[r] = True
                elif call.kind == AggKind.STRING_AGG:
                    vals_col[r] = call.delimiter.join(
                        str(v) for v in items if v is not None)
                else:                # ARRAY_AGG keeps NULL elements
                    vals_col[r] = tuple(items)
            out[j] = (vals_col, nulls_col)
        return out

    def _state_rows(self, fr, gk, idx: np.ndarray,
                    prev: bool) -> List[tuple]:
        """Physical value-state rows for the given flush indices
        (per-call column layout: AggSpec.host_acc_cols)."""
        from risingwave_tpu.ops.hash_agg import _call_slices
        rows_col = fr.prev_rows if prev else fr.group_rows
        outs = fr.prev_outs if prev else fr.outs
        nulls = fr.prev_nulls if prev else fr.nulls
        nns = fr.prev_nns if prev else fr.nns
        raw = fr.prev_raw_accs if prev else fr.raw_accs
        cols: List[list] = []
        for vals, ok in gk:
            sel = vals[idx]
            okl = ok[idx]
            cols.append([v if o else None
                         for v, o in zip(sel.tolist(), okl.tolist())])
        cols.append(rows_col[idx].tolist())
        for j, (spec, sl) in enumerate(
                zip(self.specs, _call_slices(self.specs))):
            nn = nns[j]
            cols.extend(spec.host_acc_cols(
                outs[j][idx], nulls[j][idx],
                None if nn is None else nn[idx],
                None if raw is None else
                [raw[k][idx] for k in range(sl.start, sl.stop)]))
        return list(zip(*cols)) if cols else []

    @staged("agg.persist")
    def _persist(self, fr, gk, ins_i, upd_i, del_i) -> None:
        # bulk row APIs, one key-encode pass per flush class; the state
        # table's pk is the group key, which ``gk`` already holds by the
        # column, so the table need not take the rows apart again
        def pk_cols(idx):
            return [(gk[i][0][idx], gk[i][1][idx])
                    for i in self._key_order]

        self.table.insert_rows(self._state_rows(fr, gk, ins_i, prev=False),
                               pk_cols(ins_i))
        self.table.update_rows(self._state_rows(fr, gk, upd_i, prev=True),
                               self._state_rows(fr, gk, upd_i, prev=False),
                               pk_cols(upd_i))
        self.table.delete_rows(self._state_rows(fr, gk, del_i, prev=True),
                               pk_cols(del_i))

    # -- recovery --------------------------------------------------------
    def _recover(self) -> None:
        keys_l: List[np.ndarray] = []
        rows_l: List[int] = []
        accs_l: List[tuple] = []
        ng = len(self.group_indices)
        for _pk, row in self.table.iter_rows():
            keys_l.append(self.key_codec.lanes_of_values(row[:ng]))
            rows_l.append(int(row[ng]))
            accs_l.append(row[ng + 1:])
        self._live_groups = len(rows_l)
        if not rows_l:
            return
        if self._tier is not None:
            # recovery rebuilds EVERYTHING resident (cold markers do
            # not survive a crash); seeding the tier clock with the
            # recovered keys lets the first checkpoint sweep re-apply
            # the cap instead of carrying the full set forever
            self._tier.touch(self._tier_part,
                             [tuple(k.tolist()) for k in keys_l],
                             self._tier_seq)
        keys = np.stack(keys_l)
        dts = acc_dtypes(self.specs)
        acc_cols = []
        for j, dt in enumerate(dts):
            col = np.asarray([0 if a[j] is None else a[j]
                              for a in accs_l], dtype=dt)
            acc_cols.append(col)
        self.kernel.rebuild(keys, np.asarray(rows_l, dtype=np.int64),
                            acc_cols)

    # -- main loop -------------------------------------------------------
    async def execute(self) -> AsyncIterator[Message]:
        it = self.input.execute()
        first = await it.__anext__()
        assert is_barrier(first), f"expected init barrier, got {first!r}"
        if self._tier is not None:
            self._tier_register()
        self.table.init_epoch(first.epoch)
        from risingwave_tpu.ops.hash_agg import hll_estimate_dense
        for j, t in self.hll_tables.items():
            t.init_epoch(first.epoch)
            for _pk, row in t.iter_rows():
                gkey = tuple(row[:-1])
                arr = np.frombuffer(row[-1], dtype=np.uint8).copy()
                self._hll_regs[j][gkey] = arr
                # emitted outputs were committed with this sketch —
                # prev estimates must match them exactly
                self._hll_prev[j][gkey] = int(
                    hll_estimate_dense(arr)[0])
        for tables, mults in ((self.minput, self._minput_mult),
                              (self.distinct_tables, self._distinct_mult)):
            for j, t in tables.items():
                t.init_epoch(first.epoch)
                mults[j].load(row for _pk, row in t.iter_rows())
        self._recover()
        yield first
        try:
            async for msg in it:
                if is_chunk(msg):
                    # the hand-off's middle leg: the ingest of the
                    # join's chunks up to the kernel's backlog
                    with join_to_agg_handoff(self.fed_by_join):
                        self._apply_chunk(msg)
                elif is_barrier(msg):
                    out = self._flush()
                    # the flush's gather ran after every step of the
                    # epoch: their counters were folded in when it
                    # adopted the exact count
                    _hotkeys.note_batch_books(
                        f"agg.{self._books_table}", self.identity,
                        getattr(self.kernel, "take_probe_rounds", None))
                    self._clean_state()
                    # resident pairs at the seal, behind the clean
                    for col, mult in self._distinct_mult.items():
                        _METRICS.agg_distinct_pairs.set(
                            float(len(mult)),
                            table=self._distinct_label[col])
                    self._maybe_gc_interner()
                    self.table.commit(msg.epoch)
                    for t in self.minput.values():
                        t.commit(msg.epoch)
                    for t in self.hll_tables.values():
                        t.commit(msg.epoch)
                    for t in self.distinct_tables.values():
                        t.commit(msg.epoch)
                    if self._tier is not None:
                        # sweep at CHECKPOINT barriers only, after the
                        # flush+advance+commit above — the evicted
                        # groups are provably clean and durable, and no
                        # epoch is in flight (tier.py epoch-sequencing)
                        self._tier_seq += 1
                        if msg.kind.is_checkpoint:
                            self._tier.sweep(self._tier_part,
                                             self._tier_seq)
                    if out is not None:
                        yield out
                    yield msg
                elif is_watermark(msg):
                    # fused blocks first map the watermark through the
                    # absorbed projects' derivations (the sequential
                    # ProjectExecutors' exact per-message semantics)
                    wms = [msg] if self.fused_stages is None \
                        else self.fused_stages.derive_watermarks(msg)
                    # forward only group-key watermarks, re-indexed
                    for m in wms:
                        if m.col_idx in self.group_indices:
                            pos = self.group_indices.index(m.col_idx)
                            if pos == self.key_lead and cleanable_type(
                                    self.group_types[pos]):
                                self._clean_wm = m.value
                            yield m.with_idx(pos)
        finally:
            # executor teardown: release this identity's gauge series
            _METRICS.agg_dirty_groups.remove(executor=self.identity)
            _METRICS.agg_table_capacity.remove(executor=self.identity)
            for label in self._distinct_label.values():
                _METRICS.agg_distinct_pairs.remove(table=label)
            if self._tier_part is not None:
                self._tier.unregister(self._tier_part)
