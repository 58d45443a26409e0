"""Composable traced stages: the kernel half of fragment fusion.

TiLT thesis (arxiv 2301.12030) applied to the plan IR: instead of
interpreting a fragment's executor chain one vectorized host pass per
operator per chunk, compile the whole source→filter→project→keyed-input
run into ONE traced dataflow step. The expression layer is already
backend-polymorphic (``get_xp`` — common/chunk.py), so the SAME
``Expression.eval`` / ``FilterExecutor`` math that runs interpretively
on numpy traces under ``jax.jit`` bit-identically; this module supplies
the static plumbing around it:

- ``traceable_reason``: the eligibility walker. An expression tree is
  fusable iff every node stays in the device domain end to end — host
  comparisons (varchar), host scalar functions, and DECIMAL casts whose
  numpy path carries overflow *detection* (raising is untraceable) all
  refuse with a reason string the rewrite layer surfaces in EXPLAIN.
- ``FusedStages``: a filter/project run in composed normal form — all
  predicates and output expressions substituted back onto the ONE input
  schema (subst_expr, the projection-composition machinery of the
  plan-rewrite engine) — plus the raw-chunk codec for the agg-prelude
  path and per-logical-stage row attribution.
- ``build_chain_step``: the standalone traced step (chunk in → chunk
  out), used by FusedStagesExecutor for runs feeding joins/materialize.
- ``build_agg_prelude``: the same chain fused INTO ``hash_agg.py``'s
  jitted apply — raw int64 chunk matrix → (key lanes, signs, vis,
  per-call input lanes), inlined ahead of the accumulator updates so a
  whole fragment step is one dispatch with donated state buffers.

Pair semantics are preserved exactly: filter degradation (U-/U+ halves
diverging under the predicate) reuses ``FilterExecutor.apply_predicate``
— the one implementation — and the project noop-update drop runs as a
branchless shifted-compare (identical result to the numpy early-out
version: no U-/U+ pairs ⇒ no drops). Batched raw matrices place one
always-invisible SEPARATOR row between chunks so the shifted compares
never marry rows across chunk boundaries.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from risingwave_tpu.common.chunk import (
    Column, Op, StreamChunk, ops_to_signs,
)
from risingwave_tpu.common.types import DataType, Field, Schema

# FuncCalls whose registered implementations are xp-generic (no numpy
# object arrays, no python loops) — verified by tests/test_fusion.py
# against the interpretive path on random data.
TRACEABLE_FUNCS = frozenset({"tumble_start", "tumble_end",
                             "extract_epoch"})


# -- eligibility walker ----------------------------------------------------


def traceable_reason(e, schema: Schema) -> Optional[str]:
    """None if `e` traces bit-identically under jit against `schema`;
    otherwise a human-readable refusal (EXPLAIN shows it)."""
    from risingwave_tpu.expr.expr import (
        BinaryOp, Case, Cast, FuncCall, InputRef, Literal, UnaryOp,
    )
    if isinstance(e, InputRef):
        if not e.return_type.is_device:
            return f"host-typed column ref ${e.index}:{e.return_type.value}"
        return None
    if isinstance(e, Literal):
        # host-typed literals (varchar format strings, intervals) are
        # CONSTANT — they evaluate host-side even inside a trace (the
        # chunk capacity is static), so they are fine as FuncCall args;
        # standalone host literals in value position are not.
        return None
    if isinstance(e, BinaryOp):
        if not e._common.is_device:
            return (f"operator {e.op!r} over host type "
                    f"{e._common.value}")
        for side in (e.left, e.right):
            # implicit float→DECIMAL promotion carries overflow/
            # non-finite DETECTION on the numpy path (raising is
            # untraceable); int→DECIMAL wraps identically wherever the
            # interpretive path doesn't raise, which is the bit-identity
            # contract — see ARCHITECTURE.md "Fragment fusion"
            if (e._common == DataType.DECIMAL
                    and side.return_type in (DataType.FLOAT32,
                                             DataType.FLOAT64)):
                return "float->DECIMAL promotion (overflow detection)"
            r = traceable_reason(side, schema)
            if r:
                return r
        return None
    if isinstance(e, UnaryOp):
        return traceable_reason(e.child, schema)
    if isinstance(e, Cast):
        if not e.return_type.is_device:
            return f"cast to host type {e.return_type.value}"
        src = e.child.return_type
        if not src.is_device:
            return f"cast from host type {src.value}"
        if e.return_type == DataType.DECIMAL and src != DataType.DECIMAL:
            return "cast to DECIMAL (overflow detection is host-only)"
        return traceable_reason(e.child, schema)
    if isinstance(e, Case):
        if not e.return_type.is_device:
            return f"CASE over host type {e.return_type.value}"
        for c, v in e.whens:
            r = traceable_reason(c, schema) or traceable_reason(v, schema)
            if r:
                return r
        return traceable_reason(e.else_, schema)
    if isinstance(e, FuncCall):
        if e.name not in TRACEABLE_FUNCS:
            return f"function {e.name}() has no traceable kernel"
        from risingwave_tpu.expr.expr import Literal as _Lit
        for a in e.args:
            if isinstance(a, _Lit):
                continue            # constant args evaluate host-side
            r = traceable_reason(a, schema)
            if r:
                return r
        return None
    return f"unknown expression node {type(e).__name__}"


# -- traced twins of the host key/lane codecs ------------------------------
# Identical bit semantics to ops/lanes.py + executors/keys.py, running
# on xp (numpy OR traced jnp). The integer paths of lanes.py are already
# xp-generic and are called directly. A FLOAT column never gives up its
# bits in-trace (the TPU compiler has no f64→int64 bitcast): its key,
# order and payload lanes are built from the int64 image it was
# uploaded as (float_image / lanes.float_key_image and friends), which
# is why a float key / MIN-MAX argument / join column must be a plain
# column reference to fuse (FusedStages.bit_image_reason).


def _is_float(data_type) -> bool:
    return np.issubdtype(np.dtype(data_type.np_dtype), np.floating)


def key_i64_traced(col: Column, image) -> object:
    """One device-typed key column → the int64 KeyCodec hashes:
    integers widen, floats take their uploaded bit image with -0.0
    folded into 0.0 (keys.to_i64's exact image)."""
    from risingwave_tpu.ops import lanes as _lanes
    from risingwave_tpu.stream.executors.keys import to_i64
    if _is_float(col.data_type):
        return _lanes.float_key_image(image)
    return to_i64(col.values)


def _float_images(fs: "FusedStages", raw, out_cols, cols, xp) -> dict:
    """{output column: its uploaded int64 bit image} for the FLOAT
    columns among ``cols`` (each image read once per trace)."""
    return {i: fs.float_image(raw, i, xp) for i in set(cols)
            if _is_float(out_cols[i].data_type)}


def key_lanes_traced(cols: Sequence[Tuple[object, Optional[object]]],
                     xp) -> object:
    """(int64 key image, validity) per key column → int32[N, 3k]
    lanes, the exact KeyCodec.build_arrays image (hi, lo, valid per
    column)."""
    from risingwave_tpu.ops import lanes as _lanes
    out = []
    for v64, ok in cols:
        if ok is not None:
            v64 = xp.where(ok, v64, xp.int64(0))
        hi, lo = _lanes.split_i64(v64)
        out.append(hi)
        out.append(lo)
        out.append(xp.ones(v64.shape[0], dtype=xp.int32)
                   if ok is None else ok.astype(xp.int32))
    return xp.stack(out, axis=1)


# -- raw-chunk codec (the ONE upload of the fused agg path) ----------------
# Layout (int64 columns): [ops, vis] then (value, valid) per referenced
# input column. f64 travels bitcast; f32 widens exactly through f64.
# One matrix = one host→device transfer per dispatch, mirroring
# pack_chunk's rationale for the unfused path.

RAW_META_COLS = 2


def raw_width(n_ref_cols: int) -> int:
    return RAW_META_COLS + 2 * n_ref_cols


def encode_raw_chunk(chunk: StreamChunk,
                     ref_cols: Sequence[int]) -> np.ndarray:
    """Host side: one int64[N, W] matrix for the referenced columns."""
    n = chunk.capacity
    m = np.zeros((n, raw_width(len(ref_cols))), dtype=np.int64)
    m[:, 0] = np.asarray(chunk.ops)
    m[:, 1] = np.asarray(chunk.visibility)
    for k, i in enumerate(ref_cols):
        c = chunk.columns[i]
        vals = np.asarray(c.values)
        if vals.dtype == np.float64:
            v = vals.view(np.int64)
        elif vals.dtype == np.float32:
            v = vals.astype(np.float64).view(np.int64)
        else:
            v = vals.astype(np.int64)
        m[:, RAW_META_COLS + 2 * k] = v
        m[:, RAW_META_COLS + 2 * k + 1] = (
            1 if c.validity is None
            else np.asarray(c.validity).astype(np.int64))
    return m


def decode_raw_cols(raw, in_schema: Schema,
                    ref_cols: Sequence[int], xp
                    ) -> Tuple[List[Column], object, object]:
    """Traced inverse of encode_raw_chunk → (columns in FULL input
    arity, vis bool, ops int8-domain). Unreferenced slots get dummy
    zero columns (never evaluated — eligibility guarantees it)."""
    cap = raw.shape[0]
    ops = raw[:, 0].astype(xp.int8)
    vis = raw[:, 1].astype(bool)
    cols: List[Column] = []
    by_pos = {i: k for k, i in enumerate(ref_cols)}
    for i, f in enumerate(in_schema):
        k = by_pos.get(i)
        if k is None:
            cols.append(Column(f.data_type, xp.zeros(cap, dtype=xp.int32)))
            continue
        v64 = raw[:, RAW_META_COLS + 2 * k]
        okl = raw[:, RAW_META_COLS + 2 * k + 1].astype(bool)
        dt = np.dtype(f.data_type.np_dtype)
        if dt == np.float64:
            vals = v64.view(xp.float64) if xp is np else \
                _bitcast(v64, xp.float64)
        elif dt == np.float32:
            vals = (v64.view(np.float64) if xp is np
                    else _bitcast(v64, xp.float64)).astype(dt)
        else:
            vals = v64.astype(dt)
        cols.append(Column(f.data_type, vals, okl))
    return cols, vis, ops


def _bitcast(a, dtype):
    import jax
    return jax.lax.bitcast_convert_type(a, dtype)


# -- composed stage normal form --------------------------------------------


@dataclass(frozen=True)
class FusedStage:
    """One logical executor inside a fused block (metrics identity +
    the pieces EXPLAIN and the fragmenter serialize)."""

    kind: str    # "filter" | "project" | "row_id_gen"
    #            # | "watermark_filter" | "hop_window"
    identity: str                  # e.g. "FilterExecutor"
    # filter: the ORIGINAL predicate (own column space); project: the
    # original exprs/names. Serialized by the fragmenter.
    exprs: tuple = ()
    names: tuple = ()
    watermark_derivations: dict = field(default_factory=dict)
    # watermark_filter: event-time column (own input space) + delay;
    # row_id_gen / watermark_filter runtime state (the id counter's
    # shard base, the watermark StateTable) is carried by `runtime` —
    # a HOST-ONLY handle, never serialized (the fragmenter re-derives
    # it from table ids); hop_window: time_col + slide/size (pure
    # parameters, no runtime)
    time_col: int = -1
    delay_usecs: int = 0
    slide_usecs: int = 0
    size_usecs: int = 0
    runtime: object = None
    # the planner's mark, carried over from the executor: a
    # hop_window names the aggregate planned over it
    # (`t<state table id>`). The run files that stage's rows under
    # the name (note_stage_rows); host-only, not serialized
    books: str = ""

    @property
    def units(self) -> int:
        return self.size_usecs // self.slide_usecs


class FusedStages:
    """A maximal fusable filter/project run in composed normal form.

    ``stages`` is the run in dataflow order (closest-to-upstream
    first). Construction composes everything onto ``in_schema``:
    ``preds`` (each substituted back to input space, applied as one
    conjunction + one pair-degradation pass) and ``out_exprs`` /
    ``out_schema`` (the final projection; None means the run is
    filter-only and the output schema is the input schema).

    The composition is visible-semantics-exact w.r.t. the sequential
    executors: predicate conjunction commutes, degradation of a pair
    whose halves diverge under ANY predicate equals sequential
    degradation, and the noop-update drop after the FINAL projection
    drops exactly the pairs the per-stage drops would have (equal
    inputs stay equal through every later projection). Invisible rows'
    op bytes may differ — they are unobservable by contract (the spine
    suppresses/compacts them end to end).
    """

    def __init__(self, in_schema: Schema, stages: Sequence[FusedStage]):
        from risingwave_tpu.frontend.opt.rules import subst_expr
        from risingwave_tpu.expr.expr import InputRef
        self.in_schema = in_schema
        self.stages = list(stages)
        if not self.stages:
            raise ValueError("FusedStages needs at least one stage")
        # synthetic RUNTIME columns appended past the real input: each
        # row_id_gen stage contributes its per-chunk id column (host
        # arithmetic: base + arange) and each watermark_filter its
        # scalar threshold, broadcast per row. The trace sees them as
        # ordinary device inputs; `augment` builds them per chunk.
        self.syn_specs: List[tuple] = []     # ("row_id"|"wm", stage_i)
        syn_fields: List[Field] = []
        n_in = len(in_schema)
        self.row_id_stages: List[tuple] = []   # (stage_i, ext col)
        self.wm_stages: List[tuple] = []       # (stage_i, ext col)
        # hop_window absorption (ISSUE 12): a head-of-run hop stage
        # replicates every row `units`× IN-TRACE and synthesizes
        # window_start/window_end columns from the time column — the
        # composition space for everything downstream is the hop
        # OUTPUT space (in_schema + the two window columns), while the
        # raw upload keeps the PRE-hop arity (the expansion never
        # crosses the host boundary).
        self.hop: Optional[FusedStage] = None
        base_fields = list(in_schema.fields)
        if self.stages[0].kind == "hop_window":
            self.hop = self.stages[0]
            base_fields = base_fields + [
                Field("window_start", DataType.TIMESTAMP),
                Field("window_end", DataType.TIMESTAMP)]
        if any(st.kind == "hop_window" for st in self.stages[1:]):
            # non-head hops never compose (the window columns would
            # not exist in the downstream stages' spaces) — the rule
            # pre-refuses these runs; fail loud on direct misuse
            raise ValueError("hop_window stage must head the run")
        base_schema = Schema(base_fields) if self.hop is not None \
            else in_schema
        self._base_schema = base_schema
        # compose onto the (post-hop) input space
        cur: Optional[list] = None          # None = identity projection
        preds: List[object] = []
        pred_stage: List[int] = []          # stage index per pred
        names = [f.name for f in base_schema]
        for si, st in enumerate(self.stages):
            if st.kind == "hop_window":
                continue                     # space change handled above
            if st.kind == "filter":
                (p,) = st.exprs
                preds.append(p if cur is None else subst_expr(p, cur))
                pred_stage.append(si)
            elif st.kind == "project":
                cur = [e if cur is None else subst_expr(e, cur)
                       for e in st.exprs]
                names = list(st.names)
            elif st.kind == "row_id_gen":
                syn = n_in + len(syn_fields)
                syn_fields.append(Field("_row_id", DataType.SERIAL))
                self.syn_specs.append(("row_id", si))
                self.row_id_stages.append((si, syn))
                if cur is None:
                    cur = [InputRef(i, f.data_type)
                           for i, f in enumerate(in_schema)]
                cur = cur + [InputRef(syn, DataType.SERIAL)]
                names = names + ["_row_id"]
            elif st.kind == "watermark_filter":
                # gated to the HEAD of the run (fusable_reason): the
                # late mask then reads the raw event-time column and
                # the synthetic threshold directly
                dt_t = in_schema[st.time_col].data_type
                syn = n_in + len(syn_fields)
                syn_fields.append(Field(f"_wm_thr{si}", dt_t))
                self.syn_specs.append(("wm", si))
                self.wm_stages.append((si, syn))
            else:
                raise ValueError(f"unknown stage kind {st.kind!r}")
        self.ext_schema = Schema(list(in_schema.fields)
                                 + syn_fields) if syn_fields \
            else in_schema
        # the space the composed preds/exprs bind against: the RAW
        # trace-input space plus synthetics — or, with an absorbed
        # hop, the hop OUTPUT space (window columns are synthesized
        # in-trace from the time column, never uploaded)
        self.body_schema = base_schema if self.hop is not None \
            else self.ext_schema
        self.preds = preds
        self._pred_stage = pred_stage
        self.out_exprs = cur
        if cur is None:
            self.out_schema = base_schema
        else:
            self.out_schema = Schema([
                Field(n, e.return_type) for n, e in zip(names, cur)])
        # referenced input columns (trace inputs); everything else
        # stays host-side. A filter-only run (out_exprs None) passes
        # EVERY column through, so all device columns are referenced —
        # omitting them would hand dummy zero columns to the consumer.
        refs: set = set()
        from risingwave_tpu.frontend.opt.checker import expr_refs
        for p in self.preds:
            refs |= expr_refs(p)
        for e in (self.out_exprs or []):
            refs |= expr_refs(e)
        for si, syn in self.wm_stages:
            refs.add(self.stages[si].time_col)
            refs.add(syn)
        for _si, syn in self.row_id_stages:
            refs.add(syn)
        # host passthrough outputs: bare InputRefs to host-typed input
        # columns ride AROUND the trace (positional vis/ops are shared)
        self.host_out: Dict[int, int] = {}
        if self.out_exprs is None:
            for i, f in enumerate(base_schema):
                if i >= n_in:
                    continue      # hop window cols: synthesized in-trace
                if f.data_type.is_device:
                    refs.add(i)
                else:
                    self.host_out[i] = i
        else:
            for j, e in enumerate(self.out_exprs):
                if isinstance(e, InputRef) and not e.return_type.is_device:
                    self.host_out[j] = e.index
        if self.hop is not None:
            # window-column refs resolve to the time column they are
            # synthesized from; the raw matrix never carries them
            refs = {self.hop.time_col if i >= n_in else i
                    for i in refs}
            refs.add(self.hop.time_col)
        self.ref_cols: List[int] = sorted(
            i for i in refs if self.ext_schema[i].data_type.is_device)
        # per-stage row attribution drained by the monitor at barriers
        self.stage_rows = np.zeros(len(self.stages), dtype=np.int64)
        self.stage_chunks = np.zeros(len(self.stages), dtype=np.int64)
        # the visible rows handed to the run since the last stage-row
        # vector was noted: what a marked hop (FusedStage.books; it
        # heads its run) takes as its rows in
        self._rows_in = 0

    # -- eligibility -------------------------------------------------------
    def fusable_reason(self) -> Optional[str]:
        """None iff the composed run traces; else the first refusal."""
        if self.hop is not None:
            if self.wm_stages or self.row_id_stages:
                # both machineries claim the head/synthetic-column
                # slots; the planner never emits these shapes anyway
                return ("hop_window cannot share a run with absorbed "
                        "runtime stages")
            for st in self.stages[1:]:
                if st.kind == "hop_window":
                    return "more than one hop_window stage in the run"
            dt_t = self.in_schema[self.hop.time_col].data_type
            if not dt_t.is_device or \
                    np.dtype(dt_t.np_dtype).kind not in "iu":
                return ("hop_window over non-integer time column "
                        f"{dt_t.value}")
        if len(self.wm_stages) > 1:
            return "more than one watermark_filter stage in the run"
        for si, _syn in self.wm_stages:
            if si != 0:
                return ("watermark_filter stage not at the head of "
                        "the run (its late mask must see raw rows)")
            st = self.stages[si]
            dt_t = self.in_schema[st.time_col].data_type
            if not dt_t.is_device or \
                    np.dtype(dt_t.np_dtype).kind not in "iu":
                # float time columns would make the no-watermark-yet
                # sentinel (I64_MIN broadcast) observable (-inf rows);
                # integer event times are the planner's only shape
                return ("watermark_filter over non-integer time "
                        f"column {dt_t.value}")
        for p in self.preds:
            r = traceable_reason(p, self.body_schema)
            if r:
                return r
        for j, e in enumerate(self.out_exprs or []):
            if j in self.host_out:
                continue            # host passthrough, never traced
            r = traceable_reason(e, self.body_schema)
            if r:
                return r
        return None

    # -- synthetic runtime columns (host side, per chunk) ------------------
    def augment(self, chunk):
        """Chunk over in_schema → chunk over ext_schema: append each
        row_id_gen stage's id column (base + arange — RowIdGenExecutor
        assigns ids to EVERY slot, visible or padding) and each
        watermark_filter's threshold column (the watermark EMITTED
        before this chunk; dtype-min sentinel = no watermark yet,
        which lates nothing since ts < dtype_min is unsatisfiable
        in-dtype). Advances
        the absorbed executors' runtime state exactly as their own
        chunk loops would have — the id counter bumps by capacity, the
        watermark advances to max(event_time) - delay."""
        if not self.syn_specs:
            return chunk
        cap = chunk.capacity
        cols = list(chunk.columns)
        for kind, si in self.syn_specs:
            rt = self.stages[si].runtime
            if kind == "row_id":
                cols.append(Column(
                    DataType.SERIAL,
                    rt._next + np.arange(cap, dtype=np.int64)))
                rt._next += cap
            else:
                thr = rt.current          # the PRE-chunk watermark:
                # the mask must not see this chunk's own max (see
                # WatermarkFilterExecutor._apply)
                dt = self.ext_schema[len(cols)].data_type
                info = np.iinfo(np.dtype(dt.np_dtype))
                # sentinel/clamp in the TIME COLUMN's OWN dtype:
                # np.full would silently WRAP an out-of-range int64
                # (int64-min → 0 on an int32 column), turning
                # "no watermark yet" into "drop every negative ts".
                # dtype-min is exact either way: ts < dtype_min is
                # unsatisfiable for in-dtype ts, same as no filter
                # (and a true threshold below dtype_min lates nothing
                # a narrower column could hold).
                val = info.min if thr is None \
                    else min(max(int(thr), info.min), info.max)
                cols.append(Column(dt, np.full(
                    cap, val, dtype=np.dtype(dt.np_dtype))))
                st = self.stages[si]
                c = chunk.columns[st.time_col]
                ts = np.asarray(c.values).astype(np.int64)
                ok = np.asarray(chunk.visibility) if c.validity is None \
                    else (np.asarray(chunk.visibility)
                          & np.asarray(c.validity))
                if ok.any():
                    mx = int(ts[ok].max()) - st.delay_usecs
                    if rt.current is None or mx > rt.current:
                        rt.current = mx
        return StreamChunk(self.ext_schema, cols, chunk.visibility,
                           chunk.ops)

    def on_barrier(self, barrier, first: bool = False) -> List:
        """Absorbed-runtime barrier work (the hosting executor calls
        this where the sequential executors' own barrier handling
        would have run). Returns watermark messages to emit AFTER the
        barrier (IN-schema column space — callers derive through the
        projection stages). First barrier: restore the persisted
        watermark; later barriers: persist + commit; row-id counters
        rebase to the epoch floor either way."""
        from risingwave_tpu.stream.message import Watermark
        out: List = []
        for si, _syn in self.row_id_stages:
            self.stages[si].runtime._rebase(barrier.epoch.curr.value)
        for si, _syn in self.wm_stages:
            st = self.stages[si]
            rt = st.runtime
            if first:
                if rt.state is not None:
                    rt.state.init_epoch(barrier.epoch)
                    row = rt.state.get_row((0,))
                    if row is not None:
                        rt.current = int(row[1])
                # the restored watermark re-announces itself, exactly
                # like the sequential executor's first-barrier yield
                if rt.current is not None:
                    out.append(Watermark(st.time_col,
                                         DataType.TIMESTAMP,
                                         rt.current))
            else:
                rt._persist()
                if rt.state is not None:
                    rt.state.commit(barrier.epoch)
        return out

    def post_chunk_watermarks(self) -> List:
        """Watermark messages due after a data chunk (IN-schema space;
        WatermarkFilterExecutor emits its current watermark after
        every chunk it forwards)."""
        from risingwave_tpu.stream.message import Watermark
        return [Watermark(self.stages[si].time_col, DataType.TIMESTAMP,
                          self.stages[si].runtime.current)
                for si, _syn in self.wm_stages
                if self.stages[si].runtime.current is not None]

    def wm_time_cols(self) -> List[int]:
        """IN-schema columns owned by absorbed watermark_filter stages
        (upstream watermarks on them are superseded, like the
        sequential executor's own-column drop)."""
        return [self.stages[si].time_col for si, _syn in self.wm_stages]

    def describe(self) -> str:
        return "→".join(s.identity for s in self.stages)

    def trace_key(self) -> str:
        """STRUCTURAL identity of the traced program this run builds:
        two FusedStages with equal keys trace byte-equivalent preludes
        (runtime state — row-id counters, watermark tables — feeds the
        host-built synthetic columns, never the trace). Keying jit
        caches by this instead of object identity lets fresh sessions
        and both join sides reuse compiled programs — warmup compiles
        stop riding every run's p99 tail."""
        import json as _json

        from risingwave_tpu.stream.plan_ir import expr_to_ir
        parts = []
        for st in self.stages:
            d = {"kind": st.kind}
            if st.kind == "filter":
                d["pred"] = expr_to_ir(st.exprs[0])
            elif st.kind == "project":
                d["exprs"] = [expr_to_ir(e) for e in st.exprs]
            elif st.kind == "watermark_filter":
                d["time_col"] = st.time_col
            elif st.kind == "hop_window":
                d["time_col"] = st.time_col
                d["slide"] = st.slide_usecs
                d["size"] = st.size_usecs
            parts.append(d)
        schema = [f.data_type.value for f in self.in_schema]
        return _json.dumps([schema, parts], sort_keys=True,
                           default=str)

    def input_positions(self, cols) -> Optional[List[int]]:
        """Map OUTPUT column positions back through the composed
        projection to RAW input positions, or None when any is not a
        pure input ref (a computed key cannot be hash-dispatched in
        raw space; synthetic runtime columns — absorbed row ids,
        watermark thresholds — do not exist pre-run either). The
        parallelism>1 fused cut (fragmenter) hashes raw rows on the
        mapped columns: value equality with the post-stage keys makes
        the partition consistent."""
        from risingwave_tpu.expr.expr import InputRef
        n_in = len(self.in_schema)
        out: List[int] = []
        for c in cols:
            if self.out_exprs is None:
                if not (0 <= c < n_in):
                    return None
                out.append(int(c))
                continue
            e = self.out_exprs[c]
            if isinstance(e, InputRef) and e.index < n_in:
                out.append(int(e.index))
            else:
                return None
        return out

    # -- float bit images (the TPU has no f64→int64 bitcast) ---------------
    def image_source(self, col: int) -> Optional[int]:
        """RAW input column whose uploaded int64 bit image IS output
        column ``col``'s, or None when the column is computed."""
        pos = self.input_positions([col])
        return None if pos is None else pos[0]

    def bit_image_reason(self, cols, what: str) -> Optional[str]:
        """None iff every FLOAT column among output ``cols`` is a plain
        reference to an input column. The consumer (group key, MIN/MAX
        argument, join key or stored join column) needs the float's
        64-bit image; a referenced column brings the image it was
        uploaded with, a computed one would have to be bitcast
        f64→int64 inside the trace, which the TPU compiler refuses —
        so the run stays on the host path, on every platform."""
        for c in cols:
            dt = self.out_schema[c].data_type
            if _is_float(dt) and self.image_source(c) is None:
                return (f"computed {dt.value} {what} "
                        f"{self.out_schema[c].name!r}: its bit image "
                        "would need an f64->int64 bitcast in-trace, "
                        "which the TPU compiler refuses")
        return None

    def float_image(self, raw, col: int, xp):
        """Traced: output column ``col``'s int64 bit image, straight
        from the raw upload (tiled like every column under an absorbed
        hop)."""
        src = self.image_source(col)
        if src is None:
            raise ValueError(
                f"output column {col} is computed: no uploaded bit "
                "image (bit_image_reason should have refused the run)")
        img = raw[:, RAW_META_COLS + 2 * self.ref_cols.index(src)]
        return img if self.hop is None \
            else xp.tile(img, self.hop.units)

    # -- watermark path (host, per message) --------------------------------
    def derive_watermarks(self, msg) -> List:
        """Watermark(s) in OUTPUT column space, composing each stage's
        semantics in order (filters pass through, projects derive or
        drop — ProjectExecutor's exact rules)."""
        from risingwave_tpu.stream.message import Watermark
        outs = [msg]
        for st in self.stages:
            if st.kind == "hop_window":
                # HopWindowExecutor's exact rule: a bound on ts is a
                # bound on the LAST covering window's start; every
                # other watermark is consumed (the expansion breaks
                # per-column monotonicity guarantees)
                nxt = []
                ws_idx = len(self.in_schema)
                for m in outs:
                    if m.col_idx == st.time_col:
                        b = (int(m.value) // st.slide_usecs) \
                            * st.slide_usecs
                        nxt.append(Watermark(
                            ws_idx, DataType.TIMESTAMP,
                            b - (st.units - 1) * st.slide_usecs))
                outs = nxt
                continue
            if st.kind != "project":
                continue
            nxt: List = []
            for m in outs:
                nxt.extend(m.derived(st.watermark_derivations))
            outs = nxt
        return outs

    def note_rows_in(self, rows: int) -> None:
        """Visible rows of a chunk the run's owner hands it; only a
        marked hop's books read it."""
        self._rows_in += int(rows)

    def note_stage_rows(self, counts: np.ndarray, chunks: int) -> None:
        counts = counts.astype(np.int64)
        self.stage_rows += counts
        self.stage_chunks += chunks
        rows_in, self._rows_in = self._rows_in, 0
        if self.hop is not None and self.hop.books:
            from risingwave_tpu.utils.metrics import note_hop_rows
            note_hop_rows(self.hop.books, rows_in, int(counts[0]))

    def drain_stage_metrics(self) -> List[Tuple[str, int, int]]:
        # same-kind stages in one run (e.g. filter→filter after an MV
        # over a filtered view) get a position suffix so their metric
        # series stay distinct instead of summing into one label
        idents = [st.identity for st in self.stages]
        dup = {n for n in idents if idents.count(n) > 1}
        out = [(f"{st.identity}[{i}]" if st.identity in dup
                else st.identity,
                int(self.stage_rows[i]), int(self.stage_chunks[i]))
               for i, st in enumerate(self.stages)]
        self.stage_rows[:] = 0
        self.stage_chunks[:] = 0
        return out

    # -- host half of the noop-pair drop ----------------------------------
    def host_noop_eq(self, chunk) -> Optional[np.ndarray]:
        """Adjacent-row equality over the HOST passthrough columns
        (ProjectExecutor._drop_noop_updates' exact semantics, numpy).
        Host columns bypass the trace, but a U-/U+ pair whose only
        change is a varchar must NOT be dropped — this mask is ANDed
        into the traced drop. None when there are no host columns."""
        if not self.host_out or self.out_exprs is None:
            return None
        same = np.ones(chunk.capacity, dtype=bool)
        for _j, src in self.host_out.items():
            c = chunk.columns[src]
            v = np.asarray(c.values)
            eq = np.asarray(v == np.roll(v, -1), dtype=bool)
            if c.validity is not None:
                ok = np.asarray(c.validity)
                ok_n = np.roll(ok, -1)
                eq = (eq & ok & ok_n) | (~ok & ~ok_n)
            same &= eq
        return same

    # -- the traced chain body --------------------------------------------
    def chain_body(self, cols: List[Column], vis, ops, xp,
                   host_same=None
                   ) -> Tuple[List[Column], object, object, object]:
        """Composed filter+project over (possibly traced) arrays.

        Returns (out device columns, vis, ops, per-stage visible-row
        counts int64[n_stages]). Host passthrough outputs come back as
        None placeholders — the caller reattaches them positionally,
        and passes ``host_same`` (host_noop_eq) so the noop-pair drop
        sees their equality too. The agg-prelude path passes None: the
        agg consumes only device columns, whose in-pair equality makes
        drop-vs-keep output-invisible there (net-zero group delta with
        unchanged accumulators either way).
        """
        from risingwave_tpu.stream.executors.simple import (
            FilterExecutor,
        )
        # per-stage rows: each filter's post-predicate count; projects
        # report the count AT THEIR POSITION in dataflow order (not the
        # final count — a filter after a project must not retroactively
        # shrink the project's attribution)
        n_stages = len(self.stages)
        stage_rows = [None] * n_stages
        if self.hop is not None:
            cols, vis, ops, host_same = self._expand_hop(
                cols, vis, ops, xp, host_same)
        chunk = StreamChunk(self.body_schema, cols, vis, ops)
        if self.hop is not None:
            stage_rows[0] = xp.sum(vis.astype(xp.int64))
        for si, syn in self.wm_stages:
            # head-of-run late mask (WatermarkFilterExecutor._apply):
            # rows with a valid event time BELOW the pre-chunk
            # watermark (the synthetic threshold column) go invisible
            st = self.stages[si]
            c_ts = chunk.columns[st.time_col]
            ts = c_ts.values
            thr = chunk.columns[syn].values
            okm = chunk.visibility if c_ts.validity is None \
                else chunk.visibility & c_ts.validity
            late = okm & (ts < thr)
            chunk = StreamChunk(self.ext_schema, chunk.columns,
                                chunk.visibility & ~late, chunk.ops)
            stage_rows[si] = xp.sum(chunk.visibility.astype(xp.int64))
        for p, si in zip(self.preds, self._pred_stage):
            chunk = FilterExecutor.apply_predicate(chunk, p)
            stage_rows[si] = xp.sum(chunk.visibility.astype(xp.int64))
        out_cols: List[Optional[Column]] = []
        if self.out_exprs is None:
            # filter-only run: every INPUT column passes through —
            # device columns from the (possibly traced) chunk, host
            # columns as None placeholders the caller reattaches
            # positionally. Synthetic runtime columns never leave;
            # hop window columns (part of the base space) do.
            out_cols = [None if j in self.host_out else c
                        for j, c in
                        enumerate(chunk.columns[:len(self._base_schema)])]
        else:
            for j, e in enumerate(self.out_exprs):
                out_cols.append(None if j in self.host_out
                                else e.eval(chunk))
        vis2, ops2 = chunk.visibility, chunk.ops
        # branchless noop-update-pair drop over the FINAL projection
        # (identity when no U-/U+ pairs — ProjectExecutor parity)
        if self.out_exprs is not None:
            vis2 = _drop_noop_pairs_xp(
                [c for c in out_cols if c is not None], vis2, ops2, xp,
                host_same=host_same)
        final_n = xp.sum(vis2.astype(xp.int64))
        cur = xp.sum(vis.astype(xp.int64))   # input visible count
        for si in range(n_stages):
            if stage_rows[si] is None:       # project: rows at its slot
                stage_rows[si] = cur
            else:                            # filter: its own count
                cur = stage_rows[si]
        # the LAST stage's emission includes the composed noop-pair
        # drop (the sequential chain's final project would drop there)
        stage_rows[-1] = final_n
        return out_cols, vis2, ops2, xp.stack(stage_rows)

    def _expand_hop(self, cols: List[Column], vis, ops, xp,
                    host_same=None):
        """In-trace hop expansion (HopWindowExecutor's exact math):
        `units` copy-major replicas of every column — copy i carries
        window_start = floor(ts/slide)*slide - i*slide — with NULL-
        timestamp rows masked invisible up front. Copy-major order
        preserves U-/U+ adjacency inside every copy, and copy
        boundaries end on the batch codec's invisible separator row,
        so the shifted pair compares never marry rows across copies.
        ``host_same`` (host passthrough adjacent-equality) tiles the
        same way — its wrap element lands exactly on the copy
        boundary's (last, first) pair, which the original wrap already
        computed."""
        st = self.hop
        units = st.units
        slide = st.slide_usecs
        c_ts = cols[st.time_col]
        ts = c_ts.values.astype(xp.int64)
        okm = vis if c_ts.validity is None else vis & c_ts.validity
        base = (ts // slide) * slide
        ws = xp.concatenate([base - i * slide for i in range(units)])
        out_cols = [Column(c.data_type, xp.tile(c.values, units),
                           None if c.validity is None
                           else xp.tile(c.validity, units))
                    for c in cols]
        out_cols.append(Column(DataType.TIMESTAMP, ws, None))
        out_cols.append(Column(DataType.TIMESTAMP, ws + st.size_usecs,
                               None))
        return (out_cols, xp.tile(okm, units), xp.tile(ops, units),
                None if host_same is None
                else xp.tile(host_same, units))


def _drop_noop_pairs_xp(cols: Sequence[Column], vis, ops, xp,
                        host_same=None):
    """Traced twin of ProjectExecutor._drop_noop_updates: clear both
    halves of adjacent (U-, U+) pairs whose projected values (and
    validities) are identical. ``host_same`` carries the host
    passthrough columns' adjacent equality (they bypass the trace)."""
    ud = xp.int8(int(Op.UPDATE_DELETE))
    ui = xp.int8(int(Op.UPDATE_INSERT))
    is_pair = (vis & xp.roll(vis, -1)
               & (ops == ud) & (xp.roll(ops, -1) == ui))
    # roll wraps the last row onto the first: a well-formed chunk never
    # ends with a dangling U-, and batched matrices carry an invisible
    # separator row per chunk, so the wrap term is always masked
    same = xp.ones(vis.shape[0], dtype=bool) if host_same is None \
        else host_same.astype(bool)
    for c in cols:
        v = c.values
        eq = v == xp.roll(v, -1)
        if c.validity is not None:
            ok = c.validity
            ok_n = xp.roll(ok, -1)
            eq = (eq & ok & ok_n) | (~ok & ~ok_n)
        same = same & eq
    drop = is_pair & same
    return vis & ~drop & ~xp.roll(drop, 1)


# -- standalone traced step (chunk → chunk) --------------------------------


def build_chain_step(fs: FusedStages):
    """jit-compiled (device cols, valids, vis, ops) → (out cols+valids,
    vis, ops, stage_rows). Host columns bypass; per-capacity compile
    cache like every other per-shape program."""
    import jax
    import jax.numpy as jnp

    in_schema = fs.ext_schema     # synthetic runtime columns (row ids,
    ref = list(fs.ref_cols)       # watermark thresholds) enter as
                                  # ordinary device inputs

    def step(vals, valids, vis, ops, host_same):
        cap = vis.shape[0]
        cols: List[Column] = []
        k = 0
        for i, f in enumerate(in_schema):
            if i in fs._ref_set:
                cols.append(Column(f.data_type, vals[k], valids[k]))
                k += 1
            else:
                cols.append(Column(f.data_type,
                                   jnp.zeros(cap, dtype=jnp.int32)))
        out_cols, vis2, ops2, stage_rows = fs.chain_body(
            cols, vis, ops, jnp, host_same=host_same)
        flat_vals = tuple(c.values for c in out_cols if c is not None)
        flat_ok = tuple((jnp.ones(cap, dtype=bool)
                         if c.validity is None else c.validity)
                        for c in out_cols if c is not None)
        return flat_vals, flat_ok, vis2, ops2, stage_rows

    fs._ref_set = set(ref)
    from risingwave_tpu.utils import jaxtools
    return jaxtools.instrumented_jit(step, "fused.chain_step")


# -- the agg prelude (inlined into hash_agg.build_apply) -------------------


def build_agg_prelude(fs: FusedStages, group_indices: Sequence[int],
                      agg_calls, specs):
    """Traced fn: raw int64 matrix → (key_lanes i32[N,3g], signs i32,
    vis bool, per-call (in_lanes, valid)) — the contract
    ops/hash_agg.build_apply's core consumes. Everything between the
    raw upload and the accumulator scatter happens inside the ONE
    jitted step (filter, project, key/lane encode)."""
    import jax.numpy as jnp

    in_schema = fs.ext_schema
    ref = list(fs.ref_cols)
    group = list(group_indices)
    image_cols = agg_image_cols(group, agg_calls, specs)

    def prelude(raw):
        from risingwave_tpu.ops import lanes as _lanes
        cols, vis, ops = decode_raw_cols(raw, in_schema, ref, jnp)
        out_cols, vis2, ops2, stage_rows = fs.chain_body(
            cols, vis, ops, jnp)
        signs = ops_to_signs(ops2)
        images = _float_images(fs, raw, out_cols, image_cols, jnp)
        key_lanes = key_lanes_traced(
            [(key_i64_traced(out_cols[i], images.get(i)),
              out_cols[i].validity) for i in group], jnp)
        call_inputs = []
        for call, spec in zip(agg_calls, specs):
            if call.input_idx is None:          # count(*)
                call_inputs.append(((), None))
                continue
            c = out_cols[call.input_idx]
            ok = (jnp.ones(vis2.shape[0], dtype=bool)
                  if c.validity is None else c.validity)
            if spec.orders_by_lanes and _is_float(c.data_type):
                # float MIN/MAX: order lanes from the uploaded image
                in_lanes = _lanes.order_lanes_from_image(
                    images[call.input_idx])
            else:
                # THE per-kind encoding — AggSpec.encode_input, same
                # as the executor's interpretive _inputs path; the
                # lane codecs it calls are xp-generic, so one
                # implementation serves both (no drifting twin)
                in_lanes = spec.encode_input(c.values)
            call_inputs.append((in_lanes, ok))
        return key_lanes, signs, vis2, tuple(call_inputs), stage_rows

    return prelude


def agg_image_cols(group_indices: Sequence[int], agg_calls,
                   specs) -> List[int]:
    """Output columns whose 64-bit image the agg prelude takes when
    they are FLOAT: the group keys and the MIN/MAX arguments."""
    return list(group_indices) + [
        call.input_idx for call, spec in zip(agg_calls, specs)
        if call.input_idx is not None and spec.orders_by_lanes]


# -- the join input prelude (inlined into hash_join's epoch jits) ----------


def build_join_prelude(fs: FusedStages, key_indices: Sequence[int],
                       pay_indices: Sequence[int]):
    """Traced fn: raw int64 matrix → the [key_lanes | payload_lanes]
    int32 upload matrix ops/hash_join's epoch apply/probe consume —
    the join twin of build_agg_prelude. The absorbed run's value
    computation (projection exprs, key/lane encode, payload encode)
    happens INSIDE the epoch dispatches; visibility decisions (filter
    predicates, the watermark late mask, pair degradation) ride in the
    host-built aux flags, which the executor derives from the SAME
    composed chain run on numpy — bit-identical by the fusion
    contract, so the device never needs to re-decide them."""
    import jax.numpy as jnp

    from risingwave_tpu.ops.lanes import payload_lanes_i64

    assert fs.hop is None, \
        "hop expansion changes cardinality — join preludes refuse it"
    schema = fs.ext_schema
    ref = list(fs.ref_cols)
    keys = list(key_indices)
    pays = list(pay_indices)
    need = set(keys) | set(pays)

    def prelude(raw):
        cols, vis, ops = decode_raw_cols(raw, schema, ref, jnp)
        chunk = StreamChunk(schema, cols, vis, ops)
        if fs.out_exprs is None:
            out_cols = list(chunk.columns[:len(fs.in_schema)])
        else:
            # only the columns the lanes read get evaluated — the rest
            # are dead in this trace (XLA would DCE them anyway; not
            # emitting them keeps the jaxpr small)
            out_cols = [e.eval(chunk) if j in need else None
                        for j, e in enumerate(fs.out_exprs)]
        images = _float_images(fs, raw, out_cols, keys + pays, jnp)
        key_lanes = key_lanes_traced(
            [(key_i64_traced(out_cols[i], images.get(i)),
              out_cols[i].validity) for i in keys], jnp)
        if not pays:
            return key_lanes
        # stored columns keep their bits exactly (NOT the key
        # normalization, which would fold -0.0 into 0.0 on the emit
        # path): a float's uploaded image as is, anything else widened
        pay_lanes = payload_lanes_i64(
            [(images[i] if i in images
              else out_cols[i].values.astype(jnp.int64),
              out_cols[i].validity) for i in pays], jnp)
        return jnp.concatenate([key_lanes, pay_lanes], axis=1)

    return prelude
