"""fusion_grouping: mark maximal fusable runs, emit fused executors.

The plan-rewrite engine's fusion rule (ISSUE 6 tentpole; TiLT shape,
arxiv 2301.12030). Walks the planned executor chain of each fragment
and collapses maximal source/coalesce → filter → project runs feeding a
keyed executor into ONE traced dataflow step:

- run ends at an ELIGIBLE HashAgg → the agg absorbs the stages as a
  kernel prelude (ops/fused.py build_agg_prelude): raw chunk upload →
  filter → project → key/lane encode → accumulator update, one jitted
  dispatch with donated state. A CoalesceExecutor directly under the
  agg is absorbed too — the kernel's raw backlog IS the batcher now
  (BATCH_ROWS), so the interpretive coalescer would only add a copy.
- any other run of ≥2 consecutive filter/project stages (join input
  sides, materialize feeds) → a standalone FusedFragmentExecutor: the
  same composed chain as one jit per chunk, host passthrough columns
  riding around the trace.

Eligibility is checked BEFORE mutating anything (traceable_reason per
expression, device group keys, no host state mirrors on the agg); an
ineligible run is simply left interpretive — and the engine's property
checker re-derives every plan invariant after the rule fires, falling
back to the unfused chain if fusion broke one (opt/checker.py grew
fused-shape checks for exactly this).

Runs last in the registry: pushdown/projection-fusion/pruning settle
the chain shape first, fusion freezes it into traces.
"""

from __future__ import annotations

import copy
from typing import List, Optional, Tuple

from risingwave_tpu.stream.executor import executor_children


# which executor kinds each absorption shape accepts: agg preludes
# take filter/project plus a head-of-run hop_window (ISSUE 12: the
# units× row expansion and window-lane synthesis happen INSIDE the
# jitted apply — the watermark transform is per-message host work the
# executor's derive_watermarks path already runs); join input runs add
# row_id_gen (the generated pk column rides the raw matrix as a
# synthetic device input) but NOT hop_window — the expansion changes
# cardinality, which the join's host-built per-row aux flags cannot
# follow; standalone blocks additionally absorb watermark_filter (the
# block's own message loop does the watermark emission/persistence
# the absorbed executor used to) and hop_window
AGG_KINDS = frozenset({"filter", "project", "hop_window"})
JOIN_KINDS = frozenset({"filter", "project", "row_id_gen"})
BLOCK_KINDS = JOIN_KINDS | {"watermark_filter", "hop_window"}


def _as_stage(ex, kinds=BLOCK_KINDS):
    """Fusable executor → FusedStage (kind-gated), else None."""
    from risingwave_tpu.ops.fused import FusedStage
    from risingwave_tpu.stream.executors.row_id_gen import (
        RowIdGenExecutor,
    )
    from risingwave_tpu.stream.executors.simple import (
        FilterExecutor, ProjectExecutor,
    )
    from risingwave_tpu.stream.executors.watermark_filter import (
        WatermarkFilterExecutor,
    )
    if isinstance(ex, FilterExecutor) and "filter" in kinds:
        return FusedStage("filter", "FilterExecutor",
                          exprs=(ex.predicate,))
    if isinstance(ex, ProjectExecutor) and "project" in kinds:
        return FusedStage(
            "project", "ProjectExecutor",
            exprs=tuple(ex.exprs),
            names=tuple(f.name for f in ex.schema),
            watermark_derivations=dict(ex.watermark_derivations))
    if isinstance(ex, RowIdGenExecutor) and "row_id_gen" in kinds:
        return FusedStage("row_id_gen", "RowIdGenExecutor",
                          runtime=ex)
    if isinstance(ex, WatermarkFilterExecutor) \
            and "watermark_filter" in kinds:
        return FusedStage("watermark_filter", "WatermarkFilterExecutor",
                          time_col=ex.time_col, delay_usecs=ex.delay,
                          runtime=ex)
    from risingwave_tpu.stream.executors.hop_window import (
        HopWindowExecutor,
    )
    if isinstance(ex, HopWindowExecutor) and "hop_window" in kinds:
        return FusedStage("hop_window", "HopWindowExecutor",
                          time_col=ex.time_col,
                          slide_usecs=ex.slide, size_usecs=ex.size,
                          books=ex.books_table)
    return None


def _collect_run(top, kinds=BLOCK_KINDS) -> Tuple[list, object]:
    """Maximal consecutive fusable run starting at `top` going
    downstream→upstream. Returns (stages in DATAFLOW order, base)."""
    rev: List = []
    node = top
    while True:
        st = _as_stage(node, kinds)
        if st is None:
            break
        rev.append(st)
        node = node.input
        if st.kind == "hop_window":
            # a hop must HEAD the run (everything downstream composes
            # in its output space) — stop extending upstream so the
            # collected run ends exactly at the expansion
            break
    return list(reversed(rev)), node


def agg_ineligible_reason(agg) -> Optional[str]:
    """THE eligibility predicate — the one copy. The rule gates on it
    before fusing, HashAggExecutor's constructor/adopt guards call it,
    and the checker re-verifies it on ALREADY-fused aggs after every
    later rewrite round (so `fused_stages is not None` is deliberately
    NOT a condition here).

    Injected SHARDED kernels are eligible since ISSUE 10: the sharded
    apply grew a prelude path (the absorbed run traces before vnode
    routing inside the same SPMD step) — only a kernel that already
    saw data, or an injected kernel with no prelude support at all,
    refuses."""
    k = agg._kernel
    if k is not None and not getattr(k, "supports_prelude", False):
        return "injected kernel without a prelude path"
    if agg.minput or agg.distinct_tables:
        return "retractable MIN/MAX or DISTINCT (host multisets)"
    if agg._hll_calls or agg._host_calls:
        return "host-side agg state (HLL/string_agg/array_agg)"
    if agg.tier_cap is not None:
        return "cold-tier governed (per-chunk host touch)"
    if agg.key_codec.interners:
        return "host-typed group keys (interning)"
    return None


def agg_fusable_reason(agg) -> Optional[str]:
    """None iff this HashAggExecutor can absorb a stage prelude NOW
    (rule-side gate: refuses re-fusing on later fixpoint rounds)."""
    if agg.fused_stages is not None:
        return "already fused"
    return agg_ineligible_reason(agg)


def join_side_ineligible_reason(join, side_idx: int) -> Optional[str]:
    """THE join-side eligibility predicate (rule, adopt guard, and
    checker all call it — the checker re-verifies ALREADY-fused sides,
    so `fused_input is not None` is deliberately not a condition).
    The prelude inlines into the epoch dispatches, which both kernel
    shapes have; host-typed keys would need interning inside the
    trace, and the cold tier reads buffered key lanes the raw matrix
    no longer carries."""
    side = join.sides[side_idx]
    if join.rebuild_opts.get("state_cap") is not None:
        return ("cold-tier governed join (reload reads the buffered "
                "key lanes)")
    for i in side.key_indices:
        if not side.schema[i].data_type.is_device:
            return (f"host-typed join key column "
                    f"{side.schema[i].data_type.value} (interned)")
    return None


def join_side_fusable_reason(join, side_idx: int) -> Optional[str]:
    """None iff this join side can absorb its input run NOW."""
    if join.sides[side_idx].fused_input is not None:
        return "already fused"
    return join_side_ineligible_reason(join, side_idx)


def fuse_fragments(root, dist_parallelism: int = 1
                   ) -> Tuple[object, int, str]:
    """The rule entry point (engine registry signature; the engine
    registers a partial binding ``dist_parallelism``). Non-
    destructive: copy-on-write along every mutated path so the engine's
    fallback plan stays intact.

    At distributed parallelism > 1 the fragmenter's hash-exchange cut
    lands BELOW an absorbed run (raw rows ship, the prelude runs on
    the consumer actors), so the cut's hash keys must map back through
    the run to raw input columns (FusedStages.input_positions) — a key
    computed by a non-trivial projection cannot be dispatched on and
    the run stays interpretive. Value equality makes the raw-column
    hash partition the post-stage keys consistently."""
    from risingwave_tpu.ops.fused import FusedStages, agg_image_cols
    from risingwave_tpu.stream.coalesce import CoalesceExecutor
    from risingwave_tpu.stream.executors.fused import (
        FusedFragmentExecutor,
    )
    from risingwave_tpu.stream.executors.hash_agg import HashAggExecutor
    from risingwave_tpu.stream.executors.hash_join import (
        HashJoinExecutor,
    )
    details: List[str] = []

    def try_fuse_agg(agg):
        """Eligible agg + run below (coalesce absorbed) → fused copy."""
        if agg_fusable_reason(agg) is not None:
            return None
        node = agg.input
        if isinstance(node, CoalesceExecutor):
            node = node.input
        stages, base = _collect_run(node, AGG_KINDS)
        if not stages:
            return None
        fs = FusedStages(base.schema, stages)
        reason = fs.fusable_reason()
        if reason is not None:
            details.append(f"agg run NOT fused ({reason})")
            return None
        reason = fs.bit_image_reason(
            agg_image_cols(agg.group_indices, agg.agg_calls,
                           agg.specs), "group key / MIN-MAX argument")
        if reason is not None:
            details.append(f"agg run NOT fused ({reason})")
            return None
        if fs.hop is not None and agg._kernel is not None:
            # injected (sharded) kernels size their vnode routing for
            # the UPLOADED row count — a hop prelude multiplies rows
            # in-trace past those shapes. Single-chip lazy kernels
            # (the _kernel-is-None case) expand freely.
            details.append(
                "agg run NOT fused (hop expansion needs the "
                "single-chip lazy kernel — sharded routing shapes "
                "are sized pre-expansion)")
            return None
        if dist_parallelism > 1 and \
                getattr(agg, "two_phase_role", None) != "local" and \
                fs.input_positions(agg.group_indices) is None:
            details.append(
                "agg run NOT fused (group keys do not map to raw "
                "input columns — parallelism>1 cut dispatches raw "
                "rows)")
            return None
        new_agg = copy.copy(agg)
        new_agg.adopt_fused_stages(fs, base)
        new_agg._info = copy.copy(agg._info)
        new_agg._info.identity = (
            f"{agg.identity}[fused:{fs.describe()}]")
        details.append(f"agg absorbed {fs.describe()}")
        return new_agg

    def try_fuse_standalone(top):
        """≥2-stage run not feeding an eligible agg → fused block."""
        stages, base = _collect_run(top, BLOCK_KINDS)
        if len(stages) < 2:
            return None
        fs = FusedStages(base.schema, stages)
        reason = fs.fusable_reason()
        if reason is not None:
            details.append(f"run NOT fused ({reason})")
            return None
        details.append(f"block {fs.describe()}")
        return FusedFragmentExecutor(base, fs)

    def try_fuse_join(join):
        """Eligible join sides absorb their input runs (coalesce
        absorbed — the epoch buffer IS the batcher) into the side's
        epoch apply+probe dispatches. Returns a fused COPY (join +
        adopted sides) or None; each side fuses independently."""
        import copy as _copy
        new_join = None
        for s, attr in ((0, "left_in"), (1, "right_in")):
            r = join_side_fusable_reason(join, s)
            if r is not None:
                continue
            node = getattr(new_join if new_join is not None else join,
                           attr)
            if isinstance(node, CoalesceExecutor):
                node = node.input
            stages, base = _collect_run(node, JOIN_KINDS)
            if not stages:
                continue
            fs = FusedStages(base.schema, stages)
            reason = fs.fusable_reason()
            if reason is not None:
                details.append(
                    f"join side {s} run NOT fused ({reason})")
                continue
            reason = fs.bit_image_reason(
                list(join.sides[s].key_indices)
                + list(join.sides[s].pay_indices),
                "join key / stored join column")
            if reason is not None:
                details.append(
                    f"join side {s} run NOT fused ({reason})")
                continue
            if dist_parallelism > 1 and fs.input_positions(
                    join.sides[s].key_indices) is None:
                details.append(
                    f"join side {s} run NOT fused (join keys do not "
                    "map to raw input columns — parallelism>1 cut "
                    "dispatches raw rows)")
                continue
            if new_join is None:
                new_join = _copy.copy(join)
                new_join.sides = tuple(_copy.copy(sd)
                                       for sd in join.sides)
                new_join._info = _copy.copy(join._info)
            new_join.adopt_fused_input(s, fs, base)
            details.append(f"join side {s} absorbed {fs.describe()}")
        if new_join is not None:
            descs = "; ".join(
                ("L:" if i == 0 else "R:") + sd.fused_input.describe()
                for i, sd in enumerate(new_join.sides)
                if sd.fused_input is not None)
            new_join._info.identity = \
                f"{join.identity}[fused:{descs}→join]"
        return new_join

    def walk(ex):
        """Top-down: an eligible agg/join absorbs its run BEFORE the
        generic descent could carve a standalone block out of it; the
        walk then resumes below the absorbed base. Returns a (possibly
        new) executor; originals are never mutated."""
        from risingwave_tpu.frontend.opt.rules import _swap_child
        nonlocal fired
        if isinstance(ex, HashAggExecutor):
            fused = try_fuse_agg(ex)
            if fused is not None:
                fired += 1
                fused.input = walk(fused.input)   # fused is a copy
                return fused
        elif isinstance(ex, HashJoinExecutor):
            fused = try_fuse_join(ex)
            if fused is not None:
                fired += 1
                ex = fused            # descend below the fused copy
        elif _as_stage(ex) is not None:
            fused = try_fuse_standalone(ex)
            if fused is not None:
                fired += 1
                fused.input = walk(fused.input)
                return fused
        out = ex
        for attr, idx, child in executor_children(ex):
            new_child = walk(child)
            if new_child is not child:
                out = _swap_child(out, attr, idx, new_child)
        return out

    fired = 0
    new_root = walk(root)
    return new_root, fired, "; ".join(details)
