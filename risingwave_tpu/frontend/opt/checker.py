"""Plan-property checker: re-derive invariants after every rewrite.

The correctness-tooling half of the rewrite subsystem. A rule's output
is never trusted: after each rule application the engine re-checks

- structural integrity — every expression's InputRefs bind inside its
  input's arity with matching types, join keys/pks index their side
  schemas and agree with the state tables, agg group/call indices
  stay in range, pass-through executors keep their input schema;
- the root contract — the rewritten subtree feeds the SAME Materialize
  schema and stream key it fed before (the MV's shape is frozen at
  plan time; a rewrite may change how rows are produced, never what
  the table holds);
- append-only-ness — any HashAgg planned on the cheap append-only
  path must still provably sit over an append-only chain, and the
  root's derived append-only-ness must not weaken (downstream plan
  decisions were made against the original derivation).

On any violation the engine falls back to the pre-rule plan; in
strict mode (tests) the violation raises instead — a rule that breaks
an invariant fails the suite loudly rather than silently degrading.
"""

from __future__ import annotations

from typing import Iterable, List, Set

from risingwave_tpu.expr.expr import expr_refs  # noqa: F401 (its old home)

_STRICT = False


def set_strict_checker(on: bool) -> None:
    """Assert-don't-fallback mode (tier-1 conftest arms this)."""
    global _STRICT
    _STRICT = bool(on)


def strict_checker() -> bool:
    return _STRICT


class CheckError(ValueError):
    """A rewrite broke a plan invariant."""


def _check_expr(e, schema, where: str) -> None:
    """Refs in range + ref types equal to the input field types."""
    from risingwave_tpu.expr.expr import InputRef
    n = len(schema)
    for i in sorted(expr_refs(e)):
        if not (0 <= i < n):
            raise CheckError(f"{where}: InputRef({i}) out of range "
                             f"(input arity {n})")

    def walk(x):
        if isinstance(x, InputRef):
            if schema[x.index].data_type != x.return_type:
                raise CheckError(
                    f"{where}: InputRef({x.index}) typed "
                    f"{x.return_type} but input column is "
                    f"{schema[x.index].data_type}")
            return
        for c in _expr_children(x):
            walk(c)

    walk(e)


def _expr_children(e) -> Iterable:
    from risingwave_tpu.expr.expr import (
        BinaryOp, Case, Cast, FuncCall, UnaryOp,
    )
    if isinstance(e, BinaryOp):
        return (e.left, e.right)
    if isinstance(e, (UnaryOp, Cast)):
        return (e.child,)
    if isinstance(e, Case):
        return tuple(x for w in e.whens for x in w) + (e.else_,)
    if isinstance(e, FuncCall):
        return tuple(e.args)
    return ()


def _same_schema(a, b) -> bool:
    return (len(a) == len(b)
            and all(fa.name == fb.name and fa.data_type == fb.data_type
                    for fa, fb in zip(a, b)))


def _same_types(a, b) -> bool:
    return (len(a) == len(b)
            and all(fa.data_type == fb.data_type
                    for fa, fb in zip(a, b)))


def snapshot(root) -> dict:
    """Baseline facts about the plan the rewrite must preserve."""
    from risingwave_tpu.frontend.planner import StreamPlanner
    return {
        "root_type": type(root),
        "schema": [(f.name, f.data_type) for f in root.schema],
        "pk": list(root.pk_indices),
        "append_only": StreamPlanner._derive_append_only(root),
    }


def check(root, baseline: dict) -> None:
    """Full invariant sweep; raises CheckError on the first violation."""
    if type(root) is not baseline["root_type"]:
        raise CheckError(
            f"rewrite replaced the plan root: {baseline['root_type']}"
            f" -> {type(root)}")
    got = [(f.name, f.data_type) for f in root.schema]
    if got != baseline["schema"]:
        raise CheckError(f"root schema changed: {baseline['schema']} "
                         f"-> {got}")
    if list(root.pk_indices) != baseline["pk"]:
        raise CheckError(f"root stream key changed: {baseline['pk']} "
                         f"-> {list(root.pk_indices)}")
    from risingwave_tpu.frontend.planner import StreamPlanner
    if baseline["append_only"] and \
            not StreamPlanner._derive_append_only(root):
        raise CheckError("rewrite weakened derived append-only-ness")
    _verify(root, seen=set())


def _verify(ex, seen: Set[int]) -> None:
    """Per-executor structural invariants, recursively."""
    from risingwave_tpu.stream.executor import executor_children
    if id(ex) in seen:
        raise CheckError(
            f"executor {ex.identity} appears twice in the plan tree "
            "(a rule shared a rebuilt subtree)")
    seen.add(id(ex))
    for _attr, _i, child in executor_children(ex):
        _verify(child, seen)
    _verify_node(ex)


def _verify_node(ex) -> None:
    from risingwave_tpu.stream.executors.hash_agg import (
        HashAggExecutor, agg_state_schema,
    )
    from risingwave_tpu.stream.executors.hash_join import (
        HashJoinExecutor,
    )
    from risingwave_tpu.stream.executors.materialize import (
        MaterializeExecutor,
    )
    from risingwave_tpu.stream.executors.row_id_gen import (
        RowIdGenExecutor,
    )
    from risingwave_tpu.stream.executors.simple import (
        FilterExecutor, ProjectExecutor,
    )

    name = type(ex).__name__
    for p in ex.pk_indices:
        if not (0 <= p < len(ex.schema)):
            raise CheckError(f"{name}: pk index {p} out of range")

    if isinstance(ex, ProjectExecutor):
        if len(ex.exprs) != len(ex.schema):
            raise CheckError("Project: expr/schema arity mismatch")
        for e, f in zip(ex.exprs, ex.schema):
            _check_expr(e, ex.input.schema, "Project")
            if e.return_type != f.data_type:
                raise CheckError(
                    f"Project: column {f.name} typed {f.data_type} "
                    f"but expr returns {e.return_type}")
        n_out = len(ex.schema)
        for in_col, specs in ex.watermark_derivations.items():
            if not (0 <= in_col < len(ex.input.schema)):
                raise CheckError(
                    f"Project: watermark derivation from input col "
                    f"{in_col} out of range")
            for spec in (specs if isinstance(specs, list) else [specs]):
                out = spec[0] if isinstance(spec, tuple) else spec
                if not (0 <= out < n_out):
                    raise CheckError(
                        f"Project: watermark derivation to output "
                        f"{out} out of range")
        return
    if isinstance(ex, FilterExecutor):
        from risingwave_tpu.common.types import DataType
        _check_expr(ex.predicate, ex.input.schema, "Filter")
        if ex.predicate.return_type != DataType.BOOLEAN:
            raise CheckError("Filter: predicate is not boolean")
        if not _same_schema(ex.schema, ex.input.schema):
            raise CheckError("Filter: schema differs from input")
        return
    if isinstance(ex, RowIdGenExecutor):
        if len(ex.schema) != len(ex.input.schema) + 1 or \
                not _same_schema(list(ex.schema)[:-1],
                                 list(ex.input.schema)):
            raise CheckError("RowIdGen: schema is not input + _row_id")
        return
    if isinstance(ex, HashJoinExecutor):
        left, right = ex.sides
        eff_arity = 0
        for idx, (side, inp, lbl) in enumerate(
                ((left, ex.left_in, "left"),
                 (right, ex.right_in, "right"))):
            # a fused input side (opt/fusion.py try_fuse_join): the
            # side's index space is the absorbed run's OUTPUT schema,
            # and the run itself must re-verify against the raw input
            # actually feeding it
            if side.fused_input is not None:
                _verify_fused_stages(side.fused_input, inp.schema,
                                     f"HashJoin[{lbl} fused]")
                from risingwave_tpu.frontend.opt.fusion import (
                    join_side_ineligible_reason,
                )
                r = join_side_ineligible_reason(ex, idx)
                if r is not None:
                    raise CheckError(
                        f"HashJoin[{lbl} fused]: ineligible ({r})")
                eff = side.fused_input.out_schema
            else:
                eff = inp.schema
            eff_arity += len(eff)
            if not _same_types(side.schema, eff):
                raise CheckError(
                    f"HashJoin: {lbl} side schema drifted from its "
                    "input")
            for k in side.key_indices:
                if not (0 <= k < len(eff)):
                    raise CheckError(
                        f"HashJoin: {lbl} key {k} out of range")
            if not _same_types(side.table.schema, eff):
                raise CheckError(
                    f"HashJoin: {lbl} state-table schema drifted")
            for p in side.table.pk_indices:
                if not (0 <= p < len(eff)):
                    raise CheckError(
                        f"HashJoin: {lbl} state pk {p} out of range")
        lt = [left.schema[i].data_type for i in left.key_indices]
        rt = [right.schema[i].data_type for i in right.key_indices]
        if lt != rt:
            raise CheckError("HashJoin: key types differ across sides")
        if ex.join_type.subject is None and \
                len(ex.schema) != eff_arity:
            raise CheckError("HashJoin: output arity != left + right")
        if ex.condition is not None:
            # boolean, and an inner join's: `adopt_condition` holds
            # those; a rewrite can only unbind its references
            _check_expr(ex.condition, ex.schema, "HashJoin condition")
        return
    if isinstance(ex, HashAggExecutor):
        # fused aggs (opt/fusion.py) absorb a filter/project run: the
        # agg's index space is the run's OUTPUT schema, and the run
        # itself must re-verify (traceable + planned against the raw
        # input actually feeding it)
        if ex.fused_stages is not None:
            _verify_fused_stages(ex.fused_stages, ex.input.schema,
                                 "HashAgg[fused]")
            from risingwave_tpu.frontend.opt.fusion import (
                agg_ineligible_reason,
            )
            r = agg_ineligible_reason(ex)
            if r is not None:
                raise CheckError(f"HashAgg[fused]: ineligible ({r})")
            in_schema = ex.fused_stages.out_schema
        else:
            in_schema = ex.input.schema
        n_in = len(in_schema)
        for g in ex.group_indices:
            if not (0 <= g < n_in):
                raise CheckError(f"HashAgg: group index {g} out of "
                                 "range")
        for c in ex.agg_calls:
            if c.input_idx is not None and not (0 <= c.input_idx < n_in):
                raise CheckError(
                    f"HashAgg: call input {c.input_idx} out of range")
        sch, pk = agg_state_schema(in_schema,
                                   list(ex.group_indices),
                                   list(ex.agg_calls), ex.key_lead)
        if not _same_types(sch, ex.table.schema) or \
                pk != list(ex.table.pk_indices):
            raise CheckError("HashAgg: state-table schema/pk no longer "
                             "matches the input")
        if ex.append_only:
            from risingwave_tpu.frontend.planner import StreamPlanner
            if not StreamPlanner._derive_append_only(ex.input):
                raise CheckError(
                    "HashAgg: planned append-only but the rewritten "
                    "input is not provably append-only")
        return
    from risingwave_tpu.stream.executors.fused import (
        FusedFragmentExecutor,
    )
    if isinstance(ex, FusedFragmentExecutor):
        _verify_fused_stages(ex.fused_stages, ex.input.schema,
                             "FusedFragment")
        if not _same_types(ex.schema, ex.fused_stages.out_schema):
            raise CheckError(
                "FusedFragment: executor schema drifted from the "
                "composed run's output schema")
        return
    if isinstance(ex, MaterializeExecutor):
        if not _same_types(ex.schema, ex.input.schema):
            raise CheckError("Materialize: input schema drifted from "
                             "the MV table schema")
        return
    # other executor types carry no rewrite-visible contract beyond
    # the recursive child checks (rules never rebuild them)


def _verify_fused_stages(fs, input_schema, where: str) -> None:
    """A fused run must still bind against the raw input actually
    feeding it AND stay traceable — the fallback contract of SET
    stream_fusion: any violation reverts to the interpretive chain."""
    if not _same_types(fs.in_schema, input_schema):
        raise CheckError(
            f"{where}: fused run planned against a different input "
            "schema than the one feeding it")
    # composed exprs bind against the BODY schema: synthetic runtime
    # columns (absorbed row ids, watermark thresholds) and an absorbed
    # hop's window columns are legal refs past the real input
    for p in fs.preds:
        _check_expr(p, fs.body_schema, f"{where} pred")
    for j, e in enumerate(fs.out_exprs or []):
        _check_expr(e, fs.body_schema, f"{where} expr")
    r = fs.fusable_reason()
    if r is not None:
        raise CheckError(f"{where}: run is not traceable ({r})")


def check_fragment_graph(graph) -> None:
    """Structural integrity of a (possibly rewritten) fragment graph:
    topological input edges, bijective exchange ports, node refs in
    range, exactly one materialize in the final fragment."""
    from risingwave_tpu.stream.plan_ir import NODE_REF_KEYS
    frags = graph.fragments
    if not frags:
        raise CheckError("empty fragment graph")
    for fi, frag in enumerate(frags):
        ports = []
        for idx, node in enumerate(frag.nodes):
            refs = [node.get(key) for key in NODE_REF_KEYS]
            if isinstance(node.get("inputs"), list):
                refs += list(node["inputs"])
            for v in refs:
                if isinstance(v, int) and not (0 <= v < idx):
                    raise CheckError(
                        f"fragment {fi} node {idx}: ref {v} does "
                        "not reference an earlier node")
            if node["op"] == "exchange_in":
                ports.append((node["port"], idx))
        if sorted(p for p, _ in ports) != list(range(len(frag.inputs))):
            raise CheckError(
                f"fragment {fi}: exchange ports {sorted(ports)} do "
                f"not match its {len(frag.inputs)} inputs")
        for p, idx in ports:
            if frag.inputs[p].node_idx != idx:
                raise CheckError(
                    f"fragment {fi}: input {p} points at node "
                    f"{frag.inputs[p].node_idx}, placeholder is {idx}")
        for inp in frag.inputs:
            if not (0 <= inp.up_frag < fi):
                raise CheckError(
                    f"fragment {fi}: upstream {inp.up_frag} is not an "
                    "earlier fragment")
    mats: List[int] = [fi for fi, f in enumerate(frags)
                       for n in f.nodes if n["op"] == "materialize"]
    if mats and mats[-1] != len(frags) - 1:
        raise CheckError("materialize is not in the final fragment")
