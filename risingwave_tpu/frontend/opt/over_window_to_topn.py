"""The over-window-to-top-N rule: `ROW_NUMBER() OVER (PARTITION BY g
ORDER BY o) AS rn ... WHERE rn <= N` is a group top-N, not a window.

Reference parity: src/frontend/src/optimizer/rule/
over_window_to_topn_rule.rs (LogicalFilter over LogicalProject over
LogicalOverWindow → LogicalTopN with the partition as its group key).

Unlike the executor-graph rules of `rules.py` this one runs INSIDE the
planner, at the one place where both halves of the pattern are in hand
(the derived table's planned chain and the outer select's WHERE): the
rank column leaves the derived table's scope, so what binds above it
has to bind against the narrowed scope, and the state table is made
for the executor that runs. No session variable turns it off; a plan
it declines is the planner's own, letter for letter, with the reason
on the over-window's line of `EXPLAIN`.

It fires when ALL of these hold:

1. the outer select's only FROM item is a derived table whose planned
   chain is a projection directly over an over-window (no join in the
   outer select);
2. the window has exactly one call and it is `row_number()`: `rank()`
   and `dense_rank()` number ties alike, so `rank() <= N` may keep
   more than N rows, which a top-N without ties cannot; a second call
   needs the whole partition (a window without ORDER BY never gets
   here: the binder refuses it, so the order is never the pk alone);
3. the rank is projected once, as a plain column, and is read by
   nothing but WHERE conjuncts of the outer select that bound it from
   above by an integer literal: `rn <= N`, `rn < N`, `rn = 1` and the
   mirrored `N >= rn`, `N > rn`, `1 = rn` (several take the smallest).
   A lower bound (`rn >= 2`, `rn = 2`), any other expression over the
   rank, or the rank among the outer select's outputs, GROUP BY,
   HAVING or ORDER BY declines: a top-N does not number its rows;
4. the limit that comes out is at least 1.

The plan it makes: `GroupTopNExecutor(group = PARTITION BY, order =
ORDER BY then the input's pk, offset 0, limit N, append_only = what
`_derive_append_only` proves of the input)`, its state table keyed
group | order | the rest of the pk and distributed by the group, the
derived table's projection over it without the rank column, and the
WHERE's other conjuncts in filters above, as before.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from risingwave_tpu.common.types import Schema
from risingwave_tpu.expr.expr import BinaryOp, InputRef, Literal
from risingwave_tpu.frontend import ast
from risingwave_tpu.frontend.binder import Binder, Scope
from risingwave_tpu.frontend.opt.checker import expr_refs
from risingwave_tpu.frontend.opt.rules import _wm_spec_list

_MIRROR = {"<=": ">=", "<": ">", ">=": "<=", ">": "<", "=": "="}


def _upper_bound(pred, rank: int) -> Optional[int]:
    """N where `pred` says no more than "the rank is at most N", else
    None."""
    if not isinstance(pred, BinaryOp):
        return None
    op, col, lit = pred.op, pred.left, pred.right
    if isinstance(col, Literal):
        op, col, lit = _MIRROR.get(op), pred.right, pred.left
    if not (isinstance(col, InputRef) and col.index == rank
            and isinstance(lit, Literal) and isinstance(lit.value, int)
            and not isinstance(lit.value, bool)):
        return None
    if op == "<=":
        return lit.value
    if op == "<":
        return lit.value - 1
    if op == "=" and lit.value == 1:
        return 1
    return None


def _names_read(node, out: set) -> set:
    """Column names an AST fragment reads (`*` for a star)."""
    if isinstance(node, ast.ColRef):
        out.add(node.name)
    elif isinstance(node, (list, tuple)):
        for x in node:
            _names_read(x, out)
    elif isinstance(node, ast.Expr):
        for x in vars(node).values():
            _names_read(x, out)
    return out


def over_window_to_topn(planner, ex, scope: Scope, sel: ast.Select,
                        conjuncts: List[ast.Expr]
                        ) -> Tuple[object, Scope, List[ast.Expr]]:
    """(executor, scope, WHERE conjuncts still to plan) of a derived
    table: the three as given, or with the over-window replaced by a
    top-N as the module docstring says."""
    from risingwave_tpu.expr.window import WindowFuncKind
    from risingwave_tpu.state.state_table import StateTable
    from risingwave_tpu.stream.executor import ExecutorInfo
    from risingwave_tpu.stream.executors.over_window import (
        OverWindowExecutor,
    )
    from risingwave_tpu.stream.executors.simple import ProjectExecutor
    from risingwave_tpu.stream.executors.top_n import GroupTopNExecutor

    proj, win = ex, getattr(ex, "input", None)
    if not (isinstance(proj, ProjectExecutor)
            and isinstance(win, OverWindowExecutor)):
        return ex, scope, conjuncts

    def declined(why: str):
        win.plan_note = "not planned as a top-N: " + why
        return ex, scope, conjuncts

    if len(win.calls) != 1:
        return declined(f"{len(win.calls)} window calls share the "
                        "window and a top-N computes none")
    kind = win.calls[0].kind
    if kind != WindowFuncKind.ROW_NUMBER:
        return declined(f"{kind.value}() numbers ties alike, so a bound "
                        "on it may keep more rows than a top-N holds")
    if sel.joins:
        return declined("the derived table is joined before it is "
                        "filtered")
    n_in, n_vis = win.n_in, len(scope.schema)
    at = [k for k, e in enumerate(proj.exprs) if n_in in expr_refs(e)]
    if len(at) != 1 or at[0] >= n_vis \
            or not isinstance(proj.exprs[at[0]], InputRef):
        return declined("the rank is projected more than once or "
                        "inside an expression")
    rank = at[0]
    name = scope.schema[rank].name
    limit, rest = None, []
    for c in conjuncts:
        pred = Binder(scope).bind(c)
        if rank not in expr_refs(pred):
            rest.append(c)
            continue
        n = _upper_bound(pred, rank)
        if n is None:
            return declined(f"a conjunct reads {name} as more than a "
                            "bound from above (a lower bound, or an "
                            "expression over the rank)")
        limit = n if limit is None else min(limit, n)
    if limit is None:
        return declined(f"no conjunct of the WHERE bounds {name} from "
                        "above")
    read = _names_read([e for e, _a in sel.projections] + sel.group_by
                       + [sel.having] + [e for e, _d in sel.order_by],
                       set())
    if name in read or "*" in read:
        return declined(f"{name} is read above the filter, and a top-N "
                        "does not number its rows")
    if limit < 1:
        return declined(f"the bound keeps {limit} rows")

    inp = win.input
    group, order = list(win.partition_indices), list(win.order_by)
    pk = list(win.input_pk)
    ordered = [i for i, _d in order]
    state = StateTable(
        win.state.table_id, inp.schema,
        group + ordered + [i for i in pk
                           if i not in group and i not in ordered],
        planner.store, dist_key_indices=group)
    append_only = planner._derive_append_only(inp)
    topn = GroupTopNExecutor(
        inp, order, offset=0, limit=limit, state=state,
        group_indices=group, append_only=append_only, pk_indices=pk,
        tier_cap=planner.state_tier_cap if group else None)
    # EXPLAIN's line, in the derived table's names where it has them
    named = {e.index: proj.schema[k].name
             for k, e in reversed(list(enumerate(proj.exprs)))
             if isinstance(e, InputRef)}

    def col(i: int) -> str:
        return named.get(i, inp.schema[i].name)

    topn.plan_note = (
        f"group: [{', '.join(col(i) for i in group)}], order: ["
        + ", ".join(f"{col(i)} {'DESC' if d else 'ASC'}"
                    for i, d in order)
        + f"], limit: {limit}, append_only: "
        + ("true" if append_only else "false"))
    keep = [k for k in range(len(proj.exprs)) if k != rank]
    moved = {k: j for j, k in enumerate(keep)}
    derivations = {
        in_col: [(moved[s[0]], s[1]) if isinstance(s, tuple) else moved[s]
                 for s in _wm_spec_list(specs)]
        for in_col, specs in proj.watermark_derivations.items()}
    out = ProjectExecutor(topn, [proj.exprs[k] for k in keep],
                          [proj.schema[k].name for k in keep],
                          watermark_derivations=derivations,
                          span_args=proj.span_args)
    out._info = ExecutorInfo(out.schema,
                             [moved[k] for k in proj.pk_indices],
                             out.identity)
    vis = [k for k in keep if k < n_vis]
    return (out,
            Scope(Schema([scope.schema[k] for k in vis]),
                  [scope.qualifiers[k] for k in vis]),
            rest)
