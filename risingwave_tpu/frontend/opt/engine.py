"""Fixpoint rewrite driver + rule registry + rewrite observability.

`rewrite_stream_plan` applies the enabled executor-graph rules round-
robin until none fires (bounded rounds). After EVERY rule application
the plan-property checker re-derives the invariants; a violation (or a
rule crash) falls back to the last good plan and disables the rule for
the rest of the run — in strict mode (tier-1 conftest) it raises
instead, so a broken rule fails the suite loudly.

Observability: every fired rule increments
`rewrite_rule_fired_total{rule=...}` (column pruning also bumps
`plan_columns_pruned`), and the per-job firing log lands in the
process-global history backing the `rw_plan_rewrites` system table.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from risingwave_tpu.frontend.opt import checker as _checker
from risingwave_tpu.frontend.opt import rules as _rules
from risingwave_tpu.frontend.opt.checker import CheckError

MAX_ROUNDS = 8

# applied in registry order each round: pushdown first (filters reach
# their sources, or the inner join whose condition they are, before
# liveness is computed), fusion + elision shrink the chain, pruning
# runs over the settled shape; fragment fusion LAST (opt/fusion.py) —
# it freezes the settled chain into traces
EXECUTOR_RULES = {
    "filter_pushdown": _rules.push_filters,
    "project_fusion": _rules.fuse_projects,
    "noop_project_elision": _rules.elide_noop_projects,
    "column_pruning": _rules.prune_columns,
}
EXECUTOR_RULE_NAMES = tuple(EXECUTOR_RULES)
FRAGMENT_RULE_NAMES = ("exchange_elision",)
RULE_NAMES = EXECUTOR_RULE_NAMES + FRAGMENT_RULE_NAMES

# fragment fusion rides its own knob (SET stream_fusion = on|off), not
# the stream_rewrite_rules csv — it changes the EXECUTION substrate
# (traced megakernel vs interpretive chain), not just the plan shape
FUSION_RULE_NAME = "fusion_grouping"


def parse_rules(spec: Optional[str]):
    """'all' | 'none' | 'a,b,c' → frozenset of enabled rule names.
    Raises PlanError on an unknown rule (SET-time validation)."""
    from risingwave_tpu.frontend.planner import PlanError
    s = (spec or "all").strip().lower()
    if s in ("all", ""):
        return frozenset(RULE_NAMES)
    if s == "none":
        return frozenset()
    names = [p.strip() for p in s.split(",") if p.strip()]
    unknown = [n for n in names if n not in RULE_NAMES]
    if unknown:
        raise PlanError(
            f"unknown rewrite rule(s) {unknown}; known: "
            f"{', '.join(RULE_NAMES)}")
    return frozenset(names)


def parse_fusion(spec: Optional[str]) -> bool:
    """SET stream_fusion validator: 'on' | 'off' → bool."""
    from risingwave_tpu.frontend.planner import PlanError
    s = (spec or "on").strip().lower()
    if s in ("on", "true", "1"):
        return True
    if s in ("off", "false", "0"):
        return False
    raise PlanError(
        f"stream_fusion must be 'on' or 'off', got {spec!r}")


class RewriteReport:
    """What one rewrite run did: per-rule fire counts + fallbacks."""

    def __init__(self, label: str = ""):
        self.label = label
        self.fired: Dict[str, int] = {}
        self.details: List[Tuple[str, str]] = []   # (rule, detail)
        self.fallbacks: List[Tuple[str, str]] = []  # (rule, reason)
        # rule → why it declined (a rule that rewrote nothing but had
        # something to say, e.g. fusion's "run NOT fused (...)")
        self.declined: Dict[str, str] = {}

    @property
    def total_fired(self) -> int:
        return sum(self.fired.values())

    def summary(self) -> str:
        if not self.fired and not self.fallbacks:
            return "no rewrites fired"
        parts = [f"{n}={c}" for n, c in sorted(self.fired.items())]
        for rule, _reason in self.fallbacks:
            parts.append(f"{rule}=FALLBACK")
        return ", ".join(parts)


# process-global firing log (the metrics registries are process-global
# too); rw_plan_rewrites serves it over the ordinary batch surface
_HISTORY: List[tuple] = []          # (seq, job, rule, fired, detail)
_HISTORY_CAP = 4096
_SEQ = [0]


def _record_history(job: str, rule: str, fired: int,
                    detail: str) -> None:
    _SEQ[0] += 1
    _HISTORY.append((_SEQ[0], job, rule, fired, detail))
    del _HISTORY[:-_HISTORY_CAP]


def rewrite_history_rows() -> List[tuple]:
    return list(_HISTORY)


def rewrite_stream_plan(root, spec: Optional[str] = "all",
                        label: str = "",
                        record: bool = True,
                        extra_rules: Optional[dict] = None,
                        fusion: bool = False,
                        dist_parallelism: int = 1
                        ) -> Tuple[object, RewriteReport]:
    """Rewrite one planned executor tree to fixpoint. Returns the
    (possibly identical) new root and a report; never raises in
    fallback mode — a rule that misbehaves is dropped, the plan that
    deployed yesterday still deploys today. ``fusion`` enables the
    fragment-fusion rule (SET stream_fusion; opt/fusion.py) on top of
    whatever ``spec`` enables — including spec='none', so fusion can
    be measured in isolation. ``dist_parallelism`` is the distributed
    session's actor parallelism: above 1 the fusion rule refuses runs
    whose hash-cut keys do not map back to raw input columns (the
    fragmenter's fused cut ships raw rows — opt/fusion.py)."""
    from risingwave_tpu.utils.metrics import STREAMING
    report = RewriteReport(label)
    enabled = parse_rules(spec) & set(EXECUTOR_RULE_NAMES)
    registry = dict(EXECUTOR_RULES)
    if fusion:
        import functools

        from risingwave_tpu.frontend.opt.fusion import fuse_fragments
        registry[FUSION_RULE_NAME] = functools.partial(
            fuse_fragments, dist_parallelism=dist_parallelism)
        enabled = enabled | {FUSION_RULE_NAME}
    if extra_rules:
        registry.update(extra_rules)
        enabled = enabled | set(extra_rules)
    if not enabled:
        return root, report
    baseline = _checker.snapshot(root)
    disabled: set = set()
    for _round in range(MAX_ROUNDS):
        progressed = False
        for name in registry:
            if name not in enabled or name in disabled:
                continue
            try:
                new_root, fired, detail = registry[name](root)
                if not fired:
                    if detail and name == FUSION_RULE_NAME:
                        report.declined[name] = detail
                    continue
                _checker.check(new_root, baseline)
            except Exception as e:          # noqa: BLE001 — fallback
                if _checker.strict_checker():
                    raise AssertionError(
                        f"rewrite rule {name!r} broke a plan "
                        f"invariant: {e}") from e
                report.fallbacks.append((name, repr(e)[:200]))
                if record:
                    _record_history(label, name, 0,
                                    f"FALLBACK: {repr(e)[:160]}")
                disabled.add(name)
                continue
            root = new_root
            progressed = True
            report.fired[name] = report.fired.get(name, 0) + fired
            report.details.append((name, detail))
            if record:
                # record=False (EXPLAIN) keeps deploy-time counters
                # honest: only rewrites of plans that ship count
                STREAMING.rewrite_rule_fired.inc(fired, rule=name)
                if name == "column_pruning":
                    STREAMING.plan_columns_pruned.inc(fired)
        if not progressed:
            break
    if record:
        for name, count in sorted(report.fired.items()):
            detail = "; ".join(d for n, d in report.details
                               if n == name)
            _record_history(label, name, count, detail)
    return root, report


def apply_rewrites(plan, spec: Optional[str],
                   label: str = "",
                   fusion: bool = False,
                   dist_parallelism: int = 1) -> RewriteReport:
    """Rewrite a StreamPlan/SinkPlan's consumer in place — the ONE
    deploy-path seam every session path (create MV/sink, reschedule,
    distributed create) goes through, so a future engine argument
    lands everywhere at once."""
    plan.consumer, report = rewrite_stream_plan(
        plan.consumer, spec, label=label, fusion=fusion,
        dist_parallelism=dist_parallelism)
    return report


def explain_with_rewrite(consumer, spec: Optional[str],
                         fusion: bool = False,
                         dist_parallelism: int = 1) -> List[tuple]:
    """EXPLAIN body shared by Frontend and DistFrontend: pre-rewrite
    tree, per-rule annotations (fusion groups included), post-rewrite
    tree, lane stats."""
    from risingwave_tpu.frontend.planner import explain_tree

    def stats_line(tag, root):
        s = plan_lane_stats(root)
        return (f"-- {tag} plan stats: executors={s['executors']} "
                f"total_lanes={s['total_lanes']} "
                f"max_width={s['max_lane_width']}",)

    pre = explain_tree(consumer)
    new_consumer, report = rewrite_stream_plan(
        consumer, spec, label="__explain__", record=False,
        fusion=fusion, dist_parallelism=dist_parallelism)
    rows = [("-- streaming plan (pre-rewrite):",)]
    rows += [(line,) for line in pre]
    rows.append(stats_line("pre-rewrite", consumer))
    rows.append((f"-- rewritten plan ({report.summary()}):",))
    for rule, detail in report.details:
        rows.append((f"--   rule {rule}: {detail}",))
    for rule, reason in report.fallbacks:
        rows.append((f"--   rule {rule}: FELL BACK ({reason})",))
    for rule, why in report.declined.items():
        if rule not in report.fired:
            rows.append((f"--   rule {rule}: nothing rewritten: {why}",))
    rows += [(line,) for line in explain_tree(new_consumer)]
    rows.append(stats_line("post-rewrite", new_consumer))
    # compiled-kernel cost footer (utils/jaxtools.KERNELS): programs
    # this process has already compiled, with the HLO cost model's
    # flops / bytes-accessed — what the deployed plan's device steps
    # SHOULD cost, next to the tree that dispatches them. Empty on a
    # fresh process (nothing compiled yet).
    from risingwave_tpu.utils.jaxtools import kernel_cost_rows
    costs = kernel_cost_rows()
    if costs:
        rows.append(("-- compiled kernel costs "
                     "(flops / bytes accessed):",))
        rows += [(f"--   {label}: {flops:.3g} flops, "
                  f"{nbytes:.3g} B", )
                 for label, flops, nbytes in costs]
    return rows


def plan_lane_stats(root) -> Dict[str, float]:
    """Carried-lane stats over an executor tree: how many column lanes
    the plan moves between executors (EXPLAIN surfaces them so a
    rewrite's narrowing is visible)."""
    from risingwave_tpu.stream.executor import executor_children
    widths: List[int] = []

    def walk(ex):
        widths.append(len(ex.schema))
        for _a, _i, c in executor_children(ex):
            walk(c)

    walk(root)
    total = sum(widths)
    return {
        "executors": len(widths),
        "total_lanes": total,
        "max_lane_width": max(widths) if widths else 0,
        "avg_lane_width": round(total / len(widths), 2)
        if widths else 0.0,
    }
