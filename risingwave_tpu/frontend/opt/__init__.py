"""Plan-rewrite engine: local-rewrite optimizer over the stream plan.

The paper's dataflow graphs pay for every lane shipped through an
exchange and every column resident in an HBM hash table; this package
applies small, independently-verifiable rewrites ("Optimizing Stateful
Dataflow with Local Rewrites", arxiv 2306.10585) to fixpoint between
the StreamPlanner and deployment:

- executor-graph rules (engine.py / rules.py): filter pushdown below
  joins (the planner's former inline pushdown, now a rule) and into an
  inner join as its own condition,
  project/filter fusion, noop-project elision, and live-lane column
  pruning that narrows join inputs, agg feeds and source scans down to
  the referenced columns;
- fragment-graph rules (fragment_rules.py): exchange elision — fuse
  adjacent fragments when the producer's hash distribution already
  satisfies the consumer's keys;
- fragment fusion (fusion.py, SET stream_fusion): collapse maximal
  filter/project runs into ONE traced dataflow step — inlined into the
  agg kernel's jitted apply with donated state, or a standalone
  FusedFragmentExecutor for join/materialize feeds (TiLT shape,
  arxiv 2301.12030);
- a plan-property checker (checker.py) that recomputes schema,
  append-only-ness and structural invariants after EVERY rewrite and
  falls back to the unrewritten plan on any violation (strict mode
  turns the fallback into a loud assertion — armed by tier-1 conftest).
"""

from risingwave_tpu.frontend.opt.checker import (    # noqa: F401
    CheckError, set_strict_checker, strict_checker,
)
from risingwave_tpu.frontend.opt.engine import (     # noqa: F401
    EXECUTOR_RULE_NAMES, FRAGMENT_RULE_NAMES, FUSION_RULE_NAME,
    RULE_NAMES, RewriteReport, apply_rewrites, explain_with_rewrite,
    parse_fusion, parse_rules, plan_lane_stats, rewrite_history_rows,
    rewrite_stream_plan,
)
from risingwave_tpu.frontend.opt.fragment_rules import (  # noqa: F401
    fragment_plan_stats, rewrite_fragment_graph,
)
