"""Fragment-graph rewrite rules: exchange elision on the shipped IR.

A hash exchange between two fragments is pure overhead when the
producer's rows are ALREADY placed so that the consumer's keys
colocate: (a) both fragments are singletons (one actor each — any
exchange between them just re-frames chunks over the wire), or (b)
both run at the same parallelism and the producer's own hash
distribution, tracked column-by-column through its node chain, is a
subset of the consumer's keys — rows with equal consumer keys carry
equal producer keys and therefore already live on the same actor.

The rule fuses such a consumer fragment into its producer (splicing
the consumer's IR nodes onto the producer's tail) and drops the cut
edge; when the fused placement is keyed by a strict subset of the
consumer's keys, the materialize `dist_key` is stripped so the vnode-
sliced rescale path never assumes a placement that no longer holds.
"""

from __future__ import annotations

import copy
from typing import Dict, List, Optional, Tuple

from risingwave_tpu.frontend.opt import checker as _checker
from risingwave_tpu.frontend.opt.checker import CheckError

_PASSTHROUGH_OPS = frozenset({
    "filter", "coalesce", "watermark_filter", "dedup", "eowc_gate",
    "top_n", "materialize", "row_id_gen",
})


def _stages_width(w: Optional[int], stages) -> Optional[int]:
    """Output arity through a serialized fused-stage list (ISSUE 10:
    fused nodes exist at parallelism > 1, so the derivation must walk
    their absorbed runs)."""
    if w is None:
        return None
    for st in stages:
        k = st["kind"]
        if k == "project":
            w = len(st["exprs"])
        elif k == "row_id_gen":
            w = w + 1
        # filter / watermark_filter keep the arity
    return w


def _stages_dist(d: Optional[List[set]], stages) -> Optional[List[set]]:
    """Track a hash distribution through a serialized fused-stage
    list: projects remap key-carrying columns (bare input refs only,
    the same rule as the `project` IR node); filters/watermark_filter
    pass through; row_id_gen appends a column (indices unchanged)."""
    if d is None:
        return None
    for st in stages:
        if st["kind"] != "project":
            continue
        ref_cols: Dict[int, set] = {}
        for j, e in enumerate(st["exprs"]):
            if e.get("t") == "input":
                ref_cols.setdefault(e["i"], set()).add(j)
        d = [set().union(*(ref_cols.get(c, set()) for c in s))
             if s else set() for s in d]
        if any(not s for s in d):
            return None
    return d


def _node_widths(frag) -> List[Optional[int]]:
    """Output arity per IR node (None where not derivable)."""
    widths: List[Optional[int]] = []
    for node in frag.nodes:
        op = node["op"]
        w: Optional[int] = None
        if op == "source":
            w = len(node["schema"])
        elif op == "exchange_in":
            w = len(frag.inputs[node["port"]].schema)
        elif op == "project":
            w = len(node["exprs"])
        elif op == "fused":
            w = _stages_width(widths[node["input"]], node["stages"])
        elif op in _PASSTHROUGH_OPS:
            inw = widths[node["input"]]
            w = inw if op != "row_id_gen" else (
                inw + 1 if inw is not None else None)
        elif op == "hash_agg":
            w = len(node["group"]) + len(node["calls"])
        elif op in ("hash_join", "temporal_join"):
            lw, rw = widths[node["left"]], widths[node["right"]]
            if node.get("left_fused"):
                lw = _stages_width(lw, node["left_fused"])
            if node.get("right_fused"):
                rw = _stages_width(rw, node["right_fused"])
            w = lw + rw if lw is not None and rw is not None else None
        elif op == "over_window":
            inw = widths[node["input"]]
            w = (inw + len(node["calls"])
                 if inw is not None else None)
        widths.append(w)
    return widths


def fragment_output_dist(frag) -> Optional[List[set]]:
    """Hash-distribution of a fragment's output rows, derived through
    its node chain: one set of output-column indices per original key
    position (every column in a set carries that key's value), or
    None when the placement is not derivable from the output."""
    if not frag.inputs or any(i.mode != "hash" or not i.keys
                              for i in frag.inputs):
        return None
    widths = _node_widths(frag)
    dists: List[Optional[List[set]]] = []
    for idx, node in enumerate(frag.nodes):
        op = node["op"]
        d: Optional[List[set]] = None
        if op == "exchange_in":
            d = [{k} for k in frag.inputs[node["port"]].keys]
        elif op == "project":
            ind = dists[node["input"]]
            if ind is not None:
                ref_cols: Dict[int, set] = {}
                for j, e in enumerate(node["exprs"]):
                    if e.get("t") == "input":
                        ref_cols.setdefault(e["i"], set()).add(j)
                d = [set().union(*(ref_cols.get(c, set())
                                   for c in s)) if s else set()
                     for s in ind]
                if any(not s for s in d):
                    d = None
        elif op == "fused":
            d = _stages_dist(dists[node["input"]], node["stages"])
        elif op in _PASSTHROUGH_OPS:
            d = dists[node["input"]]
        elif op == "hash_agg":
            ind = dists[node["input"]]
            # a fused agg's group indices live in the absorbed run's
            # OUTPUT space — map the input distribution through it
            if node.get("fused_stages"):
                ind = _stages_dist(ind, node["fused_stages"])
            group = list(node["group"])
            if ind is not None:
                d = [{group.index(c) for c in s if c in group}
                     for s in ind]
                if any(not s for s in d):
                    d = None
        elif op == "hash_join":
            # both inputs are hashed on the join keys; every output
            # row carries the key value in its left AND right column.
            # Fused sides: the exchange dispatched RAW rows on raw-
            # mapped key columns; key positions (and the left width)
            # live in each run's OUTPUT space — map through the run.
            lind = dists[node["left"]]
            rind = dists[node["right"]]
            n_left = widths[node["left"]]
            if node.get("left_fused"):
                lind = _stages_dist(lind, node["left_fused"])
                n_left = _stages_width(widths[node["left"]],
                                       node["left_fused"])
            if node.get("right_fused"):
                rind = _stages_dist(rind, node["right_fused"])
            lk = list(node["left_keys"])
            rk = list(node["right_keys"])
            if (n_left is not None
                    and lind == [{k} for k in lk]
                    and rind == [{k} for k in rk]):
                d = [{lc, n_left + rc} for lc, rc in zip(lk, rk)]
        elif op == "temporal_join":
            lind = dists[node["left"]]
            lk = list(node["left_keys"])
            if lind == [{k} for k in lk]:
                d = [{k} for k in lk]
        dists.append(d)
    return dists[-1] if dists else None


def _fuse(graph, u: int, f: int, edge, strip_dist: bool) -> None:
    """Splice fragment f's nodes onto fragment u's tail, dropping the
    cut edge; rewire every other fragment's upstream references."""
    from risingwave_tpu.frontend.fragmenter import Fragment
    from risingwave_tpu.stream.plan_ir import remap_node_refs
    P, F = graph.fragments[u], graph.fragments[f]
    tail = len(P.nodes) - 1
    new_nodes = [dict(n) for n in P.nodes]
    remap: Dict[int, int] = {}
    for i, node in enumerate(F.nodes):
        if i == edge.node_idx:
            remap[i] = tail
            continue
        n2 = remap_node_refs(node, remap)
        if strip_dist and n2["op"] == "materialize":
            n2.pop("dist_key", None)
        new_nodes.append(n2)
        remap[i] = len(new_nodes) - 1
    graph.fragments[u] = Fragment(
        nodes=new_nodes,
        parallelism=max(P.parallelism, F.parallelism),
        inputs=list(P.inputs))
    del graph.fragments[f]
    for frag in graph.fragments:
        for inp in frag.inputs:
            if inp.up_frag == f:
                inp.up_frag = u
            elif inp.up_frag > f:
                inp.up_frag -= 1


def elide_exchanges(graph) -> Tuple[object, int, List[str]]:
    """Apply exchange elision to fixpoint on a COPY of the graph."""
    g = copy.deepcopy(graph)
    fired = 0
    details: List[str] = []
    progress = True
    while progress:
        progress = False
        for fi, frag in enumerate(g.fragments):
            if len(frag.inputs) != 1:
                continue
            edge = frag.inputs[0]
            u = edge.up_frag
            up = g.fragments[u]
            if len(g.consumers_of(u)) != 1:
                continue
            if up.parallelism == 1 and frag.parallelism == 1:
                strip = False
                why = "singleton producer and consumer"
            elif (up.parallelism == frag.parallelism
                    and edge.mode == "hash" and edge.keys):
                dist = fragment_output_dist(up)
                ckeys = set(edge.keys)
                if dist is None or not all(s & ckeys for s in dist):
                    continue
                covered = set().union(*(s & ckeys for s in dist))
                # dist_key survives only when the producer hashed the
                # SAME key tuple in the same order (identical vnodes)
                exact = (len(dist) == len(edge.keys)
                         and all(edge.keys[p] in dist[p]
                                 for p in range(len(dist))))
                strip = not exact
                why = (f"producer distribution {sorted(covered)} "
                       f"satisfies consumer keys {sorted(ckeys)}")
            else:
                continue
            _fuse(g, u, fi, edge, strip)
            fired += 1
            details.append(f"fragment {fi} fused into {u} ({why})")
            progress = True
            break
    return g, fired, details


def rewrite_fragment_graph(graph, spec: Optional[str] = "all",
                           label: str = "", record: bool = True):
    """Fragment-graph rewrite entry point (DistFrontend deploys call
    it between the fragmenter and the scheduler). Same fallback /
    strict contract as the executor-graph engine."""
    from risingwave_tpu.frontend.opt.engine import (
        _record_history, parse_rules,
    )
    from risingwave_tpu.utils.metrics import STREAMING
    if "exchange_elision" not in parse_rules(spec):
        return graph, 0
    try:
        new_graph, fired, details = elide_exchanges(graph)
        if fired:
            _checker.check_fragment_graph(new_graph)
    except Exception as e:              # noqa: BLE001 — fallback
        if _checker.strict_checker():
            raise AssertionError(
                f"exchange_elision broke the fragment graph: {e}"
            ) from e
        if record:
            _record_history(label, "exchange_elision", 0,
                            f"FALLBACK: {repr(e)[:160]}")
        return graph, 0
    if not fired:
        return graph, 0
    if record:
        # record=False (plan previews) keeps deploy-time counters
        # honest — same contract as the executor-graph engine
        STREAMING.rewrite_rule_fired.inc(fired,
                                         rule="exchange_elision")
        STREAMING.plan_exchanges_elided.inc(fired)
        _record_history(label, "exchange_elision", fired,
                        "; ".join(details))
    return new_graph, fired


def fragment_plan_stats(graph) -> dict:
    """Exchange-hop and exchanged-lane-width stats for one fragment
    graph (tests compare these with rewrites on vs off)."""
    hops = 0
    lanes = 0
    for frag in graph.fragments:
        for inp in frag.inputs:
            hops += 1
            lanes += len(inp.schema)
    return {
        "fragments": len(graph.fragments),
        "exchange_hops": hops,
        "exchanged_lanes": lanes,
        "avg_exchanged_lane_width": round(lanes / hops, 2)
        if hops else 0.0,
    }
