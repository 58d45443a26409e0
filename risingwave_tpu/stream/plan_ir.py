"""Typed streaming plan IR: serializable fragments + executor factory.

Reference parity: the plan/service protos (SURVEY §2.2 — the reference
ships `StreamNode` protobufs from meta's fragmenter to compute nodes,
src/stream/src/from_proto/ builds executors from them). TPU re-design:
a JSON-able node tree — `source → project/filter → hash_agg → …` —
plus `build_fragment`, the plan-IR→executor factory. The coordinator
ships a fragment IR over the control channel and ANY worker
materializes it (no more per-query hand-wired fragment functions);
expressions serialize with full fidelity through `expr_to_ir`.

Node shapes (dicts, `op` discriminated):
  {"op": "source", "connector": {...opts}, "schema": [...],
   "actor_id": n, "split_table_id": n, "rate_limit": n,
   "min_chunks": n}
  {"op": "project", "input": N, "exprs": [...], "names": [...]}
  {"op": "filter",  "input": N, "pred": EXPR}
  {"op": "coalesce", "input": N, "target_rows": n,
   "max_chunks": n}                     # barrier-bounded chunk
                                        # coalescing (stream/coalesce)
  {"op": "fused", "input": N,
   "stages": [{"kind": "filter", "pred": EXPR} |
              {"kind": "project", "exprs": [...],
               "names": [...]}]}        # fused filter/project run —
                                        # ONE traced step per chunk
                                        # (ops/fused.py); hash_agg
                                        # nodes may instead carry the
                                        # same list as "fused_stages"
                                        # to inline it into the
                                        # kernel's jitted apply
  {"op": "row_id_gen", "input": N}
  {"op": "hash_agg", "input": N, "group": [...],
   "calls": [{"kind","input_idx","distinct","delimiter","filter_idx"}],
   "table_id": n, "append_only": bool, "output_names": [...],
   "dedup_table_ids": {input_idx: n},   # required per DISTINCT column
   "minput_table_ids": {call_idx: n}}   # required per retractable
                                        # min/max + per host agg
  {"op": "remote_input", "host": h, "port": n, "up_actor": n,
   "schema": [...]}                     # consume another fragment's
                                        # exchange; barriers arrive
                                        # in-band, so a fragment fed
                                        # only by these has no source
  {"op": "merge", "inputs": [N, ...],
   "coalesce_rows": n,
   "coalesce_chunks": n}                # N-way barrier-aligned fan-in
                                        # (coalesce_rows: re-merge
                                        # post-dispatch slivers, 0 off;
                                        # coalesce_chunks: linger bound)
                                        # over earlier nodes (merge.rs
                                        # over exchange inputs) — the
                                        # receive side of a hash
                                        # exchange from a parallel
                                        # upstream fragment
  {"op": "hash_join", "left": N, "right": N, "left_keys": [...],
   "right_keys": [...], "left_table_id": n, "right_table_id": n,
   "left_pk": [...], "right_pk": [...], "join_type": "inner",
   "left_dist_key": [...], "right_dist_key": [...],  # optional:
   "output_names": [...],   # vnode dist of the join state tables
   "condition": EXPR}       # optional: an inner join's own condition
                            # over its output columns
  {"op": "materialize", "input": N, "table_id": n, "pk": [...],
   "dist_key": [...]}           # optional: vnode partitioning of the
                                # MV rows (must be a pk subset) — set
                                # by the fragmenter when the fragment's
                                # exchange keys prefix the pk, so
                                # rescale can slice state by vnode
  {"op": "top_n", "input": N, "order_by": [[i, desc], ...],
   "offset": n, "limit": n|null, "table_id": n, "group": [...],
   "append_only": bool, "pk": [...],
   "state_pk": [...], "dist_key": [...],   # optional: the state
                                # table's key and vnode distribution
                                # (default: "pk", undistributed)
   "tier_cap": n|null}
  {"op": "over_window", "input": N, "partition": [...],
   "order_by": [[i, desc], ...],
   "calls": [{"kind", "input_idx", "offset"}], "table_id": n,
   "input_pk": [...], "output_names": [...]}
  {"op": "project_set", "input": N,
   "items": [["scalar", EXPR] | ["series", [EXPR, ...]]],
   "names": [...], "pass_pk": [...]}
  {"op": "dynamic_filter", "left": N, "right": N, "left_col": n,
   "cmp": "<"|"<="|">"|">=", "table_id": n}
  {"op": "eowc_gate", "input": N, "wm_col": n, "table_id": n,
   "pk": [...]}
  {"op": "temporal_join", "left": N, "right": N, "left_keys": [...],
   "right_keys": [...], "outer": bool, "output_names": [...]}
  {"op": "dedup", "input": N, "keys": [...], "table_id": n}
  {"op": "backfill", "input": N, "mv_table_id": n, "mv_pk": [...],
   "progress_table_id": n}      # input feeds live deltas; the
                                # snapshot reads the LOCAL store
"""

from __future__ import annotations

import decimal
from typing import Dict, List, Optional

from risingwave_tpu.common.types import (
    DataType, Field, Interval, Schema,
)
from risingwave_tpu.expr.expr import (
    BinaryOp, Case, Cast, Expression, FuncCall, InputRef, Literal,
    UnaryOp,
)

# -- expression serde -----------------------------------------------------


def expr_to_ir(e: Expression) -> dict:
    if isinstance(e, InputRef):
        return {"t": "input", "i": e.index, "dt": e.return_type.value}
    if isinstance(e, Literal):
        v = e.value
        if isinstance(v, Interval):
            v = {"__interval": [v.months, v.days, v.usecs]}
        elif isinstance(v, bytes):
            v = {"__bytes": v.hex()}
        elif isinstance(v, decimal.Decimal):
            v = {"__decimal": str(v)}
        return {"t": "lit", "v": v, "dt": e.return_type.value}
    if isinstance(e, BinaryOp):
        return {"t": "bin", "op": e.op, "l": expr_to_ir(e.left),
                "r": expr_to_ir(e.right)}
    if isinstance(e, UnaryOp):
        return {"t": "un", "op": e.op, "c": expr_to_ir(e.child)}
    if isinstance(e, Cast):
        return {"t": "cast", "c": expr_to_ir(e.child),
                "dt": e.return_type.value}
    if isinstance(e, Case):
        return {"t": "case",
                "whens": [[expr_to_ir(c), expr_to_ir(v)]
                          for c, v in e.whens],
                "else": expr_to_ir(e.else_)}
    if isinstance(e, FuncCall):
        return {"t": "fn", "name": e.name,
                "dt": e.return_type.value,
                "args": [expr_to_ir(a) for a in e.args]}
    raise TypeError(f"unserializable expression {type(e).__name__}")


def _const_from_ir(v):
    if isinstance(v, dict):
        if "__interval" in v:
            m, d, us = v["__interval"]
            return Interval(months=m, days=d, usecs=us)
        if "__bytes" in v:
            return bytes.fromhex(v["__bytes"])
        if "__decimal" in v:
            return decimal.Decimal(v["__decimal"])
    return v


def expr_from_ir(d: dict) -> Expression:
    t = d["t"]
    if t == "input":
        return InputRef(d["i"], DataType(d["dt"]))
    if t == "lit":
        v = _const_from_ir(d["v"])
        return Literal(v, DataType(d["dt"]))
    if t == "bin":
        return BinaryOp(d["op"], expr_from_ir(d["l"]),
                        expr_from_ir(d["r"]))
    if t == "un":
        return UnaryOp(d["op"], expr_from_ir(d["c"]))
    if t == "cast":
        return Cast(expr_from_ir(d["c"]), DataType(d["dt"]))
    if t == "case":
        return Case([(expr_from_ir(c), expr_from_ir(v))
                     for c, v in d["whens"]],
                    expr_from_ir(d["else"]))
    if t == "fn":
        return FuncCall(d["name"],
                        [expr_from_ir(a) for a in d["args"]],
                        DataType(d["dt"]))
    raise TypeError(f"unknown expression IR {t!r}")


def stages_from_ir(in_schema: Schema, stages_ir: List[dict],
                   store=None):
    """IR stage list → FusedStages (the worker-side half of the
    fragmenter's _stages_ir). ``store`` backs the bare runtimes of
    absorbed row_id_gen / watermark_filter stages (their host-only
    executor handles never serialize)."""
    from risingwave_tpu.ops.fused import FusedStage, FusedStages
    stages = []
    for st in stages_ir:
        if st["kind"] == "filter":
            stages.append(FusedStage(
                "filter", "FilterExecutor",
                exprs=(expr_from_ir(st["pred"]),)))
        elif st["kind"] == "project":
            stages.append(FusedStage(
                "project", "ProjectExecutor",
                exprs=tuple(expr_from_ir(e) for e in st["exprs"]),
                names=tuple(st["names"])))
        elif st["kind"] == "row_id_gen":
            from risingwave_tpu.stream.executors.row_id_gen import (
                RowIdCounter,
            )
            stages.append(FusedStage(
                "row_id_gen", "RowIdGenExecutor",
                runtime=RowIdCounter(int(st.get("vnode_base", 0)))))
        elif st["kind"] == "watermark_filter":
            from risingwave_tpu.state.state_table import StateTable
            from risingwave_tpu.stream.executors.watermark_filter \
                import WATERMARK_STATE_SCHEMA, WatermarkRuntime
            wm_state = None
            if st.get("table_id") is not None and store is not None:
                wm_state = StateTable(int(st["table_id"]),
                                      WATERMARK_STATE_SCHEMA, [0],
                                      store)
            stages.append(FusedStage(
                "watermark_filter", "WatermarkFilterExecutor",
                time_col=int(st["time_col"]),
                delay_usecs=int(st["delay_usecs"]),
                runtime=WatermarkRuntime(wm_state)))
        elif st["kind"] == "hop_window":
            stages.append(FusedStage(
                "hop_window", "HopWindowExecutor",
                time_col=int(st["time_col"]),
                slide_usecs=int(st["slide_usecs"]),
                size_usecs=int(st["size_usecs"])))
        else:
            raise TypeError(f"unknown fused stage IR {st['kind']!r}")
    return FusedStages(in_schema, stages)


# node-index reference keys: every IR node points at earlier nodes in
# its fragment through these (plus the list-valued "inputs" of merge).
# Shared by the scheduler's exchange_in expansion and the exchange-
# elision rewrite's fragment fusion — two drifting copies would let a
# new ref key silently dangle after a splice.
NODE_REF_KEYS = ("input", "left", "right")


def remap_node_refs(node: dict, remap: Dict[int, int]) -> dict:
    """Copy of an IR node with every node-index reference remapped
    (fragment splicing / placeholder expansion)."""
    n2 = dict(node)
    for key in NODE_REF_KEYS:
        if isinstance(n2.get(key), int):
            n2[key] = remap[n2[key]]
    if isinstance(n2.get("inputs"), list):
        n2["inputs"] = [remap[i] for i in n2["inputs"]]
    return n2


class _SchemaShim:
    """Placeholder input for constructing a HashJoinExecutor whose
    side schema is a fused run's OUTPUT space — adopt_fused_input
    swaps in the real raw child right after construction."""

    def __init__(self, schema: Schema):
        self.schema = schema
        self.pk_indices: List[int] = []


def schema_to_ir(schema: Schema) -> List[dict]:
    return [{"name": f.name, "dt": f.data_type.value} for f in schema]


def schema_from_ir(ir: List[dict]) -> Schema:
    return Schema([Field(f["name"], DataType(f["dt"])) for f in ir])


# -- fragment factory (from_proto/ analog) --------------------------------


def build_fragment(nodes: List[dict], store, local,
                   channel_factory, actor_id: Optional[int] = None
                   ) -> tuple:
    """IR node list (topological; `input` indexes earlier nodes) →
    (source_executor, consumer_executor). `channel_factory()` returns
    (tx, rx) for the source's barrier channel; the caller registers
    tx with its barrier manager under the source's actor id.
    `actor_id` is THIS fragment's actor — required for remote_input
    nodes (the exchange edge is keyed (up_actor, down_actor)); a
    remote-fed fragment returns source_executor=None since its
    barriers arrive in-band over the exchange."""
    from risingwave_tpu.frontend.planner import (
        SPLIT_STATE_SCHEMA, _source_reader,
    )
    from risingwave_tpu.frontend.catalog import SourceCatalog
    from risingwave_tpu.state.state_table import StateTable
    from risingwave_tpu.stream.executors.hash_agg import (
        AggCall, HashAggExecutor, agg_aux_tables, agg_state_schema,
    )
    from risingwave_tpu.stream.executors.row_id_gen import (
        RowIdGenExecutor,
    )
    from risingwave_tpu.stream.executors.simple import (
        FilterExecutor, ProjectExecutor,
    )
    from risingwave_tpu.stream.executors.source import SourceExecutor
    from risingwave_tpu.ops.hash_agg import AggKind

    built: List[object] = []
    src_executor = None
    for node in nodes:
        op = node["op"]
        if op == "source":
            cat = SourceCatalog(
                name=node.get("name", "src"), source_id=0,
                schema=schema_from_ir(node["schema"]),
                options=dict(node["connector"]))
            reader = _source_reader(cat)
            tx, rx = channel_factory()
            split = StateTable(int(node["split_table_id"]),
                               SPLIT_STATE_SCHEMA, [0], store)
            local.register_sender(int(node["actor_id"]), tx)
            ex = SourceExecutor(
                reader, rx, split, actor_id=int(node["actor_id"]),
                rate_limit_chunks_per_barrier=node.get("rate_limit"),
                min_chunks_per_barrier=node.get("min_chunks"),
                freshness_key=node.get("freshness_key"))
            src_executor = ex
        elif op == "project":
            child = built[node["input"]]
            ex = ProjectExecutor(
                child, [expr_from_ir(e) for e in node["exprs"]],
                node["names"])
        elif op == "filter":
            child = built[node["input"]]
            ex = FilterExecutor(child, expr_from_ir(node["pred"]))
        elif op == "coalesce":
            from risingwave_tpu.stream.coalesce import (
                DEFAULT_MAX_CHUNKS, DEFAULT_TARGET_ROWS,
                CoalesceExecutor,
            )
            ex = CoalesceExecutor(
                built[node["input"]],
                target_rows=int(node.get("target_rows",
                                         DEFAULT_TARGET_ROWS)),
                max_chunks=int(node.get("max_chunks",
                                        DEFAULT_MAX_CHUNKS)))
        elif op == "row_id_gen":
            ex = RowIdGenExecutor(built[node["input"]])
        elif op == "fused":
            from risingwave_tpu.stream.executors.fused import (
                FusedFragmentExecutor,
            )
            child = built[node["input"]]
            ex = FusedFragmentExecutor(
                child, stages_from_ir(child.schema, node["stages"],
                                      store=store))
        elif op == "watermark_filter":
            from risingwave_tpu.stream.executors.watermark_filter \
                import WATERMARK_STATE_SCHEMA, WatermarkFilterExecutor
            wm_state = None
            if node.get("table_id") is not None:
                wm_state = StateTable(int(node["table_id"]),
                                      WATERMARK_STATE_SCHEMA, [0],
                                      store)
            ex = WatermarkFilterExecutor(
                built[node["input"]], int(node["time_col"]),
                Interval(usecs=int(node["delay_usecs"])), wm_state)
        elif op == "hop_window":
            from risingwave_tpu.stream.executors.hop_window import (
                HopWindowExecutor,
            )
            ex = HopWindowExecutor(
                built[node["input"]], int(node["time_col"]),
                Interval(usecs=int(node["slide_usecs"])),
                Interval(usecs=int(node["size_usecs"])))
        elif op == "remote_input":
            from risingwave_tpu.stream.remote import RemoteInput
            if actor_id is None:
                raise ValueError(
                    "remote_input needs the fragment's actor_id")
            ex = RemoteInput(node["host"], int(node["port"]),
                             int(node["up_actor"]), int(actor_id),
                             schema_from_ir(node["schema"]))
        elif op == "merge":
            from risingwave_tpu.stream.coalesce import (
                DEFAULT_MAX_CHUNKS,
            )
            from risingwave_tpu.stream.executor import ExecutorInfo
            from risingwave_tpu.stream.merge import MergeExecutors
            children = [built[i] for i in node["inputs"]]
            if len({len(c.schema) for c in children}) != 1:
                raise ValueError("merge inputs must share a schema")
            # re-coalesce post-dispatch slivers at the fan-in: N
            # parallel upstreams each deliver compacted 1/N slices,
            # and downstream keyed executors should see dense
            # target-sized batches again. The scheduler always writes
            # coalesce_rows (from the session knob via the cut edge);
            # absent == 0 == off, matching every other layer
            ex = MergeExecutors(
                ExecutorInfo(children[0].schema, [],
                             f"Merge({len(children)})"),
                children, actor_id=int(actor_id or 0),
                coalesce_rows=int(node.get("coalesce_rows", 0)),
                coalesce_chunks=int(node.get("coalesce_chunks",
                                             DEFAULT_MAX_CHUNKS)))
        elif op == "hash_join":
            from risingwave_tpu.stream.executors.hash_join import (
                HashJoinExecutor, JoinType,
            )
            left = built[node["left"]]
            right = built[node["right"]]
            # fused input sides (opt/fusion.py try_fuse_join): the
            # side's index space is the absorbed run's OUTPUT schema —
            # construct against schema shims, then adopt the runs so
            # the real (raw) children wire back in
            l_fs = (stages_from_ir(left.schema, node["left_fused"],
                                   store=store)
                    if node.get("left_fused") else None)
            r_fs = (stages_from_ir(right.schema, node["right_fused"],
                                   store=store)
                    if node.get("right_fused") else None)
            l_in = left if l_fs is None else _SchemaShim(l_fs.out_schema)
            r_in = right if r_fs is None else _SchemaShim(r_fs.out_schema)
            lt = StateTable(int(node["left_table_id"]), l_in.schema,
                            [int(i) for i in node["left_pk"]], store,
                            dist_key_indices=node.get("left_dist_key"))
            rt = StateTable(int(node["right_table_id"]), r_in.schema,
                            [int(i) for i in node["right_pk"]], store,
                            dist_key_indices=node.get(
                                "right_dist_key"))
            cap = node.get("state_cap")
            ex = HashJoinExecutor(
                l_in, r_in,
                [int(i) for i in node["left_keys"]],
                [int(i) for i in node["right_keys"]], lt, rt,
                actor_id=int(actor_id or 0),
                join_type=JoinType(node.get("join_type", "inner")),
                output_names=node.get("output_names"),
                state_cap=None if cap is None else int(cap),
                condition=(expr_from_ir(node["condition"])
                           if node.get("condition") else None))
            if l_fs is not None:
                ex.adopt_fused_input(0, l_fs, left)
            if r_fs is not None:
                ex.adopt_fused_input(1, r_fs, right)
        elif op == "materialize":
            from risingwave_tpu.stream.executors.materialize import (
                MaterializeExecutor,
            )
            child = built[node["input"]]
            dist = node.get("dist_key")
            mv = StateTable(int(node["table_id"]), child.schema,
                            [int(i) for i in node["pk"]], store,
                            dist_key_indices=(
                                [int(i) for i in dist]
                                if dist else None))
            ex = MaterializeExecutor(child, mv,
                                     mv_name=node.get("mv_name", ""))
        elif op == "sink":
            from risingwave_tpu.connectors.sink import (
                AppendSegmentSink, UpsertSegmentSink, make_sink_target,
            )
            from risingwave_tpu.stream.executors.sink import (
                CoordinatedSinkExecutor,
            )
            child = built[node["input"]]
            names = [f.name for f in child.schema]
            target = make_sink_target({"path": node["path"]},
                                      node["mode"], names)
            enc = (AppendSegmentSink(target)
                   if node["mode"] == "append"
                   else UpsertSegmentSink(
                       target, [int(i) for i in node.get("pk", [])]))
            # INLINE mode (no coordinator): the worker stages
            # synchronously at barrier passage, BEFORE the barrier is
            # collected — the meta-side floor then only ever covers
            # durable staging; manifests are the coordinator's job
            ex = CoordinatedSinkExecutor(
                child, node["sink_name"], enc,
                writer=int(node.get("writer", 0)),
                n_writers=int(node.get("n_writers", 1)))
        elif op == "hash_agg":
            child = built[node["input"]]
            calls = [AggCall(AggKind(c["kind"]),
                             c.get("input_idx"),
                             distinct=bool(c.get("distinct", False)),
                             delimiter=c.get("delimiter", ","),
                             filter_idx=c.get("filter_idx"))
                     for c in node["calls"]]
            group = list(node["group"])
            # a fused agg's index space is the absorbed run's OUTPUT
            # schema — rebuild the composed prelude first and derive
            # state schemas against it (coordinator parity)
            fused = None
            if node.get("fused_stages"):
                fused = stages_from_ir(child.schema,
                                       node["fused_stages"],
                                       store=store)
            agg_in_schema = child.schema if fused is None \
                else fused.out_schema
            key_lead = int(node.get("key_lead", 0))
            sch, pk = agg_state_schema(agg_in_schema, group, calls,
                                       key_lead)
            table = StateTable(int(node["table_id"]), sch, pk, store,
                               dist_key_indices=list(range(len(pk))))
            # default FALSE like HashAggExecutor itself: a silently
            # append-only agg over a retracting input would produce
            # wrong results; False at worst raises a clean
            # missing-minput error at construction
            append_only = bool(node.get("append_only", False))
            # aux state tables, ids shipped in the IR (the coordinator
            # owns catalog id allocation; deriving ids here could
            # collide with other fragments sharing the store)
            dedup_ids = {int(k): int(v) for k, v in
                         (node.get("dedup_table_ids") or {}).items()}
            minput_ids = {int(k): int(v) for k, v in
                          (node.get("minput_table_ids") or {}).items()}

            def _shipped_id(ids, field, key):
                tid = ids.get(key)
                if tid is None:
                    raise ValueError(
                        f"hash_agg: ship {field}[{key}] — the agg "
                        "needs that aux state table")
                return tid

            distinct_tables, minput_tables = agg_aux_tables(
                agg_in_schema, group, calls, append_only, store,
                dedup_table_id=lambda col: _shipped_id(
                    dedup_ids, "dedup_table_ids", col),
                minput_table_id=lambda j: _shipped_id(
                    minput_ids, "minput_table_ids", j),
                key_lead=key_lead)
            tier_cap = node.get("tier_cap")
            ex = HashAggExecutor(
                child, group, calls, table,
                append_only=append_only,
                output_names=node.get("output_names"),
                distinct_tables=distinct_tables,
                minput_tables=minput_tables,
                tier_cap=None if tier_cap is None else int(tier_cap),
                fused_stages=fused)
        elif op == "top_n":
            from risingwave_tpu.stream.executors.top_n import (
                GroupTopNExecutor,
            )
            child = built[node["input"]]
            pk = [int(i) for i in node["pk"]]
            state = StateTable(
                int(node["table_id"]), child.schema,
                [int(i) for i in node.get("state_pk", pk)], store,
                dist_key_indices=[int(i)
                                  for i in node.get("dist_key", [])])
            tier_cap = node.get("tier_cap")
            ex = GroupTopNExecutor(
                child,
                [(int(i), bool(d)) for i, d in node["order_by"]],
                offset=int(node.get("offset", 0)),
                limit=node.get("limit"), state=state,
                group_indices=[int(i)
                               for i in node.get("group", [])],
                append_only=bool(node.get("append_only", False)),
                pk_indices=pk,
                tier_cap=None if tier_cap is None else int(tier_cap))
        elif op == "over_window":
            from risingwave_tpu.expr.window import (
                WindowCall, WindowFuncKind,
            )
            from risingwave_tpu.stream.executors.over_window import (
                OverWindowExecutor,
            )
            child = built[node["input"]]
            partition = [int(i) for i in node["partition"]]
            order = [(int(i), bool(d)) for i, d in node["order_by"]]
            calls = [WindowCall(WindowFuncKind(c["kind"]),
                                c.get("input_idx"),
                                offset=int(c.get("offset", 1)))
                     for c in node["calls"]]
            input_pk = [int(i) for i in node["input_pk"]]
            suffix = [i for i in input_pk if i not in partition
                      and i not in [o for o, _ in order]]
            state = StateTable(
                int(node["table_id"]), child.schema,
                partition + [i for i, _d in order] + suffix, store,
                dist_key_indices=partition)
            ex = OverWindowExecutor(
                child, partition, order, calls, state,
                input_pk=input_pk,
                output_names=node.get("output_names"),
                actor_id=int(actor_id or 0))
        elif op == "project_set":
            from risingwave_tpu.stream.executors.project_set import (
                ProjectSetExecutor,
            )
            child = built[node["input"]]
            items = []
            for kind, payload in node["items"]:
                if kind == "scalar":
                    items.append(("scalar", expr_from_ir(payload)))
                else:
                    items.append((kind, tuple(
                        expr_from_ir(e) for e in payload)))
            ex = ProjectSetExecutor(
                child, items, list(node["names"]),
                pass_pk=[int(i) for i in node.get("pass_pk", [])])
        elif op == "dynamic_filter":
            from risingwave_tpu.stream.executors.dynamic_filter \
                import DynamicFilterExecutor
            left = built[node["left"]]
            lstate = StateTable(int(node["table_id"]), left.schema,
                                list(left.pk_indices), store)
            ex = DynamicFilterExecutor(
                left, built[node["right"]], int(node["left_col"]),
                node["cmp"], lstate)
        elif op == "eowc_gate":
            from risingwave_tpu.stream.executors.eowc import (
                EowcGateExecutor,
            )
            child = built[node["input"]]
            state = StateTable(int(node["table_id"]), child.schema,
                               [int(i) for i in node["pk"]], store)
            ex = EowcGateExecutor(child, int(node["wm_col"]), state,
                                  actor_id=int(actor_id or 0))
        elif op == "temporal_join":
            from risingwave_tpu.stream.executors.temporal_join import (
                TemporalJoinExecutor,
            )
            ex = TemporalJoinExecutor(
                built[node["left"]], built[node["right"]],
                [int(i) for i in node["left_keys"]],
                [int(i) for i in node["right_keys"]],
                outer=bool(node.get("outer", False)),
                actor_id=int(actor_id or 0),
                output_names=node.get("output_names"))
        elif op == "dedup":
            from risingwave_tpu.stream.executors.dedup import (
                AppendOnlyDedupExecutor,
            )
            child = built[node["input"]]
            keys = [int(i) for i in node["keys"]]
            state = StateTable(int(node["table_id"]), child.schema,
                               keys, store)
            ex = AppendOnlyDedupExecutor(child, keys, state)
        elif op == "backfill":
            from risingwave_tpu.stream.executors.backfill import (
                PROGRESS_SCHEMA, BackfillExecutor,
            )
            child = built[node["input"]]
            mv = StateTable(int(node["mv_table_id"]), child.schema,
                            [int(i) for i in node["mv_pk"]], store)
            progress = StateTable(int(node["progress_table_id"]),
                                  PROGRESS_SCHEMA, [0], store)
            ex = BackfillExecutor(child, mv, progress)
        else:
            raise ValueError(f"unknown plan-IR op {op!r}")
        built.append(ex)
    return src_executor, built[-1]
