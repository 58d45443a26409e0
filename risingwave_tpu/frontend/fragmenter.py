"""Fragmenter: executor plan → distributable fragment graph of plan IR.

Reference parity: src/frontend/src/stream_fragmenter/mod.rs:115,199 —
the reference splits the stream plan at exchanges into a
StreamFragmentGraph whose fragments meta schedules onto compute nodes
(meta/src/stream/stream_graph/schedule.rs:195-251). TPU re-design: the
planner's EXECUTOR tree is already the physical plan, so the fragmenter
walks it and serializes each segment to plan IR (stream/plan_ir.py),
cutting where the reference inserts a hash exchange — before every
HashAgg (dist keys = group keys) and on both inputs of every HashJoin
(dist keys = join keys). Everything else stays colocated with its
input (NoShuffle), including the terminal Materialize, so each parallel
actor materializes its vnode slice into its worker's namespace.

The cut carries `keys` in the UPSTREAM fragment's output schema; the
scheduler (cluster/scheduler.py) turns each cut edge into a
HashDispatcher on the upstream actors and remote_input+merge nodes on
the downstream actors.

Cuts are not final: the plan-rewrite engine's exchange-elision pass
(frontend/opt/fragment_rules.py) runs over this graph before
scheduling and fuses adjacent fragments whose distribution already
satisfies the consumer's keys — the fragmenter cuts wherever the
reference would, the rewrite removes the cuts that prove redundant.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

from risingwave_tpu.stream.executors.hash_agg import HashAggExecutor
from risingwave_tpu.stream.executors.hash_join import HashJoinExecutor
from risingwave_tpu.stream.executors.materialize import (
    MaterializeExecutor,
)
from risingwave_tpu.stream.executors.row_id_gen import RowIdGenExecutor
from risingwave_tpu.stream.executors.simple import (
    FilterExecutor, ProjectExecutor,
)
from risingwave_tpu.stream.executors.source import SourceExecutor
from risingwave_tpu.stream.plan_ir import expr_to_ir, schema_to_ir


class FragmentError(ValueError):
    """Plan shape the distributed lowering cannot express (yet)."""


@dataclass
class FragInput:
    """One cut edge: this fragment consumes `up_frag`'s output hashed
    on `keys` (indices into the upstream OUTPUT schema), or fully
    REPLICATED when mode="broadcast" (temporal-join arrangements need
    every row on every actor — dispatch.rs:507)."""

    up_frag: int
    keys: List[int]
    schema: List[dict]              # IR schema of the exchanged rows
    node_idx: int                   # index of the exchange_in placeholder
    mode: str = "hash"              # "hash" | "broadcast"
    # downstream fan-in re-coalescing target + linger bound
    # (stream/coalesce.py); rows=0 disables — the scheduler copies
    # both onto the merge node
    coalesce_rows: int = 0
    coalesce_chunks: int = 0


@dataclass
class Fragment:
    """A deployable pipeline segment. `nodes` is plan IR where
    {"op": "exchange_in", "port": k} placeholders stand for the k-th
    entry of `inputs`; the scheduler expands each into per-upstream-
    actor remote_input nodes plus a merge."""

    nodes: List[dict] = field(default_factory=list)
    parallelism: int = 1
    inputs: List[FragInput] = field(default_factory=list)


@dataclass
class FragmentGraph:
    """Fragments in topological order (every FragInput.up_frag precedes
    its consumer). The LAST fragment holds the Materialize."""

    fragments: List[Fragment] = field(default_factory=list)

    def consumers_of(self, frag_idx: int) -> List[tuple]:
        """[(down_frag_idx, FragInput)] — at most one in a tree plan."""
        out = []
        for di, f in enumerate(self.fragments):
            for inp in f.inputs:
                if inp.up_frag == frag_idx:
                    out.append((di, inp))
        return out


def _stages_ir(fs) -> List[dict]:
    """FusedStages → serializable stage list ({"op":"fused"} payload
    and the hash_agg node's "fused_stages"); plan_ir rebuilds the
    composed normal form from it."""
    out = []
    for st in fs.stages:
        if st.kind == "filter":
            out.append({"kind": "filter",
                        "pred": expr_to_ir(st.exprs[0])})
        elif st.kind == "project":
            out.append({"kind": "project",
                        "exprs": [expr_to_ir(e) for e in st.exprs],
                        "names": list(st.names)})
        elif st.kind == "row_id_gen":
            # runtime = the absorbed RowIdGenExecutor (host) — the
            # worker rebuilds a bare RowIdCounter with the same shard
            out.append({"kind": "row_id_gen",
                        "vnode_base": st.runtime.vnode_base})
        elif st.kind == "watermark_filter":
            out.append({"kind": "watermark_filter",
                        "time_col": st.time_col,
                        "delay_usecs": st.delay_usecs,
                        "table_id": (st.runtime.state.table_id
                                     if st.runtime.state is not None
                                     else None)})
        elif st.kind == "hop_window":
            out.append({"kind": "hop_window",
                        "time_col": st.time_col,
                        "slide_usecs": st.slide_usecs,
                        "size_usecs": st.size_usecs})
        else:
            raise FragmentError(f"unknown fused stage kind {st.kind!r}")
    return out


def _agg_call_ir(c) -> dict:
    d = {"kind": c.kind.value}
    if c.input_idx is not None:
        d["input_idx"] = c.input_idx
    if c.distinct:
        d["distinct"] = True
    if c.delimiter != ",":
        d["delimiter"] = c.delimiter
    if c.filter_idx is not None:
        d["filter_idx"] = c.filter_idx
    return d


class Fragmenter:
    """One-shot walker over a planned executor tree."""

    def __init__(self, parallelism: int,
                 merge_coalesce_rows: Optional[int] = None,
                 merge_coalesce_chunks: Optional[int] = None):
        from risingwave_tpu.stream.coalesce import (
            DEFAULT_MAX_CHUNKS, DEFAULT_TARGET_ROWS,
        )
        self.parallelism = max(1, parallelism)
        # fan-in re-coalescing knobs stamped on every cut edge (the
        # session's stream_chunk_target_rows /
        # stream_coalesce_linger_chunks; rows=0 disables end to end)
        self.merge_coalesce_rows = DEFAULT_TARGET_ROWS \
            if merge_coalesce_rows is None else int(merge_coalesce_rows)
        self.merge_coalesce_chunks = DEFAULT_MAX_CHUNKS \
            if merge_coalesce_chunks is None \
            else int(merge_coalesce_chunks)
        self.graph = FragmentGraph()

    def lower(self, consumer) -> FragmentGraph:
        self._lower(consumer)
        return self.graph

    # -- helpers ----------------------------------------------------------
    def _new_fragment(self, parallelism: int) -> int:
        self.graph.fragments.append(Fragment(parallelism=parallelism))
        return len(self.graph.fragments) - 1

    def _append(self, fi: int, node: dict) -> int:
        self.graph.fragments[fi].nodes.append(node)
        return len(self.graph.fragments[fi].nodes) - 1

    def _cut(self, up_fi: int, keys: List[int], schema,
             parallelism: int, mode: str = "hash") -> tuple:
        """Close `up_fi` at its current tail and start a new fragment
        consuming it through an exchange. Returns (new_frag_idx,
        node_idx of the exchange_in placeholder)."""
        fi = self._new_fragment(parallelism)
        frag = self.graph.fragments[fi]
        port = len(frag.inputs)
        ni = self._append(fi, {"op": "exchange_in", "port": port})
        frag.inputs.append(FragInput(up_fi, list(keys),
                                     schema_to_ir(schema), ni, mode,
                                     self.merge_coalesce_rows,
                                     self.merge_coalesce_chunks))
        return fi, ni

    def _cut_into(self, fi: int, up_fi: int, keys: List[int],
                  schema, mode: str = "hash") -> int:
        """Add another exchange port to an existing fragment (the
        second input of a join)."""
        frag = self.graph.fragments[fi]
        port = len(frag.inputs)
        ni = self._append(fi, {"op": "exchange_in", "port": port})
        frag.inputs.append(FragInput(up_fi, list(keys),
                                     schema_to_ir(schema), ni, mode,
                                     self.merge_coalesce_rows,
                                     self.merge_coalesce_chunks))
        return ni

    # -- the walk ---------------------------------------------------------
    def _lower(self, ex) -> tuple:
        """Returns (frag_idx, node_idx) of ex's IR node."""
        if isinstance(ex, SourceExecutor):
            opts = getattr(ex, "ir_connector", None)
            if opts is None:
                raise FragmentError(
                    "source executor carries no connector options "
                    "(ir_connector) — planned outside the frontend?")
            if ex.split_state is None:
                raise FragmentError("distributed source needs durable "
                                    "split state")
            fi = self._new_fragment(1)
            ni = self._append(fi, {
                "op": "source", "name": ex.identity,
                "connector": dict(opts),
                "schema": schema_to_ir(ex.schema),
                "actor_id": 0,              # scheduler assigns
                "split_table_id": ex.split_state.table_id,
                "rate_limit": ex.rate_limit,
                "min_chunks": ex.min_chunks,
                # freshness accounting key (stream/freshness.py): the
                # worker-side rebuild keeps the CATALOG source name so
                # the coordinator merge joins MV ↔ source frontiers
                "freshness_key": ex.freshness_key,
            })
            return fi, ni
        if isinstance(ex, ProjectExecutor):
            # note: watermark_derivations may hold host lambdas (tumble
            # floor transforms) — fine in process, not shippable; the
            # distributed plan drops derivations (EOWC rejects upstream)
            fi, ci = self._lower(ex.input)
            ni = self._append(fi, {
                "op": "project", "input": ci,
                "exprs": [expr_to_ir(e) for e in ex.exprs],
                "names": [f.name for f in ex.schema]})
            return fi, ni
        if isinstance(ex, FilterExecutor):
            fi, ci = self._lower(ex.input)
            ni = self._append(fi, {"op": "filter", "input": ci,
                                   "pred": expr_to_ir(ex.predicate)})
            return fi, ni
        from risingwave_tpu.stream.coalesce import CoalesceExecutor
        if isinstance(ex, CoalesceExecutor):
            # keyed-input coalescing ships with the plan: on the
            # upstream side of a cut it densifies the exchange send
            # path; the downstream merge re-coalesces post-dispatch
            # slivers (scheduler merge nodes carry their own knob)
            fi, ci = self._lower(ex.input)
            ni = self._append(fi, {
                "op": "coalesce", "input": ci,
                "target_rows": ex.target_rows,
                "max_chunks": ex.max_chunks})
            return fi, ni
        if isinstance(ex, RowIdGenExecutor):
            fi, ci = self._lower(ex.input)
            ni = self._append(fi, {"op": "row_id_gen", "input": ci})
            return fi, ni
        from risingwave_tpu.stream.executors.fused import (
            FusedFragmentExecutor,
        )
        if isinstance(ex, FusedFragmentExecutor):
            # fused filter/project block: ship the ORIGINAL stage list
            # (plan_ir re-composes the normal form on the worker, so
            # the traced program there is byte-equivalent). Watermark
            # derivations drop like plain distributed projects do.
            fi, ci = self._lower(ex.input)
            ni = self._append(fi, {
                "op": "fused", "input": ci,
                "stages": _stages_ir(ex.fused_stages)})
            return fi, ni
        from risingwave_tpu.stream.executors.watermark_filter import (
            WatermarkFilterExecutor,
        )
        if isinstance(ex, WatermarkFilterExecutor):
            fi, ci = self._lower(ex.input)
            ni = self._append(fi, {
                "op": "watermark_filter", "input": ci,
                "time_col": ex.time_col, "delay_usecs": ex.delay,
                "table_id": (ex.state.table_id
                             if ex.state is not None else None)})
            return fi, ni
        from risingwave_tpu.stream.executors.hop_window import (
            HopWindowExecutor,
        )
        if isinstance(ex, HopWindowExecutor):
            fi, ci = self._lower(ex.input)
            ni = self._append(fi, {
                "op": "hop_window", "input": ci,
                "time_col": ex.time_col,
                "slide_usecs": ex.slide, "size_usecs": ex.size})
            return fi, ni
        if isinstance(ex, HashAggExecutor):
            up_fi, ci = self._lower(ex.input)
            node = {
                "op": "hash_agg", "input": None,
                "group": list(ex.group_indices),
                "calls": [_agg_call_ir(c) for c in ex.agg_calls],
                "table_id": ex.table.table_id,
                "append_only": ex.append_only,
                "output_names": [f.name for f in ex.schema],
                "dedup_table_ids": {
                    col: t.table_id
                    for col, t in ex.distinct_tables.items()},
                # sketch tables ride in the same map: the executor's
                # __init__ POPPED approx_count_distinct entries out of
                # minput into hll_tables, but the worker-side rebuild
                # (plan_ir agg_aux_tables) transports them through
                # minput_table_ids — omitting them made every
                # distributed CREATE MV with approx_count_distinct
                # fail at build ("ship minput_table_ids[j]")
                "minput_table_ids": {
                    **{j: t.table_id for j, t in ex.minput.items()},
                    **{j: t.table_id
                       for j, t in ex.hll_tables.items()}},
                # cold-tier resident-group cap (state/tier.py): worker
                # fragments rebuild with the same memory governance the
                # coordinator planned
                "tier_cap": ex.tier_cap,
            }
            if ex.key_lead:
                # the group position that leads the state tables' keys
                # where it is not the first (the planner's: the one
                # that carries a watermark)
                node["key_lead"] = ex.key_lead
            if ex.fused_stages is not None:
                # the agg's index space is the run's OUTPUT schema —
                # worker rebuild re-composes the prelude from this
                node["fused_stages"] = _stages_ir(ex.fused_stages)
            if self.parallelism > 1 and \
                    getattr(ex, "two_phase_role", None) != "local":
                if ex.fused_stages is not None:
                    # fused cut (ISSUE 10): the exchange ships RAW
                    # rows, hashed on the group keys mapped back
                    # through the absorbed run — value-equal columns,
                    # so the partition is consistent; the fusion rule
                    # refused any run whose keys don't map, so a None
                    # here is a planner bug, not a user error
                    keys = ex.fused_stages.input_positions(
                        ex.group_indices)
                    if keys is None:
                        raise FragmentError(
                            "fused agg group keys do not map to raw "
                            "input columns — the fusion rule should "
                            "have refused this run")
                else:
                    keys = list(ex.group_indices)
                fi, xi = self._cut(up_fi, keys, ex.input.schema,
                                   self.parallelism)
                node["input"] = xi
            else:
                # parallelism 1, or the LOCAL phase of a two-phase
                # split: colocate with the input chain (NoShuffle) —
                # the local phase exists precisely to pre-reduce
                # before the exchange
                fi, node["input"] = up_fi, ci
            ni = self._append(fi, node)
            return fi, ni
        if isinstance(ex, HashJoinExecutor):
            left, right = ex.sides
            l_fi, _ = self._lower(ex.left_in)
            r_fi, _ = self._lower(ex.right_in)
            # a fused side's key positions live in the absorbed run's
            # OUTPUT space; the exchange ships RAW rows. At
            # parallelism 1 the single consumer makes routing trivial
            # (no hash keys); above 1 the keys map back through the
            # run to raw columns (ISSUE 10 — the fusion rule refused
            # any run whose keys don't map, so None is a planner bug)
            def _side_cut(side):
                if side.fused_input is None:
                    return list(side.key_indices)
                if self.parallelism <= 1:
                    return []
                keys = side.fused_input.input_positions(
                    side.key_indices)
                if keys is None:
                    raise FragmentError(
                        "fused join keys do not map to raw input "
                        "columns — the fusion rule should have "
                        "refused this run")
                return keys

            l_cut = _side_cut(left)
            r_cut = _side_cut(right)
            fi, lxi = self._cut(l_fi, l_cut, ex.left_in.schema,
                                self.parallelism)
            rxi = self._cut_into(fi, r_fi, r_cut, ex.right_in.schema)
            node = {
                "op": "hash_join", "left": lxi, "right": rxi,
                "left_keys": list(left.key_indices),
                "right_keys": list(right.key_indices),
                "left_table_id": left.table.table_id,
                "right_table_id": right.table.table_id,
                "left_pk": list(left.table.pk_indices),
                "right_pk": list(right.table.pk_indices),
                "join_type": ex.join_type.value,
                # cold-tier resident-key cap (state/tier.py): the
                # shipped pks are already key-prefixed when set, and
                # worker rebuilds run the same epoch-batched path
                "state_cap": left.state_cap,
                "output_names": [f.name for f in ex.schema]}
            if ex.condition is not None:
                node["condition"] = expr_to_ir(ex.condition)
            if left.fused_input is not None:
                node["left_fused"] = _stages_ir(left.fused_input)
            if right.fused_input is not None:
                node["right_fused"] = _stages_ir(right.fused_input)
            ni = self._append(fi, node)
            return fi, ni
        from risingwave_tpu.stream.executors.temporal_join import (
            TemporalJoinExecutor,
        )
        if isinstance(ex, TemporalJoinExecutor):
            l_fi, _ = self._lower(ex.left_in)
            r_fi, _ = self._lower(ex.right_in)
            # left: hash on the probe keys; right: BROADCAST — every
            # actor maintains the full arrangement (lookup.rs delta-
            # join spirit; the dim side is small by design)
            fi, lxi = self._cut(l_fi, list(ex.left_keys),
                                ex.left_in.schema, self.parallelism)
            rxi = self._cut_into(fi, r_fi, [], ex.right_in.schema,
                                 mode="broadcast")
            ni = self._append(fi, {
                "op": "temporal_join", "left": lxi, "right": rxi,
                "left_keys": list(ex.left_keys),
                "right_keys": list(ex.right_keys),
                "outer": ex.outer,
                "output_names": [f.name for f in ex.schema]})
            return fi, ni
        from risingwave_tpu.stream.executors.top_n import (
            GroupTopNExecutor,
        )
        if isinstance(ex, GroupTopNExecutor):
            up_fi, ci = self._lower(ex.input)
            node = {
                "op": "top_n", "input": None,
                "order_by": [[i, d] for i, d in ex.order_by],
                "offset": ex.offset, "limit": ex.limit,
                "table_id": ex.state.table_id,
                "group": list(ex.group_indices),
                "append_only": ex.append_only,
                "pk": list(ex.pk_indices),
                # the state table's own key and vnode distribution:
                # a top-N the over-window rule planned keys it group |
                # order | rest of the pk and distributes it by group
                "state_pk": list(ex.state.pk_indices),
                "dist_key": list(ex.state.dist_key_indices),
                "tier_cap": ex.tier_cap}
            by_group = bool(ex.group_indices) and \
                ex.state.dist_key_indices == ex.group_indices
            if self.parallelism > 1 and by_group:
                # hash exchange on the group key, as the over-window's
                # on its partition: each actor owns whole groups, and
                # the state table's vnodes follow the same key
                fi, xi = self._cut(up_fi, list(ex.group_indices),
                                   ex.input.schema, self.parallelism)
                node["input"] = xi
            elif len(self.graph.fragments[up_fi].nodes) > 1 or \
                    self.parallelism > 1:
                # a plain TopN (or a grouped one whose table is not
                # distributed by its group) is a SINGLETON: a global
                # window cannot split across actors (DispatcherType::
                # SIMPLE, stream_graph/schedule.rs singleton placement)
                keys = list(ex.group_indices)
                fi, xi = self._cut(up_fi, keys, ex.input.schema, 1)
                node["input"] = xi
            else:
                fi, node["input"] = up_fi, ci
            ni = self._append(fi, node)
            return fi, ni
        from risingwave_tpu.stream.executors.over_window import (
            OverWindowExecutor,
        )
        if isinstance(ex, OverWindowExecutor):
            up_fi, ci = self._lower(ex.input)
            node = {
                "op": "over_window", "input": None,
                "partition": list(ex.partition_indices),
                "order_by": [[i, d] for i, d in ex.order_by],
                "calls": [{"kind": c.kind.value,
                           "input_idx": c.input_idx,
                           "offset": c.offset} for c in ex.calls],
                "table_id": ex.state.table_id,
                "input_pk": list(ex.input_pk),
                "output_names": [f.name for f in ex.schema]}
            if self.parallelism > 1 and ex.partition_indices:
                # hash exchange on the partition keys — each actor
                # owns whole partitions
                fi, xi = self._cut(up_fi, list(ex.partition_indices),
                                   ex.input.schema, self.parallelism)
                node["input"] = xi
            elif self.parallelism > 1:
                fi, xi = self._cut(up_fi, [], ex.input.schema, 1)
                node["input"] = xi        # unpartitioned → singleton
            else:
                fi, node["input"] = up_fi, ci
            ni = self._append(fi, node)
            return fi, ni
        from risingwave_tpu.stream.executors.project_set import (
            ProjectSetExecutor,
        )
        if isinstance(ex, ProjectSetExecutor):
            fi, ci = self._lower(ex.input)
            items = []
            for kind, payload in ex.items:
                if kind == "scalar":
                    items.append(["scalar", expr_to_ir(payload)])
                else:
                    items.append([kind,
                                  [expr_to_ir(e) for e in payload]])
            ni = self._append(fi, {
                "op": "project_set", "input": ci, "items": items,
                "names": list(ex.names), "pass_pk": list(ex.pass_pk)})
            return fi, ni
        from risingwave_tpu.stream.executors.eowc import (
            EowcGateExecutor,
        )
        if isinstance(ex, EowcGateExecutor):
            fi, ci = self._lower(ex.input)
            ni = self._append(fi, {
                "op": "eowc_gate", "input": ci, "wm_col": ex.wm_col,
                "table_id": ex.state.table_id,
                "pk": list(ex.state.pk_indices)})
            return fi, ni
        from risingwave_tpu.stream.executors.dedup import (
            AppendOnlyDedupExecutor,
        )
        if isinstance(ex, AppendOnlyDedupExecutor):
            fi, ci = self._lower(ex.input)
            ni = self._append(fi, {
                "op": "dedup", "input": ci,
                "keys": list(ex.dedup_indices),
                "table_id": ex.state.table_id})
            return fi, ni
        from risingwave_tpu.stream.executors.sink import (
            CoordinatedSinkExecutor,
        )
        if isinstance(ex, CoordinatedSinkExecutor):
            # terminal sink writer: colocated with its input (NoShuffle,
            # like Materialize) — each parallel actor is one of N
            # writers staging its slice per epoch; the scheduler stamps
            # writer=rank and n_writers=parallelism per actor, and the
            # coordinator (meta side) commits from the listing
            fi, ci = self._lower(ex.input)
            ni = self._append(fi, {
                "op": "sink", "input": ci,
                "sink_name": ex.sink_name,
                "mode": ex.encoder.mode,
                "path": ex.encoder.target.store.root,
                "pk": list(getattr(ex.encoder, "pk_indices", []))})
            return fi, ni
        if isinstance(ex, MaterializeExecutor):
            fi, ci = self._lower(ex.input)
            node = {
                "op": "materialize", "input": ci,
                "table_id": ex.table.table_id,
                "pk": list(ex.table.pk_indices),
                "mv_name": ex.mv_name}
            # vnode-partition the MV by its GROUP-KEY pk columns when
            # this is an exchange-fed agg fragment: the planner orders
            # the MV pk by group index, and agg output group j carries
            # the SAME value as dispatched key j — so hashing the pk
            # columns in pk order reproduces the dispatcher's vnode
            # exactly (exchange keys index the UPSTREAM schema and
            # must NOT be used as MV positions). Rescale then slices
            # every fragment table by one consistent mapping.
            frag = self.graph.fragments[fi]
            if (frag.inputs
                    and all(i.mode == "hash" for i in frag.inputs)
                    and sum(n["op"] == "hash_agg"
                            for n in frag.nodes) == 1
                    and node["pk"]
                    and len(frag.inputs[0].keys) == len(node["pk"])):
                node["dist_key"] = list(node["pk"])
            ni = self._append(fi, node)
            return fi, ni
        raise FragmentError(
            f"{type(ex).__name__} has no distributed lowering yet "
            "(deploy this MV on the in-process session)")
