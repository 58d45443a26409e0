"""Planner: bound SELECT → streaming executor chain or batch tree.

Reference parity: src/frontend/src/planner/ + optimizer/mod.rs:346
(gen_stream_plan) + the fragmenter — collapsed: the supported SQL
surface maps directly onto executor chains (source → [tumble-project]
→ [filter] → [join] → [pre-agg project → hash-agg] → project →
materialize), so the logical/physical split and exchange insertion are
not yet needed (single-fragment plans; the dispatch layer exists under
stream/ for when the fragmenter lands).

Supported streaming shapes: MV over one source (optionally TUMBLE) or
over another MV (backfill chain), WHERE conjuncts as filters over the
join chain (the frontend/opt filter_pushdown rule sinks them below
joins, gated by join kind, and into an inner join as its own condition
where they read both of its sides), multi-way left-deep
INNER/LEFT/RIGHT/FULL joins of sources on equi-keys, GROUP BY with
count/sum/min/max/avg (+DISTINCT) over arbitrary expressions, ORDER
BY/LIMIT TopN, EXPLAIN. Batch: scan/filter/project/agg/join/order/
limit over committed MV snapshots.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from risingwave_tpu.common.errors import PlanError  # re-exported
from risingwave_tpu.common.types import DataType, Field, Interval, Schema
from risingwave_tpu.expr.expr import (
    BinaryOp, Cast, Expression, InputRef, tumble_start,
)
from risingwave_tpu.frontend import ast
from risingwave_tpu.frontend.binder import (
    BindError, Binder, Scope, expr_name,
)
from risingwave_tpu.frontend.catalog import Catalog, MvCatalog, SourceCatalog
from risingwave_tpu.state.state_table import StateTable
from risingwave_tpu.stream.executor import Executor
from risingwave_tpu.stream.executors.hash_agg import (
    AggCall, HashAggExecutor, agg_state_schema, cleanable_type,
)
from risingwave_tpu.stream.executors.hash_join import (
    HashJoinExecutor, JoinType,
)
from risingwave_tpu.stream.executors.materialize import MaterializeExecutor
from risingwave_tpu.stream.executors.row_id_gen import RowIdGenExecutor
from risingwave_tpu.stream.executors.simple import (
    FilterExecutor, ProjectExecutor,
)
from risingwave_tpu.stream.executors.source import SourceExecutor

SPLIT_STATE_SCHEMA = Schema([Field("split_id", DataType.VARCHAR),
                             Field("offset", DataType.INT64)])


@dataclass
class StreamPlan:
    """Everything the session needs to deploy one MV pipeline."""

    consumer: MaterializeExecutor
    mv: MvCatalog
    readers: Dict[int, object]          # actor_id → split reader
    # MV-on-MV chain edges: (upstream actor id, Output) to attach at
    # deploy (NOT at plan time — a failed plan must leak nothing)
    attaches: List[tuple] = field(default_factory=list)


@dataclass
class SinkPlan:
    consumer: Executor                  # SinkExecutor chain
    deps: List[str]
    readers: Dict[int, object]
    attaches: List[tuple] = field(default_factory=list)
    # exactly-once epoch-segment sinks (connector='epochlog'): the
    # derived record mode and the built encoder — the SESSION
    # registers the encoder on its SinkCoordinator after the plan
    # validates (a failed plan must leak no registration), with the
    # store's committed floor as the recovery sweep point
    mode: str = ""                      # "append" | "upsert" | legacy ""
    encoder: object = None


def validate_sink_options(options: Dict[str, str]) -> None:
    """Pre-plan option validation (CREATE SINK fails before any
    barrier sender registers)."""
    if options.get("connector", "").lower() == "epochlog":
        if not options.get("path"):
            raise PlanError("epochlog sink needs path='...'")
        return
    make_sink_writer(options)


def make_sink_writer(options: Dict[str, str]):
    """connector= blackhole | file | filelog (build_sink analog)."""
    from risingwave_tpu.stream.executors.sink import (
        BlackholeSink, FileSink, FilelogSink,
    )
    connector = options.get("connector", "").lower()
    if connector == "blackhole":
        return BlackholeSink()
    if connector == "file":
        path = options.get("path")
        if not path:
            raise PlanError("file sink needs path='...'")
        return FileSink(path)
    if connector == "filelog":
        path = options.get("path")
        topic = options.get("topic")
        if not path or not topic:
            raise PlanError(
                "filelog sink needs path='...' and topic='...'")
        return FilelogSink(path, topic,
                           partition=int(options.get("partition", 0)))
    raise PlanError(f"unknown sink connector {connector!r}")


def _source_reader(src: SourceCatalog):
    opts = src.options
    connector = opts.get("connector", "").lower()
    if connector == "nexmark":
        from risingwave_tpu.connectors.nexmark import (
            NexmarkConfig, NexmarkSplitReader,
        )
        cfg = NexmarkConfig(
            table_type=opts.get("nexmark.table.type", "bid"),
            event_num=int(opts.get("nexmark.event.num", 1 << 62)),
            max_chunk_size=int(opts.get("nexmark.max.chunk.size", 1024)),
            min_event_gap_in_ns=int(
                opts.get("nexmark.min.event.gap.in.ns", 100_000)),
            seed=int(opts.get("nexmark.seed", 0x5EED0)),
            generate_strings=str(opts.get(
                "nexmark.generate.strings", "true")).lower()
            not in ("false", "0"),
        )
        return NexmarkSplitReader(cfg)
    if connector == "datagen":
        from risingwave_tpu.connectors.datagen import (
            DatagenConfig, DatagenSplitReader,
        )
        return DatagenSplitReader(DatagenConfig.from_options(opts))
    if connector == "filelog":
        from risingwave_tpu.connectors.filelog import (
            FileLogEnumerator, FileLogSplitReader,
        )
        path = opts.get("path")
        topic = opts.get("topic", src.name)
        if not path:
            raise PlanError("filelog source needs path='...'")
        part = int(opts.get("partition", 0))
        if opts.get("segmented", "").lower() in ("true", "1"):
            # a filelog SINK's output: immutable per-epoch segments
            from risingwave_tpu.connectors.filelog import (
                SegmentedFileLogReader,
            )
            return SegmentedFileLogReader(
                path, topic, part, src.schema,
                fmt=opts.get("format", "json"),
                max_chunk_size=int(opts.get("max.chunk.size", 1024)),
                options=opts)
        if "partitions" in opts:
            # explicit split subset (the scheduler stamps each source
            # actor's assignment here — the split-rebalancing
            # contract); "" is a legal EMPTY assignment: scale-out
            # past the partition count leaves idle source actors
            from risingwave_tpu.connectors.filelog import (
                FileLogMultiReader,
            )
            spec = str(opts["partitions"]).strip()
            parts = [int(p) for p in spec.split(",") if p != ""]
            return FileLogMultiReader(
                path, topic, parts, src.schema,
                fmt=opts.get("format", "json"),
                max_chunk_size=int(opts.get("max.chunk.size", 1024)),
                options=opts)
        splits = FileLogEnumerator(path, topic).list_splits()
        # bare single-pipeline sources: one reader drives partition 0
        # (the distributed scheduler assigns explicit partition sets)
        if splits and not any(
                int(s.split_id.rsplit("-", 1)[1]) == part
                for s in splits):
            raise PlanError(
                f"filelog partition {part} not found in {path!r}")
        return FileLogSplitReader(
            path, topic, part, src.schema,
            fmt=opts.get("format", "json"),
            max_chunk_size=int(opts.get("max.chunk.size", 1024)),
            options=opts)
    if connector == "tpch":
        from risingwave_tpu.connectors.tpch import (
            TpchConfig, TpchSplitReader,
        )
        return TpchSplitReader(TpchConfig(
            table=opts.get("tpch.table", "lineitem"),
            customers=int(opts.get("tpch.customers", 1500)),
            orders=int(opts.get("tpch.orders", 15000)),
            max_chunk_size=int(opts.get("tpch.max.chunk.size", 1024)),
        ))
    raise PlanError(f"unknown connector {connector!r}")


def source_schema(options: Dict[str, str],
                  columns=None) -> Schema:
    """A source's schema: its connector's own, or (filelog) the
    declared column list. A list declared over a connector that has a
    schema of its own must be that schema, names and types in order."""
    declared = None
    if columns is not None:
        fields = []
        for name, type_name in columns:
            try:
                fields.append(Field(name, DataType.from_sql(type_name)))
            except KeyError:
                raise PlanError(f"unknown type {type_name!r}")
        declared = Schema(fields)
    connector = options.get("connector", "").lower()
    if connector == "nexmark":
        from risingwave_tpu.connectors.nexmark import TABLE_SCHEMAS
        own = TABLE_SCHEMAS[options.get("nexmark.table.type", "bid")]
    elif connector == "datagen":
        from risingwave_tpu.connectors.datagen import DatagenConfig
        own = DatagenConfig.from_options(options).schema
    elif connector == "tpch":
        from risingwave_tpu.connectors.tpch import TABLE_SCHEMAS
        own = TABLE_SCHEMAS[options.get("tpch.table", "lineitem")]
    elif connector == "filelog":
        if declared is None:
            raise PlanError(
                "filelog sources need an explicit column list: "
                "CREATE SOURCE t (a INT, ...) WITH (...)")
        return declared
    else:
        raise PlanError(f"unknown connector {connector!r}")
    if declared is not None:
        want = [(f.name.lower(), f.data_type) for f in own]
        got = [(f.name.lower(), f.data_type) for f in declared]
        if got != want:
            raise BindError(
                f"the column list of a {connector} source must be the "
                "connector's own: "
                + ", ".join(f"{n} {t.name}" for n, t in want)
                + "; got "
                + ", ".join(f"{n} {t.name}" for n, t in got))
    return own


class StreamPlanner:
    """Plans one CREATE MATERIALIZED VIEW into an executor chain."""

    def __init__(self, catalog: Catalog, store, local, definition: str,
                 mesh=None, actors=None, dist_parallelism: int = 1,
                 join_state_cap=None, inline_mvs=None,
                 chunk_target_rows: Optional[int] = None,
                 coalesce_linger_chunks: Optional[int] = None,
                 state_tier_cap: Optional[int] = None):
        from risingwave_tpu.stream.coalesce import (
            DEFAULT_MAX_CHUNKS, DEFAULT_TARGET_ROWS,
        )
        self.catalog = catalog
        self.store = store
        # adaptive coalescing in front of keyed executors (session var
        # stream_chunk_target_rows; 0 disables — the oracle-equivalence
        # tests compare on vs off)
        self.chunk_target_rows = DEFAULT_TARGET_ROWS \
            if chunk_target_rows is None else chunk_target_rows
        self.coalesce_linger_chunks = DEFAULT_MAX_CHUNKS \
            if coalesce_linger_chunks is None else coalesce_linger_chunks
        self.local = local           # LocalBarrierManager
        self.definition = definition
        self.mesh = mesh             # non-None ⇒ sharded GROUP BY plans
        # > 1 ⇒ the plan deploys over N cluster actors: eligible
        # GROUP BYs split into local partial + global merge aggs
        # (logical_agg.rs two-phase), with the hash exchange between
        # them inserted by the fragmenter
        self.dist_parallelism = max(1, dist_parallelism)
        # resident-row cap per join side: INNER joins get the
        # cold-state tier (evict to the state table, reload on probe
        # miss — managed_state/join/mod.rs:379-420)
        self.join_state_cap = join_state_cap
        # unified state-tiering cap (SET state_tier_cap, state/tier.py):
        # resident-key cap per stateful executor cache — applies to
        # hash-agg groups AND join sides (where it takes precedence
        # over the legacy join_state_cap)
        self.state_tier_cap = state_tier_cap
        # name → (select AST, eowc): FROM <mv> replans the view's
        # definition INLINE instead of attaching to its live actor —
        # the distributed session's MV-on-MV form (classic view
        # expansion; no cross-job edges needed, every fragment ships)
        self.inline_mvs = dict(inline_mvs or {})
        self.actors = actors or {}   # actor_id → Actor (MV-on-MV attach)
        self.readers: Dict[int, object] = {}
        # chain edges produced by _chain_upstream_mv, attached by the
        # session once the WHOLE plan has validated
        self.pending_attaches: List[tuple] = []
        self.registered_senders: List[int] = []   # cleanup on failure
        self._actor_id = 0           # downstream actor id (Output tag)
        self._edge_seq = 0           # per-channel edge-label uniquifier

    def _edge_label(self, kind: str, name: str) -> str:
        """Unique exchange-edge label: kind:name->actor[.seq]."""
        self._edge_seq += 1
        base = f"{kind}:{name}->{self._actor_id}"
        return base if self._edge_seq == 1 else \
            f"{base}.{self._edge_seq}"

    def _coalesced(self, ex: Executor) -> Executor:
        """Adaptive coalescing in front of a keyed executor's input:
        every device dispatch then carries a dense target-sized batch
        instead of per-upstream-chunk slivers (stream/coalesce.py).
        Disabled when stream_chunk_target_rows = 0."""
        if not self.chunk_target_rows or self.chunk_target_rows <= 0:
            return ex
        from risingwave_tpu.stream.coalesce import CoalesceExecutor
        return CoalesceExecutor(ex, self.chunk_target_rows,
                                self.coalesce_linger_chunks)

    # -- source chains ---------------------------------------------------
    def _base_chain(self, item, rate_limit: Optional[int],
                    min_chunks: Optional[int]
                    ) -> Tuple[Executor, Scope, List[str]]:
        """FROM item → executor + scope (+ dependent source names)."""
        from risingwave_tpu.stream.exchange import channel_for_test

        if isinstance(item, ast.Subquery):
            return self._plan_derived(item.select, item.alias,
                                      rate_limit, min_chunks)
        if isinstance(item, (ast.Tumble, ast.Hop)):
            ref, alias = item.table, item.alias or item.table.name
        elif isinstance(item, ast.TableRef):
            ref, alias = item, item.alias or item.name
        else:
            raise PlanError(f"unsupported FROM item {item!r}")
        obj = self.catalog.resolve(ref.name)
        if isinstance(obj, MvCatalog):
            if isinstance(item, (ast.Tumble, ast.Hop)):
                raise PlanError(
                    "TUMBLE/HOP over an MV not supported yet")
            inline = self.inline_mvs.get(obj.name)
            if inline is not None:
                sel_i, eowc_i = inline
                if eowc_i:
                    raise PlanError(
                        "cannot inline an EMIT ON WINDOW CLOSE view")
                ex, scope, deps = self._plan_derived(
                    sel_i, alias, rate_limit, min_chunks)
                # the VIEW name joins the dep list: DROP of the base
                # view must refuse while this consumer runs (the
                # in-process chain branch records it the same way)
                return ex, scope, deps + [obj.name]
            ex, scope = self._chain_upstream_mv(obj, alias)
            return ex, scope, [obj.name]
        assert isinstance(obj, SourceCatalog)
        reader = _source_reader(obj)
        # edge labels are unique per CHANNEL (consumer actor id + a
        # per-plan sequence for self-joins of one source): sharing a
        # series would merge independent pipes, and teardown of one
        # would remove the other's queue-depth gauge
        tx, rx = channel_for_test(edge=self._edge_label("barrier",
                                                        obj.name))
        split_state = StateTable(self.catalog.next_id(),
                                 SPLIT_STATE_SCHEMA, [0], self.store)
        # source sender id: unique per source instance (shares the
        # catalog id space; the barrier manager only needs uniqueness)
        sid = self.catalog.next_id()
        self.local.register_sender(sid, tx)
        self.registered_senders.append(sid)
        ex: Executor = SourceExecutor(
            reader, rx, split_state, actor_id=sid,
            rate_limit_chunks_per_barrier=rate_limit,
            min_chunks_per_barrier=min_chunks,
            # freshness accounting key (stream/freshness.py): the
            # CATALOG name, so per-MV lag joins source frontiers by
            # the name the MV's dependency list carries
            freshness_key=obj.name)
        # connector options ride along for the fragmenter: the shipped
        # source IR node rebuilds the reader worker-side from these
        ex.ir_connector = dict(obj.options)
        self.readers[sid] = reader
        scope = Scope.of(obj.schema, alias)
        # event-time watermarks from SQL: the source's WATERMARK FOR
        # clause (source/watermark.rs), as the catalog keeps it,
        # driving state cleaning and EOWC
        wm_idx = None
        if obj.watermark is not None:
            from risingwave_tpu.stream.executors.watermark_filter \
                import WATERMARK_STATE_SCHEMA, WatermarkFilterExecutor
            wm_col_name, delay_usecs = obj.watermark
            wm_idx, _wdt = scope.find(wm_col_name, None)
            wm_state = StateTable(self.catalog.next_id(),
                                  WATERMARK_STATE_SCHEMA, [0],
                                  self.store)
            ex = WatermarkFilterExecutor(
                ex, wm_idx, Interval(usecs=delay_usecs), wm_state,
                source=obj.name)
        if isinstance(item, ast.Tumble):
            idx, dt = scope.find(item.time_col, None)
            if dt not in (DataType.TIMESTAMP, DataType.TIMESTAMPTZ):
                raise PlanError("TUMBLE time column must be a timestamp")
            exprs = [InputRef(i, f.data_type)
                     for i, f in enumerate(scope.schema)]
            names = [f.name for f in scope.schema]
            exprs.append(tumble_start(InputRef(idx, dt),
                                      Interval(usecs=item.window_usecs)))
            names.append("window_start")
            derivs = {}
            if wm_idx is not None:
                # identity for the raw column AND (when it is the
                # tumble column) the floored window_start image — one
                # input watermark derives both outputs
                derivs[wm_idx] = [wm_idx]
                if wm_idx == idx:
                    w = item.window_usecs
                    derivs[idx].append(
                        (len(exprs) - 1,
                         (lambda v, _w=w: v - v % _w)))
            ex = ProjectExecutor(ex, exprs, names,
                                 watermark_derivations=derivs)
            scope = Scope(ex.schema,
                          scope.qualifiers + [alias])
        elif isinstance(item, ast.Hop):
            from risingwave_tpu.stream.executors.hop_window import (
                HopWindowExecutor,
            )
            idx, dt = scope.find(item.time_col, None)
            if dt not in (DataType.TIMESTAMP, DataType.TIMESTAMPTZ):
                raise PlanError("HOP time column must be a timestamp")
            ex = HopWindowExecutor(
                ex, idx, Interval(usecs=item.slide_usecs),
                Interval(usecs=item.size_usecs))
            # schema gains window_start/window_end, same qualifier
            scope = Scope(ex.schema,
                          scope.qualifiers + [alias, alias])
        return ex, scope, [obj.name]

    def _plan_derived(self, sel, alias, rate_limit, min_chunks):
        """Derived table: plan an inner SELECT as this fragment's
        upstream chain (binder/bind_query subquery analog — shared by
        FROM-subqueries and inlined views). Hidden pk columns stay in
        the executor schema but out of the visible scope; the derived
        pk is STAMPED onto the executor so a consumer join keys its
        state by it — fresh row ids instead would orphan every U-
        retraction half and leave stale rows in join state."""
        from risingwave_tpu.stream.executor import ExecutorInfo

        ex, pk, deps, n_vis = self._plan_query(
            sel, self._actor_id, rate_limit, min_chunks)
        ex._info = ExecutorInfo(ex.schema, list(pk), ex.identity)
        self._eowc_wm_col = None      # inner value is meaningless
        #                               against the OUTER schema
        vis = Schema(list(ex.schema)[:n_vis])
        return ex, Scope(vis, [alias] * n_vis), deps

    def _chain_upstream_mv(self, mv: MvCatalog, alias: str):
        """FROM <mv>: attach a new output to the upstream MV's actor
        (chain.rs:28) and backfill its committed snapshot
        (no_shuffle_backfill.rs:68). The attach happens under the
        session's barrier lock — the pipeline is quiescent between
        barrier rounds, so mutating the dispatcher's output set is the
        Mutation::Add analog without the RPC hop."""
        from risingwave_tpu.stream.dispatch import Output
        from risingwave_tpu.stream.exchange import channel_for_test
        from risingwave_tpu.stream.executor import ExecutorInfo
        from risingwave_tpu.stream.executors.backfill import (
            PROGRESS_SCHEMA, BackfillExecutor,
        )
        from risingwave_tpu.stream.executors.simple import (
            ReceiverExecutor,
        )

        upstream = self.actors.get(mv.actor_id)
        if upstream is None or not upstream.dispatchers:
            raise PlanError(
                f"upstream MV {mv.name!r} has no attachable actor")
        tx, rx = channel_for_test(edge=self._edge_label("chain",
                                                        mv.name))
        # deferred: the session attaches AFTER the whole plan validates
        # (a failed CREATE must not leave an orphan output that blocks
        # the upstream on exhausted permits), tagged with the DOWNSTREAM
        # actor id so drops can detach exactly this edge
        self.pending_attaches.append(
            (mv.actor_id, Output(self._actor_id, tx)))
        recv = ReceiverExecutor(
            ExecutorInfo(mv.schema, list(mv.pk_indices),
                         f"Chain({mv.name})"), rx)
        mv_read = StateTable(mv.table_id, mv.schema, mv.pk_indices,
                             self.store)
        progress = StateTable(self.catalog.next_id(), PROGRESS_SCHEMA,
                              [0], self.store)
        ex = BackfillExecutor(recv, mv_read, progress,
                              identity=f"Backfill({mv.name})")
        # the backfill snapshot replays the MV's TABLE as inserts and
        # the live tail is the MV's changelog — the chain is
        # append-only exactly when the MV's own changelog is
        # (_derive_append_only reads this hint at the chain boundary)
        ex.append_only_hint = mv.append_only
        # expose only the MV's user-facing columns (hidden _row_id /
        # group-key plumbing stays out of downstream scopes)
        return ex, Scope.of(mv.visible_schema, alias)

    # -- the main plan ---------------------------------------------------
    def plan(self, name: str, sel: ast.Select, actor_id: int,
             rate_limit: Optional[int] = 8,
             min_chunks: Optional[int] = None,
             emit_on_window_close: bool = False) -> StreamPlan:
        self._actor_id = actor_id
        self._eowc_wm_col = None
        ex, pk, deps, nvis = self._plan_query(sel, actor_id,
                                              rate_limit, min_chunks)
        if emit_on_window_close:
            # gate results behind the window watermark (sort_buffer.rs
            # / AggGroup::create_eowc semantics as a downstream gate)
            from risingwave_tpu.stream.executors.eowc import (
                EowcGateExecutor,
            )
            wm_col = self._eowc_wm_col
            if wm_col is None:
                raise PlanError(
                    "EMIT ON WINDOW CLOSE needs a windowed GROUP BY "
                    "whose first group key is projected and carries a "
                    "watermark (e.g. TUMBLE window_start)")
            gate_pk = [wm_col] + [p for p in pk if p != wm_col]
            gate_state = StateTable(self.catalog.next_id(), ex.schema,
                                    gate_pk, self.store)
            ex = EowcGateExecutor(ex, wm_col, gate_state,
                                  actor_id=actor_id)
        mv_table = StateTable(self.catalog.next_id(), ex.schema, pk,
                              self.store)
        mat = MaterializeExecutor(ex, mv_table, mv_name=name)
        mv = MvCatalog(name, mv_table.table_id, ex.schema, pk,
                       self.definition, actor_id, deps,
                       n_visible=nvis if nvis < len(ex.schema) else None,
                       append_only=self._derive_append_only(ex))
        return StreamPlan(mat, mv, self.readers, self.pending_attaches)

    def plan_sink(self, sel: ast.Select, options: Dict[str, str],
                  actor_id: int, rate_limit: Optional[int] = 8,
                  min_chunks: Optional[int] = None,
                  sink_name: str = "",
                  append_only: Optional[bool] = None,
                  coordinator=None, writer_id: int = 0,
                  n_writers: int = 1) -> SinkPlan:
        """CREATE SINK AS SELECT: same chain, terminal SinkExecutor."""
        from risingwave_tpu.stream.executors.sink import SinkExecutor

        self._actor_id = actor_id
        ex, pk, deps, nvis = self._plan_query(sel, actor_id,
                                              rate_limit, min_chunks)
        # _plan_query appends hidden _pk columns even when the stream
        # key is already visibly projected (e.g. SELECT * over a
        # group-by MV re-emits the group key as _pk0).  A sink drops
        # hidden columns, so remap each hidden pk ref to its visible
        # twin when both project the same upstream column.
        if pk and nvis < len(ex.schema) and isinstance(ex, ProjectExecutor):
            vis_by_ref = {e.index: v
                          for v, e in enumerate(ex.exprs[:nvis])
                          if isinstance(e, InputRef)}
            remapped = []
            for p in pk:
                if p < nvis:
                    remapped.append(p)
                    continue
                e = ex.exprs[p] if p < len(ex.exprs) else None
                if isinstance(e, InputRef) and e.index in vis_by_ref:
                    remapped.append(vis_by_ref[e.index])
                else:
                    remapped = None
                    break
            if remapped is not None:
                pk = remapped
        if nvis < len(ex.schema):
            # hidden plumbing columns (_row_id, unprojected group keys)
            # must not reach an EXTERNAL sink — emit exactly the
            # declared SELECT list
            ex = ProjectExecutor(
                ex, [InputRef(i, f.data_type)
                     for i, f in enumerate(list(ex.schema)[:nvis])],
                [f.name for f in list(ex.schema)[:nvis]])
        if options.get("connector", "").lower() == "epochlog":
            return self._plan_epoch_sink(
                ex, pk, deps, options, sink_name=sink_name,
                append_only=append_only, coordinator=coordinator,
                writer_id=writer_id, n_writers=n_writers)
        writer = make_sink_writer(options)
        # durable stream-position counter: the exactly-once writers'
        # recovery reconciliation anchor (sink coordinator epoch-log);
        # built only for writers that reconcile — an unread counter
        # would cost a table id + a write per checkpoint for nothing
        sink_state = None
        if hasattr(writer, "reset_stream_position"):
            sink_state = StateTable(
                self.catalog.next_id(),
                Schema([Field("_k", DataType.INT64),
                        Field("_count", DataType.INT64)]),
                [0], self.store)
        return SinkPlan(SinkExecutor(ex, writer, state=sink_state),
                        deps, self.readers, self.pending_attaches)

    def _plan_epoch_sink(self, ex: Executor, pk: List[int],
                         deps: List[str], options: Dict[str, str],
                         sink_name: str,
                         append_only: Optional[bool],
                         coordinator, writer_id: int,
                         n_writers: int) -> SinkPlan:
        """connector='epochlog': the exactly-once epoch-segment sink
        (connectors/sink.py). Derives the record mode from the input
        chain — provably append-only ⇒ insert-only records; anything
        else ⇒ keyed upsert records folded per epoch — and builds the
        terminal CoordinatedSinkExecutor. Registration on the
        coordinator is the CALLER's job post-validation."""
        from risingwave_tpu.connectors.sink import (
            AppendSegmentSink, UpsertSegmentSink, make_sink_target,
        )
        from risingwave_tpu.stream.executors.sink import (
            CoordinatedSinkExecutor,
        )
        derived = self._derive_append_only(ex)
        if append_only and not derived \
                and options.get("force", "").lower() != "true":
            raise PlanError(
                "sink declared AS APPEND-ONLY but the query is not "
                "provably append-only; add force='true' to override "
                "(retractions then fail the sink loudly)")
        mode = "append" if (append_only or derived) else "upsert"
        names = [f.name for f in ex.schema]
        pk_indices: List[int] = []
        if mode == "upsert":
            if options.get("primary_key"):
                want = [c.strip() for c in
                        options["primary_key"].split(",") if c.strip()]
                missing = [c for c in want if c not in names]
                if missing:
                    raise PlanError(
                        f"primary_key column(s) {missing} not in sink "
                        f"schema {names}")
                pk_indices = [names.index(c) for c in want]
            else:
                if not pk or any(i >= len(names) for i in pk):
                    raise PlanError(
                        "upsert sink needs a key: the query's stream "
                        "key is hidden or absent — name one with "
                        "primary_key='col1,col2' in WITH (...)")
                pk_indices = list(pk)
        try:
            target = make_sink_target(options, mode, names)
        except ValueError as e:
            raise PlanError(str(e)) from e
        encoder = (AppendSegmentSink(target) if mode == "append"
                   else UpsertSegmentSink(target, pk_indices))
        consumer = CoordinatedSinkExecutor(
            ex, sink_name, encoder, writer=writer_id,
            n_writers=n_writers, coordinator=coordinator)
        return SinkPlan(consumer, deps, self.readers,
                        self.pending_attaches, mode=mode,
                        encoder=encoder)

    def _plan_query(self, sel: ast.Select, actor_id: int,
                    rate_limit: Optional[int],
                    min_chunks: Optional[int]):
        if sel.from_item is None:
            raise PlanError("a streaming job needs a FROM clause")
        ex, scope, deps = self._base_chain(sel.from_item,
                                           rate_limit, min_chunks)
        join_pk_cols: Optional[List[int]] = None
        conjuncts = _flatten_and(sel.where) if sel.where is not None \
            else []
        if isinstance(sel.from_item, ast.Subquery):
            # `row_number() ... WHERE rn <= N` over a derived table is
            # a group top-N (frontend/opt/over_window_to_topn.py)
            from risingwave_tpu.frontend.opt.over_window_to_topn import (
                over_window_to_topn,
            )
            ex, scope, conjuncts = over_window_to_topn(
                self, ex, scope, sel, conjuncts)
        if sel.joins:
            # Optimizer v0 (multi-way planning, collapsed): a
            # left-deep chain of HashJoins in syntax order. WHERE
            # conjuncts bind AFTER the chain against the full scope
            # (ambiguous unqualified columns raise properly — ADVICE
            # r3) and land as filters ABOVE the joins; the
            # filter_pushdown rewrite rule (frontend/opt/rules.py, the
            # former inline pushdown) then sinks each one below every
            # side its join never null-pads.
            left, lscope = self._joinable(ex, scope)
            rights = []
            for jn in sel.joins:
                rex, rscope, rdeps = self._base_chain(
                    jn.item, rate_limit, min_chunks)
                deps += rdeps
                if getattr(jn, "temporal", False):
                    # temporal join: the right side IS a versioned
                    # table (MV chain with a pk) probed as-of process
                    # time — no row-id wrapping, no join state
                    if not rex.pk_indices:
                        raise PlanError(
                            "temporal join (FOR SYSTEM_TIME AS OF "
                            "PROCTIME()) needs a materialized view "
                            "on the right side")
                    if jn.kind not in ("inner", "left"):
                        raise PlanError(
                            "temporal join supports INNER and LEFT "
                            "only")
                    rights.append((jn, rex, rscope))
                    continue
                right, rscope = self._joinable(rex, rscope)
                rights.append((jn, right, rscope))
            for jn, right, rscope in rights:
                if getattr(jn, "temporal", False):
                    from risingwave_tpu.stream.executors.temporal_join \
                        import TemporalJoinExecutor
                    lkeys, rkeys, cond = _join_keys(jn, conjuncts,
                                                    lscope, rscope)
                    _inner_condition(jn, cond)
                    if sorted(rkeys) != sorted(right.pk_indices):
                        raise PlanError(
                            "temporal join ON keys must equal the "
                            "right table's primary key")
                    if not self._derive_append_only(left):
                        raise PlanError(
                            "temporal join left input must be "
                            "append-only")
                    left = TemporalJoinExecutor(
                        left, right, lkeys, rkeys,
                        outer=(jn.kind == "left"),
                        actor_id=actor_id)
                    lscope = lscope.concat(rscope)
                    continue
                lkeys, rkeys, cond = _join_keys(jn, conjuncts, lscope,
                                                rscope)
                cond = _inner_condition(jn, cond)
                jt = {"inner": JoinType.INNER,
                      "left": JoinType.LEFT_OUTER,
                      "right": JoinType.RIGHT_OUTER,
                      "full": JoinType.FULL_OUTER}[jn.kind]
                # cold-tier eligibility: INNER or OUTER (outer-side
                # degrees recompute on reload — semi/anti transition
                # history cannot be evicted) + single-chip AND both
                # inputs PROVABLY append-only — a retraction for an
                # evicted key cannot be applied against device state
                # (ADVICE r5 high: the silent-skip would leave
                # already-emitted join outputs permanently stale), so
                # a retracting input runs uncapped instead
                tierable = (jt in (JoinType.INNER, JoinType.LEFT_OUTER,
                                   JoinType.RIGHT_OUTER,
                                   JoinType.FULL_OUTER)
                            and self.mesh is None
                            # distributed joins are fine: the
                            # fragmenter ships state_cap on the
                            # hash_join IR node, and worker rebuilds
                            # run the same single-chip epoch-batched
                            # path (per-actor cap)
                            and self._derive_append_only(left)
                            and self._derive_append_only(right))
                cap = (self.state_tier_cap or self.join_state_cap) \
                    if tierable else None
                if cap is not None:
                    # cold tier: state-table pks lead with the join
                    # keys so evicted keys reload by prefix scan
                    lpk = lkeys + [p for p in left.pk_indices
                                   if p not in lkeys]
                    rpk = rkeys + [p for p in right.pk_indices
                                   if p not in rkeys]
                    lt = StateTable(self.catalog.next_id(),
                                    left.schema, lpk, self.store,
                                    dist_key_indices=lkeys)
                    rt = StateTable(self.catalog.next_id(),
                                    right.schema, rpk, self.store,
                                    dist_key_indices=rkeys)
                else:
                    lt = StateTable(self.catalog.next_id(), left.schema,
                                    list(left.pk_indices), self.store,
                                    dist_key_indices=None)
                    rt = StateTable(self.catalog.next_id(), right.schema,
                                    list(right.pk_indices), self.store)
                # parallel plan: the hash exchange feeding N parallel
                # join actors (dispatch.rs:582) is the sharded kernel's
                # in-program all_to_all — same wiring as the agg path
                left = HashJoinExecutor(self._coalesced(left),
                                        self._coalesced(right),
                                        lkeys, rkeys, lt,
                                        rt, actor_id=actor_id,
                                        join_type=jt, mesh=self.mesh,
                                        state_cap=cap)
                lscope = lscope.concat(rscope)
                for c in cond:
                    # the ON's other conjuncts, directly above their
                    # join and below any later join of the chain: the
                    # pushdown rule sinks each below a side, or into
                    # the join as its own condition
                    left = FilterExecutor(left, Binder(lscope).bind(c))
            ex = left
            scope = lscope
            join_pk_cols = list(ex.pk_indices)
        for c in conjuncts:
            ex = FilterExecutor(ex, Binder(scope).bind(c))
        projections = _expand_star(sel.projections, scope)
        if any(isinstance(e, ast.Call)
               and e.name in ("generate_series", "unnest")
               for e, _a in projections):
            return self._plan_project_set(ex, scope, sel, projections,
                                          deps)
        from risingwave_tpu.frontend.binder import contains_agg
        binder = Binder(scope, allow_aggs=True)
        names = [a or expr_name(e, f"col{i}")
                 for i, (e, a) in enumerate(projections)]
        has_agg = (bool(sel.group_by) or sel.having is not None
                   or any(contains_agg(e) for e, _a in projections))
        if has_agg:
            if any(isinstance(e, ast.Over) for e, _a in projections):
                raise PlanError("window functions cannot be mixed "
                                "with GROUP BY / aggregates (yet)")
            ex, out_exprs, having_pred = self._plan_agg(
                ex, scope, sel, binder, projections)
            # MV/stream key = the FULL group-key set. Unprojected group
            # keys ride along as hidden trailing columns (nexmark q4's
            # inner query groups by (id, category) but projects only
            # category — without the hidden id the change stream would
            # collide distinct groups). Global aggs carry ONE synthetic
            # constant key (set by _plan_agg).
            g = self._agg_group_arity
            proj_of_group: Dict[int, int] = {}
            for pos, e in enumerate(out_exprs):
                if isinstance(e, InputRef) and e.index < g \
                        and e.index not in proj_of_group:
                    proj_of_group[e.index] = pos
            for gi in range(g):
                if gi not in proj_of_group:
                    proj_of_group[gi] = len(out_exprs)
                    out_exprs.append(
                        InputRef(gi, ex.schema[gi].data_type))
                    names.append(f"_g{gi}")
            pk = [proj_of_group[gi] for gi in range(g)]
            # plain group-key outputs carry the agg's watermarks (the
            # EOWC gate and downstream window ops depend on them)
            derivs = {e.index: j for j, e in enumerate(out_exprs)
                      if isinstance(e, InputRef)}
            if having_pred is not None:
                # HAVING filters the agg's change stream BEFORE the
                # output projection (logical_agg.rs plans it as a
                # LogicalFilter over the agg)
                ex = FilterExecutor(ex, having_pred)
            ex = ProjectExecutor(ex, out_exprs, names,
                                 watermark_derivations=derivs,
                                 span_args=self._agg_span_args)
            # EOWC window column: the first group key that PROVABLY
            # carries a watermark all the way from the source (a gate
            # with no watermark feed would hold results forever)
            self._eowc_wm_col = next(
                (derivs[pos] for pos in self._agg_wm_positions
                 if pos in derivs), None)
        else:
            bound = [binder.bind_projection(e) for e, _a in projections]
            if binder.window_calls:
                ex, bound = self._plan_over_window(ex, binder, bound)
            exprs = list(bound)
            base_pk = list(ex.pk_indices)
            if join_pk_cols is not None:
                pk = list(range(len(exprs),
                                len(exprs) + len(join_pk_cols)))
                exprs += [InputRef(c, scope.schema[c].data_type)
                          for c in join_pk_cols]
                names += [f"_row_id_{j}"
                          for j in range(len(join_pk_cols))]
                ex = ProjectExecutor(ex, exprs, names)
            elif base_pk:
                # pk-keyed upstream (MV chain): carry its pk through as
                # hidden columns — a generated row id would turn every
                # upstream update pair into a fresh row (duplicates)
                pk = list(range(len(exprs), len(exprs) + len(base_pk)))
                # ex.schema, not scope.schema: the chain may have grown
                # columns past the bind scope (row-id gen, window cols)
                exprs += [InputRef(c, ex.schema[c].data_type)
                          for c in base_pk]
                names += [f"_pk{j}" for j in range(len(base_pk))]
                ex = ProjectExecutor(ex, exprs, names)
            else:
                ex = RowIdGenExecutor(ProjectExecutor(ex, exprs, names))
                pk = [len(exprs)]
                names = names + ["_row_id"]
        if sel.limit is not None or (sel.offset or 0) > 0:
            # ORDER BY alone is a no-op for a pk-keyed MV (pg drops it
            # too) — only a real window needs the TopN executor.
            # append-only-ness is DERIVED over the chain (agg outputs
            # and outer joins retract; inner chains of append-only
            # sources do not) — TopN prunes beyond-window state only
            # when provably append-only (top_n_appendonly analog)
            ex = self._plan_topn(ex, sel, pk,
                                 append_only=self._derive_append_only(ex))
        return ex, pk, deps, len(projections)

    @staticmethod
    def _joinable(ex: Executor, scope: Scope) -> Tuple[Executor, Scope]:
        """Make one join input key-stable with a scope covering its
        whole schema. A pk-less (append-only) chain gets a generated
        row id; a pk-keyed input KEEPS its pk — retractions replay by
        pk, so join state updates consistently. Hidden columns beyond
        the bind scope are projected down to visible + pk so scope and
        executor schema stay index-aligned (the join's output offsets
        are schema offsets)."""
        from risingwave_tpu.stream.executor import ExecutorInfo

        if not ex.pk_indices:
            ex2: Executor = RowIdGenExecutor(ex)
            return ex2, Scope(ex2.schema, scope.qualifiers + [None])
        n_vis = len(scope.schema)
        if n_vis == len(ex.schema):
            return ex, scope
        keep_hidden = [i for i in ex.pk_indices if i >= n_vis]
        exprs = [InputRef(i, ex.schema[i].data_type)
                 for i in range(n_vis)]
        names = [f.name for f in scope.schema]
        for k, i in enumerate(keep_hidden):
            exprs.append(InputRef(i, ex.schema[i].data_type))
            names.append(f"_jpk{k}")
        proj = ProjectExecutor(ex, exprs, names)
        new_pk = [i if i < n_vis else n_vis + keep_hidden.index(i)
                  for i in ex.pk_indices]
        proj._info = ExecutorInfo(proj.schema, new_pk, proj.identity)
        return proj, Scope(proj.schema,
                           scope.qualifiers + [None] * len(keep_hidden))

    def _plan_topn(self, ex: Executor, sel: ast.Select,
                   pk: List[int], append_only: bool = False) -> Executor:
        """ORDER BY [+ LIMIT/OFFSET] MV → streaming TopN (top_n_plain
        analog): maintains the window incrementally, emitting deltas."""
        from risingwave_tpu.stream.executors.top_n import (
            GroupTopNExecutor,
        )
        post = Scope.of(ex.schema, None)
        order = []
        for e_ast, desc in sel.order_by:
            b = Binder(post).bind(e_ast)
            if not isinstance(b, InputRef):
                raise PlanError(
                    "MV ORDER BY must reference output columns")
            order.append((b.index, desc))
        if not order:
            # LIMIT without ORDER BY: deterministic order by pk
            order = [(i, False) for i in pk]
        state = StateTable(self.catalog.next_id(), ex.schema, pk,
                           self.store)
        return GroupTopNExecutor(
            ex, order, offset=sel.offset or 0, limit=sel.limit,
            state=state, pk_indices=pk, append_only=append_only)

    @staticmethod
    def _derive_append_only(ex: Executor) -> bool:
        """Conservative append-only derivation over the executor chain
        (the reference's input_append_only on StreamHashAgg,
        logical_agg.rs). Append-only ⇢ the cheap device agg path; any
        possibility of retraction ⇢ the minput path. Unknown executors
        default to False — silent wrongness is the only unacceptable
        outcome (VERDICT r3 #7)."""
        # chained-MV edges carry the upstream MV's own proof (stamped
        # in _chain_upstream_mv from MvCatalog.append_only) — the
        # chain boundary would otherwise hit the Backfill default and
        # lose provably-append-only upstreams
        hint = getattr(ex, "append_only_hint", None)
        if hint is not None:
            return bool(hint)
        from risingwave_tpu.stream.executors.source import SourceExecutor
        from risingwave_tpu.stream.executors.simple import (
            FilterExecutor, ProjectExecutor,
        )
        from risingwave_tpu.stream.executors.row_id_gen import (
            RowIdGenExecutor,
        )
        if isinstance(ex, SourceExecutor):
            return True
        if isinstance(ex, HashJoinExecutor):
            # inner joins of append-only inputs emit only inserts;
            # any outer/semi/anti kind emits padded-row flips
            return (ex.join_type == JoinType.INNER
                    and StreamPlanner._derive_append_only(ex.left_in)
                    and StreamPlanner._derive_append_only(ex.right_in))
        from risingwave_tpu.stream.executors.temporal_join import (
            TemporalJoinExecutor,
        )
        if isinstance(ex, TemporalJoinExecutor):
            # temporal output is append-only by construction
            return StreamPlanner._derive_append_only(ex.left_in)
        from risingwave_tpu.stream.executors.hop_window import (
            HopWindowExecutor,
        )
        if isinstance(ex, (ProjectExecutor, FilterExecutor,
                           RowIdGenExecutor, HopWindowExecutor)):
            return StreamPlanner._derive_append_only(ex.input)
        from risingwave_tpu.stream.executors.watermark_filter import (
            WatermarkFilterExecutor,
        )
        if isinstance(ex, WatermarkFilterExecutor):
            return StreamPlanner._derive_append_only(ex.input)
        from risingwave_tpu.stream.coalesce import CoalesceExecutor
        if isinstance(ex, CoalesceExecutor):
            # pure re-batching: op multiset is untouched
            return StreamPlanner._derive_append_only(ex.input)
        from risingwave_tpu.stream.executors.project_set import (
            ProjectSetExecutor,
        )
        if isinstance(ex, ProjectSetExecutor):
            # deterministic expansion of inserts is inserts
            return StreamPlanner._derive_append_only(ex.input)
        from risingwave_tpu.stream.executors.fused import (
            FusedFragmentExecutor,
        )
        if isinstance(ex, FusedFragmentExecutor):
            # a fused block composes filter/project/row_id_gen/
            # watermark_filter stages — each append-only-transparent,
            # so the block is too
            return StreamPlanner._derive_append_only(ex.input)
        # HashAgg/TopN/Backfill/DynamicFilter/unknown: assume retracting
        return False

    def _plan_project_set(self, ex: Executor, scope: Scope,
                          sel: ast.Select, projections, deps):
        """SELECT list with set-returning functions → ProjectSet
        (src/stream/src/executor/project_set.rs parity): each row
        expands to the rows its table functions return, and the
        hidden _projected_row_id joins the stream key so equal
        per-element rows retract exactly."""
        from risingwave_tpu.expr.expr import Literal
        from risingwave_tpu.stream.executors.project_set import (
            ProjectSetExecutor,
        )
        if sel.group_by or sel.having is not None:
            raise PlanError("set-returning functions cannot be mixed "
                            "with GROUP BY / HAVING")
        binder = Binder(scope)      # aggregates raise naturally
        items, names = [], []
        ints = (DataType.INT16, DataType.INT32, DataType.INT64)
        for i, (e, a) in enumerate(projections):
            if isinstance(e, ast.Call) and e.name == "unnest":
                raise PlanError(
                    "unnest is not supported yet — LIST columns do "
                    "not carry an element type")
            if isinstance(e, ast.Call) and e.name == "generate_series":
                if len(e.args) not in (2, 3):
                    raise PlanError(
                        "generate_series(start, stop [, step])")
                args = [binder.bind(x) for x in e.args]
                for b in args:
                    if b.return_type not in ints:
                        raise PlanError("generate_series arguments "
                                        "must be integers")
                if len(args) == 2:
                    args.append(Literal(1, DataType.INT64))
                step = args[2]
                if isinstance(step, Literal) and int(step.value) == 0:
                    raise PlanError(
                        "generate_series step must be nonzero")
                items.append(("series", tuple(args)))
                names.append(a or "generate_series")
            else:
                items.append(("scalar", binder.bind(e)))
                names.append(a or expr_name(e, f"col{i}"))
        seen: dict = {}
        for idx, n in enumerate(names):
            k = seen.get(n, 0)
            seen[n] = k + 1
            if k:
                # two unaliased series items share a name; uniquify so
                # the MV's columns stay addressable (SELECT * binds by
                # name downstream)
                names[idx] = f"{n}_{k}"
        base_pk = list(ex.pk_indices)
        if not base_pk:
            ex = RowIdGenExecutor(ex)
            base_pk = [len(ex.schema) - 1]
        ex = ProjectSetExecutor(ex, items, names, pass_pk=base_pk)
        pk = list(ex.pk_indices)
        if sel.limit is not None or (sel.offset or 0) > 0:
            ex = self._plan_topn(
                ex, sel, pk,
                append_only=self._derive_append_only(ex))
        return ex, pk, deps, len(names)

    def _plan_over_window(self, ex: Executor, binder: Binder, bound):
        """Insert an OverWindowExecutor (optimizer/plan_node/
        stream_over_window.rs analog): output = input + one column per
        window call; ('win', j) projection items map to those columns.
        State pk = partition | order | input pk (general.rs:59)."""
        from risingwave_tpu.stream.executors.over_window import (
            OverWindowExecutor,
        )
        if not ex.pk_indices:
            ex = RowIdGenExecutor(ex)
        n_in = len(ex.schema)
        pk = [i for i in ex.pk_indices]
        order = list(binder.window_order)
        partition = list(binder.window_partition)
        # state pk = partition | order | input-pk tie-break suffix
        # (pk columns that double as partition/order keys drop out of
        # the suffix — rows are then unique by their order key alone);
        # the executor's OUTPUT identity stays the FULL input pk
        suffix = [i for i in pk if i not in partition
                  and i not in [o for o, _ in order]]
        state = StateTable(self.catalog.next_id(), ex.schema,
                           partition + [i for i, _d in order] + suffix,
                           self.store, dist_key_indices=partition)
        win = OverWindowExecutor(ex, partition, order,
                                 binder.window_calls, state,
                                 input_pk=pk,
                                 actor_id=self._actor_id)
        out = [InputRef(n_in + b[1],
                        win.schema[n_in + b[1]].data_type)
               if isinstance(b, tuple) and b[0] == "win" else b
               for b in bound]
        return win, out

    def _plan_agg(self, ex: Executor, scope: Scope, sel: ast.Select,
                  binder: Binder, projections) -> Tuple[Executor, List, object]:
        """Insert pre-agg projection + HashAggExecutor; returns
        (agg executor, output exprs over the agg row, HAVING predicate
        over the agg row or None). SELECT items and HAVING bind through
        PostAggBinder, so expressions OVER aggregates (sum(x)+1,
        avg(q.final), HAVING count(*) > 5) work — the reference resolves
        these in LogicalAgg planning (logical_agg.rs)."""
        from risingwave_tpu.frontend.binder import PostAggBinder
        group_bound = [Binder(scope).bind(g) for g in sel.group_by]
        if not group_bound:
            # global aggregation: a synthetic constant group key routes
            # it through the SAME hash-agg machinery — one real group,
            # full retraction support (minput MIN/MAX, host aggs); the
            # hidden-group-key logic keys the single-row MV by it.
            # (simple_agg.rs covers the append-only fast path; the
            # planner prefers the general one.)
            from risingwave_tpu.expr.expr import Literal
            group_bound = [Literal(0, DataType.INT32)]
        self._agg_group_arity = len(group_bound)
        group_reprs = [repr(g) for g in group_bound]
        pab = PostAggBinder(binder, group_reprs)
        bound = [pab.bind(e) for e, _a in projections]
        # for the span of the projection these expressions run in
        self._agg_span_args = {"avg_division": pab.avg_division} \
            if pab.avg_division else {}
        having_pred = None
        if sel.having is not None:
            having_pred = pab.bind(sel.having)
            if having_pred.return_type != DataType.BOOLEAN:
                raise PlanError("HAVING must be a boolean expression")
        # pre-agg projection: group exprs, then each agg input column
        pre_exprs: List[Expression] = list(group_bound)
        pre_names = [f"_g{i}" for i in range(len(group_bound))]
        remapped: List[AggCall] = []
        in_expr_idx: Dict[str, int] = {}

        def pre_col(expr: Expression) -> int:
            # identical expressions share one projected column —
            # count(DISTINCT x) + sum(DISTINCT x) then share their
            # dedup table and per-chunk gating in the executor, and so
            # do the calls that differ in their FILTER only
            k = repr(expr)
            if k not in in_expr_idx:
                pre_exprs.append(expr)
                pre_names.append(f"_a{len(pre_exprs) - 1}")
                in_expr_idx[k] = len(pre_exprs) - 1
            return in_expr_idx[k]

        for call, in_expr, flt in zip(binder.agg_calls, binder.agg_inputs,
                                      binder.agg_filters):
            if in_expr is None:            # count(*)
                remapped.append(call)
                continue
            remapped.append(AggCall(
                call.kind, pre_col(in_expr), distinct=call.distinct,
                delimiter=call.delimiter,
                filter_idx=None if flt is None else pre_col(flt)))
        # plain-column group keys pass their watermarks through the
        # pre-agg projection (EOWC and agg state cleaning need them)
        pre_derivs = {e.index: j for j, e in enumerate(group_bound)
                      if isinstance(e, InputRef)}
        pre = ProjectExecutor(ex, pre_exprs, pre_names,
                              watermark_derivations=pre_derivs)
        # group positions that provably carry a watermark (the pre-agg
        # projection puts group i at column i): EOWC validation, and
        # the column that leads the state tables' keys. Upstream keys
        # an aggregate's state by the watermark column first whatever
        # order GROUP BY was written in (generic/agg.rs window_col_idx):
        # a watermark's range delete covers the key's first column only
        g = len(group_bound)
        wm_in = watermark_columns(pre)
        self._agg_wm_positions = [pos for pos in range(g)
                                  if pos in wm_in]
        key_lead, plan_note = 0, None
        if self._agg_wm_positions:
            cleanable = [pos for pos in self._agg_wm_positions
                         if cleanable_type(pre.schema[pos].data_type)]
            if cleanable:
                key_lead = cleanable[0]
            else:
                plan_note = (
                    "state not cleaned: the watermark is on "
                    + ", ".join(
                        f"group key {pos} "
                        f"({pre.schema[pos].data_type.name})"
                        for pos in self._agg_wm_positions)
                    + ", no type a watermark can order; every group "
                    "is kept")
        calls = remapped
        # append-only-ness decides the agg mode (VERDICT r3 #7: the
        # old hardcoded append_only=True was silently wrong over
        # retracting upstreams, e.g. GROUP BY over an outer join)
        append_only = self._derive_append_only(ex)
        from risingwave_tpu.ops.hash_agg import AggKind as _AK
        if (self.dist_parallelism > 1 and self.mesh is None
                and all(c.kind in (_AK.COUNT, _AK.SUM, _AK.MIN,
                                   _AK.MAX) and not c.distinct
                        for c in calls)):
            return self._plan_two_phase_agg(
                pre, g, calls, append_only, bound, having_pred,
                key_lead)
        sch, agg_pk = agg_state_schema(pre.schema, list(range(g)), calls,
                                       key_lead)
        table = StateTable(self.catalog.next_id(), sch, agg_pk,
                           self.store,
                           dist_key_indices=list(range(len(agg_pk))))
        from risingwave_tpu.stream.executors.hash_agg import (
            agg_aux_tables,
        )
        distinct_tables, minput_tables = agg_aux_tables(
            pre.schema, list(range(g)), calls, append_only, self.store,
            dedup_table_id=lambda _col: self.catalog.next_id(),
            minput_table_id=lambda _j: self.catalog.next_id(),
            key_lead=key_lead)
        kernel = None
        if self.mesh is not None:
            # parallel plan: the hash exchange that the reference's
            # fragmenter inserts before a parallel agg
            # (stream_fragmenter/mod.rs:199, dispatch.rs:582) is the
            # sharded kernel's in-program all_to_all. Retracting
            # upstreams shard too (signed scatters + sharded acc
            # patching for minput MIN/MAX recompute); host aggs keep
            # their executor-side multiset path under any kernel.
            # NOTE: this block allocates no catalog ids, so its
            # position does not disturb the id-base replay contract.
            from risingwave_tpu.parallel.agg import ShardedAggKernel
            from risingwave_tpu.stream.executors.keys import LANES_PER_KEY
            kernel = ShardedAggKernel(
                self.mesh, key_width=LANES_PER_KEY * g,
                specs=[c.spec(pre.schema) for c in calls])
        agg = HashAggExecutor(self._coalesced(pre), list(range(g)),
                              calls, table,
                              append_only=append_only, kernel=kernel,
                              minput_tables=minput_tables,
                              distinct_tables=distinct_tables,
                              # cold tier: single-chip lazy kernel only
                              # (the sharded kernel has no targeted
                              # evict path)
                              tier_cap=self.state_tier_cap
                              if kernel is None else None)
        # shown by EXPLAIN beside the aggregate, once, at plan time
        agg.plan_note = plan_note
        join = ex
        while isinstance(join, FilterExecutor):
            join = join.input
        if isinstance(join, HashJoinExecutor):
            # a join whose output reaches this aggregate through the
            # WHERE's residual filters and the projection above only:
            # both ends book the hand-off (trace_ctx.join_to_agg_handoff)
            join.feeds_agg = agg.fed_by_join = True
        from risingwave_tpu.stream.executors.hop_window import (
            HopWindowExecutor,
        )
        hop = pre
        while isinstance(hop, (FilterExecutor, ProjectExecutor)):
            hop = hop.input
        if isinstance(hop, HopWindowExecutor):
            # an aggregate over a HOP: the expansion's rows in and out
            # are filed under this aggregate, fused into it or not
            hop.books_table = f"t{table.table_id}"
        # bound items are already typed refs over the agg output row
        return agg, bound, having_pred

    def _plan_two_phase_agg(self, pre: Executor, g: int,
                            calls: List[AggCall], append_only: bool,
                            bound, having_pred, key_lead: int = 0):
        """Two-phase aggregation for distributed plans
        (logical_agg.rs two-phase split): a LOCAL partial agg stays
        colocated with its input fragment (the fragmenter cuts at the
        GLOBAL agg's input, so the hash exchange carries per-group
        partials instead of raw rows), and the global agg merges —
        COUNT partials by SUM, SUM/MIN/MAX by themselves. The global
        side is never append-only (local updates retract), so merged
        MIN/MAX get materialized-input tables automatically."""
        from risingwave_tpu.ops.hash_agg import AggKind
        from risingwave_tpu.stream.executor import ExecutorInfo
        from risingwave_tpu.stream.executors.hash_agg import (
            agg_aux_tables,
        )

        group = list(range(g))
        lsch, lpk = agg_state_schema(pre.schema, group, calls, key_lead)
        ltable = StateTable(self.catalog.next_id(), lsch, lpk,
                            self.store,
                            dist_key_indices=list(range(len(lpk))))
        ldistinct, lminput = agg_aux_tables(
            pre.schema, group, calls, append_only, self.store,
            dedup_table_id=lambda _c: self.catalog.next_id(),
            minput_table_id=lambda _j: self.catalog.next_id(),
            key_lead=key_lead)
        local = HashAggExecutor(self._coalesced(pre), group, calls,
                                ltable,
                                append_only=append_only,
                                distinct_tables=ldistinct,
                                minput_tables=lminput,
                                tier_cap=self.state_tier_cap)
        local._info = ExecutorInfo(local.schema,
                                   list(local.pk_indices),
                                   "HashAggExecutor(phase=local)")
        # the fragmenter colocates the local phase with its input
        # (no exchange cut) — that IS the point of the split
        local.two_phase_role = "local"
        merge = [AggCall(AggKind.SUM if c.kind == AggKind.COUNT
                         else c.kind, g + j)
                 for j, c in enumerate(calls)]
        gsch, gpk = agg_state_schema(local.schema, group, merge,
                                     key_lead)
        gtable = StateTable(self.catalog.next_id(), gsch, gpk,
                            self.store,
                            dist_key_indices=list(range(len(gpk))))
        gdistinct, gminput = agg_aux_tables(
            local.schema, group, merge, False, self.store,
            dedup_table_id=lambda _c: self.catalog.next_id(),
            minput_table_id=lambda _j: self.catalog.next_id(),
            key_lead=key_lead)
        agg = HashAggExecutor(local, group, merge, gtable,
                              append_only=False,
                              distinct_tables=gdistinct,
                              minput_tables=gminput,
                              tier_cap=self.state_tier_cap)
        agg._info = ExecutorInfo(agg.schema, list(agg.pk_indices),
                                 "HashAggExecutor(phase=global)")
        return agg, bound, having_pred


def _expand_star(projections, scope: Scope):
    out = []
    for e, a in projections:
        if isinstance(e, ast.ColRef) and e.name == "*":
            # `*`: every column in scope; `t.*`: those of FROM item t
            cols = [i for i in range(len(scope.schema))
                    if e.table is None or scope.qualifiers[i] == e.table]
            if not cols:
                raise PlanError(f"{e.table}.*: no FROM item {e.table!r}")
            for i in cols:
                out.append((ast.ColRef(scope.schema[i].name,
                                       scope.qualifiers[i]), None))
        else:
            out.append((e, a))
    return out


def _flatten_and(e: ast.Expr) -> List[ast.Expr]:
    """WHERE → list of AND conjuncts (pushdown granularity)."""
    if isinstance(e, ast.Bin) and e.op == "and":
        return _flatten_and(e.left) + _flatten_and(e.right)
    return [e]




def watermark_columns(ex) -> set:
    """Output columns of a planned (not yet fused) chain that provably
    carry a watermark at run time: what each executor does with the
    `Watermark` messages it gets, followed down to the sources'
    WATERMARK FOR clauses. An executor not named here forwards none
    as far as the planner knows."""
    from risingwave_tpu.stream.coalesce import CoalesceExecutor
    from risingwave_tpu.stream.executors.hop_window import (
        HopWindowExecutor,
    )
    from risingwave_tpu.stream.executors.watermark_filter import (
        WatermarkFilterExecutor,
    )
    from risingwave_tpu.stream.message import derivation_images
    if isinstance(ex, WatermarkFilterExecutor):
        return watermark_columns(ex.input) | {ex.time_col}
    if isinstance(ex, (FilterExecutor, CoalesceExecutor)):
        return watermark_columns(ex.input)
    if isinstance(ex, ProjectExecutor):
        return {out for col in watermark_columns(ex.input)
                for out, _fn in derivation_images(
                    ex.watermark_derivations, col)}
    if isinstance(ex, HopWindowExecutor):
        return {len(ex.input.schema)} \
            if ex.time_col in watermark_columns(ex.input) else set()
    if isinstance(ex, HashAggExecutor):
        below = watermark_columns(ex.input)
        return {pos for pos, col in enumerate(ex.group_indices)
                if col in below}
    if isinstance(ex, HashJoinExecutor):
        lw = watermark_columns(ex.left_in)
        rw = watermark_columns(ex.right_in)
        out = set()
        for lk, rk in zip(ex.sides[0].key_indices,
                          ex.sides[1].key_indices):
            if lk in lw and rk in rw:
                subj = ex.join_type.subject
                out |= {lk, ex.n_left + rk} if subj is None \
                    else {(lk, rk)[subj]}
        return out
    return set()


def explain_tree(ex, indent: int = 0) -> List[str]:
    """Executor chain → indented plan text (planner_test snapshot
    style; the EXPLAIN statement surfaces it). Walks the same
    `executor_children` set install_monitoring wraps."""
    from risingwave_tpu.stream.executor import executor_children
    label = getattr(ex, "identity", None) or type(ex).__name__
    note = getattr(ex, "plan_note", None)
    out = [("  " * indent) + label + (f"  -- {note}" if note else "")]
    for _attr, _i, child in executor_children(ex):
        out += explain_tree(child, indent + 1)
    return out


def _equi_keys(on: ast.Expr, lscope: Scope, rscope: Scope
               ) -> Tuple[List[int], List[int], List[ast.Expr]]:
    """ON conjunction → (left key idxs, right key idxs, condition).
    A conjunct `column = column` with one column on each side is a
    hash key; every other conjunct is the join's condition, in the
    order it was written. No equality across the sides is an error:
    no cross product is planned."""
    lkeys: List[int] = []
    rkeys: List[int] = []
    condition: List[ast.Expr] = []
    for c in _flatten_and(on):
        sides = []
        if isinstance(c, ast.Bin) and c.op == "=" \
                and isinstance(c.left, ast.ColRef) \
                and isinstance(c.right, ast.ColRef):
            for col in (c.left, c.right):
                try:
                    sides.append(
                        ("l", lscope.find(col.name, col.table)[0]))
                except BindError:
                    sides.append(
                        ("r", rscope.find(col.name, col.table)[0]))
        if {s[0] for s in sides} != {"l", "r"}:
            condition.append(c)
            continue
        for tag, idx in sides:
            (lkeys if tag == "l" else rkeys).append(idx)
    if not lkeys:
        raise PlanError("JOIN ON needs a column = column between the "
                        "two sides (the hash join's key): a cross "
                        "product is not planned")
    return lkeys, rkeys, condition


def _join_keys(jn: ast.Join, conjuncts: List[ast.Expr], lscope: Scope,
               rscope: Scope
               ) -> Tuple[List[int], List[int], List[ast.Expr]]:
    """The hash keys of one join of the left-deep chain, and the
    join's own condition. `JOIN … ON` gives both itself: its
    `column = column` conjuncts across the two sides are the keys, its
    other conjuncts the condition, which the caller plans as filters
    directly above this join (the same relation for an inner join,
    and only for an inner join: `_inner_condition`). A comma-separated
    FROM item (`on` is None) takes every WHERE conjunct
    `column = column` with one column on each side and removes it
    from `conjuncts`; what stays there is the residual, filtered above
    the chain like any other WHERE, and the condition is empty. A
    conjunct that names a later item of the list resolves on neither
    side yet and is left for that item's join."""
    if jn.on is not None:
        return _equi_keys(jn.on, lscope, rscope)
    both, n_left = lscope.concat(rscope), len(lscope.schema)
    lkeys: List[int] = []
    rkeys: List[int] = []
    for c in list(conjuncts):
        if not (isinstance(c, ast.Bin) and c.op == "="
                and isinstance(c.left, ast.ColRef)
                and isinstance(c.right, ast.ColRef)):
            continue
        try:
            idx = sorted(both.find(col.name, col.table)[0]
                         for col in (c.left, c.right))
        except BindError:
            continue
        if idx[0] < n_left <= idx[1]:
            lkeys.append(idx[0])
            rkeys.append(idx[1] - n_left)
            conjuncts.remove(c)
    if not lkeys:
        raise BindError(
            "a comma-separated FROM list needs a WHERE equality "
            "column = column between each item and the items before "
            "it (the hash join's key): none found for "
            f"{_item_name(jn.item)!r}; a cross product is not planned")
    return lkeys, rkeys, []


def _inner_condition(jn: ast.Join, condition: List[ast.Expr]
                     ) -> List[ast.Expr]:
    """The ON conjuncts that are not hash keys, for a join whose
    condition is a filter of its matched pairs. Only an inner join's
    is (the pushdown rule hands it to the join): an outer join keeps
    the rows its ON rejects, NULL-padded, so its condition has to run
    before the degree bookkeeping and is refused until the join does
    that."""
    temporal = getattr(jn, "temporal", False)
    if condition and (temporal or jn.kind != "inner"):
        what = "a temporal join" if temporal \
            else f"a {jn.kind.upper()} OUTER JOIN"
        raise BindError(
            f"the ON of {what} takes column = column conjuncts only: "
            "any other condition there decides which rows are "
            "NULL-padded, which a filter of the matched pairs cannot "
            "do, and only an inner join evaluates a condition yet. "
            "Move it to the WHERE only if that is the relation you "
            "mean")
    return condition


def _item_name(item) -> str:
    return getattr(item, "alias", None) or getattr(item, "name", None) \
        or type(item).__name__


def _system_catalog_rows(name: str, catalog: Catalog, profiler=None):
    """rw_catalog-style system tables (src/frontend/src/catalog/
    system_catalog/ analog, bare-named): introspection over the live
    catalog AND the process metrics registry, served as batch values.
    Returns (schema, rows) or None. `profiler` is the session barrier
    loop's EpochProfiler (rw_barrier_latency's source); sessions that
    don't thread one through still serve the metric-backed tables."""
    from risingwave_tpu.utils.metrics import STREAMING

    n = name.lower()
    if n == "rw_actor_metrics":
        # live actors (stream_actor_count series — torn-down actors'
        # series are removed) joined with the per-executor counters
        sch = Schema([Field("actor_id", DataType.INT64),
                      Field("fragment", DataType.VARCHAR),
                      Field("executor", DataType.VARCHAR),
                      Field("node", DataType.INT64),
                      Field("row_count", DataType.INT64),
                      Field("chunk_count", DataType.INT64),
                      Field("busy_seconds", DataType.FLOAT64),
                      Field("device_dispatch_count", DataType.INT64)])
        live = {labels["actor"]: labels.get("fragment", "")
                for labels, _v in STREAMING.actor_count.series()
                if "actor" in labels}
        per_exec: Dict[tuple, List[float]] = {}
        for metric, slot in ((STREAMING.executor_rows, 0),
                             (STREAMING.executor_chunks, 1),
                             (STREAMING.executor_busy, 2)):
            for labels, v in metric.series():
                a = labels.get("actor")
                if a not in live:
                    continue
                key = (a, labels.get("executor", ""),
                       labels.get("node", ""))
                per_exec.setdefault(key, [0.0, 0.0, 0.0])[slot] += v
        # keyed executors label device dispatches by identity alone
        # (identity embeds the actor, e.g. "HashAggExecutor(actor=N)")
        # — join on the executor name the monitor also labels with
        dispatches = {labels.get("executor", ""): v for labels, v in
                      STREAMING.device_dispatch.series()}
        rows = []
        seen_actors = set()
        for (a, ex_name, node), (nrows, nchunks, busy) in \
                per_exec.items():
            seen_actors.add(a)
            rows.append((int(a), live[a], ex_name,
                         int(node) if node else 0,
                         int(nrows), int(nchunks), busy,
                         int(dispatches.get(ex_name, 0))))
        for a, frag in live.items():
            if a not in seen_actors:    # deployed but unmonitored
                rows.append((int(a), frag, "", 0, 0, 0, 0.0, 0))
        return sch, sorted(rows)
    if n == "rw_fragment_backpressure":
        sch = Schema([Field("edge", DataType.VARCHAR),
                      Field("send_count", DataType.INT64),
                      Field("backpressure_seconds", DataType.FLOAT64),
                      Field("queue_depth", DataType.INT64)])
        edges: Dict[str, List[float]] = {}
        for metric, slot in ((STREAMING.exchange_send_count, 0),
                             (STREAMING.exchange_backpressure, 1),
                             (STREAMING.exchange_queue_depth, 2)):
            for labels, v in metric.series():
                e = labels.get("edge")
                if e is not None:
                    edges.setdefault(e, [0.0, 0.0, 0.0])[slot] += v
        rows = [(e, int(s[0]), s[1], int(s[2]))
                for e, s in edges.items()]
        return sch, sorted(rows)
    if n == "rw_barrier_latency":
        sch = Schema([Field("epoch", DataType.INT64),
                      Field("kind", DataType.VARCHAR),
                      Field("inject_to_collect_s", DataType.FLOAT64),
                      Field("collect_to_commit_s", DataType.FLOAT64),
                      Field("total_s", DataType.FLOAT64),
                      Field("in_flight", DataType.INT64),
                      Field("slowest_actor", DataType.INT64),
                      Field("slowest_actor_lag_s", DataType.FLOAT64),
                      Field("upload_s", DataType.FLOAT64),
                      Field("queue_depth", DataType.INT64),
                      Field("domain", DataType.VARCHAR)])
        rows = list(profiler.rows()) if profiler is not None else []
        return sch, rows
    if n == "rw_state_tier":
        # state-tiering residency (state/tier.py): one row per
        # registered executor cache; cap = -1 means uncapped
        # (pressure-only governance)
        from risingwave_tpu.state.tier import GLOBAL as _TIER
        sch = Schema([Field("executor", DataType.VARCHAR),
                      Field("cap", DataType.INT64),
                      Field("resident_keys", DataType.INT64),
                      Field("evicted_total", DataType.INT64),
                      Field("reload_total", DataType.INT64),
                      Field("resident_bytes", DataType.INT64)])
        return sch, sorted(_TIER.stats_rows())
    if n == "rw_epoch_trace":
        # epoch-causal traces (utils/spans.py flight recorder +
        # retained slow-barrier store): one row per span, plus one
        # cat='diagnosis' row per retained trace carrying the
        # straggler line. Joins rw_barrier_latency on epoch.
        from risingwave_tpu.utils.spans import EPOCH_TRACER
        sch = Schema([Field("epoch", DataType.INT64),
                      Field("span_id", DataType.INT64),
                      Field("parent_id", DataType.INT64),
                      Field("name", DataType.VARCHAR),
                      Field("cat", DataType.VARCHAR),
                      Field("worker", DataType.VARCHAR),
                      Field("actor", DataType.INT64),
                      Field("start_s", DataType.FLOAT64),
                      Field("dur_s", DataType.FLOAT64),
                      Field("retained", DataType.INT64),
                      Field("detail", DataType.VARCHAR)])
        return sch, EPOCH_TRACER.rows()
    if n == "rw_metrics_history":
        # bounded per-barrier time series (utils/metrics.HISTORY, fed
        # at every ledger seal): counter deltas, gauge values and the
        # epoch phase breakdown per barrier — the telemetry history
        # the elastic-serving control loop (ROADMAP item 3) reads.
        # Long format: one row per (barrier, series).
        from risingwave_tpu.utils.metrics import HISTORY
        sch = Schema([Field("seq", DataType.INT64),
                      Field("epoch", DataType.INT64),
                      Field("ts", DataType.FLOAT64),
                      Field("interval_s", DataType.FLOAT64),
                      Field("name", DataType.VARCHAR),
                      Field("value", DataType.FLOAT64),
                      Field("domain", DataType.VARCHAR)])
        return sch, HISTORY.rows()
    if n == "rw_mv_freshness":
        # per-MV event-time freshness (stream/freshness.py): how far
        # the materialized result lags the data's own timestamps, per
        # barrier, with percentiles over the retained sample ring —
        # the consumer-experience half of the observability stack
        from risingwave_tpu.stream.freshness import FRESHNESS
        sch = Schema([Field("mv", DataType.VARCHAR),
                      Field("domain", DataType.VARCHAR),
                      Field("samples", DataType.INT64),
                      Field("epoch", DataType.INT64),
                      Field("lag_s", DataType.FLOAT64),
                      Field("wall_lag_s", DataType.FLOAT64),
                      Field("lag_p50_s", DataType.FLOAT64),
                      Field("lag_p99_s", DataType.FLOAT64),
                      Field("wall_lag_p99_s", DataType.FLOAT64)])
        return sch, FRESHNESS.rows()
    if n == "rw_bottlenecks":
        # bottleneck walker (stream/bottleneck.py): the ranked
        # per-domain culprit table — operator, busy share, downstream
        # backpressure evidence, contiguous-barrier streak and a
        # one-line diagnosis (the autoscaler's target signal)
        from risingwave_tpu.stream.bottleneck import BOTTLENECKS
        sch = Schema([Field("domain", DataType.VARCHAR),
                      Field("operator", DataType.VARCHAR),
                      Field("fragment", DataType.VARCHAR),
                      Field("actor_id", DataType.INT64),
                      Field("node", DataType.INT64),
                      Field("busy_ratio", DataType.FLOAT64),
                      Field("downstream_backpressure",
                            DataType.FLOAT64),
                      Field("streak", DataType.INT64),
                      Field("sustained", DataType.INT64),
                      Field("epoch", DataType.INT64),
                      Field("diagnosis", DataType.VARCHAR)])
        return sch, BOTTLENECKS.rows()
    if n == "rw_actor_utilization":
        # utilization tricolor (stream/monitor.py): busy /
        # backpressure / idle shares of the last barrier interval per
        # (actor, executor) — sorted busiest first, the `ctl top` feed
        from risingwave_tpu.stream.monitor import UTILIZATION
        sch = Schema([Field("actor_id", DataType.INT64),
                      Field("fragment", DataType.VARCHAR),
                      Field("node", DataType.INT64),
                      Field("executor", DataType.VARCHAR),
                      Field("epoch", DataType.INT64),
                      Field("interval_s", DataType.FLOAT64),
                      Field("busy_ratio", DataType.FLOAT64),
                      Field("backpressure_ratio", DataType.FLOAT64),
                      Field("idle_ratio", DataType.FLOAT64)])
        return sch, UTILIZATION.rows()
    if n == "rw_kernel_costs":
        # compiled-program cost analysis (utils/jaxtools.KERNELS):
        # flops / bytes-accessed from each kernel's lowered program —
        # the yardstick the ledger's device_compute measurements are
        # sanity-checked against
        from risingwave_tpu.utils.jaxtools import kernel_cost_rows
        sch = Schema([Field("kernel", DataType.VARCHAR),
                      Field("flops", DataType.FLOAT64),
                      Field("bytes_accessed", DataType.FLOAT64)])
        return sch, kernel_cost_rows()
    if n == "rw_recovery":
        # supervised-recovery event log (meta/supervisor.py): one row
        # per recovery with its classified cause, graduated action,
        # touched worker slots, recovered-to epoch and MTTR sample.
        # Joins rw_epoch_trace on epoch for the recovery.* span chain.
        from risingwave_tpu.meta.supervisor import recovery_rows
        sch = Schema([Field("seq", DataType.INT64),
                      Field("cause", DataType.VARCHAR),
                      Field("action", DataType.VARCHAR),
                      Field("workers", DataType.VARCHAR),
                      Field("epoch", DataType.INT64),
                      Field("duration_s", DataType.FLOAT64),
                      Field("ok", DataType.INT64),
                      Field("attempt", DataType.INT64),
                      Field("detail", DataType.VARCHAR)])
        return sch, recovery_rows()
    if n == "rw_compaction":
        # dedicated-compaction task log (meta/compaction.py): one row
        # per task with its picker, lifecycle state (pending/running/
        # applied/aborted/requeued/failed), frozen inputs, landed
        # outputs and merge I/O — `ctl compaction` reads this
        from risingwave_tpu.meta.compaction import compaction_rows
        sch = Schema([Field("task_id", DataType.INT64),
                      Field("namespace", DataType.VARCHAR),
                      Field("picker", DataType.VARCHAR),
                      Field("state", DataType.VARCHAR),
                      Field("inputs", DataType.VARCHAR),
                      Field("outputs", DataType.VARCHAR),
                      Field("bytes_read", DataType.INT64),
                      Field("bytes_written", DataType.INT64),
                      Field("attempts", DataType.INT64),
                      Field("duration_s", DataType.FLOAT64),
                      Field("detail", DataType.VARCHAR)])
        return sch, compaction_rows()
    if n == "rw_autoscaler":
        # elastic-control-loop decision ledger (meta/autoscaler.py):
        # one row per completed scaling decision — direction, the
        # signal that triggered it, and the guarded-rescale outcome
        # (applied / rolled_back / rollback_failed / storm_disabled).
        # Joins rw_recovery on wall time for the rollback story.
        from risingwave_tpu.meta.autoscaler import autoscaler_rows
        sch = Schema([Field("seq", DataType.INT64),
                      Field("mv", DataType.VARCHAR),
                      Field("fragment", DataType.INT64),
                      Field("operator", DataType.VARCHAR),
                      Field("direction", DataType.VARCHAR),
                      Field("from_parallelism", DataType.INT64),
                      Field("to_parallelism", DataType.INT64),
                      Field("outcome", DataType.VARCHAR),
                      Field("reason", DataType.VARCHAR),
                      Field("epoch", DataType.INT64),
                      Field("duration_s", DataType.FLOAT64),
                      Field("detail", DataType.VARCHAR)])
        return sch, autoscaler_rows()
    if n == "rw_mv_costs":
        # per-MV resource ledger (stream/costs.py, ISSUE 16): the
        # barrier-interval device/transfer split by owning MV, joined
        # at read time with state bytes (topology), compile-cache
        # attribution and recovery/rescale charge-back — `ctl cost`
        # reads this
        from risingwave_tpu.stream.costs import COSTS
        sch = Schema([Field("mv", DataType.VARCHAR),
                      Field("domain", DataType.VARCHAR),
                      Field("device_seconds", DataType.FLOAT64),
                      Field("h2d_bytes", DataType.INT64),
                      Field("d2h_bytes", DataType.INT64),
                      Field("state_bytes", DataType.INT64),
                      Field("compile_hits", DataType.INT64),
                      Field("compile_misses", DataType.INT64),
                      Field("shared_compile_hits", DataType.INT64),
                      Field("rescale_s", DataType.FLOAT64),
                      Field("recovery_s", DataType.FLOAT64)])
        return sch, COSTS.rows()
    if n == "rw_hot_keys":
        # heavy-hitter telemetry (stream/hotkeys.py): sustained hot
        # keys per hash-join/hash-agg input with share estimates —
        # max_share_err bounds the space-saving overcount, so
        # share - max_share_err is a guaranteed lower bound
        from risingwave_tpu.stream.hotkeys import HOTKEYS
        sch = Schema([Field("mv", DataType.VARCHAR),
                      Field("executor", DataType.VARCHAR),
                      Field("rank", DataType.INT64),
                      Field("key", DataType.VARCHAR),
                      Field("est_count", DataType.INT64),
                      Field("share", DataType.FLOAT64),
                      Field("max_share_err", DataType.FLOAT64)])
        return sch, HOTKEYS.rows()
    if n == "rw_state_topology":
        # per-(table, vnode) state footprint (state/topology.py):
        # maintained incrementally at flush — the rescale planner's
        # move-cost input and `ctl memory`'s breakdown
        from risingwave_tpu.state.topology import TOPOLOGY
        sch = Schema([Field("table_id", DataType.INT64),
                      Field("mv", DataType.VARCHAR),
                      Field("vnode", DataType.INT64),
                      Field("rows", DataType.INT64),
                      Field("bytes", DataType.INT64)])
        return sch, TOPOLOGY.rows()
    if n == "rw_watermarks":
        # per state table that a watermark cleans: the value it was
        # last cleaned to and the rows it keeps (state/topology.py;
        # table_id as in rw_state_topology)
        from risingwave_tpu.state.topology import TOPOLOGY
        sch = Schema([Field("table_id", DataType.INT64),
                      Field("mv", DataType.VARCHAR),
                      Field("watermark", DataType.TIMESTAMP),
                      Field("rows", DataType.INT64)])
        return sch, TOPOLOGY.watermark_rows()
    if n == "rw_mesh_tables":
        # per-shard occupancy and capacity of the sharded kernels'
        # device tables (parallel/exchange.py): beside
        # rw_state_topology, whose table_id it shares — a mesh plan's
        # growth rungs are per shard, and skew shows here first
        from risingwave_tpu.parallel.exchange import mesh_table_rows
        sch = Schema([Field("table_id", DataType.INT64),
                      Field("mv", DataType.VARCHAR),
                      Field("kernel", DataType.VARCHAR),
                      Field("part", DataType.VARCHAR),
                      Field("shard", DataType.INT64),
                      Field("occupied", DataType.INT64),
                      Field("capacity", DataType.INT64)])
        return sch, mesh_table_rows()
    if n == "rw_plan_rewrites":
        # plan-rewrite firing log (frontend/opt engine): one row per
        # (job, rule) application, FALLBACK rows record checker trips
        from risingwave_tpu.frontend.opt import rewrite_history_rows
        sch = Schema([Field("seq", DataType.INT64),
                      Field("job", DataType.VARCHAR),
                      Field("rule", DataType.VARCHAR),
                      Field("fired", DataType.INT64),
                      Field("detail", DataType.VARCHAR)])
        return sch, sorted(rewrite_history_rows())
    if n in ("rw_materialized_views", "rw_tables"):
        want_tables = n == "rw_tables"
        sch = Schema([Field("name", DataType.VARCHAR),
                      Field("table_id", DataType.INT64),
                      Field("actor_id", DataType.INT64),
                      Field("definition", DataType.VARCHAR)])
        rows = [(m.name, m.table_id, m.actor_id, m.definition or "")
                for m in catalog.mvs.values()
                if m.is_table == want_tables]
        return sch, sorted(rows)
    if n == "rw_sources":
        sch = Schema([Field("name", DataType.VARCHAR),
                      Field("connector", DataType.VARCHAR),
                      Field("columns", DataType.INT64)])
        rows = [(s.name, s.options.get("connector", ""),
                 len(s.schema))
                for s in catalog.sources.values()]
        return sch, sorted(rows)
    if n == "rw_sinks":
        # exactly-once sinks report their commit frontier straight off
        # the object-store listing (meta/sink_coordinator.sink_stats)
        # — usable from any process without an RPC to the coordinator;
        # legacy writers show NULL-ish zeros
        sch = Schema([Field("name", DataType.VARCHAR),
                      Field("connector", DataType.VARCHAR),
                      Field("mode", DataType.VARCHAR),
                      Field("committed_epoch", DataType.INT64),
                      Field("staged_epochs", DataType.INT64),
                      Field("staged_bytes", DataType.INT64),
                      Field("writer_lag", DataType.INT64)])
        rows = []
        for s in catalog.sinks.values():
            conn = s.options.get("connector", "")
            stats = {"committed_epoch": 0, "staged_epochs": 0,
                     "staged_bytes": 0, "writer_lag": 0}
            if conn == "epochlog":
                from risingwave_tpu.connectors.sink import (
                    make_sink_target,
                )
                from risingwave_tpu.meta.sink_coordinator import (
                    sink_stats,
                )
                try:
                    stats = sink_stats(
                        make_sink_target(s.options, s.mode or "append",
                                         []),
                        s.n_writers, name=s.name, mode=s.mode)
                except OSError:
                    pass             # path gone: keep the zero row
            rows.append((s.name, conn, s.mode,
                         stats["committed_epoch"],
                         stats["staged_epochs"], stats["staged_bytes"],
                         stats["writer_lag"]))
        return sch, sorted(rows)
    return None


# -- batch planning -------------------------------------------------------


def plan_batch(sel: ast.Select, catalog: Catalog, store, epoch: int,
               profiler=None):
    """SELECT over committed snapshots → batch executor tree.

    `profiler` (the session's EpochProfiler, optional) backs the
    rw_barrier_latency system table."""
    from risingwave_tpu.batch import (
        BatchFilter, BatchHashAgg, BatchHashJoin, BatchLimit,
        BatchOrderBy, BatchProject, BatchValues, RowSeqScan, StorageTable,
    )

    def scan(item) -> Tuple[object, Scope]:
        if isinstance(item, ast.TableFn):
            # table functions (src/expr/src/table_function/ parity:
            # generate_series); evaluated to rows at plan time — args
            # are constant expressions
            if item.name != "generate_series":
                raise PlanError(
                    f"unknown table function {item.name!r}")
            if len(item.args) not in (2, 3):
                raise PlanError(
                    "generate_series(start, stop [, step])")
            binder = Binder(Scope.of(Schema([]), None))
            vals = []
            for a in item.args:
                b = binder.bind(a)
                from risingwave_tpu.expr.expr import Literal, UnaryOp
                if isinstance(b, Literal):
                    vals.append(int(b.value))
                elif isinstance(b, UnaryOp) and b.op == "neg" and \
                        isinstance(b.child, Literal):
                    vals.append(-int(b.child.value))
                else:
                    raise PlanError(
                        "generate_series arguments must be integer "
                        "literals")
            start, stop = vals[0], vals[1]
            step = vals[2] if len(vals) == 3 else 1
            if step == 0:
                raise PlanError("generate_series step must be nonzero")
            rows = [(v,) for v in range(start, stop + (1 if step > 0
                                                       else -1), step)]
            # pg: the alias names BOTH the table and the single column
            col = item.alias or "generate_series"
            sch = Schema([Field(col, DataType.INT64)])
            return (BatchValues(sch, rows), Scope.of(sch, col))
        if isinstance(item, ast.Subquery):
            sub = plan_batch(item.select, catalog, store, epoch,
                             profiler)
            return sub, Scope.of(sub.schema, item.alias)
        if not isinstance(item, ast.TableRef):
            raise PlanError("batch FROM supports tables/MVs")
        try:
            obj = catalog.resolve(item.name)
        except Exception:
            # USER objects win over system catalogs (pg search-path
            # spirit); only an unresolved name falls through to rw_*
            sysrows = _system_catalog_rows(item.name, catalog,
                                           profiler)
            if sysrows is None:
                raise
            sch, rows = sysrows
            return (BatchValues(sch, rows),
                    Scope.of(sch, item.alias or item.name))
        if isinstance(obj, SourceCatalog):
            raise PlanError("cannot batch-scan a pure source; "
                            "create a materialized view over it")
        st = StorageTable(obj.table_id, obj.schema, obj.pk_indices, store)
        ex = RowSeqScan(st, epoch)
        vis = obj.visible_schema
        if len(vis) < len(obj.schema):
            # hidden trailing columns (_row_id, unprojected group keys)
            # must leave the EXECUTOR schema too, not just the binding
            # scope — a downstream join concatenates executor schemas,
            # and a width mismatch would shift every right-side index
            ex = BatchProject(ex, [InputRef(i, f.data_type)
                                   for i, f in enumerate(vis)])
        return ex, Scope.of(vis, item.alias or item.name)

    if sel.from_item is None:
        # SELECT <exprs>: evaluate over one synthetic row
        from risingwave_tpu.common.types import Schema as Sch
        binder = Binder(Scope.of(Sch([]), None))
        exprs = [binder.bind(e) for e, _ in sel.projections]
        from risingwave_tpu.common.chunk import DataChunk
        import numpy as np
        one = DataChunk.empty(Sch([]), capacity=8)
        one.visibility[0] = True
        cols = [e.eval(one) for e in exprs]
        row = tuple(
            None if (c.validity is not None and not c.validity[0])
            else (c.values[0].item() if hasattr(c.values[0], "item")
                  else c.values[0])
            for c in cols)
        names = [a or expr_name(e, f"col{i}")
                 for i, (e, a) in enumerate(sel.projections)]
        sch = Sch([Field(n, c.data_type) for n, c in zip(names, cols)])
        return BatchValues(sch, [row])

    ex, scope = scan(sel.from_item)
    conjuncts = _flatten_and(sel.where) if sel.where is not None else []
    for jn in sel.joins:
        rex, rscope = scan(jn.item)
        lkeys, rkeys, cond = _join_keys(jn, conjuncts, scope, rscope)
        ex = BatchHashJoin(ex, rex, lkeys, rkeys)
        scope = scope.concat(rscope)
        for c in _inner_condition(jn, cond):
            ex = BatchFilter(ex, Binder(scope).bind(c))
    for c in conjuncts:
        ex = BatchFilter(ex, Binder(scope).bind(c))
    projections = _expand_star(sel.projections, scope)
    from risingwave_tpu.frontend.binder import PostAggBinder, contains_agg
    binder = Binder(scope, allow_aggs=True)
    names = [a or expr_name(e, f"col{i}")
             for i, (e, a) in enumerate(projections)]
    has_agg = (bool(sel.group_by) or sel.having is not None
               or any(contains_agg(e) for e, _a in projections))
    if has_agg:
        group_bound = [Binder(scope).bind(g) for g in sel.group_by]
        group_reprs = [repr(g) for g in group_bound]
        pab = PostAggBinder(binder, group_reprs)
        bound = [pab.bind(e) for e, _a in projections]
        having_pred = None
        if sel.having is not None:
            having_pred = pab.bind(sel.having)
            if having_pred.return_type != DataType.BOOLEAN:
                raise PlanError("HAVING must be a boolean expression")
        pre_exprs = list(group_bound)
        remapped = []
        for call, in_expr, flt in zip(binder.agg_calls, binder.agg_inputs,
                                      binder.agg_filters):
            if in_expr is None:            # count(*)
                remapped.append(call)
                continue
            pre_exprs.append(in_expr)      # agg over any expression
            idx, filter_idx = len(pre_exprs) - 1, None
            if flt is not None:            # agg(DISTINCT x) FILTER
                pre_exprs.append(flt)
                filter_idx = len(pre_exprs) - 1
            remapped.append(AggCall(call.kind, idx,
                                    distinct=call.distinct,
                                    delimiter=call.delimiter,
                                    filter_idx=filter_idx))
        pre = BatchProject(ex, pre_exprs)
        g = len(group_bound)
        ex = BatchHashAgg(pre, list(range(g)), remapped)
        if having_pred is not None:
            ex = BatchFilter(ex, having_pred)
        ex = BatchProject(ex, bound, names)
        post_scope = Scope.of(ex.schema, None)
    else:
        bound = [binder.bind_projection(e) for e, _a in projections]
        ex = BatchProject(ex, bound, names)
        post_scope = Scope.of(ex.schema, None)
    if sel.order_by:
        cols = []
        for e, desc in sel.order_by:
            b = Binder(post_scope).bind(e)
            if not isinstance(b, InputRef):
                raise PlanError("ORDER BY must reference output columns")
            cols.append((b.index, desc))
        ex = BatchOrderBy(ex, cols)
    if sel.limit is not None or sel.offset is not None:
        ex = BatchLimit(ex, sel.limit if sel.limit is not None else 1 << 62,
                        sel.offset or 0)
    return ex
