"""MaterializeExecutor: sink a stream into its materialized-view table.

Reference parity: src/stream/src/executor/mview/materialize.rs:53 — apply
each StreamChunk to the MV's StateTable (pk-conflict handling per
ConflictBehavior), commit on barrier, forward messages downstream.

TPU notes: the MV table is the queryable result — batch `SELECT` reads the
committed snapshot (storage side of the same state store). Overwrite
conflict handling turns blind inserts into updates so the MV stays a
function of pk (materialize.rs `handle_conflict` analog).
"""

from __future__ import annotations

import enum
from typing import AsyncIterator

from risingwave_tpu.common.chunk import Op, StreamChunk
from risingwave_tpu.state.state_table import StateTable
from risingwave_tpu.stream.executor import Executor, ExecutorInfo
from risingwave_tpu.stream.message import is_barrier, is_chunk, Message
from risingwave_tpu.utils.ledger import staged


class ConflictBehavior(enum.Enum):
    NO_CHECK = "no_check"        # trust upstream ops (MV over keyed stream)
    OVERWRITE = "overwrite"      # last write wins on pk conflict
    IGNORE = "ignore"            # first write wins


class MaterializeExecutor(Executor):
    """Materialize a changelog into a StateTable (materialize.rs:53)."""

    def __init__(self, input_: Executor, table: StateTable,
                 conflict: ConflictBehavior = ConflictBehavior.NO_CHECK,
                 mv_name: str = ""):
        self.input = input_
        self.table = table
        self.conflict = conflict
        # freshness accounting identity (stream/freshness.py): the MV
        # name readers know; empty = an unnamed/test pipeline whose
        # barrier passages still sample under the table id
        self.mv_name = mv_name or f"table-{table.table_id}"
        info = ExecutorInfo(input_.schema, list(table.pk_indices),
                            "MaterializeExecutor")
        super().__init__(info)

    async def execute(self) -> AsyncIterator[Message]:
        from risingwave_tpu.stream import freshness as _fresh
        it = self.input.execute()
        first = await it.__anext__()
        assert is_barrier(first), "executor protocol: first message is the " \
            f"init barrier, got {first!r}"
        self.table.init_epoch(first.epoch)
        yield first
        async for msg in it:
            if is_chunk(msg):
                self._apply(msg)
                yield msg
            elif is_barrier(msg):
                self.table.commit(msg.epoch)
                # everything ingested before this barrier is now
                # applied (and commits with its collection): the
                # MV's visible event frontier advances to the
                # source frontiers recorded at the same barrier
                _fresh.FRESHNESS.note_visible(
                    self.mv_name, msg.epoch.curr.value)
                yield msg
            else:
                yield msg

    @staged("mv.write")
    def _apply(self, chunk: StreamChunk) -> None:
        if self.conflict == ConflictBehavior.NO_CHECK:
            # NO_CHECK trusts upstream ops by contract — all-insert
            # epochs stage past the memtable and land in the store as
            # one bulk ingest at the barrier (ISSUE 12 emit path)
            self.table.write_chunk(chunk, defer=True)
            return
        _idx, rows, ops = chunk.to_physical_records()
        for op, row in zip(ops.tolist(), rows):
            pk = self.table.pk_of(row)
            old = self.table.get_row(pk)
            if op in (int(Op.INSERT), int(Op.UPDATE_INSERT)):
                if old is None:
                    self.table.insert(row)
                elif self.conflict == ConflictBehavior.OVERWRITE:
                    self.table.update(old, row)
                # IGNORE: keep first write
            else:
                if old is not None:
                    self.table.delete(old)
