"""Stream messages: the protocol every executor speaks.

Reference parity: src/stream/src/executor/mod.rs:173 (``Message::{Chunk,
Barrier, Watermark}``), :223-246 (``Mutation``), :622 (``Barrier``);
proto/stream_plan.proto:85-122 (Barrier/Watermark wire shape);
BarrierKind: proto/stream_plan.proto:86-92.

TPU re-design notes: messages are host-side control objects — the device
only ever sees the arrays inside a ``StreamChunk``. A ``Barrier`` is the
global synchronization token; everything between two barriers is one
"micro-batch" that kernels may process as a single fused device step.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, List, Optional, Sequence, Union

import numpy as np

from risingwave_tpu.common.chunk import StreamChunk
from risingwave_tpu.common.epoch import EpochPair
from risingwave_tpu.common.types import DataType


class BarrierKind(enum.Enum):
    """proto/stream_plan.proto:86-92: not every barrier is a checkpoint."""

    INITIAL = "initial"        # first barrier after boot/recovery
    BARRIER = "barrier"        # flush memtables, no durable sync
    CHECKPOINT = "checkpoint"  # flush + sync: durable recovery point

    @property
    def is_checkpoint(self) -> bool:
        return self in (BarrierKind.INITIAL, BarrierKind.CHECKPOINT)


# ---------------------------------------------------------------------------
# Mutations: control-plane commands piggybacked on barriers
# (src/stream/src/executor/mod.rs:223 — Add/Update/Stop/Pause/Resume)


@dataclass(frozen=True)
class AddMutation:
    """New downstream actors added to dispatchers (job creation)."""

    # dispatcher updates keyed by upstream actor id: list of new outputs
    adds: Dict[int, list] = field(default_factory=dict)


@dataclass(frozen=True)
class UpdateMutation:
    """Scaling / reschedule: vnode bitmaps + dispatcher output swaps."""

    # actor_id -> new vnode ownership bitmap (np.bool_[VNODE_COUNT])
    vnode_bitmaps: Dict[int, np.ndarray] = field(default_factory=dict)
    # actor_id -> replacement output lists for its dispatcher
    dispatcher_updates: Dict[int, list] = field(default_factory=dict)
    dropped_actors: FrozenSet[int] = frozenset()


@dataclass(frozen=True)
class StopMutation:
    """Actors to stop (job drop). Actors in the set terminate after this
    barrier; their downstream channels close."""

    actors: FrozenSet[int] = frozenset()


@dataclass(frozen=True)
class PauseMutation:
    """Pause sources (no data until Resume; barriers still flow)."""


@dataclass(frozen=True)
class ResumeMutation:
    """Resume paused sources."""


@dataclass(frozen=True)
class SourceChangeSplitMutation:
    """Reassign source splits to actors (actor_id -> split id list)."""

    assignments: Dict[int, tuple] = field(default_factory=dict)


Mutation = Union[AddMutation, UpdateMutation, StopMutation, PauseMutation,
                 ResumeMutation, SourceChangeSplitMutation]


# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Barrier:
    """The checkpoint token (executor/mod.rs:622 analog).

    Flows from sources to sinks through every channel; aligned at fan-in.
    Carrying `epoch = EpochPair(curr, prev)`: data after this barrier lands
    at `curr`; state committed by this barrier is readable at `prev`.
    """

    epoch: EpochPair
    kind: BarrierKind = BarrierKind.CHECKPOINT
    mutation: Optional[Mutation] = None
    passed_actors: tuple = ()  # debug trail, actor ids appended in transit

    @property
    def is_checkpoint(self) -> bool:
        return self.kind.is_checkpoint

    def is_stop(self, actor_id: int) -> bool:
        return (isinstance(self.mutation, StopMutation)
                and actor_id in self.mutation.actors)

    def is_pause(self) -> bool:
        return isinstance(self.mutation, PauseMutation)

    def is_resume(self) -> bool:
        return isinstance(self.mutation, ResumeMutation)

    def with_passed(self, actor_id: int) -> "Barrier":
        return Barrier(self.epoch, self.kind, self.mutation,
                       self.passed_actors + (actor_id,))

    def __repr__(self) -> str:
        m = f", {type(self.mutation).__name__}" if self.mutation else ""
        return f"Barrier({self.epoch.curr.value:#x}, {self.kind.value}{m})"


@dataclass(frozen=True)
class Watermark:
    """Monotonic lower bound on future values of one column
    (executor/mod.rs watermark; used for state cleaning and EOWC)."""

    col_idx: int
    data_type: DataType
    value: object  # host scalar in the column's logical domain

    def with_idx(self, idx: int) -> "Watermark":
        return Watermark(idx, self.data_type, self.value)

    def derived(self, derivations: dict) -> List["Watermark"]:
        """This watermark's images under a projection's derivations
        (input col -> an output col, an (output col, transform) for a
        monotone expression, or a list of those: the raw column beside
        a windowed image of it); none where the column is not kept."""
        return [Watermark(out, self.data_type,
                          self.value if fn is None else fn(self.value))
                for out, fn in derivation_images(derivations,
                                                 self.col_idx)]

    def __repr__(self) -> str:
        return f"Watermark(col={self.col_idx}, {self.value})"


def derivation_images(derivations: dict, col_idx: int):
    """(output col, transform or None) of every image ``col_idx`` has
    under a projection's watermark derivations."""
    d = derivations.get(col_idx)
    for one in d if isinstance(d, list) else [] if d is None else [d]:
        yield one if isinstance(one, tuple) else (one, None)


Message = Union[StreamChunk, Barrier, Watermark]


def is_chunk(m: Message) -> bool:
    return isinstance(m, StreamChunk)


def is_barrier(m: Message) -> bool:
    return isinstance(m, Barrier)


def is_watermark(m: Message) -> bool:
    return isinstance(m, Watermark)
