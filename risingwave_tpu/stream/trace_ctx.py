"""Trace-context propagation: dispatch spans + span ctx on the wire.

The stream-facing half of the epoch tracer (utils/spans.py): executors
and kernels stamp device dispatches into the current epoch's trace, and
remote exchange barriers carry a span context trailer so the receiving
worker's spans link causally to the coordinator's inject span.

Wire shape (appended to every 'B' barrier frame payload):

    trailer = magic(2B b"TC") ++ epoch(u64) ++ parent_span(u64)
              ++ send_wall_ts(f64)      — struct ">2sQQd", 26 bytes
"""

from __future__ import annotations

import contextlib
import struct
import time
from typing import Optional, Tuple

from risingwave_tpu.stream import costs as _costs
from risingwave_tpu.utils import spans as _spans
from risingwave_tpu.utils.metrics import STREAMING
from risingwave_tpu.utils.spans import dispatch_span  # noqa: F401
#                     (re-export: the executors' natural import home)

_TRAILER = struct.Struct(">2sQQd")
_MAGIC = b"TC"


@contextlib.contextmanager
def join_to_agg_handoff(on: bool):
    """One leg of the join -> aggregate hand-off, on the host's clock:
    the join's chunk build from its probe result, the aggregate's
    ingest of those chunks, the pack, upload and dispatch of its
    staged batch. `on` is the mark the planner puts on a hash join
    and the aggregate it plans over it (planner.py _plan_query); no
    leg is booked without it. The legs add up in counter
    ``stream_join_to_agg_seconds{view}`` (``join_to_agg.seconds`` of
    rw_metrics_history) and each is a ``handoff.join_to_agg``
    annotation on the profiler's clock. A second cut of the wall time
    the ledger's phases partition (like ``exec_s.<Kind>``): the phases
    inside a leg keep their seconds."""
    if not on:
        yield
        return
    t0 = time.perf_counter()
    try:
        with _spans.annotation("handoff.join_to_agg"):
            yield
    finally:
        STREAMING.join_to_agg_seconds.inc(
            time.perf_counter() - t0, view=_costs.current_mv() or "")


@contextlib.contextmanager
def join_condition_span(join: str):
    """The host seconds in which an inner join evaluates its own
    condition (the conjuncts of its ON / WHERE that are no hash keys)
    on the pairs it matched, filed under the join: counter
    ``stream_join_condition_seconds{table}``
    (``join_condition.<join>.seconds`` of rw_metrics_history) and a
    ``join.condition`` annotation on the profiler's clock. They lie
    inside the join's ``join.pairs`` stage: a second cut of the wall
    time, like ``exec_s.<Kind>``."""
    t0 = time.perf_counter()
    try:
        with _spans.annotation("join.condition"):
            yield
    finally:
        STREAMING.join_condition_seconds.inc(
            time.perf_counter() - t0, table=join)


# -- remote-exchange span context ------------------------------------------


def barrier_trailer(barrier) -> bytes:
    """Span-context bytes to append to an outgoing 'B' frame
    payload."""
    epoch = barrier.epoch.curr.value
    parent = _spans.EPOCH_TRACER.root_id(epoch) or 0
    return _TRAILER.pack(_MAGIC, epoch, parent, time.time())


def decode_trailer(payload: bytes) -> Optional[Tuple[int, int, float]]:
    """(epoch, parent_span_id, send_wall_ts) if the payload ends in a
    span-context trailer, else None. The magic guards against a stop
    mutation's actor list happening to leave 26 trailing bytes."""
    if len(payload) < _TRAILER.size:
        return None
    magic, epoch, parent, ts = _TRAILER.unpack_from(
        payload, len(payload) - _TRAILER.size)
    if magic != _MAGIC:
        return None
    return epoch, parent, ts


def record_remote_transfer(payload: bytes, up: int, down: int) -> None:
    """Receiver side of one remote barrier frame: if the sender shipped
    a span context, record the exchange-transfer span — parented to the
    SENDER's inject span, so the cross-worker edge links causally —
    and adopt the sender's epoch/root for spans this process records
    next (a pure-executor worker has no barrier loop to set them)."""
    ctx = decode_trailer(payload)
    if ctx is None:
        return
    epoch, parent, sent = ctx
    now = time.time()
    _spans.EPOCH_TRACER.record(
        f"exchange {up}->{down}", "exchange", epoch=epoch,
        start_s=sent, dur_s=max(0.0, now - sent),
        parent=parent or None, edge=f"{up}->{down}")
    if parent and _spans.EPOCH_TRACER.root_id(epoch) is None:
        _spans.EPOCH_TRACER.set_root(epoch, parent)
    if epoch > _spans.current_epoch():
        _spans.set_current_epoch(epoch)
