"""History `stage.host_emit.agg.persist` (the aggregate's state rows
built from its flush, `HashAggExecutor._persist` / `_state_rows`, and
its multisets' pending writes; the `state.write` nested inside is its
own stage; counter `stream_phase_stage_seconds{phase, stage}`), over
the span of `stage_span.py`. A program that writes no such name reads
nothing."""

from stage_span import share


def read(record):
    return share(record, lambda k: k == "stage.host_emit.agg.persist")
