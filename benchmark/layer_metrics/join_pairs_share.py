"""History `stage.host_emit.join.pairs` (the hash join's output chunks
built from its probe result: `HashJoinExecutor._pairs_chunk`,
`_padded_from_*`, `_subject_from_*`; counter
`stream_phase_stage_seconds{phase, stage}`), over the span of
`stage_span.py`. A program that writes no such name reads nothing."""

from stage_span import share


def read(record):
    return share(record, lambda k: k == "stage.host_emit.join.pairs")
