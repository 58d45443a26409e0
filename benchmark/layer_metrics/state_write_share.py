"""History `stage.host_emit.state.write` (`StateTable.write_chunk`,
`insert_rows`, `delete_rows`, `update_rows`: key encoding, value rows,
memtable insert, whoever called them; counter
`stream_phase_stage_seconds{phase, stage}`), over the span of
`stage_span.py`. A program that writes no such name reads nothing."""

from stage_span import share


def read(record):
    return share(record, lambda k: k == "stage.host_emit.state.write")
