"""Whose `host_emit` it is: history `exec_phase.<Kind>.host_emit` of the
executor kinds containing `Join` (counter
`stream_exec_phase_seconds{kind, phase}`), over the span of
`stage_span.py`. A program that writes no `exec_phase.*` reads
nothing."""

from stage_span import exec_phase


def read(record):
    return exec_phase(record, "Join", "host_emit")
