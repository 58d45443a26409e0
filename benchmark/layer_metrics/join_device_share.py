"""Whose `device_compute` it is: history
`exec_phase.<Kind>.device_compute` of the executor kinds containing
`Join` (counter `stream_exec_phase_seconds{kind, phase}`), over the
span of `stage_span.py`. A program that writes no `exec_phase.*` reads
nothing."""

from stage_span import exec_phase


def read(record):
    return exec_phase(record, "Join", "device_compute")
