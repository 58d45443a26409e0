"""Whose `device_compute` it is: history
`exec_phase.<Kind>.device_compute` of the executor kinds containing
`Agg` (the launches and ready-waits booked inside an aggregate's pulls;
counter `stream_exec_phase_seconds{kind, phase}`), over the span of
`stage_span.py`. A program that writes no `exec_phase.*` reads
nothing."""

from stage_span import exec_phase


def read(record):
    return exec_phase(record, "Agg", "device_compute")
