"""Host seconds the projections took, as a share of the span of
`stage_span.py`: history `exec_phase.ProjectExecutor.host_emit`
(counter `stream_exec_phase_seconds{kind, phase}`: the exclusive busy
seconds of every `ProjectExecutor`, less what it spent in another named
phase). In q15 that is the block under the aggregate which evaluates
`to_char(date_time, 'YYYY-MM-DD')`, the GROUP BY key, and the three
rank predicates on the host: `to_char` is host-typed, so the block does
not fuse into the aggregate's prelude. A plan without a projection of
its own, or a program from before the names (PR 35), reads nothing."""

from stage_span import exec_phase


def read(record):
    return exec_phase(record, "ProjectExecutor", "host_emit")
