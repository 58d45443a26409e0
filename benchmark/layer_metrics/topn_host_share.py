"""Host seconds the top-N took, as a share of the span of
`stage_span.py`: history `stage.host_emit.topn.apply`
(`GroupTopNExecutor._walk`: a chunk's rows against the groups' sorted
caches and the diff of the touched groups' windows), `.topn.state`
(`_persist_window`: the append-only arm's lists of rows that entered
and left, around the table's two batch calls) and `.topn.emit`
(`_delta_chunk`: the delta chunk's columns). The `state.write` of those
batch calls nests in `topn.state`, is exclusive of it and is read by
`state_write_share`. A plan without a top-N, or a program from before
the names, reads nothing."""

from stage_span import share

_STAGES = ("stage.host_emit.topn.apply", "stage.host_emit.topn.state",
           "stage.host_emit.topn.emit")


def read(record):
    return share(record, lambda name: name in _STAGES)
