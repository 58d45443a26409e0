"""Host seconds the DISTINCT dedup took, as a share of the span of
`stage_span.py`: history `stage.host_emit.agg.distinct`
(`HashAggExecutor._apply_distinct`: the gating of a chunk's rows on the
(group, value) counts in memory, once a distinct column and chunk,
exclusive of the `agg.ingest` around it) plus, by dedup table,
`agg_distinct.t<table>.persist_s` and `.write_s` (counter
`stream_agg_distinct_seconds{table, stage}`: the dedup tables' part of
the barrier's `agg.persist`, and the `state.write` of their batch calls
nested in it). The value state's own `agg.persist` and `state.write` are
not in it. A plan without a DISTINCT call, or a program from before the
names, reads nothing."""

from stage_span import share


def _dedup(name: str) -> bool:
    return name == "stage.host_emit.agg.distinct" or (
        name.startswith("agg_distinct.")
        and name.endswith((".persist_s", ".write_s")))


def read(record):
    return share(record, _dedup)
