"""Host seconds the watermark's cleaning took, as a share of the span
of `stage_span.py`: history `stage.host_emit.agg.clean`
(`HashAggExecutor._clean_to`: the device retire's launch and the
memory-side cuts; the range deletes nested in it are `state.clean`),
`stage.host_emit.join.expire` (`HashJoinExecutor._expire_to`: the scan
of the live refs, the dead pks, the tombstones' launch; the batch
delete nested in it is `state.write`) and `stage.host_emit.state.clean`
(`StateTable.delete_below_prefix`: since PR 38 the clean index's buckets
under the watermark and a tombstone a row), each exclusive of what nests inside it. A program
without a watermark, or from before the names, reads nothing."""

from stage_span import share

STAGES = ("stage.host_emit.agg.clean", "stage.host_emit.join.expire",
          "stage.host_emit.state.clean")


def read(record):
    return share(record, lambda k: k in STAGES)
