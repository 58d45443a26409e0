"""Host seconds the outer half of a degree-tracked join took, as a share
of the span of `stage_span.py`: history `stage.host_emit.join.degrees`
(`HashJoinExecutor._degree_transitions`: a chunk's pairs folded to the
stored rows of the outer side whose match degree crossed zero) and
`stage.host_emit.join.pad` (`_padded_from_chunk`, `_padded_from_arena`:
the NULL-padded chunks of the unmatched incoming rows and of the stored
rows that flipped). The matched pairs stay under `join.pairs`, read by
`join_pairs_share`. An inner join runs neither stage, and on a program
from before `join.pad` the padded chunks are inside `join.pairs`, where
this reader cannot tell them from the pairs: both read nothing."""

from stage_span import share

_STAGES = ("stage.host_emit.join.degrees", "stage.host_emit.join.pad")


def read(record):
    if not any("stage.host_emit.join.pad" in h
               for h in record["history"].values()):
        return None
    return share(record, lambda name: name in _STAGES)
