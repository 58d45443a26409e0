"""Device milliseconds a barrier spends retiring state behind the
watermark: the seconds of the programs `hash_agg.retire` (an
aggregate's whole-capacity rebuild from its survivors),
`hash_join.tombstone` (a join side's expired refs) and the join side's
compaction (`hash_join.link`, `hash_table.probe_insert`,
`hash_join.masked_scatter`: the rebuild of the survivors), by their
`jaxtools.program_name` in the device trace, over the barriers sealed
inside the traced span.

The record carries the trace as `trace_reduce.reduce_trace` leaves it:
`device_ops`, the ten largest programs of the span by name (the
modules line does not nest, so a name's seconds are a union already),
and `epochs_in_span`. A retiring program that is not among the ten is
not in the sum: the number is a floor then, and where none of them is
there is nothing to read. No device plane (a rehearsal): nothing."""

PROGRAMS = ("hash_agg_retire", "hash_join_tombstone", "hash_join_link",
            "hash_table_probe_insert", "hash_join_masked_scatter")


def read(record):
    trace = record.get("trace") or {}
    barriers = trace.get("epochs_in_span")
    if not barriers:
        return None
    seconds = [s for name, s in trace.get("device_ops", ())
               if any(p in name for p in PROGRAMS)]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / barriers
