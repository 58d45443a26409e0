"""Device milliseconds a barrier spends in the hash join's two epoch
programs: the seconds of the programs named `hash_join_epoch_apply`
(a side's staged epoch linked into its chains) and
`hash_join_epoch_probe` (the epoch's rows probed against the other
side; with degrees, both sides' degree arrays updated in the same
dispatch), by their `jaxtools.program_name` in the device trace, over
the barriers sealed inside the traced span.

The record carries the trace as `trace_reduce.reduce_trace` leaves it:
`device_ops`, the ten largest programs of the span by name, and
`epochs_in_span`. A join program that is not among the ten is not in
the sum: the number is a floor then (as `retire_device_ms_per_barrier`).
No device plane (a rehearsal), or no such program: nothing."""

PROGRAMS = ("hash_join_epoch_apply", "hash_join_epoch_probe")


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
