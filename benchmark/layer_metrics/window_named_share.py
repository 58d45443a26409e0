"""How much of the window's time has a name. Per epoch of the span of
`stage_span.py`: the `phase.<name>` seconds of `rw_metrics_history`
but `phase.unattributed`, up to the epoch's `interval_s` (a
checkpoint's worker thread can name a second an actor names too: a
row's phases then add up to more than its interval, and the ledger
publishes no `phase.unattributed` there), i.e. `interval_s` less
`phase.unattributed`; and between the epochs `phase.heartbeat_wait`
(`meta/barrier.py` `HeartbeatTick.file`: the seconds the serving
heartbeat waited for its tick and for the sealed checkpoint's commit
before it injected the row's epoch, less the loop time the checkpoint
took meanwhile). The first row's wait precedes the span and is left
out. All over the span. What is missing from 100 is in no phase and no
heartbeat wait. A program from before `phase.heartbeat_wait` reads
nothing."""

from stage_span import span_rows, span_s


def read(record):
    rows = span_rows(record)
    if not any("phase.heartbeat_wait" in h for h in rows) \
            or span_s(rows) <= 0:
        return None
    named = sum(h["interval_s"] - h.get("phase.unattributed", 0.0)
                for h in rows)
    between = sum(h.get("phase.heartbeat_wait", 0.0) for h in rows[1:])
    return 100.0 * (named + between) / span_s(rows)
