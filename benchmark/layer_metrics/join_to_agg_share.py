"""Host seconds of the join -> aggregate hand-off (`join_to_agg.seconds`
of `rw_metrics_history`: counter `stream_join_to_agg_seconds`, the host
clock around the join's chunk build from its probe result, the
aggregate's ingest of those chunks and the pack, upload and dispatch of
its staged batch), as a share of the time the window's barriers took.
Both are taken over the window's epochs but the closing one, from the
first one's start to the last one's seal by the history's own stamps:
`window.wall_s` and the closing barrier's epoch hold, in a traced run,
the seconds the paused program waits for the profiler after its last
heartbeat barrier. A program from before the counter writes no such
name and has nothing to read."""


def read(record):
    rows = sorted((h for h in record["history"].values()
                   if "join_to_agg.seconds" in h), key=lambda h: h["ts"])
    if len(rows) > 2:
        rows = rows[:-1]
    if not rows:
        return None
    span = rows[-1]["ts"] - min(h["ts"] - h["interval_s"] for h in rows)
    if span <= 0:
        return None
    return 100.0 * sum(h["join_to_agg.seconds"] for h in rows) / span
