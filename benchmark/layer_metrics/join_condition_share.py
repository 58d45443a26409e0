"""Host seconds spent evaluating the condition of a `JOIN ... ON` (its
conjuncts that are no hash keys; `join_condition.t<join>.seconds` of
`rw_metrics_history`: counter `stream_join_condition_seconds{table}`,
the host clock around the filter the planner marked as the join's,
or around the step of the fused block that holds it, which evaluates
it together with the block's projection and waits for the result), as
a share of the time the window's barriers took. Both are taken over
the window's epochs but the closing one, from the first one's start
to the last one's seal by the history's own stamps, as
`join_to_agg_share` takes them: `window.wall_s` and the closing
barrier's epoch hold, in a traced run, the seconds the paused program
waits for the profiler. A program from before the counter writes no
such name and has nothing to read."""


def read(record):
    rows = sorted((h for h in record["history"].values()
                   if any(k.startswith("join_condition.")
                          and k.endswith(".seconds") for k in h)),
                  key=lambda h: h["ts"])
    if len(rows) > 2:
        rows = rows[:-1]
    if not rows:
        return None
    span = rows[-1]["ts"] - min(h["ts"] - h["interval_s"] for h in rows)
    if span <= 0:
        return None
    seconds = sum(v for h in rows for k, v in h.items()
                  if k.startswith("join_condition.")
                  and k.endswith(".seconds"))
    return 100.0 * seconds / span
