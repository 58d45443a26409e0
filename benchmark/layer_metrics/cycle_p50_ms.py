"""Median barrier cycle of the window, seal to seal: the difference of
successive seal stamps (`ts` of `rw_metrics_history`'s rows, one a
barrier) over the window's epochs, in ms. The closing epoch is left
out: it seals after the harness's pause and `FLUSH`, which in a traced
run come after the profiler's span. What the heartbeat adds to a cycle
beside the work (a sleep after every collect, or the rest of a tick) is
in this number and in no ledger phase. It reads any program that writes
the history, so the parent's reading is the before."""

import statistics


def read(record):
    seals = sorted(h["ts"] for h in record["history"].values()
                   if "ts" in h)[:-1]
    if len(seals) < 2:
        return None
    return 1e3 * statistics.median(
        b - a for a, b in zip(seals, seals[1:]))
