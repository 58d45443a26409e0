"""Median over the window's epochs of `heartbeat.wait_s`, in ms: the
seconds the serving heartbeat waited for its tick before it injected
the epoch's barrier (`meta/barrier.py` `HeartbeatTick.wait`, filed on
the row of the epoch the wait preceded). Near the interval less the
work where the tick sets the pace, 0 where the work does. A program
from before the name writes none and has nothing to read."""

import statistics


def read(record):
    waited = [h["heartbeat.wait_s"] for h in record["history"].values()
              if "heartbeat.wait_s" in h]
    if not waited:
        return None
    return 1e3 * statistics.median(waited)
