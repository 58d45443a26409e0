"""Median inject-to-collect time of the window's barriers
(`rw_barrier_latency.inject_to_collect_s`): the barrier plane waiting
for every actor to pass the barrier, the epoch's remaining work included."""

import statistics


def read(record):
    if not record["barriers"]:
        return None
    return 1e3 * statistics.median(
        b["inject_to_collect_s"] for b in record["barriers"])
