"""Nearest-rank 90th percentile of inject → durable commit (`total_s +
upload_s` of `rw_barrier_latency`) over the window's barriers: the
statistic of the end-to-end `barrier_p90_ms`, under a name of its own
for the cells that do not hold it end to end. In `q8_steady` it is the
largest of five barriers, and the seed decides it (PERF.md section 2)."""

import math


def read(record):
    if not record["barriers"]:
        return None
    lat_ms = sorted(1e3 * (b["total_s"] + b["upload_s"])
                    for b in record["barriers"])
    return lat_ms[max(0, math.ceil(0.9 * len(lat_ms)) - 1)]
