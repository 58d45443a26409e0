"""Median over the window's checkpoints of `ckpt.queue_s`:
a checkpoint's wait behind the older epoch's build and
commit. The program
writes it into the sealing barrier's row of `rw_metrics_history` when
the commit lands."""

import statistics


def read(record):
    took = [h["ckpt.queue_s"] for h in record["history"].values()
            if "ckpt.queue_s" in h]
    if not took:
        return None
    return 1e3 * statistics.median(took)
