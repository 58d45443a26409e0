"""Median over the window's checkpoints of `ckpt.put_s`:
the object-store PUTs of the checkpoint's SSTs, retries
included, off the event loop. The program
writes it into the sealing barrier's row of `rw_metrics_history` when
the commit lands."""

import statistics


def read(record):
    took = [h["ckpt.put_s"] for h in record["history"].values()
            if "ckpt.put_s" in h]
    if not took:
        return None
    return 1e3 * statistics.median(took)
