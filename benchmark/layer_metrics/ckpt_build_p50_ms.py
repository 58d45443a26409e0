"""Median over the window's checkpoints of `ckpt.build_s`:
the SST build (`build_ssts`: sort and encode every dirty
key, synchronous on the event loop). The program
writes it into the sealing barrier's row of `rw_metrics_history` when
the commit lands."""

import statistics


def read(record):
    took = [h["ckpt.build_s"] for h in record["history"].values()
            if "ckpt.build_s" in h]
    if not took:
        return None
    return 1e3 * statistics.median(took)
