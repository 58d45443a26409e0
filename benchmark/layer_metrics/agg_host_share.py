"""Exclusive busy seconds of the executors whose kind contains `Agg`
(`exec_s.<Kind>` of `rw_metrics_history`: the host clock around each
executor's pulls, less its inputs' pulls, its parks and the loop time a
checkpoint stole), summed over the window's epochs, as a share of the
window's wall time. It holds the executor's host half and its waits for
the device alike."""


def read(record):
    by_kind = [(k, v) for h in record["history"].values()
               for k, v in h.items() if k.startswith("exec_s.")]
    if not by_kind:
        return None
    return 100.0 * sum(v for k, v in by_kind if "Agg" in k) \
        / record["window"]["wall_s"]
