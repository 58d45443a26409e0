"""Rounds of `hash_table.probe_insert`'s claim loop per staged batch,
the mean over the window's epochs, of the kernel where it is largest
(`probe_insert.<kind>.t<state table id>.rounds` over `.batches` of
`rw_metrics_history`: the device step returns its loop's length with
its insert count, and the executor files both at the barrier). A round
is one pass over the whole batch; a batch needs as many as its longest
probe chain, one more where many of its rows share a new key. A program
from before the counter writes no such name and has nothing to read."""


def read(record):
    rounds, batches = {}, {}
    for h in record["history"].values():
        for name, value in h.items():
            if not name.startswith("probe_insert."):
                continue
            kernel, field = name[len("probe_insert."):].rsplit(".", 1)
            into = rounds if field == "rounds" else batches
            into[kernel] = into.get(kernel, 0.0) + value
    means = [rounds[k] / n for k, n in batches.items() if n]
    return max(means) if means else None
