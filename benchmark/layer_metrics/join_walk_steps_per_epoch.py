"""Steps the join probe's walk took a barrier: the mean, over the
window's barriers that probed, of the largest
`join_probe.<kernel>.walk_steps` of `rw_metrics_history` (gauge
`stream_join_probe_walk_steps`: the device step returns its `while`'s
trip count in the probe matrix's header and the executor files it at
the barrier). The walk steps a run at a time, the rows one batch gave
one key, so this is the batches that touched the most-touched key
probed. A program that writes no such name walks a row a step, and its
steps are its `join_probe.<kernel>.longest_chain` (written since PR 33):
the reader falls back on that name, so both sides of a pair read. A
program from before either, or a plan without a join, has nothing to
read."""

PREFIX = "join_probe."


def read(record):
    for field in (".walk_steps", ".longest_chain"):
        steps = []
        for h in record["history"].values():
            values = [v for name, v in h.items()
                      if name.startswith(PREFIX) and name.endswith(field)]
            if values:
                steps.append(max(values))
        if steps:
            return sum(steps) / len(steps)
    return None
