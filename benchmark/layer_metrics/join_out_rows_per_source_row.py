"""Rows the view's hash joins emitted, before any condition above
them, per source row of the window (`join_output.t<join>.rows` of
`rw_metrics_history`: counter `stream_join_output_rows{table}`, the
visible rows of every chunk a HashJoinExecutor emits at a barrier;
over the same epochs' `source_rows`): how much wider than its input
the join's output is. Both sums run over the window's epochs that
carry the counter. A program from before the counter writes no such
name and has nothing to read."""


def read(record):
    out_rows = source_rows = 0.0
    for h in record["history"].values():
        names = [k for k in h if k.startswith("join_output.")
                 and k.endswith(".rows")]
        if not names:
            continue
        out_rows += sum(h[k] for k in names)
        source_rows += h.get("source_rows", 0.0)
    if not source_rows:
        return None
    return out_rows / source_rows
