"""Rows that take something back, `delete` and `update_delete`, as a
share of all rows into the view's aggregates over the window's epochs
(`agg_input_rows.t<state table id>.<op>` of `rw_metrics_history`:
counter `stream_agg_input_rows{table, op}`, the visible rows of every
chunk a HashAggExecutor ingests, by op): what an upstream aggregate's
updates cost the aggregate it feeds. A program from before the counter
writes no such name and has nothing to read."""


def read(record):
    by_op = {}
    for h in record["history"].values():
        for name, value in h.items():
            if name.startswith("agg_input_rows."):
                op = name.rsplit(".", 1)[1]
                by_op[op] = by_op.get(op, 0.0) + value
    total = sum(by_op.values())
    if not total:
        return None
    return 100.0 * (by_op.get("delete", 0.0)
                    + by_op.get("update_delete", 0.0)) / total
