"""Rows that take something back, `delete` and `update_delete`, as a
share of all rows into the view's hash joins, both sides, over the
window's epochs (`join_input_rows.t<join>.<side>.<op>` of
`rw_metrics_history`: counter `stream_join_input_rows{table, side,
op}`, the visible rows of every chunk a HashJoinExecutor ingests, by
side and op): what its inputs' updates cost a join, whose deletes
tombstone rows of its device chains. A program from before the counter
writes no such name and has nothing to read."""


def read(record):
    by_op = {}
    for h in record["history"].values():
        for name, value in h.items():
            if name.startswith("join_input_rows."):
                op = name.rsplit(".", 1)[1]
                by_op[op] = by_op.get(op, 0.0) + value
    total = sum(by_op.values())
    if not total:
        return None
    return 100.0 * (by_op.get("delete", 0.0)
                    + by_op.get("update_delete", 0.0)) / total
