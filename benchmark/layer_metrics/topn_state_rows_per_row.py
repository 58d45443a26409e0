"""Rows the top-N wrote to and deleted from its state table per row it
was given: history `topn.t<table>.state_writes` + `.state_deletes` over
`.rows_in` (counter `stream_topn_rows{table, event}`), window sums over
the plan's top-N tables. An append-only top-N laid out as upstream's
`top_n_appendonly.rs` writes only the rows that enter its kept range
and deletes only the rows pushed out of it, so with limit 1 this reads
what `topn_out_rows_per_row` reads (every row of a delta is one write
or one delete); a top-N that writes every row and deletes each loser
reads 1.93 on q9's traffic. Nothing to read where no plan has a
top-N."""


def window_sum(record, suffixes) -> float:
    return sum(v for h in record["history"].values()
               for k, v in h.items() if isinstance(k, str)
               and k.startswith("topn.") and k.endswith(suffixes))


def read(record):
    rows_in = window_sum(record, (".rows_in",))
    if not rows_in:
        return None
    return window_sum(record, (".state_writes", ".state_deletes")) / rows_in
