"""Rows the top-N emitted per row it was given: history
`topn.t<table>.rows_out` over `.rows_in` (counter
`stream_topn_rows{table, event}`), window sums over the plan's top-N
tables. It is the data's own number, a witness of the traffic: on q9
about 0.083 (of twelve joined bids eleven lose where they stand; of
32,768 a barrier some 2,140 open a new auction, one insert each, and
some 300 take an auction's lead, a delete and an insert). Nothing to read where no plan has a top-N."""

from topn_state_rows_per_row import window_sum


def read(record):
    rows_in = window_sum(record, (".rows_in",))
    return window_sum(record, (".rows_out",)) / rows_in if rows_in else None
