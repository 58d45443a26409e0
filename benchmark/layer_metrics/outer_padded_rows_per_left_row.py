"""NULL-padded rows a degree-tracked join emitted and took back, per row
into its left side: history `join_outer.t<join>.padded_insert` +
`.padded_delete` (counter `stream_join_outer_rows{table, event}`,
written only by a join that tracks degrees) over
`join_input_rows.t<join>.left.<op>`, window sums over the plan's joins.
It is a witness that the outer half engaged: on q101 an auction is
padded when its chunk is ingested before the aggregate's first row for
it, and the padded row is retracted when that row comes, so the ratio
lies between 0 (every auction matched on arrival) and 2. Where it
falls follows from where in the epoch the aggregate's one flush lands
among the auction reader's chunks (the two readers run side by side):
1.03 at the cell's 16 chunks a reader on the chip, 1.84 at 8, 1.99 in
the rehearsal's 2. An inner join, or a program from before the counter,
writes no such name and has nothing to read."""


def read(record):
    padded = left = 0.0
    seen = False
    for h in record["history"].values():
        for name, value in h.items():
            if name.startswith("join_outer.") and name.endswith(
                    (".padded_insert", ".padded_delete")):
                padded += value
                seen = True
            elif name.startswith("join_input_rows.") and \
                    name.split(".")[2:3] == ["left"]:
                left += value
    if not seen or not left:
        return None
    return padded / left
