"""`strftime` calls `to_char` made, over the valid rows it was given:
history `expr_to_char.formats` over `expr_to_char.rows` (counters
`expr_to_char_formats`, `expr_to_char_rows`), both summed over the
window's barriers. `to_char` formats once a distinct value, in a chunk,
of the finest field its pattern proves the text a function of (the
microsecond where it proves none): 1 / 4,096 in q15, whose chunks of
4,096 bids lie within one day each; 1.0 where every row differs.
Nothing to read where no plan calls `to_char`, or on a program from
before the counters."""


def _sum(record, name: str) -> float:
    return sum(h.get(name, 0) for h in record["history"].values())


def read(record):
    rows = _sum(record, "expr_to_char.rows")
    return _sum(record, "expr_to_char.formats") / rows if rows else None
