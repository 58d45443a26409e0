"""Rows the cleaned state tables keep at the end of the window over
the rows they kept at its start: history `state_resident.t<table>.rows`
(gauge `stream_state_resident_rows{table}`: the topology books' total
of every state table a watermark cleans, set at the table's commit),
summed over the tables of each row. 1.0 +- 0.15 or the state is not
bounded.

A window closes every 2 s of event time and a barrier carries 1.78 s,
so the sum swings by a window's groups (a fifth of the whole) with the
phase of the barrier in the slide, and comes round every nine
barriers. The two ends are therefore each the mean of nine seals (of a
third of the rows where the window is shorter than 27): like is
compared with like whatever phase the window opens and closes on."""

CYCLE = 9


def read(record):
    rows = sorted((h for h in record["history"].values() if "ts" in h),
                  key=lambda h: h["ts"])
    sums = [sum(v for k, v in h.items() if isinstance(k, str)
                and k.startswith("state_resident.")
                and k.endswith(".rows"))
            for h in rows
            if any(isinstance(k, str) and k.startswith("state_resident.")
                   for k in h)]
    k = min(CYCLE, len(sums) // 3)
    if k < 1 or not sum(sums[:k]):
        return None
    return sum(sums[-k:]) / sum(sums[:k])
