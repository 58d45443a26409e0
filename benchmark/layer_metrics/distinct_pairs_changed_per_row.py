"""(group, value) pairs of which a barrier changed any count, over the
source rows: history `agg_distinct.t<table>.changed` (counter
`stream_agg_distinct_changed{table}`: the rows the barrier's
write-through inserted, updated and deleted in a dedup table), summed
over the dedup tables and the window's barriers, over the window's
durable source rows. It is what the dedup state costs the store a row:
0.094 for q15 under upstream's layout at 131,072 bids a barrier (a
pair is one row whatever its calls, written once a barrier however many
chunks moved it), four times that where every filtered call keeps a
table of its own. Nothing to read where no plan has a DISTINCT call."""


def read(record):
    changed = [v for h in record["history"].values()
               for k, v in h.items() if isinstance(k, str)
               and k.startswith("agg_distinct.")
               and k.endswith(".changed")]
    rows = record["window"]["rows"]
    return sum(changed) / rows if changed and rows else None
