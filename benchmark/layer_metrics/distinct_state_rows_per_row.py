"""(group, value) pairs resident in the dedup state at the window's
closing seal, over the rows the readers' closing checkpoint covers:
history `agg_distinct.t<table>.pairs` (gauge
`stream_agg_distinct_pairs{table}`, set at each barrier behind the
watermark's clean: the rows of that dedup table), summed over the dedup
tables of the newest row that carries the name. 0.087 for q15 under
upstream's layout (one table a distinct column: 0.022 bidders and 0.065
auctions a bid), 0.33 with a table per filtered call. Nothing to read
where no plan has a DISTINCT call."""


def read(record):
    rows = sorted((h for h in record["history"].values() if "ts" in h),
                  key=lambda h: h["ts"])
    covered = sum(r["rows"] for r in record["window"]["close"])
    for h in reversed(rows):
        pairs = [v for k, v in h.items() if isinstance(k, str)
                 and k.startswith("agg_distinct.")
                 and k.endswith(".pairs")]
        if pairs:
            return sum(pairs) / covered if covered else None
    return None
