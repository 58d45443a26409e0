"""Rows a watermark's range delete read back from the store, over the
rows it deleted: history `state_clean.t<table>.reads` over
`state_clean.t<table>.cleaned` (counters `stream_state_clean_reads`,
`stream_state_cleaned_rows{table}`, `StateTable.delete_below_prefix`),
window sums over every table. 0.0 since PR 38: the table's clean index
names the keys under the watermark and only the scan that seeds it, at
a table's first clean, reads (1.0 while the delete was a scan that read
each row and deleted it by its key). Nothing to read where no watermark
cleaned a row."""


def read(record):
    reads = cleaned = 0.0
    for h in record["history"].values():
        for name, value in h.items():
            if not (isinstance(name, str)
                    and name.startswith("state_clean.")):
                continue
            if name.endswith(".reads"):
                reads += value
            elif name.endswith(".cleaned"):
                cleaned += value
    return reads / cleaned if cleaned else None
