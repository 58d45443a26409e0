"""Device time per source row: the seconds in which an operation ran on
the device (the union of the operations' intervals: the trace's operation
line nests, so a sum of durations counts a loop's body twice) between the
first and the last barrier sealed inside the traced span, over the source
rows of the epochs between the two."""


def read(record):
    whole = (record.get("trace") or {}).get("whole_epochs")
    if not whole or not whole["source_rows"]:
        return None
    return whole["busy_s"] * 1e6 / whole["source_rows"]
