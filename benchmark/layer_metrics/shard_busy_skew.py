"""Busy seconds of the busiest chip over the chips' mean, inside the
traced span (`trace.per_device`: per chip, the union of its device-op
intervals). 1 is four chips equally busy. A trace of one chip, or of
none, has nothing to read."""


def read(record):
    trace = record.get("trace")
    busy = list((trace or {}).get("per_device", {}).values())
    if len(busy) < 2 or not sum(busy):
        return None
    return max(busy) * len(busy) / sum(busy)
