"""Share of the traced span in which no operation ran on the device:
1 - (union of device-op intervals) / span, averaged over the chips."""


def read(record):
    trace = record.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
