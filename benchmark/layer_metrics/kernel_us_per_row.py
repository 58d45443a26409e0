"""Device time per source row over the window: the seconds in which an
operation ran on the device (the union of the operations' intervals: the
trace's operation line nests, so a sum of durations counts a loop's body
twice) from the start of the traced span, which is the window's opening,
to the last barrier sealed inside it, the closing one, over the source
rows of every epoch sealed inside the span: all the window's barriers
and all its rows (`trace.whole_epochs`, `run.reduce_span`). Until PR 51
the span opened 2 s into the window and the reader took the seals inside
it less the first; a window of five barriers gave it one epoch or none."""


def read(record):
    whole = (record.get("trace") or {}).get("whole_epochs")
    if not whole or not whole["source_rows"]:
        return None
    return whole["busy_s"] * 1e6 / whole["source_rows"]
