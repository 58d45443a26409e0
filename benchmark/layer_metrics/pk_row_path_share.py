"""State-table keys that the scalar codec made one call at a time
(`state_pk.row` of `rw_metrics_history`: `StateTable._encode_pk`, the
point operations' encoder), as a share of all state-table keys the
window's epochs encoded (with `state_pk.columnar`, the bulk encoder's
count by the batch). A program from before the counter writes neither
name and has nothing to read."""


def read(record):
    rows = [h for h in record["history"].values() if "state_pk.row" in h]
    keys = sum(h["state_pk.row"] + h["state_pk.columnar"] for h in rows)
    if not keys:
        return None
    return 100.0 * sum(h["state_pk.row"] for h in rows) / keys
