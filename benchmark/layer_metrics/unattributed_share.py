"""What the phase ledger could put in no phase (`unattributed`: each
epoch's interval less its named phases), summed over the window's
epochs, as a share of the window's wall time."""


def read(record):
    if "unattributed" not in record["phase_seconds"]:
        return None
    return 100.0 * record["phase_seconds"]["unattributed"] \
        / record["window"]["wall_s"]
