"""Ledger phase `compaction` (the inline LSM compaction a commit
triggers, synchronous on the event loop: `HummockLite.compact`), summed
over the window's epochs, as a share of the window's wall time."""


def read(record):
    if "compaction" not in record["phase_seconds"]:
        return None
    return 100.0 * record["phase_seconds"]["compaction"] \
        / record["window"]["wall_s"]
