"""Ledger phase `checkpoint` (what a checkpoint does synchronously on
the event loop besides compacting: the SST build, the manifest commit,
the checkpoint-time sweeps), summed over the window's epochs, as a share
of the window's wall time."""


def read(record):
    if "checkpoint" not in record["phase_seconds"]:
        return None
    return 100.0 * record["phase_seconds"]["checkpoint"] \
        / record["window"]["wall_s"]
