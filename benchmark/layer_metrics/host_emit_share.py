"""Ledger phase `host_emit` (the executors' host half turning device
results into chunks and state writes), summed over the window's epochs,
as a share of the window's wall time."""


def read(record):
    if "host_emit" not in record["phase_seconds"]:
        return None
    return 100.0 * record["phase_seconds"]["host_emit"] \
        / record["window"]["wall_s"]
