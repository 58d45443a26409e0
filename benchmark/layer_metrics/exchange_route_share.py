"""Ledger phase `exchange_route` (the sharded kernels' host routing in
front of their all_to_all: every row's owner shard, the skew-exact
bucket, the bucket choice; `parallel/exchange.route_phase`), summed over
the window's epochs, as a share of the window's wall time. A program
without the phase, or a single-chip plan, has nothing to read."""


def read(record):
    if "exchange_route" not in record["phase_seconds"]:
        return None
    return 100.0 * record["phase_seconds"]["exchange_route"] \
        / record["window"]["wall_s"]
