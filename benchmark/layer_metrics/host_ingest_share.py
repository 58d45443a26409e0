"""Ledger phases `host_ingest` + `host_pack` (source generation, parse
and the packing of chunks for upload), summed over the window's epochs,
as a share of the window's wall time."""


def read(record):
    phases = record["phase_seconds"]
    if "host_ingest" not in phases and "host_pack" not in phases:
        return None
    return 100.0 * (phases.get("host_ingest", 0.0)
                    + phases.get("host_pack", 0.0)) \
        / record["window"]["wall_s"]
