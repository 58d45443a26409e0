"""Median collect-to-durable-commit time of the window's barriers:
`collect_to_commit_s` (the seal) plus `upload_s` (SST build, upload and
the manifest commit, inline compaction included)."""

import statistics


def read(record):
    if not record["barriers"]:
        return None
    return 1e3 * statistics.median(
        b["collect_to_commit_s"] + b["upload_s"]
        for b in record["barriers"])
