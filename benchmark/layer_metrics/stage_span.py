"""What the readers of the ledger's second coordinate share (PR 35):
the span they divide by and the sum they divide.

The span is `join_to_agg_share`'s: the window's epochs but the closing
one, from the first one's start (`ts - interval_s`) to the last one's
seal (`ts`), by `rw_metrics_history`'s own stamps: the closing epoch
is the harness's own `FLUSH`, sealed after its watch has paused the
heartbeat (until PR 51 a traced run also waited for the profiler
there, up to 12 s; it no longer does). Every row of the
span counts, also one that lacks the name: an epoch in which a stage
did not run has no seconds of it. The names read are
`exec_phase.<Kind>.<phase>`, `stage.<phase>.<stage>` and
`device.<launch|wait>.<kernel>` (`risingwave_tpu/utils/ledger.py`,
"The second coordinate")."""


def span_rows(record):
    """The window's history rows, oldest first, the closing one left
    out."""
    rows = sorted((h for h in record["history"].values() if "ts" in h),
                  key=lambda h: h["ts"])
    return rows[:-1] if len(rows) > 2 else rows


def span_s(rows) -> float:
    return rows[-1]["ts"] - min(h["ts"] - h["interval_s"] for h in rows)


def share(record, wanted):
    """100 x the seconds of every name `wanted(name)` accepts, summed
    over the span's rows, over the span. None where no row carries
    such a name: a program from before the names."""
    rows = span_rows(record)
    picked = [v for h in rows for k, v in h.items()
              if isinstance(k, str) and wanted(k)]
    if not picked or span_s(rows) <= 0:
        return None
    return 100.0 * sum(picked) / span_s(rows)


def exec_phase(record, kind_part: str, phase: str):
    """`exec_phase.<Kind>.<phase>` of the kinds whose name contains
    `kind_part`, as a share of the span."""
    return share(record, lambda k: k.startswith("exec_phase.")
                 and k.endswith("." + phase)
                 and kind_part in k.split(".")[1])
