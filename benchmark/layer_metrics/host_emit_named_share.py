"""How much of `host_emit` has a name: the sum of history
`stage.host_emit.<stage>` (the staged scopes of the executors' host
half; counter `stream_phase_stage_seconds{phase, stage}`) over
`phase.host_emit`, both summed over the window's epochs. What is left
is the executors' residue outside every stage. A program that writes
no `stage.host_emit.*` reads nothing."""


def read(record):
    rows = list(record["history"].values())
    named = [v for h in rows for k, v in h.items()
             if isinstance(k, str) and k.startswith("stage.host_emit.")]
    total = sum(h.get("phase.host_emit", 0.0) for h in rows)
    if not named or total <= 0:
        return None
    return 100.0 * sum(named) / total
