"""The actor await-state registry.

Reference parity: the await-tree actor stack dumps exposed by
MonitorService (src/compute/src/rpc/service/monitor_service.rs:72) —
reduced to a per-actor "currently awaiting" table that a debugger (or
test) can dump when a barrier stalls. Spans live in utils/spans.py
(the epoch trace) and, on the profiler's clock, in its annotations.
"""

from __future__ import annotations

import time
from typing import Dict


class AwaitRegistry:
    """Who is waiting on what (await-tree analog).

    Actors/executors report their current await point; ``dump()`` shows
    the live picture — the first tool to reach for when an epoch never
    collects.
    """

    def __init__(self, clock=time.monotonic) -> None:
        self._state: Dict[str, tuple] = {}
        self.clock = clock

    def enter(self, who: str, what: str) -> None:
        self._state[who] = (what, self.clock())

    def exit(self, who: str) -> None:
        self._state.pop(who, None)

    def dump(self) -> str:
        now = self.clock()
        lines = []
        for who in sorted(self._state):
            what, since = self._state[who]
            lines.append(f"{who}: {what} [{now - since:.3f}s]")
        return "\n".join(lines)


GLOBAL_AWAITS = AwaitRegistry()
