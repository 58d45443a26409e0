"""Reduction of a `jax.profiler` trace (`*.xplane.pb`) to what the
per-layer metrics read: the seconds in which an operation ran on each
device, the operations that took most time, and the device's idle gaps.

The reduction works on plain `(name, start_ns, duration_ns)` tuples so
that `selfcheck/test_trace_reduce.py` can hold it against a synthetic
trace with a known answer; `load_xplane` turns a recorded file into them.
"""

from __future__ import annotations

import collections
import glob
import os

MARK = "bench_mark"          # the host annotation that ties the clocks
OPS_LINE = "XLA Ops"         # device operations; NESTED on the TPU: a
                             # `while` holds its body's ops, so only the
                             # union of the intervals is time
MODULES_LINE = "XLA Modules"  # one event per launched program (jit_<name>)


def newest_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path: str) -> dict:
    """{"devices": {plane: {line: [(name, start_ns, dur_ns)]}},
        "mark_ns": start of the MARK annotation or None,
        "summary": [(plane, line, events)]}."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, summary, mark_ns = {}, [], None
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            events = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                      for ev in line.events]
            summary.append((plane.name, line.name, len(events)))
            if is_device:
                devices.setdefault(plane.name, {})[line.name] = events
            elif mark_ns is None:
                for name, start, _dur in events:
                    if name == MARK:
                        mark_ns = start
                        break
    return {"devices": devices, "mark_ns": mark_ns, "summary": summary}


def union_intervals(events):
    """Sorted, merged [start, end) intervals of the events."""
    out = []
    for start, end in sorted((s, s + d) for _n, s, d in events if d > 0):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def op_events(lines: dict):
    """The events that say when the device ran an operation: the
    per-operation line where the trace has one, else every line but the
    step markers."""
    if OPS_LINE in lines:
        return lines[OPS_LINE]
    return [ev for name, evs in lines.items()
            if name not in ("Steps", MODULES_LINE) for ev in evs]


def reduce_trace(loaded: dict, span_ns) -> dict:
    """`span_ns`: (start, end) of the traced span on the trace's clock.
    Returns per-chip busy seconds, the top programs and the gaps:
      busy_s        mean over the device planes of the union of op time
      per_device    {plane: busy seconds}
      device_ops    [[name, seconds]] ten largest, summed over devices,
                    by program (MODULES_LINE, which does not nest) where
                    present, else by op
      gaps          [(start_ns, end_ns)] idle gaps of the first device
                    plane inside the span, longest first (at most 50)
    """
    lo, hi = span_ns
    per_device, by_name, gaps = {}, collections.Counter(), []
    for i, (plane, lines) in enumerate(sorted(loaded["devices"].items())):
        ops = [(n, max(s, lo), min(s + d, hi) - max(s, lo))
               for n, s, d in op_events(lines) if s < hi and s + d > lo]
        merged = union_intervals(ops)
        per_device[plane] = sum(e - s for s, e in merged) / 1e9
        named = lines.get(MODULES_LINE) or op_events(lines)
        for n, s, d in named:
            if s < hi and s + d > lo:
                by_name[n] += (min(s + d, hi) - max(s, lo)) / 1e9
        if i == 0:
            edge = lo
            for s, e in merged:
                if s > edge:
                    gaps.append((edge, s))
                edge = max(edge, e)
            if hi > edge:
                gaps.append((edge, hi))
    n_dev = max(len(per_device), 1)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(per_device.values()) / n_dev,
        "per_device": per_device,
        "device_ops": [[n, s] for n, s in by_name.most_common(10)],
        "gaps": gaps[:50],
    }
