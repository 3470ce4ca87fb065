"""The profiler's trace, reduced to the numbers the per-layer metrics read.

:func:`load` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain lists of ``(name, start_ns, duration_ns)`` per line per plane;
:func:`reduce` works on that form alone, so it is checked on a small
recorded trace (``fixtures/``) without a chip.

The window is the host span ``bench.window`` that the harness opens
around the measured loop. On each device plane, busy time is the union
of the intervals of its ``XLA Ops`` events inside the window; idle is
the rest. Gaps in the union of all devices' ops are split where a
``bench.*`` host span opens or closes, and each piece is named by the
innermost span open over it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]   # name, start_ns, duration_ns

WINDOW_SPAN = "bench.window"
#: the device line whose events are single operations
OPS_LINE = "XLA Ops"


def load(log_dir: str) -> dict:
    """``{"planes": [{"name", "lines": [{"name", "events"}]}]}`` of the
    newest trace under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no trace under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def is_device_plane(name: str) -> bool:
    """An accelerator's plane (``/device:TPU:0``), not the host's or a
    runtime's (``/device:CUSTOM:...``)."""
    return re.match(r"^/device:(TPU|GPU):\d+$", name) is not None


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Disjoint, sorted union of ``(start, end)`` intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle stretches of ``[lo, hi]`` between the disjoint ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(event_name: str) -> str:
    """An operation's own name: the HLO text left of `` = `` without its
    ``%`` (``%sort.0 = (...) sort(...)`` -> ``sort.0``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def is_all_to_all(event_name: str) -> bool:
    return op_name(event_name).startswith("all-to-all")


def _host_spans(trace: dict) -> List[Event]:
    return [ev for p in trace["planes"] if not is_device_plane(p["name"])
            for line in p["lines"] for ev in line["events"]
            if ev[0].startswith("bench.")]


def _innermost(spans: List[Event], t: float) -> str:
    best = None
    for name, s, d in spans:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "(no bench span)"


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy and idle time per device, the operations' device time
    (averaged over devices) and idle gaps inside the window, in seconds.
    A gap is idle on every device at once."""
    spans = _host_spans(trace)
    wins = [ev for ev in spans if ev[0] == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    _, w0, wd = max(wins, key=lambda ev: ev[2])
    w1 = w0 + wd
    devices = []
    op_s: Dict[str, float] = {}
    n_dev = max(1, sum(is_device_plane(p["name"]) for p in trace["planes"]))
    all_ops = []
    for p in trace["planes"]:
        if not is_device_plane(p["name"]):
            continue
        ops = [ev for line in p["lines"] if line["name"] == OPS_LINE
               for ev in line["events"]]
        iv = clip([(s, s + d) for _, s, d in ops], w0, w1)
        busy = union(iv)
        a2a = sum(min(s + d, w1) - max(s, w0) for n, s, d in ops
                  if is_all_to_all(n) and s + d > w0 and s < w1)
        devices.append({"plane": p["name"], "busy_s": covered(busy) * 1e-9,
                        "n_ops": len(iv), "all_to_all_s": a2a * 1e-9})
        for name, s, d in ops:
            if s + d > w0 and s < w1:
                key = op_name(name)
                op_s[key] = op_s.get(key, 0.0) + (
                    min(s + d, w1) - max(s, w0)) * 1e-9 / n_dev
        all_ops.extend(iv)
    window_s = wd * 1e-9
    idle: Dict[str, float] = {}
    inner = [ev for ev in spans if ev[0] != WINDOW_SPAN]
    cuts = sorted({t for _, s, d in inner for t in (s, s + d)})
    for s, e in gaps(union(all_ops), w0, w1):
        # split the gap where a host span opens or closes inside it
        points = [s] + [t for t in cuts if s < t < e] + [e]
        for a, b in zip(points, points[1:]):
            label = _innermost(inner, (a + b) / 2)
            idle[label] = idle.get(label, 0.0) + (b - a) * 1e-9
    return {
        "window_s": window_s,
        "devices": devices,
        "busy_s": sum(d["busy_s"] for d in devices) / n_dev,
        "busy_s_total": sum(d["busy_s"] for d in devices),
        "idle_pct": [100.0 * (1.0 - d["busy_s"] / window_s)
                     for d in devices] if window_s > 0 else [],
        "device_ops": sorted(op_s.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1])[:top],
    }
