"""From a profiler trace (`.xplane.pb`) to numbers, with nothing but JAX's
own reader (`jax.profiler.ProfileData`), under JAX_PLATFORMS=cpu and only
after the node has exited.

Which planes are devices and which of their lines hold operations is
data (`trace_names.json`, keyed by platform). Device busy time is the
union of the intervals in which an operation runs on a device plane,
averaged over the device planes that ran anything; idle is the rest of
the traced slice.
"""

from __future__ import annotations

import glob
import json
import os
import re

HARNESS = os.path.dirname(os.path.abspath(__file__))


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, merged (start, end) intervals."""
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def reduce_trace(xplane_path: str, platform: str, window_s: float) -> dict:
    """{"busy_s", "window_s", "devices", "op_seconds": {name: s},
    "gaps": [(start_s, seconds)], "planes": [...]}; busy_s is None when
    no operation ran on any device plane."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    with open(os.path.join(HARNESS, "trace_names.json")) as f:
        names = json.load(f)[platform]
    plane_re, line_re = re.compile(names["plane"]), re.compile(names["op_line"])
    data = ProfileData.from_file(xplane_path)
    op_seconds: dict[str, float] = {}
    busy_per_device, gaps, planes = [], [], []
    for plane in data.planes:
        lines = list(plane.lines)
        planes.append({"plane": plane.name, "lines": [ln.name for ln in lines]})
        if not plane_re.search(plane.name):
            continue
        intervals = []
        for line in lines:
            if not line_re.search(line.name):
                continue
            for ev in line.events:
                if ev.duration_ns <= 0:
                    continue
                intervals.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                op_seconds[ev.name] = op_seconds.get(ev.name, 0.0) + ev.duration_ns / 1e9
        if not intervals:
            continue
        merged = union(intervals)
        busy_per_device.append(sum(e - s for s, e in merged) / 1e9)
        t_first = merged[0][0]
        gaps += [((a_end - t_first) / 1e9, (b_start - a_end) / 1e9)
                 for (_, a_end), (b_start, _) in zip(merged, merged[1:])]
    busy = sum(busy_per_device) / len(busy_per_device) if busy_per_device else None
    return {
        "busy_s": busy,
        "window_s": window_s,
        "devices": len(busy_per_device),
        "op_seconds": op_seconds,
        "gaps": sorted(gaps, key=lambda g: -g[1])[:10],
        "planes": planes,
    }


def matching_seconds(op_seconds: dict[str, float], pattern: str) -> float | None:
    """Device seconds of the operations whose name matches; None where
    none does (nothing to read is not zero)."""
    rx = re.compile(pattern)
    hits = [s for name, s in op_seconds.items() if rx.search(name)]
    return sum(hits) if hits else None
