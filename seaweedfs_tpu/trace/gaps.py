"""Put each gap of a device trace down to what the host did in it.

    python -m seaweedfs_tpu.trace.gaps <xplane.pb> [min_gap_ms]

Reads a profiler trace (`jax.profiler.start_trace`; the benchmark's
`run.py --keep DIR` leaves one as `<cell>-<seed>.xplane.pb`) with JAX's
own reader, on the CPU and after the traced process has exited. The
device is busy wherever an operation runs on ANY device plane (the
union: four chips list a gap once); a gap is the time between two such
bursts. For every gap of `min_gap_ms` (default 20) or more it prints the
overlap of every `ec.*` host annotation with the gap, summed by name
over all threads (docs/TRACING.md: the phases `ec.op.*`, the pool stages
`ec.read` ... `ec.write`, and `ec.wait.*`, where a thread of the stream
driver's shell stood waiting for another), so eight writers at the latch
through a 30 ms gap read `ec.wait.latch` 240. Below the gaps: where each
phase started, and every annotation's count and thread-seconds.
"""

from __future__ import annotations

import os
import re
import sys

# where operations run: a chip's planes, or (a trace made on the CPU
# backend, as the tests make one) the CPU client's own threads
_DEVICE = (re.compile(r"^/device:TPU:\d+$"), re.compile(r"^XLA Ops$"))
_CPU = (re.compile(r"^/host:CPU$"), re.compile(r"XLAPjRtCpuClient"))
_PHASES = ("ec.op.head", "ec.op.dispatch", "ec.op.drain", "ec.op.write_tail",
           "ec.op.flush", "ec.publish")


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def read(path: str) -> tuple[list[tuple[int, int]], list[tuple[str, int, int]], int]:
    """(device bursts on the union of the device planes, the `ec.*` host
    events as (name, start, end), device planes that ran anything); ns."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    on_chip = any(_DEVICE[0].search(p.name) for p in planes)
    plane_re, line_re = _DEVICE if on_chip else _CPU
    device: list[tuple[int, int]] = []
    host: list[tuple[str, int, int]] = []
    ran = 0
    for plane in planes:
        before = len(device)
        for line in plane.lines:
            if plane_re.search(plane.name) and line_re.search(line.name):
                device += [(e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events if e.duration_ns > 0]
            elif plane.name.startswith("/host:"):
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.name.startswith("ec.")]
        ran += len(device) > before
    return _union(device), host, ran


def report(path: str, min_gap_ms: float = 20.0) -> dict:
    """The trace reduced: seconds throughout, gaps longest first, each
    `{"at_s", "seconds", "cover": {name: thread-seconds inside it}}`."""
    bursts, host, planes = read(path)
    if not bursts:
        raise SystemExit(f"no operation ran on a device plane in {path}")
    t0 = bursts[0][0]
    gaps = []
    for (_, a_end), (b_start, _) in zip(bursts, bursts[1:]):
        if b_start - a_end < min_gap_ms * 1e6:
            continue
        cover: dict[str, float] = {}
        for name, start, end in host:
            overlap = min(b_start, end) - max(a_end, start)
            if overlap > 0:
                cover[name] = cover.get(name, 0.0) + overlap / 1e9
        gaps.append({"at_s": (a_end - t0) / 1e9, "seconds": (b_start - a_end) / 1e9,
                     "cover": dict(sorted(cover.items(), key=lambda kv: -kv[1]))})
    totals: dict[str, list] = {}
    for name, start, end in host:
        entry = totals.setdefault(name, [0, 0.0])
        entry[0] += 1
        entry[1] += (end - start) / 1e9
    return {
        "device_planes": planes,
        "busy_s": sum(e - s for s, e in bursts) / 1e9,
        "span_s": (bursts[-1][1] - t0) / 1e9,
        "bursts": len(bursts),
        "gaps": sorted(gaps, key=lambda g: -g["seconds"]),
        "phases": {name: sorted(((s - t0) / 1e9, (e - s) / 1e9)
                                for n, s, e in host if n == name)
                   for name in _PHASES},
        "annotations": dict(sorted(totals.items(), key=lambda kv: -kv[1][1])),
    }


def main(argv: list[str]) -> int:
    if not 1 <= len(argv) <= 2:
        print(__doc__.split("\n\n")[1].strip(), file=sys.stderr)
        return 2
    rep = report(argv[0], float(argv[1]) if len(argv) > 1 else 20.0)
    print(f"device busy {rep['busy_s']:.4f} s of {rep['span_s']:.4f} s on the union "
          f"of {rep['device_planes']} plane(s); {rep['bursts']} bursts")
    for name, events in rep["phases"].items():
        if events:
            print(name, "at s", [round(at, 3) for at, _ in events],
                  "for ms", [round(s * 1e3, 1) for _, s in events])
    for gap in rep["gaps"][:14]:
        cover = {k: round(v * 1e3, 1) for k, v in list(gap["cover"].items())[:10]}
        print(f"gap {gap['seconds'] * 1e3:7.1f} ms at {gap['at_s']:.3f} s: {cover}")
    print("annotations (count, thread-seconds):",
          {k: (n, round(s, 4)) for k, (n, s) in rep["annotations"].items()})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
