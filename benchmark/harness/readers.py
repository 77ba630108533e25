"""Per-layer metrics from data. A metric is one file
`benchmark/metrics/<name>.json`:

    {"unit": "s/GiB", "better": "lower", "layer": "stream driver",
     "moves": "ec_gbps", "source": "program_span",
     "num": ["report:device_s"], "den": ["window:gib"], "scale": 1}

value = scale * sum(num) / sum(den) (no `den`: 1), or scale * (1 - that)
with `"one_minus": true`. A term names where its number is read, by one of
three readers (report, trace, window):

    report:<field>        sum of the field over the node's own
                          `ec.<verb> ... report={...}` lines of the window
    trace:busy_s | trace:window_s | trace:op_s:<regex> |
    trace:floor_s:<work>  from the reduced profiler trace; floor_s is the
                          least seconds for the traced slice's <work>
                          bytes through HBM at the device kind's peak
    window:seconds | window:gib | window:requests
                          the generator's own counts

A term with nothing to read makes the reader return None, and the
metric is left out of the result: never 0 for a share or a rate.
"""

from __future__ import annotations

import json
import os

from harness import roofline, trace_reduce

METRICS_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "metrics")


def load_metric(name: str) -> dict:
    with open(os.path.join(METRICS_DIR, name + ".json")) as f:
        return json.load(f)


def read_report(field: str, obs: dict) -> float | None:
    values = [rep[field] for rep in obs["reports"] if field in rep]
    return float(sum(values)) if values else None


def read_trace(what: str, obs: dict) -> float | None:
    tr = obs.get("trace")
    if not tr or tr.get("busy_s") is None:
        return None
    if what in ("busy_s", "window_s"):
        return tr[what]
    kind, _, arg = what.partition(":")
    if kind == "op_s":
        return trace_reduce.matching_seconds(tr["op_seconds"], arg)
    if kind == "floor_s":
        work = (obs.get("traced_work") or {}).get(arg)
        if not work or obs.get("rehearse"):  # no peak of a CPU is in the table
            return None
        return work / roofline.peaks(obs["device_kind"])["hbm_bytes_per_s"]
    raise ValueError(f"unknown trace term {what!r}")


def term(spec: str, obs: dict) -> float | None:
    kind, _, what = spec.partition(":")
    if kind == "report":
        return read_report(what, obs)
    if kind == "trace":
        return read_trace(what, obs)
    if kind == "window":
        return obs["window"].get(what)
    raise ValueError(f"unknown term {spec!r}")


def read_metric(metric: dict, obs: dict) -> float | None:
    sums = []
    for side in ("num", "den"):
        values = [term(spec, obs) for spec in metric.get(side, [])]
        if any(v is None for v in values):
            return None
        sums.append(sum(values))
    if "den" not in metric:
        sums[1] = 1.0
    if sums[1] == 0:
        return None
    value = sums[0] / sums[1]
    if metric.get("one_minus"):
        value = 1.0 - value
    return value * metric.get("scale", 1)
