"""The per-layer metrics that read the phases and the device split of an
encode operation off the node's report line (`op_*_s_per_gib`,
`flush_s_per_gib`, `publish_s_per_gib`, `h2d_s_per_gib`,
`launch_s_per_gib`): each is one file of `metrics/`, read
by the readers that were there, checked here against a canned node log
(`selftest/node_log_phases.txt`) with the arithmetic done by hand. A
program whose report line lacks the fields, as every commit before them
does, makes each read nothing.

    python -m pytest benchmark/tests/test_phase_metrics.py -q     (a second; no node, no JAX)
"""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import readers  # noqa: E402
from harness.node import verb_reports  # noqa: E402

GIB = 2.0  # the canned window: two operations of 1 GiB
# two report lines, each: head 0.05 + dispatch span 0.7 + drain 0.3 +
# write tail 0.1 + flush 0.15 = wall 1.3; publish 0.02; h2d 0.25 +
# launch 0.5 = device_s 0.75
WANT = {
    "op_head_s_per_gib": 2 * 0.05 / GIB,
    "op_dispatch_span_s_per_gib": 2 * 0.7 / GIB,
    "op_drain_s_per_gib": 2 * 0.3 / GIB,
    "op_write_tail_s_per_gib": 2 * 0.1 / GIB,
    "flush_s_per_gib": 2 * 0.15 / GIB,
    "publish_s_per_gib": 2 * 0.02 / GIB,
    "h2d_s_per_gib": 2 * 0.25 / GIB,
    "launch_s_per_gib": 2 * 0.5 / GIB,
}


def observed(log_name: str) -> dict:
    with open(os.path.join(BENCH, "selftest", log_name)) as f:
        reports = verb_reports(f.read(), "generate")
    assert len(reports) == 2, reports
    return {"reports": reports, "window": {"seconds": 5.0, "gib": GIB, "requests": 2},
            "trace": None}


def manifest_entry(name: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return next(m for m in json.load(f)["per_layer"] if m["name"] == name)


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_file_and_manifest_agree(name):
    metric = readers.load_metric(name)
    entry = manifest_entry(name)
    for key in ("name", "unit", "better", "layer", "moves", "source", "workloads"):
        assert metric[key] == entry[key], key
    assert entry["moves"] == "ec_gbps" and entry["source"] == "program_span"
    assert entry["workloads"] == ["encode-1g", "batch-encode-256m"]
    obs = observed("node_log_phases.txt")
    for spec in metric["num"] + metric["den"]:
        readers.term(spec, obs)  # raises on a term no reader knows


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_reads_the_hand_computed_value(name):
    got = readers.read_metric(readers.load_metric(name), observed("node_log_phases.txt"))
    assert got == pytest.approx(WANT[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_reads_nothing_where_the_fields_are_absent(name):
    """`node_log.txt` is the report line as PR 25's program wrote it."""
    assert readers.read_metric(readers.load_metric(name), observed("node_log.txt")) is None


def test_the_phases_of_the_canned_log_partition_its_wall():
    obs = observed("node_log_phases.txt")
    parts = ("head_s", "dispatch_span_s", "drain_s", "write_tail_s", "flush_s")
    assert sum(readers.term(f"report:{f}", obs) for f in parts) == pytest.approx(
        readers.term("report:wall_s", obs))
    assert readers.term("report:h2d_s", obs) + readers.term("report:launch_s", obs) == \
        pytest.approx(readers.term("report:device_s", obs))
