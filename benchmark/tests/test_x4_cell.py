"""The four-chip cell `batch-encode-x4` (ISSUE 28) as data: its
configuration `configs/batch-256m-x4.json` beside the one-chip
`batch-256m.json` it was made from, and the three per-layer metrics that
read what a mesh adds (`crc_gather_pct`, `mesh_dispatcher_busy_pct`,
`mesh_devices_per_round`), each one file of `metrics/` read by the
readers that were there, checked against a canned node log
(`selftest/node_log_x4.txt`) and canned operation names with the
arithmetic done by hand. A log without batch lines, as
`node_log_phases.txt` is, makes each read nothing.

    python -m pytest benchmark/tests/test_x4_cell.py -q     (a second; no node, no JAX)
"""

import json
import math
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import readers  # noqa: E402
from harness.node import verb_reports  # noqa: E402

CELL = "batch-encode-x4"
# The canned window: three operations, each h2d 0.2 + launch 0.3 of
# wall 2.0; the mesh held 4, 4 and, in the third, 3 devices.
# Canned device seconds by operation name (HLO text, as a chip's trace
# names them): the collective 0.004 + 0.002 of 2.0 in all. The last
# name only MENTIONS the gather's result as an operand and is no
# collective.
OP_SECONDS = {
    "%all_gather.3 = u32[2,3,14]{2,1,0:T(4,128)S(1)} all-gather(%fusion.129), channel_id=1, "
    "replica_groups={{0,1},{2,3}}, dimensions={0}": 0.004,
    "%all-gather-start.1 = (u32[3,14], u32[2,3,14]) all-gather-start(%fusion.7)": 0.0015,
    "%all-gather-done.1 = u32[2,3,14] all-gather-done(%all-gather-start.1)": 0.0005,
    "%swar_apply_u32.1 = u32[3,4,131072] custom-call(%p0), custom_call_target=\"tpu_custom_call\"": 0.094,
    "%fusion.130 = u32[3,14]{1,0} fusion(%all_gather.3), kind=kLoop": 1.9,
}
WANT = {
    "crc_gather_pct": 100 * 0.006 / 2.0,
    "mesh_dispatcher_busy_pct": 100 * 3 * (0.2 + 0.3) / (3 * 2.0),
    "mesh_devices_per_round": (4 + 4 + 3) / 3,
}


def load(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def observed(log_name: str) -> dict:
    with open(os.path.join(BENCH, "selftest", log_name)) as f:
        reports = verb_reports(f.read(), "batch_generate")
    return {"reports": reports,
            "window": {"seconds": 7.0, "gib": 4.5, "requests": len(reports)},
            "trace": {"busy_s": 0.5, "window_s": 7.0, "op_seconds": OP_SECONDS}}


def manifest_entry(kind: str, name: str) -> dict:
    return next(m for m in load(ROOT, "BENCHMARK.json")[kind] if m["name"] == name)


# --- the configuration and the cell ------------------------------------------------


def test_config_is_batch_256m_on_four_chips():
    one, four = (load(BENCH, "configs", n + ".json") for n in ("batch-256m", "batch-256m-x4"))
    assert list(four) == list(one)
    for key in ("code", "guarantees", "needle_sizes"):
        assert four[key] == one[key], key  # word for word: no guarantee is weakened
    assert four["chips"] == 4 and one["chips"] == 1
    assert [v["collection"] for v in four["volumes"]] == [f"x{i}" for i in range(6)]
    assert {(v["mib"], v["rehearse_mib"]) for v in four["volumes"]} == {(256, 6)}
    # the driver's own recipe: vol = gcd(batch, devices), stripe = devices / vol
    assert math.gcd(len(four["volumes"]), four["chips"]) == 2
    for key in ("needle_sizes", "rewrite", "disk"):
        assert four["assumed"][key] == one["assumed"][key], key


def test_manifest_entries_of_the_cell():
    entry = manifest_entry("configs", "batch-256m-x4")
    config = load(ROOT, entry["file"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == list(config["reduced"])
    cell = manifest_entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "batch-256m-x4", "batch-encode-loop", config["chips"])
    traffic = load(BENCH, "traffic", cell["traffic"] + ".json")
    assert traffic["rpc"] == "VolumeEcShardsBatchGenerate" and traffic["concurrency"] == 1
    manifest = load(ROOT, "BENCHMARK.json")
    assert manifest["workloads"][-1] == cell and manifest["configs"][-1] == entry
    assert CELL in manifest_entry("end_to_end", "ec_gbps")["workloads"]
    reported = [m["name"] for m in manifest["per_layer"] if CELL in m["workloads"]]
    assert sorted(reported) == sorted([
        "device_idle_pct.ec", "dispatch_s_per_gib", "encode_kernel_roofline",
        "handler_overhead_pct", "read_s_per_gib", "swar_kernel_roofline",
        "write_s_per_gib", "writeback_s_per_gib", *WANT])


# --- the three metrics ---------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_file_and_manifest_agree(name):
    metric = readers.load_metric(name)
    entry = manifest_entry("per_layer", name)
    for key in ("name", "unit", "better", "layer", "moves", "source", "workloads"):
        assert metric[key] == entry[key], key
    assert entry["moves"] == "ec_gbps" and entry["workloads"] == [CELL]
    obs = observed("node_log_x4.txt")
    for spec in metric["num"] + metric.get("den", []):
        readers.term(spec, obs)  # raises on a term no reader knows


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_reads_the_hand_computed_value(name):
    obs = observed("node_log_x4.txt")
    assert len(obs["reports"]) == 3
    got = readers.read_metric(readers.load_metric(name), obs)
    assert got == pytest.approx(WANT[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_reads_nothing_where_there_is_nothing_to_read(name):
    """No batch line in the log, no collective among the operations: the
    metric is left out of the line, never 0."""
    obs = observed("node_log_phases.txt")
    assert obs["reports"] == []
    obs["trace"]["op_seconds"] = {k: v for k, v in OP_SECONDS.items() if "all-gather" not in k}
    assert readers.read_metric(readers.load_metric(name), obs) is None


def test_mesh_devices_is_absent_from_a_parents_line():
    """A program without the field (every commit before ISSUE 28) reports
    the dispatcher's share and no device count."""
    obs = observed("node_log_x4.txt")
    for rep in obs["reports"]:
        del rep["mesh_devices"]
    assert readers.read_metric(readers.load_metric("mesh_devices_per_round"), obs) is None
    assert readers.read_metric(readers.load_metric("mesh_dispatcher_busy_pct"), obs) == \
        pytest.approx(WANT["mesh_dispatcher_busy_pct"])
