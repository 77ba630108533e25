"""The repair cell `rebuild-1data` (ISSUE 32) as data: its configuration
`configs/rebuild-1g.json` beside `seal-1g.json`, whose shapes it keeps,
its traffic and its manifest entries; and a rehearsal-size run of the
cell on the CPU (`--rehearse`), once sound and once with each control of
`traffic/rebuild_loop.py`: `correct` has to come out true for the first
and false for every other, by the number the control is there to move.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rebuild_cell.py -q   (about a minute)
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

CELL, CONFIG = "rebuild-1data", "rebuild-1g"


def load(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def manifest_entry(kind: str, name: str) -> dict:
    return next(m for m in load(ROOT, "BENCHMARK.json")[kind] if m["name"] == name)


def test_config_keeps_the_shapes_of_seal_1g():
    seal, repair = (load(BENCH, "configs", n + ".json") for n in ("seal-1g", CONFIG))
    for key in ("chips", "layout", "code", "volumes", "needle_sizes"):
        assert repair[key] == seal[key], key  # word for word: the source's shapes
    assert repair["failure"]["lost_shards"] == [3]  # one DATA shard, fixed
    assert list(repair["reduced"]) == list(seal["reduced"]) == ["volumeSizeLimitMB", "nodes"]
    assert {"needle_sizes", "disk", "lost_shards", "relose"} <= set(repair["assumed"])
    assert len(repair["guarantees"]) == 5 and "state" in repair


def test_manifest_entries_of_the_cell():
    entry = manifest_entry("configs", CONFIG)
    config = load(ROOT, entry["file"])
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json" and config["name"] == CONFIG
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == list(config["reduced"])
    cell = manifest_entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "rebuild-loop", config["chips"])
    assert len(cell["why"]) <= 200
    traffic = load(BENCH, "traffic", cell["traffic"] + ".json")
    assert traffic == {"generator": "rebuild_loop", "rpc": "VolumeEcShardsRebuild",
                       "concurrency": 1, "trace_ops": 3, "read_back": 28}
    manifest = load(ROOT, "BENCHMARK.json")
    assert CELL in manifest_entry("end_to_end", "ec_gbps")["workloads"]
    reported = [m["name"] for m in manifest["per_layer"] if CELL in m["workloads"]]
    assert sorted(reported) == sorted([
        "device_idle_pct.ec", "dispatch_s_per_gib", "handler_overhead_pct",
        "read_s_per_gib", "write_s_per_gib", "writeback_s_per_gib",
        "reserve_s_per_gib", "reserve_done_s_per_gib",
        "rebuild_kernel_roofline", "rebuild_swar_roofline",
        "rebuild_dispatcher_busy_pct", "rebuild_launches_per_gib"])
    four_chips = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(manifest["workloads"]) == 4 and len(four_chips) == 1


CASES = [
    ([], True, None),
    (["--control", "cauchy"], False, "rebuilt_differs_from_decode"),
    (["--control", "crc32"], False, "ecc_crcs_differ"),
    (["--control", "not_rebuilt"], False, "shards_not_rewritten"),
]


@pytest.mark.parametrize("extra,correct,number", CASES,
                         ids=["sound", "cauchy", "crc32", "not_rebuilt"])
def test_correct_comes_out(extra, correct, number):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 32), "--seconds", "2", "--trace", "0", "--rehearse", *extra],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is correct, line["compared"]
    assert line["failed"] == 0 and line["attempted"] >= 2
    if number:
        assert line["compared"][number]["value"] > line["compared"][number]["limit"]
        # a control breaks one guarantee: the operations themselves were sound
        for name in ("ops_failed", "ops_without_report", "ops_wrong_shards",
                     "dat_needles_differ", "survivors_rewritten"):
            assert line["compared"][name]["value"] == 0, name
    assert all(name.startswith("rehearsal.") for name in line["metrics"])
