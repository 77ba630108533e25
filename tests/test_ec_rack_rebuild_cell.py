"""The rack repair (ISSUE 34: configuration `rack-rebuild-1g`, cell
`rack-rebuild-4lost`): three volume servers in one process under one
master, the 14 shards of a sealed volume placed as the configuration's
file says, the four shards of the lost server gone, and
`VolumeEcShardsRebuild` on the holder of four, which streams six of its
ten survivors from the two others. Held to the bytes before the loss
and to the benchmark's plain numpy decode of the ten survivors read
from the three servers' directories; the report line, the root span and
the `ec.remote_read` annotation of the gather; the cell's three
per-layer metrics as files; a `--rehearse` run of the cell and of each
of its controls.

Everything runs on the CPU backend (the device stage then takes its
bit-matmul arm): what is asserted is bytes, counts and bookkeeping,
never a device time."""

import importlib
import json
import logging
import os
import subprocess
import sys
import threading
import time
import urllib.request

import grpc
import numpy as np
import pytest

from seaweedfs_tpu import trace
from seaweedfs_tpu.ec import ec_files, ec_stream
from seaweedfs_tpu.pb import master_pb2, rpc, volume_pb2
from seaweedfs_tpu.scrub.arbiter import BandwidthArbiter, set_arbiter
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.util.availability import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIB = 1 << 20
CELL, CONFIG, MIX = "rack-rebuild-4lost", "rack-rebuild-1g", "rack-rebuild-loop"
# three stripe rows of upstream's 1 MiB blocks: shard files of 3 MiB, the
# tail of the cell's 103 MiB shards under the gather's 4 MiB tiles
SHARD_BYTES = 3 * MIB
PHASE_FIELDS = ("head_s", "dispatch_span_s", "drain_s", "write_tail_s", "flush_s")
GATHER_FIELDS = ("remote_survivors", "survivor_bytes_remote", "rebuilt_bytes",
                 "arbiter_wait_s", "remote_fetches", "remote_fetches_dataplane")


def _json(*parts: str) -> dict:
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config() -> dict:
    return _json("benchmark", "configs", CONFIG + ".json")


@pytest.fixture(scope="module")
def bench():
    """benchmark/harness as benchmark/run.py imports it."""
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(os.path.join(REPO, "benchmark"))
        names = ("reference", "reference_rebuild", "roofline_rebuild", "readers", "node")
        yield type("Harness", (), {
            name: importlib.import_module("harness." + name) for name in names})


# --- the power-of-two spans of a short tile -----------------------------------------


@pytest.mark.parametrize("length,tile,want", [
    (4 * MIB, 4 * MIB, [4 * MIB]),              # a whole tile is one span
    (3 * MIB, 4 * MIB, [2 * MIB, MIB]),         # the cell's tail
    (MIB, 4 * MIB, [MIB]),
    (512 * 1024, 512 * 1024, [512 * 1024]),     # a whole small tile
    (300 * 1024 + 5, 512 * 1024, [256 * 1024, 44 * 1024 + 5]),  # under an eighth: one odd rest
    (7, 4 * MIB, [7]),
], ids=["whole", "tail-3m", "tail-1m", "local", "odd-rest", "tiny"])
def test_a_short_tile_goes_as_power_of_two_spans(length, tile, want):
    from seaweedfs_tpu.ec import crc_kernel

    spans = ec_stream._pow2_spans(1000, length, tile)
    assert [n for _, n in spans] == want
    assert spans[0][0] == 1000 and all(
        a + n == b for (a, n), (b, _) in zip(spans, spans[1:]))
    for _, n in spans[:-1]:
        assert crc_kernel.crc_supported(n)


# --- three servers, the configuration's placement, the lost four -----------------------


def _wait_for(what: str, fn, seconds: float = 20.0):
    deadline = time.time() + seconds
    while time.time() < deadline:
        got = fn()
        if got:
            return got
        time.sleep(0.05)
    raise AssertionError(f"never saw {what}")


def _located(master, vid: int) -> dict[int, list[str]]:
    with rpc.dial(f"127.0.0.1:{master.grpc_port}") as ch:
        resp = rpc.master_stub(ch).LookupEcVolume(
            master_pb2.LookupEcVolumeRequest(volume_id=vid), timeout=5)
    return {e.shard_id: sorted(loc.url for loc in e.locations)
            for e in resp.shard_id_locations if e.locations}


class Annotations:
    """Stands in for jax.profiler.TraceAnnotation: (name, thread) of
    every annotation opened."""

    opened: list[tuple[str, str]] = []

    def __init__(self, name, **kw):
        self.opened.append((name, threading.current_thread().name))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture(scope="module")
def repaired(tmp_path_factory, config, bench):
    """One operation of the cell: a volume of three stripe rows through
    ec.encode to its end on A, C's and D's shards moved to them with
    the program's verbs, B's four unmounted and deleted, the rebuild on
    A, mount. A's codec is tpu and the verb is steered the chip's way
    (the device stage), as tests/test_ec_rebuild_cell.py does."""
    import jax

    placement = {k: v for k, v in config["placement"].items() if k != "why"}
    lost = config["failure"]["lost_shards"]
    master = MasterServer(port=free_port(), volume_size_limit_mb=64)
    master.start()
    servers: dict[str, VolumeServer] = {}

    def start(name: str, codec: str) -> VolumeServer:
        vs = servers[name] = VolumeServer(
            [str(tmp_path_factory.mktemp("rack" + name))], port=free_port(),
            master=f"127.0.0.1:{master.port}", heartbeat_interval=0.2,
            max_volume_counts=[100], rack=name, ec_codec=codec,
        )
        vs.start()
        return vs

    a = start("A", "tpu")
    _wait_for("A in the topology", lambda: master.topology.data_nodes())
    handler = logging.Handler()
    handler.lines = []
    handler.emit = lambda record: handler.lines.append("I] " + record.getMessage())
    logger = logging.getLogger("seaweedfs_tpu")
    logger.addHandler(handler)
    # the arbiter ON, with room: the reads go through its accounting
    previous = set_arbiter(BandwidthArbiter(total_bytes_s=64e9))
    try:
        needles = {}
        rng = np.random.default_rng(34)
        with urllib.request.urlopen(  # one volume for the collection, as the loader grows it
            f"http://127.0.0.1:{master.port}/vol/grow?collection=big&count=1", timeout=10
        ) as r:
            assert json.loads(r.read())["count"] == 1
        for n in range(7):  # 24.5 MB in one volume: three rows of 10 MiB
            with urllib.request.urlopen(
                f"http://127.0.0.1:{master.port}/dir/assign?collection=big", timeout=10
            ) as r:
                assign = json.loads(r.read())
            body = rng.bytes(3_500_000 + n)
            urllib.request.urlopen(urllib.request.Request(
                f"http://{assign['url']}/{assign['fid']}", data=body, method="POST"),
                timeout=30).close()
            needles[assign["fid"]] = body
        vids = {int(fid.split(",")[0]) for fid in needles}
        assert len(vids) == 1
        vid = vids.pop()
        holders = {name: start(name, "native") for name in placement
                   if name != "A" and placement[name] != lost}
        with pytest.MonkeyPatch.context() as mp, \
                grpc.insecure_channel(f"127.0.0.1:{a.grpc_port}") as ch:
            mp.setattr(ec_files, "_use_stream_driver", lambda rs: True)
            stub = rpc.volume_stub(ch)
            stub.VolumeMarkReadonly(volume_pb2.VolumeMarkReadonlyRequest(volume_id=vid))
            stub.VolumeEcShardsGenerate(volume_pb2.VolumeEcShardsGenerateRequest(
                volume_id=vid, collection="big"))
            stub.VolumeEcShardsMount(volume_pb2.VolumeEcShardsMountRequest(
                volume_id=vid, collection="big", shard_ids=list(range(14))))
            stub.VolumeDelete(volume_pb2.VolumeDeleteRequest(volume_id=vid))
            base = a.store.find_ec_volume(vid).base_name
            name = os.path.basename(base)
            assert os.path.getsize(base + ".ec00") == SHARD_BYTES
            before = {i: np.fromfile(base + ec_files.to_ext(i), dtype=np.uint8)
                      for i in range(14)}

            def drop(ids):
                stub.VolumeEcShardsUnmount(volume_pb2.VolumeEcShardsUnmountRequest(
                    volume_id=vid, shard_ids=ids))
                stub.VolumeEcShardsDelete(volume_pb2.VolumeEcShardsDeleteRequest(
                    volume_id=vid, collection="big", shard_ids=ids))

            where = {i: os.path.dirname(base) for i in placement["A"] + lost}
            want = {i: [f"127.0.0.1:{a.port}"] for i in placement["A"]}
            for peer, vs in holders.items():
                ids = placement[peer]
                with grpc.insecure_channel(f"127.0.0.1:{vs.grpc_port}") as pch:
                    pstub = rpc.volume_stub(pch)
                    pstub.VolumeEcShardsCopy(volume_pb2.VolumeEcShardsCopyRequest(
                        volume_id=vid, collection="big", shard_ids=ids,
                        copy_ecx_file=True, source_data_node=f"127.0.0.1:{a.port}"))
                    pstub.VolumeEcShardsMount(volume_pb2.VolumeEcShardsMountRequest(
                        volume_id=vid, collection="big", shard_ids=ids))
                drop(ids)
                where.update({i: vs.store.locations[0].directory for i in ids})
                want.update({i: [f"127.0.0.1:{vs.port}"] for i in ids})
            drop(lost)
            _wait_for("the master's lookup to name the holders and nobody for the lost",
                      lambda: _located(master, vid) == want)
            for i in lost:
                assert not os.path.exists(base + ec_files.to_ext(i))
            survivors = [i for i in range(14) if i not in lost]
            paths = {i: os.path.join(where[i], name + ec_files.to_ext(i)) for i in range(14)}
            mtimes = {i: os.stat(paths[i]).st_mtime_ns for i in survivors}
            # the reference's decode, of the survivors where they lie
            gathered = tmp_path_factory.mktemp("gathered")
            for i in survivors:
                os.link(paths[i], str(gathered / (name + ec_files.to_ext(i))))
            decoded = bench.reference_rebuild.decode(str(gathered / name), lost)
            trace.reset()
            del handler.lines[:]
            del Annotations.opened[:]
            mp.setattr(jax.profiler, "TraceAnnotation", Annotations)
            resp = stub.VolumeEcShardsRebuild(
                volume_pb2.VolumeEcShardsRebuildRequest(volume_id=vid, collection="big"),
                metadata=((trace.TRACE_HEADER, "00000000000000ab:000000cd:serve"),),
            )
            spans = trace.debug_payload(n=256)["recent"]
            lines = list(handler.lines)
            annotations = list(Annotations.opened)
            stub.VolumeEcShardsMount(volume_pb2.VolumeEcShardsMountRequest(
                volume_id=vid, collection="big", shard_ids=lost))
        read_back = {}
        for fid, body in needles.items():  # through A, which fetches from C and D
            with urllib.request.urlopen(f"http://127.0.0.1:{a.port}/{fid}", timeout=30) as r:
                read_back[fid] = r.read() == body
        yield {
            "rebuilt": list(resp.rebuilt_shard_ids), "lost": lost, "lines": lines,
            "spans": spans, "annotations": annotations,
            "same_as_before": [np.array_equal(np.fromfile(paths[i], dtype=np.uint8), before[i])
                               for i in lost],
            "same_as_decode": [np.array_equal(np.fromfile(paths[i], dtype=np.uint8), d)
                               for i, d in zip(lost, decoded)],
            "crcs": [bench.reference.crc32c(d) for d in decoded],
            "ecc": _json(base + ".ecc")["shards"],
            "survivors_untouched": all(
                os.stat(paths[i]).st_mtime_ns == mtimes[i] for i in survivors),
            "copied_to_a": [i for i in survivors if i not in placement["A"]
                            and os.path.exists(base + ec_files.to_ext(i))],
            "read_back": read_back,
        }
    finally:
        set_arbiter(previous)
        logger.removeHandler(handler)
        trace.reset()
        for vs in servers.values():
            vs.stop()
        master.stop()


@pytest.fixture(scope="module")
def report(repaired, bench) -> dict:
    reports = bench.node.verb_reports("\n".join(repaired["lines"]), "rebuild")
    assert len(reports) == 1, repaired["lines"]
    return reports[0]


def test_the_four_lost_shards_come_back_byte_for_byte(repaired):
    assert repaired["rebuilt"] == repaired["lost"] == [1, 5, 9, 13]
    assert repaired["same_as_before"] == [True] * 4
    assert repaired["same_as_decode"] == [True] * 4
    for sid, crc in zip(repaired["lost"], repaired["crcs"]):
        assert repaired["ecc"][str(sid)]["crc"] == crc, sid


def test_no_survivor_is_written_or_copied_on_any_server(repaired):
    assert repaired["survivors_untouched"]
    assert repaired["copied_to_a"] == []


def test_the_volume_reads_back_through_the_rebuilder(repaired):
    assert len(repaired["read_back"]) == 7 and all(repaired["read_back"].values())


def test_report_line_carries_the_gather(report):
    assert report["driver"] == "stream-device"
    assert (report["survivors"], report["targets"]) == (10, 4)
    assert report["remote_survivors"] == 6
    assert report["survivor_bytes"] == 10 * SHARD_BYTES
    assert report["survivor_bytes_remote"] == 6 * SHARD_BYTES
    assert report["rebuilt_bytes"] == 4 * SHARD_BYTES
    assert report["remote_read_s"] > 0
    # six remote survivors, two spans, every one over the holders' data plane
    assert report["remote_fetches"] == report["remote_fetches_dataplane"] == 12
    # inside the fetch pool's seconds; with room in the budget, next to nothing
    assert 0 <= report["arbiter_wait_s"] <= report["remote_read_s"]
    # one 3 MiB tile, dispatched as its 2 MiB and 1 MiB spans
    assert report["tiles"] == 2 == sum(report["arms"].values())
    for field in PHASE_FIELDS + ("h2d_s", "launch_s", "lookup_s", "publish_s"):
        assert field in report, field
    assert sum(report[f] for f in PHASE_FIELDS) == pytest.approx(
        report["wall_s"], abs=3.5e-4
    )


def test_root_span_carries_the_gather(repaired, report):
    spans = repaired["spans"]
    handler = [s for s in spans if s["name"] == "volume.ec_rebuild"]
    root = [s for s in spans if s["name"] == "ec_stream.rebuild"]
    assert len(handler) == len(root) == 1
    assert root[0]["parent"] == handler[0]["span"]
    for key in GATHER_FIELDS + ("tiles", "survivors", "targets", "survivor_bytes"):
        assert root[0]["annot"][key] == str(report[key]), key
    assert root[0]["stages_ms"]["remote_read_s"] == pytest.approx(
        report["remote_read_s"] * 1e3, abs=0.11)
    # no span per tile or per fetch on the rebuilder: handler, driver
    # root, five phases, the publish (the holders' own spans of the
    # reads they served lie beside them: one process here)
    ours = [s for s in spans if s["name"] != "volume.ec_shard_read"]
    assert len(ours) == 8, sorted(s["name"] for s in ours)


def test_each_remote_fetch_is_one_annotation_on_a_pool_thread(repaired):
    fetches = [t for name, t in repaired["annotations"] if name == "ec.remote_read"]
    # six remote survivors, two spans
    assert len(fetches) == 12
    readers = {t for name, t in repaired["annotations"] if name == "ec.read"}
    assert readers and not readers & set(fetches)


def test_arbiter_wait_is_what_the_arbiter_counted():
    """take_timed's second value is what the claimant's WaitedSeconds
    grew by; with pacing off it is exactly 0."""
    arb = BandwidthArbiter(total_bytes_s=1e6)
    arb.enabled = False
    assert arb.take_timed("rebuild", 10**9) == (True, 0.0)
    arb = BandwidthArbiter(total_bytes_s=4e6)
    ok, waited = arb.take_timed("rebuild", 400_000)  # an empty bucket: 0.1 s
    assert ok and 0.05 < waited < 1.0
    assert arb.stats()["Claimants"]["rebuild"]["WaitedSeconds"] == pytest.approx(
        waited, abs=1e-3)
    stop = threading.Event()
    stop.set()
    assert arb.take_timed("rebuild", 10**9, stop=stop) == (False, 0.0)
    assert arb.take("rebuild", 1) is True


# --- the benchmark's new files against the manifest and a real report line --------------

GIB = 1.25
METRICS = {
    "remote_read_s_per_gib": lambda rep: rep["remote_read_s"] / GIB,
    "remote_bytes_per_rebuilt_byte": lambda rep: 1.5,
    "arbiter_wait_s_per_gib": lambda rep: rep["arbiter_wait_s"] / GIB,
    "remote_dataplane_fetch_pct": lambda rep: (
        100.0 * rep["remote_fetches_dataplane"] / rep["remote_fetches"]),
}
JOINED = [
    "rebuild_kernel_roofline", "rebuild_swar_roofline", "device_idle_pct.ec",
    "dispatch_s_per_gib", "handler_overhead_pct", "read_s_per_gib", "write_s_per_gib",
    "writeback_s_per_gib", "reserve_s_per_gib", "reserve_done_s_per_gib",
    "ring_fresh_bytes_per_gib", "rebuild_dispatcher_busy_pct", "rebuild_launches_per_gib",
]


def _observed(reports: list[dict]) -> dict:
    return {"reports": reports,
            "window": {"seconds": 2.0, "gib": GIB, "requests": len(reports)},
            "trace": {"busy_s": 0.02, "window_s": 2.0, "op_seconds": {}},
            "traced_work": None, "device_kind": "TPU v5 lite", "rehearse": False}


def test_manifest_entries_of_the_cell(config):
    manifest = _json("BENCHMARK.json")
    entry = manifest["configs"][4]
    assert entry["name"] == config["name"] == CONFIG
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == list(config["reduced"])
    cell = manifest["workloads"][4]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    assert sum(1 for w in manifest["workloads"][:5] if w["chips"] == 4) == 1
    # the cell's own three list it alone; the lists it joined by the
    # manifest are held as a subset, so that a later PR's metric that
    # lists every cell (ISSUE 36's eight waits) does not fail this test
    listing = {m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", [])}
    assert set(METRICS) | set(JOINED) <= listing
    for name in METRICS:
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL], name
    assert CELL in next(
        m for m in manifest["end_to_end"] if m["name"] == "ec_gbps")["workloads"]
    mix = _json("benchmark", "traffic", MIX + ".json")
    assert mix["generator"] == "rack_rebuild_loop" and mix["rpc"] == "VolumeEcShardsRebuild"
    assert (mix["concurrency"], mix["trace_ops"], mix["read_back"]) == (1, 3, 28)


def test_configuration_is_seal_1gs_volume_under_the_stated_placement(config):
    seal = _json("benchmark", "configs", "seal-1g.json")
    for key in ("code", "volumes", "needle_sizes"):
        assert config[key] == seal[key], key
    placement = {k: v for k, v in config["placement"].items() if k != "why"}
    assert placement == {"A": [0, 4, 8, 12], "B": [1, 5, 9, 13],
                         "C": [2, 6, 10], "D": [3, 7, 11]}
    assert config["failure"]["lost_shards"] == placement["B"]
    assert config["budget"]["env"] == {"WEED_ARBITER": "0"}
    assert config["chips"] == 1 and len(config["guarantees"]) == 6


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_file_and_manifest_agree(name, bench, report):
    metric = bench.readers.load_metric(name)
    entry = next(m for m in _json("BENCHMARK.json")["per_layer"] if m["name"] == name)
    for key in ("name", "unit", "better", "layer", "moves", "source", "workloads"):
        assert metric[key] == entry[key], key
    assert entry["moves"] == "ec_gbps" and entry["workloads"] == [CELL]
    for spec in metric["num"] + metric.get("den", []):
        bench.readers.term(spec, _observed([report]))  # raises on a term no reader knows


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_reads_the_hand_computed_value(name, bench, report):
    got = bench.readers.read_metric(bench.readers.load_metric(name), _observed([report]))
    assert got == pytest.approx(METRICS[name](report), rel=1e-12)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_reads_nothing_from_a_parents_run(name, bench, report):
    """The parent's program writes none of the gather's fields: each
    metric is left out of its line, never 0."""
    old = {k: v for k, v in report.items()
           if k not in GATHER_FIELDS + ("remote_read_s",)}
    assert bench.readers.read_metric(
        bench.readers.load_metric(name), _observed([old])) is None


def test_traced_work_of_a_repair(bench):
    """What the generator hands the two rebuild rooflines: shard files
    of 103 MiB, ten survivor rows read and four target rows written."""
    assert bench.roofline_rebuild.rebuild_hbm_bytes(103 * MIB, 4) == 1_512_046_592


# --- a rehearsal of the cell, and of each control ------------------------------------------

# benchmark/run.py as the manifest's command runs it, but for the native
# shims, which it would delete and build again under the other workers'
# feet: this run takes them as they are
RUN = """
import runpy, sys
sys.path[:0] = [{bench!r}, {root!r}]
import harness.node
harness.node.build_native_shims = lambda: None
sys.argv = ["run.py"] + sys.argv[1:]
runpy.run_path({run!r}, run_name="__main__")
"""
CASES = [
    ([], True, None),
    (["--control", "cauchy"], False, "rebuilt_differs_from_decode"),
    (["--control", "crc32"], False, "ecc_crcs_differ"),
    (["--control", "not_rebuilt"], False, "shards_not_rewritten"),
]


@pytest.mark.parametrize("extra,correct,number", CASES,
                         ids=["sound", "cauchy", "crc32", "not_rebuilt"])
def test_rehearsal_of_the_cell(extra, correct, number, one_bench_rehearsal_at_a_time):
    bench_dir = os.path.join(REPO, "benchmark")
    code = RUN.format(bench=bench_dir, root=REPO, run=os.path.join(bench_dir, "run.py"))
    proc = subprocess.run(
        [sys.executable, "-c", code, "--workload", CELL, "--seed", str(2**31 + 34),
         "--seconds", "2", "--trace", "0", "--rehearse", *extra],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is correct, line["compared"]
    # the window ends with the first repair that completes past its seconds:
    # beside five other workers that can be the first
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert len(line["compared"]) == 12 and "ops_wrong_gather" in line["compared"]
    if number:
        assert line["compared"][number]["value"] > 0
        # a control breaks one guarantee: the operations themselves were sound
        for name in ("ops_failed", "ops_without_report", "ops_wrong_shards",
                     "ops_wrong_gather", "dat_needles_differ", "survivors_rewritten"):
            assert line["compared"][name]["value"] == 0, name
    assert all(name.startswith("rehearsal.") for name in line["metrics"])
