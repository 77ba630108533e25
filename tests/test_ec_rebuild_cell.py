"""The repair deployment (ISSUE 32: configuration `rebuild-1g`, cell
`rebuild-1data`): the single-volume rebuild driver through its device
stage pair, held byte for byte to the benchmark's plain reference of
the DECODE (`benchmark/harness/reference_rebuild.py`: numpy and
google_crc32c, nothing of the program); that reference tied to the
reference of the encode; the `ec.rebuild` report line of a real node's
`_ec_shards_rebuild`; and the cell's four per-layer metrics as files.

Everything runs on the CPU backend (the stage then takes its bit-matmul
arm): what is asserted is bytes, CRCs and bookkeeping, never a device
time."""

import importlib
import json
import logging
import os
import shutil
import time
import urllib.request

import grpc
import numpy as np
import pytest

from seaweedfs_tpu import trace
from seaweedfs_tpu.ec import ec_files, ec_stream
from seaweedfs_tpu.pb import rpc, volume_pb2
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.util.availability import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIB = 1 << 20
# one stripe row of upstream's 1 MiB blocks: shard files of 1 MiB, which
# 384 KiB tiles walk as 384 + 384 + 256, a partial last tile; the device
# stage launches each 384 as its power-of-two spans, 256 + 128 (ISSUE 34)
DAT_BYTES = 3 * MIB + 77
TILE = 384 * 1024
TILES = 5
# each single data shard, one parity shard, two shards, four shards
LOSSES = [(i,) for i in range(10)] + [(12,), (3, 12), (0, 1, 2, 3)]
IDS = ["-".join(map(str, lost)) for lost in LOSSES]
PHASE_FIELDS = ("head_s", "dispatch_span_s", "drain_s", "write_tail_s", "flush_s")
CELL = "rebuild-1data"


@pytest.fixture(scope="module")
def bench():
    """benchmark/harness as benchmark/run.py imports it (the decode's
    reference imports the encode's as `harness.reference`)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(os.path.join(REPO, "benchmark"))
        names = ("reference", "reference_rebuild", "roofline_rebuild", "readers", "node")
        yield type("Harness", (), {
            name: importlib.import_module("harness." + name) for name in names})


@pytest.fixture(scope="module")
def shard_set(tmp_path_factory, bench):
    """(base, the reference's 14 shards) of a seeded `.dat`, written by
    the reference of the ENCODE."""
    base = str(tmp_path_factory.mktemp("cell") / "big_7")
    rng = np.random.default_rng(3200)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, DAT_BYTES, dtype=np.uint8).tobytes())
    bench.reference.write_shards(base + ".dat", base)
    return base, bench.reference.encode(bench.reference.read_rows(base + ".dat"))


def _copy_without(base: str, lost, dest_dir) -> str:
    copy = str(dest_dir / os.path.basename(base))
    for i in range(14):
        if i not in lost:
            shutil.copy2(base + ec_files.to_ext(i), copy + ec_files.to_ext(i))
    return copy


# --- the driver against the reference of the decode -------------------------------


@pytest.mark.parametrize("lost", LOSSES, ids=IDS)
def test_device_stage_rebuild_equals_the_plain_decode(lost, shard_set, bench, tmp_path):
    base, shards = shard_set
    copy = _copy_without(base, lost, tmp_path)
    survivors = [i for i in range(14) if i not in lost]
    before = {i: os.stat(copy + ec_files.to_ext(i)).st_mtime_ns for i in survivors}
    want = bench.reference_rebuild.decode(copy, lost)
    stats: dict = {}
    rebuilt = ec_stream.stream_rebuild_ec_files(
        copy, tile_bytes=TILE, stats=stats, durable=True, want_crcs=True
    )
    assert rebuilt == list(lost)
    assert stats["driver"] == "stream-device"
    for sid, decoded in zip(lost, want):
        got = np.fromfile(copy + ec_files.to_ext(sid), dtype=np.uint8)
        assert np.array_equal(got, decoded), sid
        assert np.array_equal(got, shards[sid]), sid
        assert stats["shard_crcs"][sid] == bench.reference.crc32c(decoded), sid
    for i in survivors:  # read, never written
        assert os.stat(copy + ec_files.to_ext(i)).st_mtime_ns == before[i], i
        got = np.fromfile(copy + ec_files.to_ext(i), dtype=np.uint8)
        assert np.array_equal(got, shards[i]), i
    # the operation's shape, as the report line carries it
    assert stats["tiles"] == TILES == sum(stats["arms"].values())
    assert (stats["survivors"], stats["targets"]) == (10, len(lost))
    assert stats["survivor_bytes"] == 10 * MIB
    # the driver's samples bracket the stage's by a call, a count and two
    # booked fields a tile; a loaded worker switches threads in between
    slack = 5e-3 * TILES + 5e-4
    assert stats["h2d_s"] + stats["launch_s"] == pytest.approx(
        stats["device_s"], abs=slack
    )
    assert stats["h2d_s"] + stats["launch_s"] <= stats["device_s"] + 5e-4


@pytest.mark.parametrize("lost", LOSSES, ids=IDS)
def test_reference_decode_equals_reference_encode(lost, shard_set, bench):
    """Ties the two references: the decode of the ten survivor files the
    encode's reference wrote gives that reference's own lost shards."""
    base, shards = shard_set
    survivors = bench.reference_rebuild.survivors_on_disk(base, lost)
    assert survivors == [i for i in range(14) if i not in lost][:10]
    rows = bench.reference_rebuild.decode_rows(survivors, lost)
    assert rows.shape == (len(lost), 10)
    for sid, decoded in zip(lost, bench.reference_rebuild.decode(base, lost, block=300_000)):
        assert np.array_equal(decoded, shards[sid]), sid
    # another code's matrix decodes other bytes: the control is a control
    other = bench.reference_rebuild.decode(base, lost, parity="cauchy")
    assert not all(np.array_equal(o, shards[sid]) for sid, o in zip(lost, other))


def test_reference_decode_refuses_what_it_cannot_decode(shard_set, bench, tmp_path):
    base, _ = shard_set
    with pytest.raises(ValueError, match="distinct survivors"):
        bench.reference_rebuild.decode_rows(range(9), (12,))
    with pytest.raises(ValueError, match="among the survivors"):
        bench.reference_rebuild.decode_rows(range(10), (3,))
    copy = _copy_without(base, (0, 1, 2, 3, 4), tmp_path)
    with pytest.raises(ValueError, match="9 survivor files"):
        bench.reference_rebuild.decode(copy, (0,))
    assert bench.reference_rebuild.rebuilt_differ(base, (3,)) == 0
    copy = _copy_without(base, (), tmp_path)
    with open(copy + ".ec03", "r+b") as f:
        f.seek(4099)
        f.write(b"\x5a")
    assert bench.reference_rebuild.rebuilt_differ(copy, (3, 12)) == 1


def test_rebuild_hbm_bytes_worked_example(bench):
    """roofline_rebuild.py's docstring: shard files of 103 MiB, one
    lost: 10 survivor rows read and 1 target row written."""
    nbytes = bench.roofline_rebuild.rebuild_hbm_bytes(103 * MIB)
    assert nbytes == 1_188_036_608
    assert nbytes / 819e9 == pytest.approx(1.451e-3, rel=1e-3)
    assert bench.roofline_rebuild.rebuild_hbm_bytes(103 * MIB, targets=4) == 103 * MIB * 14
    with pytest.raises(ValueError):
        bench.roofline_rebuild.rebuild_hbm_bytes(103 * MIB, targets=0)


# --- the report line of a real node's repair ----------------------------------------


@pytest.fixture(scope="module")
def repaired(tmp_path_factory):
    """One operation of the cell on an in-process node with a master: a
    volume taken through ec.encode to its end, shard 3 unmounted and
    deleted, `VolumeEcShardsRebuild`, mount. Returns the node's log
    lines and the spans of the rebuild call. On the chip a node whose
    codec is tpu repairs through the stream driver's device stage; here
    the chip is a CPU and ec_files routes the verb to the classic loop,
    so it is steered the chip's way."""
    master = MasterServer(port=free_port(), volume_size_limit_mb=64)
    master.start()
    vs = VolumeServer(
        [str(tmp_path_factory.mktemp("repairvs"))], port=free_port(),
        master=f"127.0.0.1:{master.port}", heartbeat_interval=0.2,
        max_volume_counts=[100], ec_codec="tpu",
    )
    vs.start()
    deadline = time.time() + 10
    while time.time() < deadline and not master.topology.data_nodes():
        time.sleep(0.05)
    handler = logging.Handler()
    handler.lines = []  # as the node's log has them, less wlog's own prefix
    handler.emit = lambda record: handler.lines.append("I] " + record.getMessage())
    logger = logging.getLogger("seaweedfs_tpu")
    logger.addHandler(handler)
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{master.port}/dir/assign?collection=big", timeout=10
        ) as r:
            assign = json.loads(r.read())
        body = np.random.default_rng(32).bytes(700_001)
        urllib.request.urlopen(urllib.request.Request(
            f"http://{assign['url']}/{assign['fid']}", data=body, method="POST"),
            timeout=10).close()
        vid = int(assign["fid"].split(",")[0])
        with pytest.MonkeyPatch.context() as mp, \
                grpc.insecure_channel(f"127.0.0.1:{vs.grpc_port}") as ch:
            mp.setattr(ec_files, "_use_stream_driver", lambda rs: True)
            stub = rpc.volume_stub(ch)
            stub.VolumeMarkReadonly(volume_pb2.VolumeMarkReadonlyRequest(volume_id=vid))
            stub.VolumeEcShardsGenerate(volume_pb2.VolumeEcShardsGenerateRequest(
                volume_id=vid, collection="big"))
            stub.VolumeEcShardsMount(volume_pb2.VolumeEcShardsMountRequest(
                volume_id=vid, collection="big", shard_ids=list(range(14))))
            stub.VolumeDelete(volume_pb2.VolumeDeleteRequest(volume_id=vid))
            base = vs.store.find_ec_volume(vid).base_name
            lost = np.fromfile(base + ".ec03", dtype=np.uint8)
            time.sleep(0.5)  # a heartbeat: the master lists all 14 on this node
            stub.VolumeEcShardsUnmount(volume_pb2.VolumeEcShardsUnmountRequest(
                volume_id=vid, shard_ids=[3]))
            stub.VolumeEcShardsDelete(volume_pb2.VolumeEcShardsDeleteRequest(
                volume_id=vid, collection="big", shard_ids=[3]))
            assert not os.path.exists(base + ".ec03")
            ecc_before = os.stat(base + ".ecc").st_mtime_ns
            trace.reset()
            del handler.lines[:]
            resp = stub.VolumeEcShardsRebuild(
                volume_pb2.VolumeEcShardsRebuildRequest(volume_id=vid, collection="big"),
                metadata=((trace.TRACE_HEADER, "00000000000000ab:000000cd:serve"),),
            )
            spans = trace.debug_payload(n=256)["recent"]
            lines = list(handler.lines)
            stub.VolumeEcShardsMount(volume_pb2.VolumeEcShardsMountRequest(
                volume_id=vid, collection="big", shard_ids=[3]))
        with urllib.request.urlopen(
            f"http://127.0.0.1:{vs.port}/{assign['fid']}", timeout=10
        ) as r:
            read_back = r.read()
        yield {
            "rebuilt": list(resp.rebuilt_shard_ids), "lines": lines, "spans": spans,
            "same_bytes": np.array_equal(np.fromfile(base + ".ec03", dtype=np.uint8), lost),
            "ecc_republished": os.stat(base + ".ecc").st_mtime_ns > ecc_before,
            "read_back": read_back == body,
        }
    finally:
        logger.removeHandler(handler)
        trace.reset()
        vs.stop()
        master.stop()


@pytest.fixture(scope="module")
def report(repaired, bench) -> dict:
    reports = bench.node.verb_reports("\n".join(repaired["lines"]), "rebuild")
    assert len(reports) == 1, repaired["lines"]
    return reports[0]


def test_the_node_rebuilds_the_lost_shard_and_serves_on(repaired):
    """The master still lists shard 3 on this node when the verb asks it;
    the node must not take itself for a remote holder."""
    assert repaired["rebuilt"] == [3]
    assert repaired["same_bytes"] and repaired["ecc_republished"] and repaired["read_back"]


def test_report_line_carries_the_repair(report):
    assert report["driver"] == "stream-device"
    assert report["tiles"] == sum(report["arms"].values()) > 0
    assert (report["survivors"], report["targets"]) == (10, 1)
    assert report["survivor_bytes"] == 10 * MIB  # one row: shard files of 1 MiB
    assert report["lookup_s"] > 0 and report["publish_s"] > 0
    assert report["program_traces"] in (0, 1)
    for field in PHASE_FIELDS + ("h2d_s", "launch_s", "reserve_s", "reserve_done_s"):
        assert field in report, field
    assert sum(report[f] for f in PHASE_FIELDS) == pytest.approx(
        report["wall_s"], abs=3.5e-4
    )
    slack = 5e-3 * report["tiles"] + 5e-4
    assert report["h2d_s"] + report["launch_s"] == pytest.approx(
        report["device_s"], abs=slack
    )


def test_report_line_is_written_after_the_ecc_merge(repaired):
    """`_log_rebuild_crcs` logs its breadcrumb and merges the `.ecc`
    inside `ec.publish`; the ONE report line follows it."""
    lines = repaired["lines"]
    report_at = [i for i, ln in enumerate(lines) if " report={" in ln]
    crc_at = [i for i, ln in enumerate(lines) if "rebuilt_crc32c=3:" in ln]
    assert len(report_at) == 1 and len(crc_at) == 1 and crc_at[0] < report_at[0]


def test_spans_of_the_repair(repaired, report):
    spans = repaired["spans"]
    handler = [s for s in spans if s["name"] == "volume.ec_rebuild"]
    root = [s for s in spans if s["name"] == "ec_stream.rebuild"]
    publish = [s for s in spans if s["name"] == "ec.publish"]
    assert len(handler) == len(root) == len(publish) == 1
    assert handler[0]["trace"] == "00000000000000ab" and handler[0]["parent"] == "000000cd"
    assert root[0]["parent"] == publish[0]["parent"] == handler[0]["span"]
    assert publish[0]["dur_ms"] == pytest.approx(report["publish_s"] * 1e3, abs=0.11)
    for key in ("tiles", "survivors", "targets", "survivor_bytes"):
        assert root[0]["annot"][key] == str(report[key]), key
    assert {"h2d_s", "launch_s"} <= set(root[0]["stages_ms"])
    # handler, driver root, five phases, the publish: no span per tile
    assert len(spans) == 8


# --- the cell's four metrics, as files ------------------------------------------------

GIB = 1.25  # the window below: the report line counted for 1.25 GiB
SWAR = ('%swar_apply_u32.1 = u32[1,131072]{1,0:T(1,128)S(1)} custom-call(%t.1), '
        'custom_call_target="tpu_custom_call"')
OP_SECONDS = {  # canned, as a chip's trace names a decode program's operations
    SWAR: 0.006,
    "%fusion.1 = s8[1,32,1024,128]{3,2,1,0} fusion(%swar_apply_u32.1), kind=kLoop": 0.010,
    "%copy.3 = u32[10,1024,128]{2,1,0} copy(%t.1)": 0.006,
}
HBM_BYTES = 3 * 1_188_036_608  # three traced repairs of 103 MiB shard files
METRICS = {
    "rebuild_kernel_roofline": lambda rep: 100 * (HBM_BYTES / 819e9) / 0.022,
    "rebuild_swar_roofline": lambda rep: 100 * (HBM_BYTES / 819e9) / 0.006,
    "rebuild_dispatcher_busy_pct":
        lambda rep: 100 * (rep["h2d_s"] + rep["launch_s"]) / rep["wall_s"],
    "rebuild_launches_per_gib": lambda rep: rep["tiles"] / GIB,
}


def _observed(reports: list[dict]) -> dict:
    return {"reports": reports,
            "window": {"seconds": 2.0, "gib": GIB, "requests": len(reports)},
            "trace": {"busy_s": 0.02, "window_s": 2.0, "op_seconds": OP_SECONDS},
            "traced_work": {"rebuild_hbm_bytes": HBM_BYTES},
            "device_kind": "TPU v5 lite", "rehearse": False}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_file_and_manifest_agree(name, bench, report):
    metric = bench.readers.load_metric(name)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    for key in ("name", "unit", "better", "layer", "moves", "source"):
        assert metric[key] == entry[key], key
    # a cell joins a metric by the manifest's list (run.py:per_layer): the
    # file's copy is this cell, and ISSUE 34's rack repair was appended to
    # the manifest alone
    assert metric["workloads"] == [CELL]
    assert entry["moves"] == "ec_gbps"
    assert entry["workloads"][:2] == [CELL, "rack-rebuild-4lost"]
    assert CELL in next(
        m for m in manifest["end_to_end"] if m["name"] == "ec_gbps")["workloads"]
    obs = _observed([report])
    for spec in metric["num"] + metric.get("den", []):
        bench.readers.term(spec, obs)  # raises on a term no reader knows


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_reads_the_hand_computed_value(name, bench, report):
    got = bench.readers.read_metric(bench.readers.load_metric(name), _observed([report]))
    assert got == pytest.approx(METRICS[name](report), rel=1e-12)
    if name.endswith("_roofline"):
        assert 0 < got < 100


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_reads_nothing_from_a_parents_run(name, bench, report):
    """A program without this PR's fields (h2d_s, launch_s and tiles on
    the rebuild line), no decode kernel among the traced operations and
    no traced work: each metric is left out of the line, never 0."""
    old = {k: v for k, v in report.items() if k not in ("h2d_s", "launch_s", "tiles")}
    obs = _observed([old])
    obs["trace"]["op_seconds"] = {}
    obs["traced_work"] = None
    assert bench.readers.read_metric(bench.readers.load_metric(name), obs) is None
