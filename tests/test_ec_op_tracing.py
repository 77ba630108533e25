"""Where one EC operation's time goes (ISSUE 26, docs/TRACING.md; every
stream driver since ISSUE 30, whose one pipeline shell takes the
boundaries): the serial phases of a driver partition the wall, the
device stage's H2D / launch split reconciles with the pool stages it
refines, the spans and profiler annotations carry the same
names, and the node's one report line per operation carries all of it.

Everything runs on the CPU backend: what is asserted is bookkeeping
(sums, parents, names, counts), never a device time."""

import glob
import importlib.util
import json
import logging
import os
import time

import grpc
import numpy as np
import pytest

from seaweedfs_tpu import trace
from seaweedfs_tpu.ec import ec_stream
from seaweedfs_tpu.ec.codec import new_encoder
from seaweedfs_tpu.pb import rpc, volume_pb2
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.util.availability import free_port
from tests.faults import ec_shards_less

LARGE = 64 * 1024
SMALL = 16 * 1024
PHASE_FIELDS = ("head_s", "dispatch_span_s", "drain_s", "write_tail_s", "flush_s")
PHASE_SPANS = tuple(ec_stream._OP_PHASES)
DEVICE_FIELDS = tuple(ec_stream._DEVICE_BUSY)
WAIT_FIELDS = tuple(ec_stream._WAIT_BUSY)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_dat(base: str, nbytes: int, seed: int = 0) -> None:
    rng = np.random.default_rng(seed)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes())


def _encode_single(tmp_path, host_pair: bool, rows: int = 6) -> dict:
    base = str(tmp_path / "v")
    _make_dat(base, 10 * SMALL * rows + 77)
    fns = {}
    if host_pair:
        fns["parity_fn"], fns["fetch_fn"] = ec_stream.local_encode_fns(
            new_encoder(backend="cpu"), want_crcs=True
        )
    stats: dict = {}
    ec_stream.stream_write_ec_files(
        base, tile_bytes=SMALL, large_block_size=LARGE, small_block_size=SMALL,
        stats=stats, want_crcs=True, **fns,
    )
    return stats


def _encode_batch(tmp_path, volumes: int = 4, rows: int = 3) -> dict:
    bases = []
    for i in range(volumes):
        bases.append(str(tmp_path / f"b{i}"))
        _make_dat(bases[-1], 10 * SMALL * rows + i, seed=i)
    stats: dict = {}
    ec_stream.stream_write_ec_files_batch(
        bases, tile_bytes=SMALL, large_block_size=LARGE, small_block_size=SMALL,
        stats=stats, want_crcs=True,
    )
    return stats


LOST = (3, 12)


def _lose(base: str, nbytes: int, seed: int) -> None:
    ec_shards_less(base, nbytes, seed, LOST, LARGE, SMALL)


def _rebuild_single(tmp_path, host_pair: bool) -> dict:
    base = str(tmp_path / "r")
    _lose(base, 10 * SMALL * 6 + 77, seed=7)
    fns = {}
    if host_pair:
        fns["rebuild_fn"], fns["fetch_fn"] = ec_stream.local_rebuild_fns(
            new_encoder(backend="cpu"), want_crcs=True
        )
    trace.reset()  # the spans below are the rebuild's alone
    stats: dict = {}
    rebuilt = ec_stream.stream_rebuild_ec_files(
        base, tile_bytes=SMALL, stats=stats, want_crcs=True, **fns
    )
    assert rebuilt == list(LOST)
    return stats


def _rebuild_batch(tmp_path, mesh: bool, volumes: int = 2) -> dict:
    bases = [str(tmp_path / f"rb{i}") for i in range(volumes)]
    for i, base in enumerate(bases):
        _lose(base, 10 * SMALL * 6 + i, seed=20 + i)
    # the CPU's default is the host arm; its tiles are fine enough here
    # for more than _HOST_INLINE_TILES work items, so it runs its pools
    codec = ec_stream._default_mesh_codec(volumes) if mesh else None
    trace.reset()
    stats: dict = {}
    rebuilt = ec_stream.stream_rebuild_ec_files_batch(
        bases, codec=codec, tile_bytes=SMALL // 2, stats=stats, want_crcs=True
    )
    assert rebuilt == [list(LOST)] * volumes and "host_inline" not in stats
    assert ("mesh" in stats) == mesh
    return stats


DRIVERS = {
    "single-host-pair": lambda p: _encode_single(p, host_pair=True),
    "single-device": lambda p: _encode_single(p, host_pair=False),
    "batch": _encode_batch,
    "rebuild-host-pair": lambda p: _rebuild_single(p, host_pair=True),
    "rebuild-device": lambda p: _rebuild_single(p, host_pair=False),
    "rebuild-batch-host": lambda p: _rebuild_batch(p, mesh=False),
    "rebuild-batch-mesh": lambda p: _rebuild_batch(p, mesh=True),
}


@pytest.fixture
def ring():
    trace.reset()
    yield
    trace.reset()


def _recent() -> list[dict]:
    return trace.debug_payload(n=256)["recent"]


# --- the phases ---------------------------------------------------------------


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_phases_partition_the_wall(driver, tmp_path):
    stats = DRIVERS[driver](tmp_path)
    for field in PHASE_FIELDS:
        assert stats[field] >= 0, field
    # each of the six numbers is rounded to 1e-4 on its own
    assert sum(stats[f] for f in PHASE_FIELDS) == pytest.approx(
        stats["wall_s"], abs=3.5e-4
    )
    assert stats["dispatch_span_s"] > 0
    assert "loop_s" not in stats and "overlap_s" not in stats
    # every driver's files are reserved by its writer pool
    assert stats["reserve_s"] > 0
    assert 0 < stats["reserve_done_s"] <= stats["wall_s"]


def _span_of_the_waits(stats: dict) -> float:
    return stats["tile_wait_s"] + stats["dispatch_call_s"] + stats["window_wait_s"]


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_dispatchers_waits_and_calls_close_on_the_span(driver, tmp_path):
    """ISSUE 36: the dispatcher's waits for a tile and for the window and
    its time inside the plan's dispatch call leave of dispatch_span_s
    only the loop's own statements; every wait is on every driver's
    line, 0.0 where nobody stood."""
    stats = DRIVERS[driver](tmp_path)
    for field in WAIT_FIELDS:
        assert type(stats[field]) is float and stats[field] >= 0, field
    # a device stage's two calls lie inside the plan's call (a host
    # stage pair's dispatch hands the tile through: microseconds)
    assert stats["dispatch_call_s"] >= (
        stats.get("h2d_s", 0.0) + stats.get("launch_s", 0.0) - 5e-4
    )
    assert _span_of_the_waits(stats) == pytest.approx(
        stats["dispatch_span_s"], rel=0.02, abs=2e-3
    )
    # the first tile's wait lies in the head, the pools' in their threads
    assert stats["first_tile_wait_s"] <= stats["head_s"] + 1e-4
    assert stats["work_wait_s"] <= stats["writer_threads"] * stats["wall_s"] + 1e-3
    assert stats["slot_wait_s"] + stats["read_q_wait_s"] <= (
        stats["reader_threads"] * stats["wall_s"] + 1e-3
    )


def test_phases_partition_an_aborted_operation(tmp_path):
    """A stage error skips phases; what was entered still sums to the
    wall, and the fields are all there."""
    base = str(tmp_path / "v")
    _make_dat(base, 10 * SMALL * 4)

    def boom(handle):
        raise RuntimeError("fetch failed")

    stats: dict = {}
    with pytest.raises(RuntimeError, match="fetch failed"):
        ec_stream.stream_write_ec_files(
            base, tile_bytes=SMALL, large_block_size=LARGE,
            small_block_size=SMALL, parity_fn=lambda t: t, fetch_fn=boom,
            stats=stats,
        )
    assert sum(stats[f] for f in PHASE_FIELDS) == pytest.approx(
        stats["wall_s"], abs=3.5e-4
    )
    # the waits are on an aborted line too: 0.0 or what was booked
    for field in WAIT_FIELDS:
        assert stats[field] >= 0, field


def test_phases_helper_shares_its_samples(ring):
    with trace.span("root") as root:
        phases = trace.Phases("p.a", 100.0)
        phases.to("p.b", 101.5)
        phases.to("p.a", 102.0)  # a phase entered twice adds up
        end = phases.close(104.0)
    assert end == 104.0
    assert phases.seconds == {"p.a": 3.5, "p.b": 0.5}
    spans = [s for s in _recent() if s["name"].startswith("p.")]
    assert sorted(s["dur_ms"] for s in spans) == [500.0, 1500.0, 2000.0]
    assert {s["parent"] for s in spans} == {root.span_id}


def test_phases_helper_takes_no_sample_older_than_the_open_phase():
    phases = trace.Phases("p.a", 100.0)
    phases.to("p.b", 101.0)
    phases.to("p.c", 99.0)  # older than p.b's start: counts as that start
    phases.close(103.0)
    assert phases.seconds == {"p.a": 1.0, "p.b": 0.0, "p.c": 2.0}


def test_drain_ends_at_the_last_fetch_not_at_the_join(tmp_path, monkeypatch):
    """The writers leave the latest fetch-return sample in one slot and
    the handler's thread reads it after the join: slow shard writes lie
    in write_tail_s, a slow fetch in drain_s."""
    real = ec_stream._pwritev_full

    def slow_write(fd, views, offset):
        time.sleep(0.02)
        real(fd, views, offset)

    monkeypatch.setattr(ec_stream, "_pwritev_full", slow_write)
    stats = _encode_single(tmp_path, host_pair=True, rows=1)
    assert stats["write_tail_s"] >= 0.2  # 14 writes of the last tile
    assert stats["drain_s"] < stats["write_tail_s"]
    assert sum(stats[f] for f in PHASE_FIELDS) == pytest.approx(
        stats["wall_s"], abs=3.5e-4
    )


# --- the device stage's split -------------------------------------------------


def test_single_driver_device_split_reconciles(tmp_path):
    stats = _encode_single(tmp_path, host_pair=False, rows=6)
    tiles = sum(stats["arms"].values())
    assert tiles == 7  # six rows and the tail
    # per dispatch the driver's own samples bracket parity_fn's by a
    # call and a few dict stores
    slack = 1e-3 * tiles + 5e-4
    assert stats["h2d_s"] + stats["launch_s"] == pytest.approx(
        stats["device_s"], abs=slack
    )
    assert stats["h2d_s"] + stats["launch_s"] <= stats["device_s"] + 5e-4
    # off the TPU the stage takes the bit-matmul arm
    assert stats["arms"]["bit-matmul"] == tiles


def test_batch_driver_device_split_reconciles(tmp_path):
    stats = _encode_batch(tmp_path, volumes=4, rows=3)
    assert stats["launch_s"] == pytest.approx(stats["device_s"], abs=2e-4)
    assert 0 < stats["h2d_s"] <= stats["stage_s"] + 1e-4


def test_chunked_batch_adds_the_seconds_up(tmp_path, monkeypatch):
    """WEED_EC_PIPELINE_BATCH splits the verb's batch into chunks whose
    seconds add up: over all chunks the phases still partition the wall
    and the device stage's split still reconciles."""
    monkeypatch.setenv("WEED_EC_PIPELINE_BATCH", "2")
    chunks: list[dict] = []
    real = ec_stream._stream_batch_chunk

    def chunk(*args):
        real(*args)
        chunks.append(dict(args[5]))  # the chunk's own stats

    monkeypatch.setattr(ec_stream, "_stream_batch_chunk", chunk)
    chunked = _encode_batch(tmp_path, volumes=4, rows=2)
    assert chunked["batch_volumes"] == 4
    assert sum(chunked[f] for f in PHASE_FIELDS) == pytest.approx(
        chunked["wall_s"], abs=7e-4
    )
    assert chunked["launch_s"] == pytest.approx(chunked["device_s"], abs=4e-4)
    # the waits add up like every stage: two chunks' spans, two chunks'
    # calls (the batch stage's own stage_s + device_s lie inside them)
    assert _span_of_the_waits(chunked) == pytest.approx(
        chunked["dispatch_span_s"], rel=0.02, abs=4e-3
    )
    assert chunked["dispatch_call_s"] >= (
        chunked["stage_s"] + chunked["device_s"] - 4e-4
    )
    assert len(chunks) == 2
    for field in WAIT_FIELDS:
        assert chunked[field] == pytest.approx(
            sum(c[field] for c in chunks), abs=1e-4
        ), field


@pytest.mark.parametrize("driver", ["single-host-pair", "rebuild-host-pair"])
def test_host_stage_pairs_book_no_device_field(driver, tmp_path):
    stats = DRIVERS[driver](tmp_path)
    assert not set(DEVICE_FIELDS) & set(stats)
    assert stats["driver"] == "stream-host" and stats["compute_s"] > 0


def test_rebuild_driver_device_split_reconciles(tmp_path):
    """The rebuild's device stage books the encode's split (ISSUE 32)."""
    stats = _rebuild_single(tmp_path, host_pair=False)
    tiles = sum(stats["arms"].values())
    assert tiles == stats["tiles"] == 7  # seven rows of one small block a shard
    slack = 5e-3 * tiles + 5e-4  # a loaded worker switches threads between the samples
    assert stats["h2d_s"] + stats["launch_s"] == pytest.approx(
        stats["device_s"], abs=slack
    )
    assert stats["h2d_s"] + stats["launch_s"] <= stats["device_s"] + 5e-4
    assert stats["arms"]["bit-matmul"] == tiles


# --- spans --------------------------------------------------------------------


ROOTS = {
    "single-device": "ec_stream.encode",
    "batch": "ec_stream.encode_batch",
    "rebuild-device": "ec_stream.rebuild",
    "rebuild-batch-host": "ec_stream.rebuild_batch",
    "rebuild-batch-mesh": "ec_stream.rebuild_batch",
}


@pytest.mark.parametrize("driver", sorted(ROOTS))
def test_phase_spans_hang_off_the_drivers_root(driver, tmp_path, ring):
    stats = DRIVERS[driver](tmp_path)
    spans = _recent()
    root = [s for s in spans if s["name"].startswith("ec_stream.")]
    assert [s["name"] for s in root] == [ROOTS[driver]]
    phases = {s["name"]: s for s in spans if s["name"] in PHASE_SPANS}
    assert set(phases) == set(PHASE_SPANS)
    for name, field in ec_stream._OP_PHASES.items():
        assert phases[name]["parent"] == root[0]["span"], name
        assert phases[name]["dur_ms"] == pytest.approx(stats[field] * 1e3, abs=0.11)
    # one vocabulary: the root's stages are the report line's fields
    assert set(root[0]["stages_ms"]) == {k for k in stats if k.endswith("_s")} - {"wall_s"}
    # and never a span per tile: the root and its five phases
    assert len(spans) == 6


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    master = MasterServer(port=free_port(), volume_size_limit_mb=64)
    master.start()
    vs = VolumeServer(
        [str(tmp_path_factory.mktemp("opvs"))],
        port=free_port(),
        master=f"127.0.0.1:{master.port}",
        heartbeat_interval=0.2,
        max_volume_counts=[100],
        ec_codec="tpu",
    )
    vs.start()
    deadline = time.time() + 10
    while time.time() < deadline and not master.topology.data_nodes():
        time.sleep(0.05)
    yield master, vs
    vs.stop()
    master.stop()


def _sealed_volume(master, vs, collection: str) -> int:
    import urllib.request

    with urllib.request.urlopen(
        f"http://127.0.0.1:{master.port}/dir/assign?collection={collection}",
        timeout=10,
    ) as r:
        assign = json.loads(r.read())
    urllib.request.urlopen(
        urllib.request.Request(
            f"http://{assign['url']}/{assign['fid']}",
            data=bytes(range(256)) * 1200, method="POST",
        ),
        timeout=10,
    ).close()
    return int(assign["fid"].split(",")[0])


class _Lines(logging.Handler):
    """The node's log as the benchmark reads it: wlog's own format."""

    def __init__(self):
        super().__init__()
        self.setFormatter(logging.Formatter(
            "%(levelname).1s%(asctime)s %(module)s:%(lineno)d] %(message)s",
            datefmt="%m%d %H:%M:%S",
        ))
        self.lines: list[str] = []

    def emit(self, record):
        self.lines.append(self.format(record))


@pytest.fixture
def node_log():
    handler = _Lines()
    logger = logging.getLogger("seaweedfs_tpu")
    logger.addHandler(handler)
    yield handler.lines
    logger.removeHandler(handler)


def _verb_reports(text: str, verb: str) -> list[dict]:
    """benchmark/harness/node.py's own parser of the report lines."""
    spec = importlib.util.spec_from_file_location(
        "bench_harness_node", os.path.join(REPO, "benchmark", "harness", "node.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.verb_reports(text, verb)


CALLS = {
    "generate": lambda stub, vids, md: stub.VolumeEcShardsGenerate(
        volume_pb2.VolumeEcShardsGenerateRequest(volume_id=vids[0], collection="op1"),
        metadata=md,
    ),
    "batch_generate": lambda stub, vids, md: stub.VolumeEcShardsBatchGenerate(
        volume_pb2.VolumeEcShardsBatchGenerateRequest(volume_ids=vids), metadata=md
    ),
}


@pytest.fixture
def stream_device_driver(monkeypatch):
    """On the chip a node whose codec is tpu encodes one volume through
    the stream driver's device stage; here the chip is a CPU and
    ec_files routes the verb to the classic loop. Steer it the chip's
    way (the stage then takes its bit-matmul arm)."""
    from seaweedfs_tpu.ec import ec_files

    monkeypatch.setattr(ec_files, "_use_stream_driver", lambda rs: True)


@pytest.mark.parametrize("verb,volumes", [("generate", 1), ("batch_generate", 2)])
def test_handler_span_report_line_and_publish(
    verb, volumes, node, node_log, ring, stream_device_driver
):
    """Over gRPC with a caller's trace header: handler span <- wire,
    driver root and ec.publish <- handler span, ONE report line, written
    after the publish, that the benchmark's regex parses and that holds
    every new field."""
    master, vs = node
    vids = [_sealed_volume(master, vs, f"op{volumes}") for _ in range(volumes)]
    with grpc.insecure_channel(f"127.0.0.1:{vs.grpc_port}") as ch:
        stub = rpc.volume_stub(ch)
        for vid in vids:
            stub.VolumeMarkReadonly(volume_pb2.VolumeMarkReadonlyRequest(volume_id=vid))
        trace.reset()
        del node_log[:]
        CALLS[verb](stub, vids, ((trace.TRACE_HEADER, "00000000000000ab:000000cd:serve"),))

    spans = _recent()
    handler = [s for s in spans if s["name"] == f"volume.ec_{verb}"]
    assert len(handler) == 1
    assert handler[0]["trace"] == "00000000000000ab"
    assert handler[0]["parent"] == "000000cd"
    roots = [s for s in spans if s["name"].startswith("ec_stream.encode")]
    publish = [s for s in spans if s["name"] == "ec.publish"]
    assert len(roots) == 1 and len(publish) == 1
    assert roots[0]["parent"] == publish[0]["parent"] == handler[0]["span"]
    assert {s["trace"] for s in spans} == {"00000000000000ab"}
    # handler, driver root, five phases, the publish: no span per tile
    assert len(spans) == 8

    text = "\n".join(node_log)
    reports = _verb_reports(text, verb)
    assert len(reports) == 1, text
    report = reports[0]
    for field in PHASE_FIELDS + DEVICE_FIELDS + ("publish_s", "wall_s"):
        assert field in report, field
    assert report["publish_s"] > 0
    assert sum(report[f] for f in PHASE_FIELDS) == pytest.approx(
        report["wall_s"], abs=3.5e-4
    )
    if verb == "batch_generate":
        # the mesh as a number on the line and as attributes of the root
        # span (ISSUE 28): two volumes on the worker's 8 virtual devices
        # are vol = gcd(2, 8) = 2 by stripe = 4
        mesh = report["mesh"]
        assert type(report["mesh_devices"]) is int
        assert report["mesh_devices"] == mesh["devices_per_round"] == 8
        assert roots[0]["annot"]["mesh"] == f"{mesh['vol']}x{mesh['stripe']}" == "2x4"
        assert roots[0]["annot"]["mesh_devices"] == "8"
        assert roots[0]["annot"]["batch_volumes"] == "2"
    else:
        assert "mesh_devices" not in report
    # after the publish: the CRC breadcrumbs, which the publish writes,
    # come before the report line in the log
    lines = text.splitlines()
    report_at = next(i for i, ln in enumerate(lines) if " report={" in ln)
    crc_at = [i for i, ln in enumerate(lines) if "shard_crc32c=" in ln]
    assert crc_at and max(crc_at) < report_at
    for vid in vids:
        base = vs.store.find_volume(vid).base_name
        assert os.path.exists(base + ".ecx") and os.path.exists(base + ".ecc")


def _ec_volume_less_a_shard(stub, vid: int, collection: str) -> None:
    """The sealed volume taken through ec.encode to its end and served
    as an EC volume, then shard 3 unmounted and deleted: what
    `VolumeEcShardsRebuild` repairs (tests/test_ec_rebuild_cell.py)."""
    stub.VolumeEcShardsGenerate(volume_pb2.VolumeEcShardsGenerateRequest(
        volume_id=vid, collection=collection))
    stub.VolumeEcShardsMount(volume_pb2.VolumeEcShardsMountRequest(
        volume_id=vid, collection=collection, shard_ids=list(range(14))))
    stub.VolumeDelete(volume_pb2.VolumeDeleteRequest(volume_id=vid))
    time.sleep(0.5)  # a heartbeat: the master lists all 14 on this node
    stub.VolumeEcShardsUnmount(volume_pb2.VolumeEcShardsUnmountRequest(
        volume_id=vid, shard_ids=[3]))
    stub.VolumeEcShardsDelete(volume_pb2.VolumeEcShardsDeleteRequest(
        volume_id=vid, collection=collection, shard_ids=[3]))


@pytest.mark.parametrize(
    "verb,volumes", [("generate", 1), ("batch_generate", 2), ("rebuild", 1)]
)
def test_report_line_and_root_span_carry_the_waits(
    verb, volumes, node, node_log, ring, stream_device_driver
):
    """ISSUE 36: the eight fields stand on the verb's ONE report line
    and among the root span's `stages_ms` under the same names, and the
    verb still leaves eight spans: a wait is never a span."""
    master, vs = node
    collection = f"wt{volumes}{verb[0]}"
    vids = [_sealed_volume(master, vs, collection) for _ in range(volumes)]
    with grpc.insecure_channel(f"127.0.0.1:{vs.grpc_port}") as ch:
        stub = rpc.volume_stub(ch)
        for vid in vids:
            stub.VolumeMarkReadonly(volume_pb2.VolumeMarkReadonlyRequest(volume_id=vid))
        if verb == "rebuild":
            _ec_volume_less_a_shard(stub, vids[0], collection)
        trace.reset()
        del node_log[:]
        if verb == "rebuild":
            resp = stub.VolumeEcShardsRebuild(volume_pb2.VolumeEcShardsRebuildRequest(
                volume_id=vids[0], collection=collection))
            assert list(resp.rebuilt_shard_ids) == [3]
        elif verb == "generate":
            stub.VolumeEcShardsGenerate(volume_pb2.VolumeEcShardsGenerateRequest(
                volume_id=vids[0], collection=collection))
        else:
            CALLS[verb](stub, vids, None)
    spans = _recent()
    assert len(spans) == 8
    root = [s for s in spans if s["name"].startswith("ec_stream.")]
    assert len(root) == 1
    reports = _verb_reports("\n".join(node_log), verb)
    assert len(reports) == 1
    for field in WAIT_FIELDS:
        assert field in reports[0], field
        assert root[0]["stages_ms"][field] == pytest.approx(
            reports[0][field] * 1e3, abs=0.11
        ), field
    assert _span_of_the_waits(reports[0]) == pytest.approx(
        reports[0]["dispatch_span_s"], rel=0.02, abs=2e-3
    )


# --- the eight metrics that read the waits, as files (ISSUE 36) ---------------

WAIT_METRICS = {
    "reader_slot_wait_s_per_gib": "slot_wait_s",
    "reader_queue_wait_s_per_gib": "read_q_wait_s",
    "first_tile_wait_s_per_gib": "first_tile_wait_s",
    "dispatcher_tile_wait_s_per_gib": "tile_wait_s",
    "dispatcher_window_wait_s_per_gib": "window_wait_s",
    "writer_work_wait_s_per_gib": "work_wait_s",
    "writer_latch_wait_s_per_gib": "latch_wait_s",
    "dispatch_span_unbooked_pct": None,
}
CELLS = ["encode-1g", "batch-encode-256m", "batch-encode-x4", "rebuild-1data",
         "rack-rebuild-4lost"]


@pytest.fixture(scope="module")
def wait_report(tmp_path_factory) -> dict:
    """A real operation's books as the node writes them on its report
    line and as the benchmark's own parser reads them back."""
    stats = _encode_single(tmp_path_factory.mktemp("waits"), host_pair=False)
    handler = _Lines()
    logger = logging.getLogger("seaweedfs_tpu")
    logger.addHandler(handler)
    try:
        VolumeServer._log_ec_verb("generate", [1], stats)
    finally:
        logger.removeHandler(handler)
    reports = _verb_reports("\n".join(handler.lines), "generate")
    assert len(reports) == 1
    return reports[0]


@pytest.mark.parametrize("name", sorted(WAIT_METRICS))
def test_wait_metric_file_manifest_entry_and_report_line(name, wait_report, monkeypatch):
    """The file is its manifest entry word for word, all five cells
    among it; it reads the hand-computed value from a real report line
    through the readers that were there; and it reads nothing from the
    lines of a program without the fields (the parent's)."""
    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmark"))
    import importlib

    readers = importlib.import_module("harness.readers")
    metric = readers.load_metric(name)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    for key in entry:
        if key != "workloads":
            assert metric[key] == entry[key], key
    # a cell joins a metric by the manifest's list (run.py:per_layer): the
    # file's copy is ISSUE 36's five, and later cells (ISSUE 38's
    # batch-rebuild-2lost) are appended to the manifest alone
    assert metric["workloads"] == CELLS == entry["workloads"][:len(CELLS)]
    assert (entry["layer"], entry["source"], entry["moves"], entry["better"]) == (
        "stream driver", "program_span", "ec_gbps", "lower")
    assert "reads nothing" in metric["reads"] or "read nothing" in metric["reads"]

    obs = {"reports": [wait_report, wait_report], "trace": None,
           "window": {"seconds": 1.0, "gib": 0.5, "requests": 2}}
    field = WAIT_METRICS[name]
    if field is None:
        assert entry["unit"] == "%"
        want = 100 * (1 - _span_of_the_waits(wait_report) / wait_report["dispatch_span_s"])
        assert abs(want) < 2 + 100 * 2e-3 / wait_report["dispatch_span_s"]
    else:
        assert entry["unit"] == "s/GiB" and field in wait_report
        want = 2 * wait_report[field] / 0.5
    assert readers.read_metric(metric, obs) == pytest.approx(want, abs=1e-9)

    with open(os.path.join(REPO, "benchmark", "selftest", "node_log_phases.txt")) as f:
        obs["reports"] = _verb_reports(f.read(), "generate")
    assert obs["reports"] and "dispatch_span_s" in obs["reports"][0]
    assert readers.read_metric(metric, obs) is None


def _same_verb_twice(node, node_log, verb: str, volumes: int, family: str):
    """The verb twice on one node, each time on new sealed volumes:
    (the two report lines, the /metrics family's value before each call,
    its value after both)."""
    import urllib.request

    master, vs = node

    def counter() -> float:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{vs.port}/metrics", timeout=10
        ) as r:
            text = r.read().decode()
        line = next(ln for ln in text.splitlines() if ln.startswith(family))
        return float(line.split()[-1])

    before = []
    with grpc.insecure_channel(f"127.0.0.1:{vs.grpc_port}") as ch:
        stub = rpc.volume_stub(ch)
        del node_log[:]
        for _ in range(2):
            vids = [
                _sealed_volume(master, vs, f"tr{volumes}") for _ in range(volumes)
            ]
            for vid in vids:
                stub.VolumeMarkReadonly(
                    volume_pb2.VolumeMarkReadonlyRequest(volume_id=vid)
                )
            before.append(counter())
            CALLS[verb](stub, vids, None)
    reports = _verb_reports("\n".join(node_log), verb)
    assert len(reports) == 2
    return reports, before, counter()


@pytest.mark.parametrize("verb,volumes", [("generate", 1), ("batch_generate", 2)])
def test_report_line_and_metrics_carry_program_traces(
    verb, volumes, node, node_log, stream_device_driver
):
    """The same verb twice on one node (ISSUE 27): every report line
    carries `program_traces`, the repeat's is 0, and the node's
    /metrics has the process-wide counter, which the repeat leaves
    where it was."""
    reports, before, after = _same_verb_twice(
        node, node_log, verb, volumes, "weed_ec_program_traces_total"
    )
    # the worker's other tests may have traced these shapes already
    assert reports[0]["program_traces"] in (0, 1)
    assert reports[1]["program_traces"] == 0
    assert before[1] - before[0] == reports[0]["program_traces"]
    assert after == before[1]


@pytest.mark.parametrize("verb,volumes", [("generate", 1), ("batch_generate", 2)])
def test_report_line_and_metrics_carry_ring_fresh_bytes(
    verb, volumes, node, node_log, stream_device_driver, monkeypatch
):
    """The same verb twice on one node (ISSUE 33): every report line
    carries `ring_fresh_bytes`, the repeat ran on the memory the first
    gave back and reads 0, and the node's /metrics counts what was
    allocated, which the repeat leaves where it was."""
    # the node is this process: make it one that has run no operation
    # (the worker's other tests may have left a ring large enough)
    monkeypatch.setattr(ec_stream, "_RING", ec_stream._KeptRing())
    reports, before, after = _same_verb_twice(
        node, node_log, verb, volumes, "weed_ec_ring_fresh_bytes_total"
    )
    # whole slots of [volumes, 10, 1 MiB] each
    slot = volumes * 10 * ec_stream.DEFAULT_TILE_BYTES
    assert reports[0]["ring_fresh_bytes"] >= 2 * slot
    assert reports[0]["ring_fresh_bytes"] % slot == 0
    assert reports[1]["ring_fresh_bytes"] == 0
    assert before[1] - before[0] == reports[0]["ring_fresh_bytes"]
    assert after == before[1]


def test_failed_publish_still_reports(node, node_log, monkeypatch, stream_device_driver):
    from seaweedfs_tpu.ec import ec_files

    master, vs = node
    vid = _sealed_volume(master, vs, "op1")

    def no_index(base, durable=False):
        raise OSError("no space for the index")

    monkeypatch.setattr(ec_files, "write_sorted_file_from_idx", no_index)
    with grpc.insecure_channel(f"127.0.0.1:{vs.grpc_port}") as ch:
        stub = rpc.volume_stub(ch)
        stub.VolumeMarkReadonly(volume_pb2.VolumeMarkReadonlyRequest(volume_id=vid))
        del node_log[:]
        with pytest.raises(grpc.RpcError):
            CALLS["generate"](stub, [vid], None)
    reports = _verb_reports("\n".join(node_log), "generate")
    assert len(reports) == 1 and "publish_s" in reports[0]


# --- the profiler's clock -----------------------------------------------------


@pytest.mark.parametrize("driver", ["single-device", "rebuild-device"])
def test_annotations_reach_a_profiler_trace(driver, tmp_path):
    """One CPU profiler session around a small encode, and around a
    small rebuild (ISSUE 32: its device stage annotates ec.h2d and
    ec.launch too): the phases and the pool stages are TraceMe events
    on the host plane's thread lines (read as benchmark/selftest reads
    its recorded trace)."""
    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # as the benchmark's node launcher sets it
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=options)
    try:
        DRIVERS[driver](tmp_path)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert found
    on_host: dict[str, int] = {}
    lines: list[set[str]] = []  # the ec.* names on each thread's line
    for plane in ProfileData.from_file(found[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            names = [ev.name for ev in line.events if ev.name.startswith("ec.")]
            for name in names:
                on_host[name] = on_host.get(name, 0) + 1
            if names:
                lines.append(set(names))
    for name in PHASE_SPANS:
        assert on_host.get(name) == 1, (name, on_host)
    for name in ("ec.read", "ec.h2d", "ec.launch", "ec.writeback", "ec.write"):
        assert on_host.get(name) == 7, (name, on_host)  # one per tile
    # the waits (ISSUE 36): an event only where the call blocked, on the
    # line of the thread that stood. Some writer always stands for work
    # (there are more of them than tiles are ready at once, and each ends
    # in a wait for the end of the stream); no reader ever stands for a
    # slot, because the ring has at least as many as this run has tiles
    assert ec_stream._INFLIGHT + ec_stream.DEFAULT_WRITER_THREADS + 1 >= 7
    assert on_host.get("ec.wait.work", 0) >= 1, on_host
    assert "ec.wait.slot" not in on_host, on_host
    waits = {n for n in on_host if n.startswith("ec.wait.")}
    assert waits <= {"ec.wait.read_q", "ec.wait.tile", "ec.wait.window",
                     "ec.wait.work", "ec.wait.latch"}, on_host
    assert on_host.get("ec.wait.read_q", 0) <= 7 and on_host.get("ec.wait.tile", 0) <= 7
    for names in lines:
        if "ec.op.head" in names:  # the handler's thread, the dispatcher
            assert not names & {"ec.wait.work", "ec.wait.latch", "ec.wait.read_q"}
        elif "ec.read" in names:  # a reader
            assert not names & {"ec.wait.work", "ec.wait.latch",
                                "ec.wait.tile", "ec.wait.window"}
        else:  # a writer
            assert not names & {"ec.wait.read_q", "ec.wait.tile", "ec.wait.window"}


def test_gap_reader_puts_device_gaps_down_to_host_annotations(tmp_path, capsys):
    """`python -m seaweedfs_tpu.trace.gaps` on a trace made here: the
    bursts of the CPU client's threads stand in for a chip's planes."""
    import jax

    from seaweedfs_tpu.trace import gaps

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=options)
    try:
        stats = _encode_single(tmp_path, host_pair=False)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    rep = gaps.report(found[0], min_gap_ms=0.0)
    assert rep["device_planes"] == 1 and rep["bursts"] >= 2
    assert 0 < rep["busy_s"] <= rep["span_s"]
    assert rep["annotations"]["ec.read"][0] == 7
    assert rep["annotations"]["ec.wait.work"][1] == pytest.approx(
        stats["work_wait_s"], rel=0.25, abs=5e-3
    )
    (at, seconds), = rep["phases"]["ec.op.dispatch"]
    assert seconds == pytest.approx(stats["dispatch_span_s"], abs=2e-3)
    assert rep["gaps"] == sorted(rep["gaps"], key=lambda g: -g["seconds"])
    for gap in rep["gaps"]:
        # the phases lie end to end on ONE thread: they cover a gap once
        on_handler = sum(s for name, s in gap["cover"].items() if name.startswith("ec.op."))
        assert on_handler <= gap["seconds"] + 1e-6
    covered = set().union(*(g["cover"] for g in rep["gaps"]))
    assert "ec.op.dispatch" in covered
    assert gaps.main([found[0], "0.5"]) == 0
    out = capsys.readouterr().out
    assert out.startswith("device busy ") and "ec.wait.work" in out
    assert gaps.main([]) == 2


def test_tracing_off_books_the_fields_and_annotates_nothing(tmp_path, monkeypatch, ring):
    import jax

    opened: list[str] = []

    class Recorder:
        def __init__(self, name, **kw):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    trace.set_enabled(False)
    try:
        stats = _encode_single(tmp_path, host_pair=False)
    finally:
        trace.set_enabled(True)
    assert opened == []
    assert _recent() == []
    for field in PHASE_FIELDS + DEVICE_FIELDS + WAIT_FIELDS:
        assert field in stats, field
    assert sum(stats[f] for f in PHASE_FIELDS) == pytest.approx(
        stats["wall_s"], abs=3.5e-4
    )
    # the writers stood waiting for work, whoever was told of it
    assert stats["work_wait_s"] > 0
    assert _span_of_the_waits(stats) == pytest.approx(
        stats["dispatch_span_s"], rel=0.02, abs=2e-3
    )
    # and with it on, the same helper does open them
    _encode_single(tmp_path, host_pair=False)
    assert "ec.op.drain" in opened and "ec.writeback" in opened
    assert "ec.wait.work" in opened


def test_annotation_leaves_jax_alone_where_it_is_not_loaded():
    """Daemons that need no JAX never import it (PR 21): the helper asks
    sys.modules, it does not import."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from seaweedfs_tpu import trace\n"
        "with trace.annotation('ec.read'):\n"
        "    phases = trace.Phases('ec.op.head')\n"
        "    phases.close()\n"
        "assert 'jax' not in sys.modules, 'the helper imported jax'\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True, text=True, timeout=60
    )
    assert proc.returncode == 0, proc.stderr


# --- the device scopes --------------------------------------------------------


def test_fused_programs_carry_the_scopes():
    """The three parts of the fused encode program, and the mesh
    program's gather, lower under stable scope names (the compiled-for-
    TPU view of the same is tests/test_tpu_compile.py's)."""
    import re

    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ec import codec_tpu
    from seaweedfs_tpu.ec.codec_tpu import TpuCodecKernels
    from seaweedfs_tpu.parallel import MeshCodec, make_mesh

    def scoped(text: str, scope: str) -> bool:
        # a component of an operation's name stack: "jit(f)/ec.swar/..."
        # at the top level, "ec.swar/..." inside a shard_map
        return re.search(rf'["/]{re.escape(scope)}/', text) is not None

    kern = TpuCodecKernels()
    x = jax.ShapeDtypeStruct((10, 1024), jnp.uint32)
    text = jax.jit(kern.encode_u32_crc).lower(x).as_text(debug_info=True)
    for scope in (codec_tpu.SCOPE_SWAR, codec_tpu.SCOPE_LAYOUT, codec_tpu.SCOPE_CRC_FOLD):
        assert scoped(text, scope), scope
    rebuilt = jax.jit(
        lambda t: kern.reconstruct_u32_crc(tuple(range(1, 11)), (0,), t)
    ).lower(x).as_text(debug_info=True)
    assert scoped(rebuilt, "ec.swar") and scoped(rebuilt, "ec.crc_fold")

    codec = MeshCodec(make_mesh(jax.devices()[:4], stripe=2))
    vols = jax.ShapeDtypeStruct((2, 10, 2048), jnp.uint32, sharding=codec.block_sharding)
    mesh_text = codec._encode_crc_sharded.lower(vols).as_text(debug_info=True)
    for scope in ("ec.swar", "ec.layout", "ec.crc_fold", "ec.crc_gather"):
        assert scoped(mesh_text, scope), scope
