"""The mixed drive loss (configuration `drive-loss-mixed-128m`, cell
`batch-rebuild-mixed`): under a placement that turns by volume (shard i
of the v-th volume on drive (i + v) mod 7) a dead drive leaves each
volume short of a pair of its own, seven damage signatures among
fourteen volumes, and ONE `VolumeEcShardsBatchRebuild` mends them in ONE
pass of the stream driver's shell: items (chunk, round), group after
group, one reservation, one latch, one root span.

The driver through a MeshCodec over CPU devices (the program the chip
runs, on its bit-matmul arm), held to the plain numpy decode of the
benchmark's reference and to the single-volume driver, byte for byte and
CRC for CRC; the pass's books; a one-signature call; a failure in the
fifth group's round; the host arm; the verb through an in-process node;
the cell's configuration, mix and metric file against the manifest; a
`--rehearse` run of the cell and of each of its controls.

Everything runs on the CPU backend: what is asserted is bytes, counts
and bookkeeping, never a time."""

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
from seaweedfs_tpu.ec.codec import new_encoder
from seaweedfs_tpu.pb import master_pb2, rpc, volume_pb2
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.util.availability import free_port
from seaweedfs_tpu.util.crc import crc32c

from faults import ec_shards_less, ec_stream_threads

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL, CONFIG, MIX = "batch-rebuild-mixed", "drive-loss-mixed-128m", "mixed-rebuild-loop"
METRIC = "batch_rebuild_passes_per_op"
KIB = 1 << 10
LARGE = 1 << 30
SMALL = 16 * KIB  # a shard file is whole rows of this
TILE = 64 * KIB
DRIVES, DEAD, VOLUMES = 7, 3, 14


def lost(v: int) -> list[int]:
    """The shards the dead drive held of the v-th volume."""
    return [i for i in range(ec_files.TOTAL_SHARDS) if (i + v) % DRIVES == DEAD]


def _size(base: str, ids) -> int:
    """The size of a volume's shard files, read off its first survivor."""
    return os.path.getsize(base + ec_files.to_ext(next(
        i for i in range(ec_files.TOTAL_SHARDS) if i not in ids)))


def _json(*parts: str) -> dict:
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(autouse=True)
def own_ring(monkeypatch):
    """A kept ring of the test's own, so that what a call here keeps goes
    with the test."""
    monkeypatch.setattr(ec_stream, "_RING", ec_stream._KeptRing())


@pytest.fixture(scope="module")
def bench():
    """benchmark/harness as benchmark/run.py imports it."""
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(os.path.join(REPO, "benchmark"))
        names = ("reference", "reference_rebuild", "readers", "node")
        yield type("Harness", (), {
            name: importlib.import_module("harness." + name) for name in names})


def _mesh_codec():
    """A 1x1 MeshCodec over a CPU device (the CPU's own default is the
    host arm), as the one chip's."""
    import jax

    from seaweedfs_tpu.parallel import MeshCodec, make_mesh

    return MeshCodec(make_mesh(jax.devices()[:1], stripe=1))


def _volumes(tmp_path, lost_of: list[list[int]], seed: int) -> list[str]:
    """Volumes of 3 to 16 rows of SMALL a shard, each short of its own
    lost shards: a chunk's two volumes end in different rounds."""
    bases = [str(tmp_path / f"v{v}") for v in range(len(lost_of))]
    for v, (base, ids) in enumerate(zip(bases, lost_of)):
        ec_shards_less(base, 10 * SMALL * (3 + v) - 7, seed + v, ids, LARGE, SMALL)
        assert _size(base, ids) == (3 + v) * SMALL
    return bases


def _files(base: str, ids) -> dict[int, np.ndarray]:
    return {i: np.fromfile(base + ec_files.to_ext(i), dtype=np.uint8) for i in ids}


def _single(bases: list[str], lost_of) -> tuple[list[dict], list[dict]]:
    """What the single-volume driver leaves of each volume, bytes and
    CRCs; the files it rebuilt are removed again."""
    want, crcs = [], []
    for base, ids in zip(bases, lost_of):
        stats: dict = {}
        assert ec_files.rebuild_ec_files(
            base, rs=new_encoder(backend="cpu"), stats=stats, want_crcs=True
        ) == ids
        want.append(_files(base, ids))
        crcs.append(stats["shard_crcs"])
        for i in ids:
            os.remove(base + ec_files.to_ext(i))
    return want, crcs


def _rounds(sizes: list[int], lost_of: list[list[int]], tile: int) -> int:
    """Rounds of the pass: each signature's chunk runs to its largest file."""
    chunks: dict[tuple, int] = {}
    for size, ids in zip(sizes, lost_of):
        chunks[tuple(ids)] = max(chunks.get(tuple(ids), 0), size)
    return sum(-(-size // tile) for size in chunks.values())


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


# --- the driver's pass --------------------------------------------------------


@pytest.fixture(scope="module")
def mixed_call(tmp_path_factory, bench):
    """Fourteen volumes under the rotated placement through ONE call of
    the mesh arm at a 64 KiB tile, beside the numpy decode and the
    single-volume driver of the same volumes."""
    import jax

    tmp = tmp_path_factory.mktemp("mixed")
    lost_of = [lost(v) for v in range(VOLUMES)]
    bases = _volumes(tmp, lost_of, seed=4100)
    decoded = [dict(zip(ids, bench.reference_rebuild.decode(base, ids)))
               for base, ids in zip(bases, lost_of)]
    want, crcs = _single(bases, lost_of)
    sizes = [_size(base, ids) for base, ids in zip(bases, lost_of)]
    stats: dict = {}
    trace.reset()
    del Annotations.opened[:]
    with pytest.MonkeyPatch.context() as mp:
        # a ring of the call's own (module scope: before own_ring)
        mp.setattr(ec_stream, "_RING", ec_stream._KeptRing())
        mp.setattr(jax.profiler, "TraceAnnotation", Annotations)
        rebuilt = ec_stream.stream_rebuild_ec_files_batch(
            bases, codec=_mesh_codec(), tile_bytes=TILE, stats=stats, want_crcs=True)
    yield {"bases": bases, "lost": lost_of, "decoded": decoded, "want": want,
           "crcs": crcs, "sizes": sizes, "stats": stats, "rebuilt": rebuilt,
           "spans": trace.debug_payload(n=256)["recent"],
           "annotations": list(Annotations.opened)}
    trace.reset()


def test_the_rotated_placement_makes_seven_signatures_of_two():
    pairs = [lost(v) for v in range(VOLUMES)]
    assert pairs[:DRIVES] == [[3, 10], [2, 9], [1, 8], [0, 7], [6, 13], [5, 12], [4, 11]]
    assert pairs[DRIVES:] == pairs[:DRIVES]


def test_every_rebuilt_file_is_the_numpy_decode_and_the_single_volume_drivers(mixed_call):
    got = mixed_call
    assert got["rebuilt"] == got["lost"]
    for base, ids, decoded, want in zip(got["bases"], got["lost"], got["decoded"], got["want"]):
        files = _files(base, ids)
        for i in ids:
            assert np.array_equal(files[i], decoded[i]), (base, i)
            assert np.array_equal(files[i], want[i]), (base, i)


def test_shard_crcs_are_each_volumes_own(mixed_call):
    got = mixed_call
    assert got["stats"]["shard_crcs"] == got["crcs"]
    for base, ids, crcs in zip(got["bases"], got["lost"], got["stats"]["shard_crcs"]):
        assert sorted(crcs) == ids
        for i in ids:
            with open(base + ec_files.to_ext(i), "rb") as f:
                assert crcs[i] == crc32c(f.read())


def test_the_report_says_seven_groups_in_one_pass(mixed_call):
    st, sizes = mixed_call["stats"], mixed_call["sizes"]
    assert (st["batch_volumes"], st["batch_groups"], st["batch_passes"]) == (14, 7, 1)
    # the pass's tiles: every chunk's rounds, each to its largest file
    assert st["tiles"] == _rounds(sizes, mixed_call["lost"], TILE) == 25
    assert (st["survivors"], st["targets"], st["tile_bytes"]) == (10, 2, TILE)
    assert st["survivor_bytes"] == 10 * sum(sizes)
    assert "codec_arm" not in st and st["mesh"]["vol"] * st["mesh"]["stripe"] == 1
    # ONE ring for the call, sized for the widest chunk's [2, 10, tile]
    assert st["ring_fresh_bytes"] == st["ring_slots"] * 2 * 10 * TILE
    phases = ("head_s", "dispatch_span_s", "drain_s", "write_tail_s", "flush_s")
    assert sum(st[f] for f in phases) == pytest.approx(st["wall_s"], abs=7e-4)


def test_one_root_span_and_an_ec_group_a_group(mixed_call):
    roots = [s for s in mixed_call["spans"] if s["name"] == "ec_stream.rebuild_batch"]
    assert len(roots) == 1
    annot = roots[0]["annot"]
    assert (annot["batch_groups"], annot["batch_passes"], annot["batch_volumes"]) == (
        "7", "1", "14")
    assert annot["tiles"] == str(mixed_call["stats"]["tiles"])
    opened = mixed_call["annotations"]
    group = [t for name, t in opened if name == "ec.group"]
    launch = [t for name, t in opened if name == "ec.launch"]
    assert len(group) == 7 and len(launch) == mixed_call["stats"]["tiles"]
    # on the dispatcher's line, the handler's thread, around a launch
    assert set(group) == set(launch) and len(set(group)) == 1


def test_chunks_of_one_group_change_no_program(tmp_path, monkeypatch):
    """WEED_EC_PIPELINE_BATCH=1 slices every group into chunks of one
    volume: fourteen chunks, still one pass, and `ec.group` only where
    the signature changes."""
    import jax

    lost_of = [lost(v % 2) for v in range(4)]  # [3,10], [2,9], [3,10], [2,9]
    bases = _volumes(tmp_path, lost_of, seed=4101)
    want, crcs = _single(bases, lost_of)
    monkeypatch.setenv("WEED_EC_PIPELINE_BATCH", "1")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotations)
    del Annotations.opened[:]
    stats: dict = {}
    ec_stream.stream_rebuild_ec_files_batch(
        bases, codec=_mesh_codec(), tile_bytes=TILE, stats=stats, want_crcs=True)
    for base, ids, w in zip(bases, lost_of, want):
        files = _files(base, ids)
        assert all(np.array_equal(files[i], w[i]) for i in ids)
    assert stats["shard_crcs"] == crcs
    assert (stats["batch_groups"], stats["batch_passes"]) == (2, 1)
    sizes = [(3 + v) * SMALL for v in range(4)]
    assert stats["tiles"] == sum(-(-s // TILE) for s in sizes)
    # the chunks run group after group: v0, v2 | v1, v3
    assert sum(name == "ec.group" for name, _ in Annotations.opened) == 2


def test_a_one_signature_call_is_the_pass_it_was(tmp_path):
    lost_of = [lost(0)] * 3
    bases = _volumes(tmp_path, lost_of, seed=4102)
    want, crcs = _single(bases, lost_of)
    stats: dict = {}
    assert ec_stream.stream_rebuild_ec_files_batch(
        bases, codec=_mesh_codec(), tile_bytes=TILE, stats=stats, want_crcs=True
    ) == lost_of
    for base, ids, w in zip(bases, lost_of, want):
        files = _files(base, ids)
        assert all(np.array_equal(files[i], w[i]) for i in ids)
    assert stats["shard_crcs"] == crcs
    assert (stats["batch_groups"], stats["batch_passes"], stats["batch_volumes"]) == (1, 1, 3)
    # one chunk: the rounds of its largest file, in slots of its own width
    assert stats["tiles"] == -(-5 * SMALL // TILE)
    assert stats["ring_fresh_bytes"] == stats["ring_slots"] * 3 * 10 * TILE


def test_a_failure_in_the_fifth_groups_round_leaves_no_target_of_any_volume(
        tmp_path, monkeypatch):
    lost_of = [lost(v) for v in range(VOLUMES)]
    bases = _volumes(tmp_path, lost_of, seed=4103)
    codec = _mesh_codec()
    real, seen = codec.reconstruct_batch_u32, []

    def fails_at_the_fifth(survivors, targets, vols):
        if (survivors, targets) not in seen:
            seen.append((survivors, targets))
        if len(seen) == 5:
            raise RuntimeError("device lost in the fifth group")
        return real(survivors, targets, vols)

    monkeypatch.setattr(codec, "reconstruct_batch_u32", fails_at_the_fifth)
    with pytest.raises(RuntimeError, match="fifth group"):
        ec_stream.stream_rebuild_ec_files_batch(
            bases, codec=codec, tile_bytes=TILE, want_crcs=True, durable=True)
    # the first four groups' files were written whole, and went all the same
    assert seen[4][1] == (6, 13)
    targets = [base + ec_files.to_ext(i) for base, ids in zip(bases, lost_of) for i in ids]
    assert len(targets) == 28 and not [p for p in targets if os.path.exists(p)]
    assert all(os.path.exists(base + ec_files.to_ext(i))
               for base, ids in zip(bases, lost_of)
               for i in range(ec_files.TOTAL_SHARDS) if i not in ids)
    assert not ec_stream_threads()


def test_the_host_arm_is_one_pass_too(tmp_path):
    """No codec on a host whose devices are CPUs: the host arm's (volume,
    tile) work items of every signature in one pipeline."""
    lost_of = [lost(v) for v in range(VOLUMES)]
    bases = _volumes(tmp_path, lost_of, seed=4104)
    want, crcs = _single(bases, lost_of)
    stats: dict = {}
    assert ec_stream.stream_rebuild_ec_files_batch(
        bases, tile_bytes=2 * SMALL, stats=stats, want_crcs=True) == lost_of
    for base, ids, w in zip(bases, lost_of, want):
        files = _files(base, ids)
        assert all(np.array_equal(files[i], w[i]) for i in ids)
    assert stats["shard_crcs"] == crcs
    assert stats["codec_arm"] == "host" and "host_inline" not in stats
    assert (stats["batch_groups"], stats["batch_passes"]) == (7, 1)
    assert stats["tiles"] == sum(-(-(3 + v) * SMALL // (2 * SMALL)) for v in range(VOLUMES))


# --- the verb ---------------------------------------------------------------


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


def test_the_verb_mends_the_mixed_set_in_one_pass(tmp_path, bench, monkeypatch):
    """Fourteen EC volumes on one node under a master, each short of its
    own pair, ONE `VolumeEcShardsBatchRebuild`: nobody falls through, the
    report line says seven groups in one pass, every lost file comes back
    as it was and its CRC is merged into the volume's `.ecc`."""
    master = MasterServer(port=free_port(), volume_size_limit_mb=64)
    master.start()
    vs = VolumeServer(
        [str(tmp_path / "node")], port=free_port(), master=f"127.0.0.1:{master.port}",
        heartbeat_interval=0.2, max_volume_counts=[100], ec_codec="tpu")
    vs.start()
    handler = logging.Handler()
    handler.lines = []
    handler.emit = lambda record: handler.lines.append("I] " + record.getMessage())
    logger = logging.getLogger("seaweedfs_tpu")
    logger.addHandler(handler)
    try:
        _wait_for("the node in the topology", lambda: master.topology.data_nodes())
        rng = np.random.default_rng(41)
        collection = {}
        for n in range(VOLUMES):
            name = f"m{n}"
            with urllib.request.urlopen(
                f"http://127.0.0.1:{master.port}/vol/grow?collection={name}&count=1",
                timeout=10,
            ) as r:
                assert json.loads(r.read())["count"] == 1
            with urllib.request.urlopen(
                f"http://127.0.0.1:{master.port}/dir/assign?collection={name}", timeout=10
            ) as r:
                assign = json.loads(r.read())
            urllib.request.urlopen(urllib.request.Request(
                f"http://{assign['url']}/{assign['fid']}", data=rng.bytes(100_001 + n),
                method="POST"), timeout=30).close()
            collection[int(assign["fid"].split(",")[0])] = name
        vids = sorted(collection, key=lambda vid: collection[vid][1:].zfill(2))
        assert len(vids) == VOLUMES
        monkeypatch.setattr(ec_files, "_use_stream_driver", lambda rs: True)
        with grpc.insecure_channel(f"127.0.0.1:{vs.grpc_port}") as ch:
            stub = rpc.volume_stub(ch)
            for vid in vids:
                stub.VolumeMarkReadonly(volume_pb2.VolumeMarkReadonlyRequest(volume_id=vid))
                stub.VolumeEcShardsGenerate(volume_pb2.VolumeEcShardsGenerateRequest(
                    volume_id=vid, collection=collection[vid]))
                stub.VolumeEcShardsMount(volume_pb2.VolumeEcShardsMountRequest(
                    volume_id=vid, collection=collection[vid], shard_ids=list(range(14))))
                stub.VolumeDelete(volume_pb2.VolumeDeleteRequest(volume_id=vid))
            base = {vid: vs.store.find_ec_volume(vid).base_name for vid in vids}
            damage = {vid: lost(v) for v, vid in enumerate(vids)}
            before = {vid: _files(base[vid], ids) for vid, ids in damage.items()}
            _wait_for("the master to list all 14 of every volume", lambda: all(
                len(_located(master, vid)) == 14 for vid in vids))
            for vid, ids in damage.items():
                stub.VolumeEcShardsUnmount(volume_pb2.VolumeEcShardsUnmountRequest(
                    volume_id=vid, shard_ids=ids))
                stub.VolumeEcShardsDelete(volume_pb2.VolumeEcShardsDeleteRequest(
                    volume_id=vid, collection=collection[vid], shard_ids=ids))
            del handler.lines[:]
            stub.VolumeEcShardsBatchRebuild(
                volume_pb2.VolumeEcShardsBatchGenerateRequest(volume_ids=vids), timeout=120)
            lines = list(handler.lines)
            for vid, ids in damage.items():
                stub.VolumeEcShardsMount(volume_pb2.VolumeEcShardsMountRequest(
                    volume_id=vid, collection=collection[vid], shard_ids=ids))
    finally:
        logger.removeHandler(handler)
        vs.stop()
        master.stop()
    (rep,) = bench.node.verb_reports("\n".join(lines), "batch_rebuild")
    assert bench.node.verb_reports("\n".join(lines), "rebuild") == []
    assert (rep["batch_volumes"], rep["batch_groups"], rep["batch_passes"],
            rep["fell_through"]) == (14, 7, 1, 0)
    assert (rep["survivors"], rep["targets"]) == (10, 2)
    assert rep["mesh"]["arm"] == "bit-matmul" and not rep.get("fallback")
    for vid, ids in damage.items():
        after = _files(base[vid], ids)
        assert all(np.array_equal(after[i], before[vid][i]) for i in ids), vid
        ecc = _json(base[vid] + ".ecc")
        assert all(ecc["shards"][str(i)]["crc"] == bench.reference.crc32c(before[vid][i])
                   for i in ids), vid


# --- the benchmark's new files against the manifest ---------------------------


@pytest.fixture(scope="module")
def config() -> dict:
    return _json("benchmark", "configs", CONFIG + ".json")


def test_manifest_entries_of_the_cell(config):
    manifest = _json("BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert manifest["configs"].index(entry) == 6
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert "Erasure-Coding-for-warm-storage" in entry["source"]
    assert entry["source"] not in [c["source"] for c in manifest["configs"] if c is not entry]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == list(config["reduced"]) == [
        "volumeSizeLimitMB", "volumes_per_drive", "drives", "nodes"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert manifest["workloads"].index(cell) == 6
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert sum(1 for w in manifest["workloads"] if w["chips"] == 4) == 1
    assert CELL in next(
        m for m in manifest["end_to_end"] if m["name"] == "ec_gbps")["workloads"]
    # the cell reports every per-layer metric of its one-signature sibling
    # but the whole-program roofline, whose bytes may no longer be what this
    # program moves, and its own
    sibling = {m["name"] for m in manifest["per_layer"]
               if "batch-rebuild-2lost" in m.get("workloads", [])}
    listing = {m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", [])}
    assert listing == sibling - {"rebuild_kernel_roofline"}
    # appended after every metric the cell reported before it
    assert METRIC in listing and [
        m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", [])
    ][-1] == METRIC
    mix = _json("benchmark", "traffic", MIX + ".json")
    assert mix == {"generator": "mixed_rebuild_loop", "rpc": "VolumeEcShardsBatchRebuild",
                   "volumes_per_call": 14, "concurrency": 1, "trace_ops": 3, "read_back": 28}


def test_configuration_is_the_drive_loss_turned_by_volume(config):
    drive = _json("benchmark", "configs", "drive-loss-256m.json")
    for key in ("code", "needle_sizes", "layout", "chips"):
        assert config[key] == drive[key], key
    assert [v["collection"] for v in config["volumes"]] == [f"m{i}" for i in range(14)]
    assert {v["mib"] for v in config["volumes"]} == {128}
    assert len(config["volumes"]) == _json(
        "benchmark", "traffic", MIX + ".json")["volumes_per_call"]
    failure = config["failure"]
    assert (failure["drives"], failure["lost_drive"]) == (DRIVES, DEAD)
    assert len(config["guarantees"]) == len(drive["guarantees"]) == 6
    assert set(config["assumed"]) == {"placement", "lost_drive", "relose", "disk", "needle_sizes"}


def test_the_generator_reads_the_placement_rule(config, bench):
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(os.path.join(REPO, "benchmark"))
        gen = importlib.import_module("traffic.mixed_rebuild_loop")
    ctx = type("Ctx", (), {"config": config, "vids": list(range(101, 115))})()
    got = gen.lost_of(ctx)
    assert [got[vid] for vid in ctx.vids] == [lost(v) for v in range(VOLUMES)]
    assert gen.signatures(ctx) == 7


REPORT = {"batch_volumes": 14, "batch_groups": 7, "batch_passes": 1, "fell_through": 0,
          "survivors": 10, "targets": 2, "tiles": 28, "compute_s": 0.05, "wall_s": 0.2}


def _observed(reports: list[dict]) -> dict:
    return {"reports": reports,
            "window": {"seconds": 2.0, "gib": 1.75 * len(reports), "requests": len(reports)},
            "trace": None, "traced_work": None, "device_kind": "TPU v5 lite",
            "rehearse": False}


def test_metric_file_and_manifest_agree(bench):
    metric = bench.readers.load_metric(METRIC)
    entry = next(m for m in _json("BENCHMARK.json")["per_layer"] if m["name"] == METRIC)
    for key in ("name", "unit", "better", "layer", "moves", "source"):
        assert metric[key] == entry[key], key
    # a cell joins a metric by the manifest's list alone (rack-batch-rebuild
    # did): the file's copy is the list as the metric came
    assert metric["workloads"] == entry["workloads"][:2] == ["batch-rebuild-2lost", CELL]
    assert (entry["moves"], entry["layer"], entry["better"]) == (
        "ec_gbps", "stream driver", "lower")
    # one pass a call, whatever the signatures: 1.0 over three calls
    assert bench.readers.read_metric(metric, _observed([REPORT] * 3)) == 1.0
    # a parent's line prints no `batch_passes`: the metric is left out, never 0
    old = {k: v for k, v in REPORT.items() if k != "batch_passes"}
    assert bench.readers.read_metric(metric, _observed([old] * 3)) is None


# --- a rehearsal of the cell, and of each control -----------------------------

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
    ([], True, ()),
    (["--control", "cauchy"], False, ("rebuilt_differs_from_decode", "data_shards_differ",
                                      "parity_shards_differ")),
    (["--control", "crc32"], False, ("ecc_crcs_differ",)),
    (["--control", "not_rebuilt"], False, ("shards_not_rewritten",)),
]
COMPARED = {
    "ops_failed", "ops_without_report", "ops_wrong_shards", "ops_not_batched",
    "dat_needles_differ", "rebuilt_differs_from_decode", "data_shards_differ",
    "parity_shards_differ", "ecc_crcs_differ", "shards_not_rewritten",
    "survivors_rewritten", "ec_bodies_differ",
}


@pytest.mark.parametrize("extra,correct,numbers", CASES,
                         ids=["sound", "cauchy", "crc32", "not_rebuilt"])
def test_rehearsal_of_the_cell(extra, correct, numbers, one_bench_rehearsal_at_a_time):
    bench_dir = os.path.join(REPO, "benchmark")
    code = RUN.format(bench=bench_dir, root=REPO, run=os.path.join(bench_dir, "run.py"))
    proc = subprocess.run(
        [sys.executable, "-c", code, "--workload", CELL, "--seed", str(2**31 + 41),
         "--seconds", "2", "--trace", "0", "--rehearse", *extra],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is correct, line["compared"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["compared"]) == COMPARED
    for name in numbers:
        assert line["compared"][name]["value"] > 0, name
    # a control breaks one guarantee: the operations themselves were sound,
    # and what another guarantee's number reads stays 0
    seen_by = set(numbers) | ({"ec_bodies_differ"} if "data_shards_differ" in numbers else set())
    for name in COMPARED - seen_by:
        assert line["compared"][name]["value"] == 0, name
    assert all(name.startswith("rehearsal.") for name in line["metrics"])
