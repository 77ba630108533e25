"""The rack loss, batched (configuration `rack-loss-256m`, cell
`rack-batch-rebuild`): a server of a four-rack EC tier dies, and ONE
`VolumeEcShardsBatchRebuild` on a surviving server mends every volume
it held shards of, its batch driver fetching six of each volume's ten
survivors from two other servers.

  driver   three volumes placed as the configuration's file says (A
           holds 0,4,8,12; B's 1,5,9,13 are lost; C's 2,6,10 and D's
           3,7,11 lie in directories of their own), read by in-process
           readers over the holders' files, through each arm of the
           batch driver: held to the benchmark's plain numpy decode and
           to the single-volume driver over the same readers; every
           remote span fetched once; a reader that comes back short
           in the third round, and the call's files are all gone
  servers  a master, the node (codec tpu, the verb steered the chip's
           way) and two host-only holders, three volumes through
           `ec.encode` and spread as the cell spreads them: ONE call of
           the verb (its report line, its root span, its annotations,
           the dials of its readers), then `ec.rebuild.batch -force`
           after a second loss, which makes ONE call as well
  cell     the configuration, the mix and the manifest's entries as
           files; a `--rehearse` run of the cell and of each control

Everything runs on the CPU backend: what is asserted is bytes, counts
and bookkeeping, never a device time."""

import importlib
import io
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
from seaweedfs_tpu.shell.command_env import CommandEnv
from seaweedfs_tpu.shell.commands import do_ec_rebuild_batch
from seaweedfs_tpu.util.availability import free_port

from faults import ec_shards_less

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KIB, MIB = 1 << 10, 1 << 20
CELL, CONFIG, MIX = "rack-batch-rebuild", "rack-loss-256m", "rack-batch-rebuild-loop"
PLACEMENT = {"A": [0, 4, 8, 12], "B": [1, 5, 9, 13], "C": [2, 6, 10], "D": [3, 7, 11]}
LOST = PLACEMENT["B"]
REMOTE = PLACEMENT["C"] + PLACEMENT["D"]
SMALL = 16 * KIB  # a shard file is whole rows of this, and the driver's round here
SHARD_BYTES = [5 * SMALL, 4 * SMALL, 3 * SMALL]  # three volumes, five rounds at most
REMOTE_FIELDS = ("remote_read_s", "survivor_bytes_remote", "rebuilt_bytes",
                 "arbiter_wait_s", "remote_fetches", "remote_fetches_dataplane")


def _json(*parts: str) -> dict:
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    """benchmark/harness as benchmark/run.py imports it."""
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(os.path.join(REPO, "benchmark"))
        names = ("reference", "reference_rebuild", "readers", "node")
        yield type("Harness", (), {
            name: importlib.import_module("harness." + name) for name in names})


@pytest.fixture(autouse=True)
def own_ring(monkeypatch):
    """A kept ring of the test's own: what a call here keeps goes with it."""
    monkeypatch.setattr(ec_stream, "_RING", ec_stream._KeptRing())


# --- the driver, with in-process readers over the holders' files ---------------------


class Rack:
    """Three volumes placed as the configuration's file says. `origin`
    keeps each volume's ten survivors (the reference decodes those),
    `a` the rebuilder's four, `c` and `d` the holders' three each; the
    readers fetch from the holders' files and log every span."""

    def __init__(self, root, seed: int):
        self.origin, self.bases, self.readers, self.log = [], [], [], []
        self.short_at: int | None = None  # a span's offset a reader comes back short at
        for v, size in enumerate(SHARD_BYTES):
            origin = str(root / "origin" / f"v{v}")
            os.makedirs(os.path.dirname(origin), exist_ok=True)
            ec_shards_less(origin, 10 * size - 11 * v - 5, seed + v, LOST, 1 << 30, SMALL)
            assert os.path.getsize(origin + ec_files.to_ext(0)) == size
            readers = {}
            for server, ids in PLACEMENT.items():
                if server == "B":
                    continue
                base = str(root / server / f"v{v}")
                os.makedirs(os.path.dirname(base), exist_ok=True)
                for sid in ids:
                    os.link(origin + ec_files.to_ext(sid), base + ec_files.to_ext(sid))
                    if server != "A":
                        readers[sid] = self._reader(v, sid, base + ec_files.to_ext(sid))
            self.origin.append(origin)
            self.bases.append(str(root / "A" / f"v{v}"))
            self.readers.append(readers)

    def _reader(self, v: int, sid: int, path: str):
        def read_into(offset: int, dest) -> int:
            self.log.append((v, sid, offset, len(dest)))
            with open(path, "rb") as f:
                f.seek(offset)
                if offset == self.short_at:
                    return f.readinto(memoryview(dest)[: len(dest) // 2])
                return f.readinto(dest)

        return read_into

    def rebuilt(self, v: int) -> list[np.ndarray]:
        return [np.fromfile(self.bases[v] + ec_files.to_ext(i), dtype=np.uint8) for i in LOST]


ARMS = {
    # the program the chip runs, over a CPU device (its bit-matmul arm)
    "mesh": lambda: {"codec": ec_stream._default_mesh_codec(3), "tile_bytes": SMALL},
    # the host arm's pools: more work items than it runs inline
    "host": lambda: {"codec": None, "tile_bytes": SMALL // 4},
    # the host arm inline, on the caller's thread
    "host-inline": lambda: {"codec": None, "tile_bytes": SMALL},
}


def _batch(rack: Rack, arm: str, stats: dict) -> list[list[int]]:
    return ec_stream.stream_rebuild_ec_files_batch(
        rack.bases, stats=stats, want_crcs=True, remote_readers=rack.readers,
        remote_report=lambda: {"remote_fetches": len(rack.log)}, **ARMS[arm](),
    )


@pytest.fixture(scope="module", params=sorted(ARMS))
def batched(request, tmp_path_factory):
    arm = request.param
    rack = Rack(tmp_path_factory.mktemp("rack-" + arm), seed=450)
    stats: dict = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ec_stream, "_RING", ec_stream._KeptRing())
        rebuilt = _batch(rack, arm, stats)
    # the spans the batch fetched (the readers serve later tests too)
    return {"arm": arm, "rack": rack, "stats": stats, "rebuilt": rebuilt,
            "log": list(rack.log)}


def test_rack_placed_volumes_rebuild_as_the_reference_decodes(batched, bench):
    rack, stats = batched["rack"], batched["stats"]
    assert batched["rebuilt"] == [LOST] * 3
    for v, origin in enumerate(rack.origin):
        want = bench.reference_rebuild.decode(origin, LOST)
        assert all(np.array_equal(got, w) for got, w in zip(rack.rebuilt(v), want)), v
        assert stats["shard_crcs"][v] == {
            sid: bench.reference.crc32c(w) for sid, w in zip(LOST, want)}
    # one group: the same four lost and ten survivors, four of them local
    assert (stats["batch_volumes"], stats["batch_groups"], stats["batch_passes"]) == (3, 1, 1)
    assert (stats["survivors"], stats["targets"]) == (10, 4)
    # no survivor comes to the rebuilder's directory
    assert sorted(f for f in os.listdir(os.path.dirname(rack.bases[0]))
                  if f.startswith("v0.")) == sorted(
        "v0" + ec_files.to_ext(i) for i in PLACEMENT["A"] + LOST)


def test_the_batch_is_the_single_volume_driver_byte_for_byte(batched, tmp_path):
    """The single-volume driver over the same readers, one volume at a
    time, into a rebuilder directory of its own."""
    rack = batched["rack"]
    rs = new_encoder(backend="cpu")
    for v, base in enumerate(rack.bases):
        alone = str(tmp_path / f"v{v}")
        for sid in PLACEMENT["A"]:
            os.link(base + ec_files.to_ext(sid), alone + ec_files.to_ext(sid))
        rebuild_fn, fetch_fn = ec_stream.local_rebuild_fns(rs)
        assert ec_stream.stream_rebuild_ec_files(
            alone, tile_bytes=SMALL, rebuild_fn=rebuild_fn, fetch_fn=fetch_fn,
            remote_readers=rack.readers[v]) == LOST
        for sid, got in zip(LOST, rack.rebuilt(v)):
            assert np.array_equal(
                np.fromfile(alone + ec_files.to_ext(sid), dtype=np.uint8), got), (v, sid)


def test_each_remote_span_is_fetched_once(batched):
    log, stats = batched["log"], batched["stats"]
    spans = [(v, sid, off) for v, sid, off, _ in log]
    assert len(spans) == len(set(spans))
    tile = ARMS[batched["arm"]]()["tile_bytes"]
    assert len(spans) == sum(6 * -(-size // tile) for size in SHARD_BYTES)
    for v, size in enumerate(SHARD_BYTES):
        assert sum(n for w, _, _, n in log if w == v) == 6 * size
        assert {sid for w, sid, _, _ in log if w == v} == set(REMOTE)
    assert stats["survivor_bytes_remote"] == 6 * sum(SHARD_BYTES)
    assert stats["survivor_bytes"] == 10 * sum(SHARD_BYTES)
    assert stats["rebuilt_bytes"] == 4 * sum(SHARD_BYTES)
    # the readers' own books ride the line, and the fetch pool's seconds
    assert stats["remote_fetches"] == len(spans) and stats["remote_read_s"] > 0


@pytest.mark.parametrize("arm", sorted(ARMS))
def test_a_short_read_in_the_third_round_leaves_no_target_file(arm, tmp_path):
    """The abort contract scrub relies on: a remote fetch that comes
    back short fails the call, and no target file of ANY volume is left,
    those of volumes whose rounds were whole included."""
    rack = Rack(tmp_path, seed=451)
    tile = ARMS[arm]()["tile_bytes"]
    rack.short_at = 2 * tile
    with pytest.raises(ValueError, match="truncated"):
        _batch(rack, arm, {})
    assert any(off == 2 * tile for _, _, off, _ in rack.log)
    for base in rack.bases:
        for sid in LOST:
            assert not os.path.exists(base + ec_files.to_ext(sid)), (base, sid)
    leftover = [t for t in threading.enumerate() if t.name.startswith("ec-stream-")]
    assert not leftover


def test_a_shard_held_elsewhere_rides_the_batch_as_a_survivor(tmp_path, bench):
    """A volume short of four shards here whose shard 7 is mounted on
    another server, with only nine local: 7 is fetched as a survivor of
    the batch and never regenerated, beside a volume with all but one
    local."""
    stats: dict = {}
    bases, fetched = [], []
    lost = [[2, 11, 12, 13], [2]]
    for v in range(2):
        base = str(tmp_path / f"v{v}")
        ec_shards_less(base, 10 * 2 * SMALL - 3, 452 + v, lost[v], 1 << 30, SMALL)
        bases.append(base)
    held = str(tmp_path / "elsewhere.ec07")
    os.rename(bases[0] + ".ec07", held)

    def read_7(offset: int, dest) -> int:
        fetched.append(offset)
        with open(held, "rb") as f:
            f.seek(offset)
            return f.readinto(dest)

    rebuilt = ec_stream.stream_rebuild_ec_files_batch(
        bases, codec=ec_stream._default_mesh_codec(2), tile_bytes=SMALL, stats=stats,
        remote_readers=[{7: read_7}, {}])
    assert rebuilt == lost
    assert not os.path.exists(bases[0] + ".ec07") and sorted(fetched) == [0, SMALL]
    assert stats["batch_groups"] == 2 and stats["survivor_bytes_remote"] == 2 * SMALL
    os.rename(held, bases[0] + ".ec07")
    for base, ids in zip(bases, lost):
        got = [np.fromfile(base + ec_files.to_ext(i), dtype=np.uint8) for i in ids]
        for i in ids:
            os.remove(base + ec_files.to_ext(i))
        want = bench.reference_rebuild.decode(base, ids)
        assert all(np.array_equal(g, w) for g, w in zip(got, want)), base


def test_too_few_survivors_anywhere_fail_before_a_file_is_made(tmp_path):
    rack = Rack(tmp_path, seed=453)
    del rack.readers[1][2]  # nine survivors of the second volume
    with pytest.raises(ValueError, match="too few shard files to rebuild"):
        _batch(rack, "mesh", {})
    for base in rack.bases:
        assert not any(os.path.exists(base + ec_files.to_ext(sid)) for sid in LOST)


# --- three servers: the verb, its line and span, and the shell ---------------------------


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
def served(tmp_path_factory, bench):
    """Three volumes through ec.encode on A, C's and D's shards moved to
    them with the program's verbs, B's four unmounted and deleted; ONE
    VolumeEcShardsBatchRebuild on A, mount; then the four lost again and
    `ec.rebuild.batch -force`."""
    import jax

    from seaweedfs_tpu.client import operation

    master = MasterServer(port=free_port(), volume_size_limit_mb=64)
    master.start()
    servers: dict[str, VolumeServer] = {}

    def start(name: str, codec: str) -> VolumeServer:
        vs = servers[name] = VolumeServer(
            [str(tmp_path_factory.mktemp("rackbatch" + name))], port=free_port(),
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
    try:
        rng = np.random.default_rng(45)
        needles: dict[int, tuple[str, bytes]] = {}
        for n in range(3):
            collection = f"r{n}"
            with urllib.request.urlopen(
                f"http://127.0.0.1:{master.port}/vol/grow?collection={collection}&count=1",
                timeout=10,
            ) as r:
                assert json.loads(r.read())["count"] == 1
            with urllib.request.urlopen(
                f"http://127.0.0.1:{master.port}/dir/assign?collection={collection}", timeout=10
            ) as r:
                assign = json.loads(r.read())
            body = rng.bytes(900_001 + n)
            urllib.request.urlopen(urllib.request.Request(
                f"http://{assign['url']}/{assign['fid']}", data=body, method="POST"),
                timeout=30).close()
            needles[int(assign["fid"].split(",")[0])] = (assign["fid"], body)
        vids = sorted(needles)
        collection = {vid: f"r{n}" for n, vid in enumerate(vids)}
        holders = {name: start(name, "native") for name in ("C", "D")}
        with pytest.MonkeyPatch.context() as mp, \
                grpc.insecure_channel(f"127.0.0.1:{a.grpc_port}") as ch:
            mp.setattr(ec_files, "_use_stream_driver", lambda rs: True)
            stub = rpc.volume_stub(ch)
            for vid in vids:
                stub.VolumeMarkReadonly(volume_pb2.VolumeMarkReadonlyRequest(volume_id=vid))
                stub.VolumeEcShardsGenerate(volume_pb2.VolumeEcShardsGenerateRequest(
                    volume_id=vid, collection=collection[vid]))
                stub.VolumeEcShardsMount(volume_pb2.VolumeEcShardsMountRequest(
                    volume_id=vid, collection=collection[vid], shard_ids=list(range(14))))
                stub.VolumeDelete(volume_pb2.VolumeDeleteRequest(volume_id=vid))
            base = {vid: a.store.find_ec_volume(vid).base_name for vid in vids}
            shard_bytes = os.path.getsize(base[vids[0]] + ".ec00")
            before = {vid: {i: np.fromfile(base[vid] + ec_files.to_ext(i), dtype=np.uint8)
                            for i in range(14)} for vid in vids}

            def lose(vid: int, ids: list[int]) -> None:
                stub.VolumeEcShardsUnmount(volume_pb2.VolumeEcShardsUnmountRequest(
                    volume_id=vid, shard_ids=ids))
                stub.VolumeEcShardsDelete(volume_pb2.VolumeEcShardsDeleteRequest(
                    volume_id=vid, collection=collection[vid], shard_ids=ids))

            where = {vid: {i: os.path.dirname(base[vid]) for i in PLACEMENT["A"] + LOST}
                     for vid in vids}
            for vid in vids:
                want = {i: [f"127.0.0.1:{a.port}"] for i in PLACEMENT["A"]}
                for peer, vs in holders.items():
                    ids = PLACEMENT[peer]
                    with grpc.insecure_channel(f"127.0.0.1:{vs.grpc_port}") as pch:
                        pstub = rpc.volume_stub(pch)
                        pstub.VolumeEcShardsCopy(volume_pb2.VolumeEcShardsCopyRequest(
                            volume_id=vid, collection=collection[vid], shard_ids=ids,
                            copy_ecx_file=True, source_data_node=f"127.0.0.1:{a.port}"))
                        pstub.VolumeEcShardsMount(volume_pb2.VolumeEcShardsMountRequest(
                            volume_id=vid, collection=collection[vid], shard_ids=ids))
                    lose(vid, ids)
                    where[vid].update({i: vs.store.locations[0].directory for i in ids})
                    want.update({i: [f"127.0.0.1:{vs.port}"] for i in ids})
                lose(vid, LOST)
                _wait_for(f"the master to name the holders of volume {vid}",
                          lambda: _located(master, vid) == want)
            name = {vid: os.path.basename(base[vid]) for vid in vids}
            paths = {vid: {i: os.path.join(where[vid][i], name[vid] + ec_files.to_ext(i))
                           for i in range(14)} for vid in vids}
            survivors = [i for i in range(14) if i not in LOST]
            mtimes = {vid: {i: os.stat(paths[vid][i]).st_mtime_ns for i in survivors}
                      for vid in vids}
            decoded = {}
            for vid in vids:  # the reference's decode, of the survivors where they lie
                gathered = tmp_path_factory.mktemp("gathered")
                for i in survivors:
                    os.link(paths[vid][i], str(gathered / (name[vid] + ec_files.to_ext(i))))
                decoded[vid] = bench.reference_rebuild.decode(str(gathered / name[vid]), LOST)
            dialed = []
            real_dial = operation._RawHTTPConnection.__init__

            def dial(self, host, port, timeout):
                dialed.append(port)
                real_dial(self, host, port, timeout)

            trace.reset()
            del handler.lines[:]
            del Annotations.opened[:]
            mp.setattr(jax.profiler, "TraceAnnotation", Annotations)
            mp.setattr(operation._RawHTTPConnection, "__init__", dial)
            stub.VolumeEcShardsBatchRebuild(
                volume_pb2.VolumeEcShardsBatchGenerateRequest(volume_ids=vids),
                metadata=((trace.TRACE_HEADER, "00000000000000ab:000000cd:serve"),),
                timeout=120)
            got = {
                "lines": list(handler.lines),
                "spans": trace.debug_payload(n=256)["recent"],
                "annotations": list(Annotations.opened),
                "dialed": list(dialed),
                "same_as_before": all(
                    np.array_equal(np.fromfile(paths[vid][i], dtype=np.uint8), before[vid][i])
                    for vid in vids for i in LOST),
                "same_as_decode": all(
                    np.array_equal(np.fromfile(paths[vid][i], dtype=np.uint8), d)
                    for vid in vids for i, d in zip(LOST, decoded[vid])),
                "ecc": all(
                    _json(base[vid] + ".ecc")["shards"][str(i)]["crc"]
                    == bench.reference.crc32c(d)
                    for vid in vids for i, d in zip(LOST, decoded[vid])),
                "survivors_untouched": all(
                    os.stat(paths[vid][i]).st_mtime_ns == mtimes[vid][i]
                    for vid in vids for i in survivors),
                "copied_to_a": [(vid, i) for vid in vids for i in survivors
                                if i not in PLACEMENT["A"]
                                and os.path.exists(base[vid] + ec_files.to_ext(i))],
                "holder_ports": sorted(vs.port for vs in holders.values()),
                "shard_bytes": shard_bytes,
                "vids": vids,
            }
            mp.undo()
            for vid in vids:
                stub.VolumeEcShardsMount(volume_pb2.VolumeEcShardsMountRequest(
                    volume_id=vid, collection=collection[vid], shard_ids=LOST))
            _wait_for("the master to list all 14 again", lambda: all(
                len(_located(master, vid)) == 14 for vid in vids))
            got["read_back"] = {}
            for vid, (fid, body) in needles.items():  # through A, which fetches from C and D
                with urllib.request.urlopen(f"http://127.0.0.1:{a.port}/{fid}", timeout=30) as r:
                    got["read_back"][vid] = r.read() == body

            # the shell, after the four are lost again
            for vid in vids:
                lose(vid, LOST)
            _wait_for("the master to have heard of the loss", lambda: all(
                not set(LOST) & set(_located(master, vid)) for vid in vids))
            del handler.lines[:]
            out = io.StringIO()
            shell = do_ec_rebuild_batch(CommandEnv([f"127.0.0.1:{master.port}"]), vids, out)
            got["shell"] = {"result": shell, "out": out.getvalue(),
                            "lines": list(handler.lines)}
            got["shell"]["mounted"] = _wait_for(
                "the master to list all 14 after the shell", lambda: all(
                    len(_located(master, vid)) == 14 for vid in vids))
            rebuilder = {url for vid in vids for i in LOST for url in _located(master, vid)[i]}
            got["shell"]["rebuilder"] = rebuilder
            at = next(vs for vs in servers.values() if {f"127.0.0.1:{vs.port}"} == rebuilder)
            got["shell"]["same_as_before"] = all(
                np.array_equal(np.fromfile(
                    at.store.find_ec_volume(vid).base_name + ec_files.to_ext(i),
                    dtype=np.uint8), before[vid][i])
                for vid in vids for i in LOST)
        yield got
    finally:
        logger.removeHandler(handler)
        trace.reset()
        for vs in servers.values():
            vs.stop()
        master.stop()


@pytest.fixture(scope="module")
def report(served, bench) -> dict:
    reports = bench.node.verb_reports("\n".join(served["lines"]), "batch_rebuild")
    assert len(reports) == 1, served["lines"]
    return reports[0]


def test_the_verb_mends_every_volume_byte_for_byte(served):
    assert served["same_as_before"] and served["same_as_decode"] and served["ecc"]
    assert served["survivors_untouched"] and served["copied_to_a"] == []
    assert len(served["read_back"]) == 3 and all(served["read_back"].values())


def test_the_handler_batches_a_rack_placed_set(report, served, bench):
    assert (report["batch_volumes"], report["batch_groups"], report["batch_passes"],
            report["fell_through"]) == (3, 1, 1, 0)
    assert bench.node.verb_reports("\n".join(served["lines"]), "rebuild") == []
    assert (report["survivors"], report["targets"]) == (10, 4)
    assert report["lookup_s"] > 0 and "mesh" in report


def test_the_line_carries_the_remote_fields(report, served):
    shard = served["shard_bytes"]
    for field in REMOTE_FIELDS:
        assert field in report, field
    assert report["survivor_bytes"] == 3 * 10 * shard
    assert report["survivor_bytes_remote"] == 3 * 6 * shard
    assert report["rebuilt_bytes"] == 3 * 4 * shard
    # one round: six remote survivors of three volumes, each a span over
    # the holders' data plane
    assert report["remote_fetches"] == report["remote_fetches_dataplane"] == 3 * 6
    assert report["remote_read_s"] > 0
    assert 0 <= report["arbiter_wait_s"] <= report["remote_read_s"]


def test_the_root_span_carries_the_remote_fields(report, served):
    spans = served["spans"]
    handler = [s for s in spans if s["name"] == "volume.ec_rebuild_batch"]
    roots = [s for s in spans if s["name"] == "ec_stream.rebuild_batch"]
    assert len(handler) == len(roots) == 1
    assert roots[0]["parent"] == handler[0]["span"]
    assert handler[0]["annot"]["fell_through"] == "0"
    for key in REMOTE_FIELDS[1:] + ("batch_volumes", "batch_passes", "survivor_bytes"):
        assert roots[0]["annot"][key] == str(report[key]), key
    assert roots[0]["stages_ms"]["remote_read_s"] == pytest.approx(
        report["remote_read_s"] * 1e3, abs=0.11)
    assert not [s for s in spans if s["name"] == "ec_stream.rebuild"]


def test_each_remote_fetch_is_one_annotation_on_a_fetch_thread(served):
    fetches = [t for name, t in served["annotations"] if name == "ec.remote_read"]
    assert len(fetches) == 3 * 6
    readers = {t for name, t in served["annotations"] if name == "ec.read"}
    assert readers and not readers & set(fetches)


def test_the_calls_volumes_share_the_readers_connections(served):
    """One kept connection per fetch thread and holder for the whole
    call, whichever volume a span belongs to: a call's dials are bounded
    by its fetch threads, not by its volumes."""
    dialed = served["dialed"]
    assert set(dialed) == set(served["holder_ports"])
    # one round: one reader thread, whose pool of six fetches the round
    assert len(dialed) <= 6 * 2


def test_the_shell_makes_one_call_for_volumes_no_node_holds_ten_of(served, bench):
    """No server holds ten shards of any volume (4 / 3 / 3): the batch
    verb's rebuilder is a holder with the most free slots, and ONE call
    there mends all three volumes, its survivors fetched from the two
    other servers."""
    shell = served["shell"]
    lines = "\n".join(shell["lines"])
    (batch,) = bench.node.verb_reports(lines, "batch_rebuild")
    assert bench.node.verb_reports(lines, "rebuild") == []
    assert (batch["batch_volumes"], batch["batch_groups"], batch["fell_through"]) == (3, 1, 0)
    assert "batch-rebuilt ec shards for volumes" in shell["out"]
    assert shell["out"].count("batch-rebuilt") == 1
    assert shell["result"] == {vid: LOST for vid in served["vids"]}
    assert len(shell["rebuilder"]) == 1 and shell["mounted"] and shell["same_as_before"]
    # the rebuilder held three or four of each volume: the rest came over the wire
    assert batch["survivor_bytes_remote"] >= 3 * 6 * served["shard_bytes"]


# --- the cell's files against the manifest ------------------------------------------------


@pytest.fixture(scope="module")
def config() -> dict:
    return _json("benchmark", "configs", CONFIG + ".json")


def test_manifest_entries_of_the_cell(config):
    manifest = _json("BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert "command_ec_rebuild.go" in entry["source"]
    assert entry["source"] not in [c["source"] for c in manifest["configs"] if c is not entry]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == list(config["reduced"]) == [
        "volumeSizeLimitMB", "volumes_per_server", "network", "servers"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert sum(1 for w in manifest["workloads"] if w["chips"] == 4) == 1
    assert CELL in next(
        m for m in manifest["end_to_end"] if m["name"] == "ec_gbps")["workloads"]
    # every metric of the one-signature batch cell, its roofline included,
    # and the rack cell's four remote ones; each list gained the cell last
    sibling = {m["name"] for m in manifest["per_layer"]
               if "batch-rebuild-2lost" in m.get("workloads", [])}
    remote = {"remote_read_s_per_gib", "remote_bytes_per_rebuilt_byte",
              "remote_dataplane_fetch_pct", "arbiter_wait_s_per_gib"}
    listing = {m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", [])}
    assert "rebuild_kernel_roofline" in sibling
    assert listing == sibling | remote
    for m in manifest["per_layer"]:
        if m["name"] in listing:
            assert m["workloads"][-1] == CELL, m["name"]
    mix = _json("benchmark", "traffic", MIX + ".json")
    assert mix == {"generator": "rack_batch_rebuild_loop", "rpc": "VolumeEcShardsBatchRebuild",
                   "volumes_per_call": 4, "concurrency": 1, "trace_ops": 3, "read_back": 28}


def test_configuration_is_the_rack_repairs_deployment_over_four_volumes(config):
    rack = _json("benchmark", "configs", "rack-rebuild-1g.json")
    drive = _json("benchmark", "configs", "drive-loss-256m.json")
    for key in ("layout", "code", "placement", "budget", "needle_sizes"):
        assert config[key] == rack[key], key
    for key in ("network", "servers"):
        assert config["reduced"][key] == rack["reduced"][key], key
    assert config["reduced"]["volumeSizeLimitMB"] == drive["reduced"]["volumeSizeLimitMB"]
    assert [v["collection"] for v in config["volumes"]] == ["r0", "r1", "r2", "r3"]
    assert {v["mib"] for v in config["volumes"]} == {256}
    assert config["failure"]["lost_shards"] == LOST
    assert {k: v for k, v in config["placement"].items() if k != "why"} == PLACEMENT
    assert config["chips"] == 1 and len(config["guarantees"]) == 6
    assert {"placement", "one_signature", "relose", "disk", "budget"} <= set(config["assumed"])


def test_the_metrics_read_the_batch_line_by_name(bench, report):
    """The rack cell's four remote metrics read the batch line's fields
    as they are: 1.5 remote bytes a rebuilt byte, every fetch over the
    data plane."""
    obs = {"reports": [report], "window": {"seconds": 2.0, "gib": 1.0, "requests": 1},
           "trace": None, "traced_work": None, "device_kind": "TPU v5 lite",
           "rehearse": False}
    read = bench.readers.read_metric
    load = bench.readers.load_metric
    assert read(load("remote_bytes_per_rebuilt_byte"), obs) == 1.5
    assert read(load("remote_dataplane_fetch_pct"), obs) == 100.0
    assert read(load("remote_read_s_per_gib"), obs) == report["remote_read_s"]
    assert read(load("batch_rebuild_volumes_per_op"), obs) == 3.0


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
    ([], True, ()),
    (["--control", "cauchy"], False, ("rebuilt_differs_from_decode", "data_shards_differ",
                                      "parity_shards_differ")),
    (["--control", "crc32"], False, ("ecc_crcs_differ",)),
    (["--control", "not_rebuilt"], False, ("shards_not_rewritten",)),
]
COMPARED = {
    "ops_failed", "ops_without_report", "ops_wrong_shards", "ops_wrong_gather",
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
        [sys.executable, "-c", code, "--workload", CELL, "--seed", str(2**31 + 45),
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
    # every volume of every call rode the batch
    assert "volumes each call's batch mended: 4" in proc.stderr
    assert "single-volume lines: 0" in proc.stderr
