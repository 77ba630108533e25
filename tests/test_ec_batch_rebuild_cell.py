"""The drive loss (ISSUE 38: configuration `drive-loss-256m`, cell
`batch-rebuild-2lost`): the first tests THROUGH `VolumeEcShardsBatchRebuild`
and the shell's `ec.rebuild.batch` (until now only the driver under the
verb was called, tests/test_ec_schedule_ecc.py). One in-process node
under a master, five small volumes through `ec.encode` to its end, and
three calls of the verb:

  mixed     two damage signatures in one call ([3, 10] lost on two
            volumes, [5] on two): two groups, nobody falls through
  fallen    a volume with a shard mounted on another server rides the
            batch beside two others: that shard is a survivor held
            elsewhere, not a target
  all_fallen  the same volume alone in its call: a batch of one
  idle      a call with nothing to mend still leaves its line
  shell     `ec.rebuild.batch -force` finds the damage itself, makes
            the ONE call and mounts

Held to the bytes before the loss and to the benchmark's plain numpy
decode of the ten survivors on disk; the `ec.batch_rebuild` report
line, the handler's and the driver's spans and the dispatcher's two
annotations; the cell's configuration, mix and three per-layer metrics
as files; a `--rehearse` run of the cell and of each of its controls.

Everything runs on the CPU backend (the mesh stage then takes its
bit-matmul arm): what is asserted is bytes, counts and bookkeeping,
never a device time."""

import importlib
import io
import json
import logging
import os
import re
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
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.shell.command_env import CommandEnv
from seaweedfs_tpu.shell.commands import run_command
from seaweedfs_tpu.util.availability import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIB = 1 << 20
CELL, CONFIG, MIX = "batch-rebuild-2lost", "drive-loss-256m", "batch-rebuild-loop"
SHARD_BYTES = MIB  # one stripe row
DRIVE = [3, 10]  # the configuration's lost drive
PHASE_FIELDS = ("head_s", "dispatch_span_s", "drain_s", "write_tail_s", "flush_s")
WAIT_FIELDS = ("slot_wait_s", "read_q_wait_s", "first_tile_wait_s", "tile_wait_s",
               "dispatch_call_s", "window_wait_s", "work_wait_s", "latch_wait_s")
NEW_FIELDS = ("h2d_s", "launch_s", "batch_groups", "lookup_s", "fell_through")


def _tile(volumes: int) -> int:
    """The mesh stage's tile for `volumes` stacked volumes by the driver's
    own rule (ISSUE 39: sized from the chunk's volumes and the kept ring,
    whatever the chip chose), under the node's default pools."""
    return ec_stream.batch_round_tile_bytes(volumes, ec_stream._ring_slots())


def _rounds(volumes: int) -> int:
    """Rounds the mesh stage makes of shard files of SHARD_BYTES."""
    return -(-SHARD_BYTES // _tile(volumes))


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
def mended(tmp_path_factory, bench):
    """The three calls of the module docstring on node A (codec tpu, the
    verb steered the chip's way as tests/test_ec_rebuild_cell.py does),
    each with what it left: log lines, spans, bytes against the bytes
    before and against the reference's decode, the `.ecc`s."""
    import jax

    master = MasterServer(port=free_port(), volume_size_limit_mb=64)
    master.start()
    servers: list[VolumeServer] = []

    def start(name: str, codec: str) -> VolumeServer:
        vs = VolumeServer(
            [str(tmp_path_factory.mktemp("drive" + name))], port=free_port(),
            master=f"127.0.0.1:{master.port}", heartbeat_interval=0.2,
            max_volume_counts=[100], rack=name, ec_codec=codec,
        )
        vs.start()
        servers.append(vs)
        return vs

    a = start("A", "tpu")
    _wait_for("A in the topology", lambda: master.topology.data_nodes())
    handler = logging.Handler()
    handler.lines = []
    handler.emit = lambda record: handler.lines.append("I] " + record.getMessage())
    logger = logging.getLogger("seaweedfs_tpu")
    logger.addHandler(handler)
    try:
        rng = np.random.default_rng(38)
        needles: dict[int, tuple[str, bytes]] = {}
        for n in range(5):  # one needle in one volume of five collections
            collection = f"d{n}"
            with urllib.request.urlopen(
                f"http://127.0.0.1:{master.port}/vol/grow?collection={collection}&count=1",
                timeout=10,
            ) as r:
                assert json.loads(r.read())["count"] == 1
            with urllib.request.urlopen(
                f"http://127.0.0.1:{master.port}/dir/assign?collection={collection}", timeout=10
            ) as r:
                assign = json.loads(r.read())
            body = rng.bytes(700_001 + n)
            urllib.request.urlopen(urllib.request.Request(
                f"http://{assign['url']}/{assign['fid']}", data=body, method="POST"),
                timeout=30).close()
            needles[int(assign["fid"].split(",")[0])] = (assign["fid"], body)
        vids = sorted(needles)
        collection = {vid: f"d{n}" for n, vid in enumerate(vids)}
        assert len(vids) == 5
        with pytest.MonkeyPatch.context() as mp, \
                grpc.insecure_channel(f"127.0.0.1:{a.grpc_port}") as ch:
            mp.setattr(ec_files, "_use_stream_driver", lambda rs: True)
            mp.setattr(jax.profiler, "TraceAnnotation", Annotations)
            stub = rpc.volume_stub(ch)
            for vid in vids:
                stub.VolumeMarkReadonly(volume_pb2.VolumeMarkReadonlyRequest(volume_id=vid))
                stub.VolumeEcShardsGenerate(volume_pb2.VolumeEcShardsGenerateRequest(
                    volume_id=vid, collection=collection[vid]))
                stub.VolumeEcShardsMount(volume_pb2.VolumeEcShardsMountRequest(
                    volume_id=vid, collection=collection[vid], shard_ids=list(range(14))))
                stub.VolumeDelete(volume_pb2.VolumeDeleteRequest(volume_id=vid))
            base = {vid: a.store.find_ec_volume(vid).base_name for vid in vids}
            assert os.path.getsize(base[vids[0]] + ".ec00") == SHARD_BYTES
            before = {vid: {i: np.fromfile(base[vid] + ec_files.to_ext(i), dtype=np.uint8)
                            for i in range(14)} for vid in vids}
            _wait_for("the master to list all 14 of every volume on A", lambda: all(
                len(_located(master, vid)) == 14 for vid in vids))

            def lose(damage: dict[int, list[int]]) -> None:
                for vid, ids in damage.items():
                    stub.VolumeEcShardsUnmount(volume_pb2.VolumeEcShardsUnmountRequest(
                        volume_id=vid, shard_ids=ids))
                    stub.VolumeEcShardsDelete(volume_pb2.VolumeEcShardsDeleteRequest(
                        volume_id=vid, collection=collection[vid], shard_ids=ids))
                    for i in ids:
                        assert not os.path.exists(base[vid] + ec_files.to_ext(i))

            def mount(damage: dict[int, list[int]]) -> None:
                for vid, ids in damage.items():
                    stub.VolumeEcShardsMount(volume_pb2.VolumeEcShardsMountRequest(
                        volume_id=vid, collection=collection[vid], shard_ids=ids))

            def observed(damage: dict[int, list[int]], call) -> dict:
                """Lose, decode with the reference, make `call`, and
                return what it left."""
                lose(damage)
                survivors = {vid: [i for i in range(14) if i not in ids
                                   and os.path.exists(base[vid] + ec_files.to_ext(i))]
                             for vid, ids in damage.items()}
                mtimes = {vid: {i: os.stat(base[vid] + ec_files.to_ext(i)).st_mtime_ns
                                for i in survivors[vid]} for vid in damage}
                decoded = {vid: bench.reference_rebuild.decode(base[vid], ids)
                           for vid, ids in damage.items()}
                trace.reset()
                del handler.lines[:]
                del Annotations.opened[:]
                call()
                got = {
                    "lines": list(handler.lines),
                    "spans": trace.debug_payload(n=256)["recent"],
                    "annotations": list(Annotations.opened),
                    "damage": damage,
                    "back": {vid: [i for i in ids
                                   if os.path.exists(base[vid] + ec_files.to_ext(i))]
                             for vid, ids in damage.items()},
                }
                got["same_as_before"] = all(
                    np.array_equal(np.fromfile(base[vid] + ec_files.to_ext(i), dtype=np.uint8),
                                   before[vid][i])
                    for vid, ids in got["back"].items() for i in ids)
                got["same_as_decode"] = all(
                    np.array_equal(np.fromfile(base[vid] + ec_files.to_ext(i), dtype=np.uint8), d)
                    for vid, ids in got["back"].items()
                    for i, d in zip(damage[vid], decoded[vid]) if i in ids)
                got["ecc_merged"] = all(
                    _json(base[vid] + ".ecc")["shards"][str(i)]["crc"] == bench.reference.crc32c(d)
                    for vid, ids in damage.items() for i, d in zip(ids, decoded[vid])
                    if i in got["back"][vid])
                got["survivors_untouched"] = all(
                    os.stat(base[vid] + ec_files.to_ext(i)).st_mtime_ns == mtimes[vid][i]
                    for vid in damage for i in survivors[vid]
                    if os.path.exists(base[vid] + ec_files.to_ext(i)))
                return got

            def batch_rebuild(ids: list[int]):
                return lambda: stub.VolumeEcShardsBatchRebuild(
                    volume_pb2.VolumeEcShardsBatchGenerateRequest(volume_ids=ids),
                    metadata=((trace.TRACE_HEADER, "00000000000000ab:000000cd:serve"),),
                    timeout=120,
                )

            # mixed: two signatures in one call
            damage = {vids[0]: DRIVE, vids[1]: DRIVE, vids[2]: [5], vids[3]: [5]}
            mixed = observed(damage, batch_rebuild(vids[:4]))
            mount(damage)

            # fallen: shard 7 of the fifth volume lives on B; A lost its 2
            b = start("B", "native")
            with grpc.insecure_channel(f"127.0.0.1:{b.grpc_port}") as bch:
                bstub = rpc.volume_stub(bch)
                bstub.VolumeEcShardsCopy(volume_pb2.VolumeEcShardsCopyRequest(
                    volume_id=vids[4], collection=collection[vids[4]], shard_ids=[7],
                    copy_ecx_file=True, source_data_node=f"127.0.0.1:{a.port}"))
                bstub.VolumeEcShardsMount(volume_pb2.VolumeEcShardsMountRequest(
                    volume_id=vids[4], collection=collection[vids[4]], shard_ids=[7]))
            damage = {vids[0]: DRIVE, vids[1]: DRIVE, vids[4]: [2, 7]}
            lose({vids[4]: [7]})  # moved, not lost: the master names B for it
            _wait_for("the master to name B alone for shard 7", lambda: _located(
                master, vids[4]).get(7) == [f"127.0.0.1:{b.port}"])
            damage[vids[4]] = [2]
            fallen = observed(damage, batch_rebuild([vids[4], vids[0], vids[1]]))
            fallen["moved_shard_regenerated"] = os.path.exists(base[vids[4]] + ".ec07")
            mount(damage)
            # the volume with a shard held elsewhere, alone in its call
            damage = {vids[4]: [2]}
            all_fallen = observed(damage, batch_rebuild([vids[4]]))
            mount(damage)
            idle = observed({}, batch_rebuild([vids[0]]))

            # shell: the damage found from the master's topology
            damage = {vids[0]: DRIVE, vids[1]: DRIVE, vids[2]: [5]}
            out = io.StringIO()
            env = CommandEnv([f"127.0.0.1:{master.port}"])

            def all_told() -> bool:
                return all(not set(ids) & set(_located(master, vid))
                           for vid, ids in damage.items())

            def shell_call():
                _wait_for("the master to have heard of the loss", all_told)
                dry = run_command(env, "ec.rebuild.batch")
                run_command(env, "ec.rebuild.batch -force", out)
                out.write(dry)

            shell = observed(damage, shell_call)
            shell["out"] = out.getvalue()
            shell["mounted"] = _wait_for(
                "the master to list all 14 again",
                lambda: all(len(_located(master, vid)) == 14 for vid in damage))
        read_back = {}
        for vid, (fid, body) in needles.items():
            with urllib.request.urlopen(f"http://127.0.0.1:{a.port}/{fid}", timeout=30) as r:
                read_back[vid] = r.read() == body
        yield {"mixed": mixed, "fallen": fallen, "all_fallen": all_fallen, "idle": idle,
               "shell": shell, "vids": vids, "read_back": read_back}
    finally:
        logger.removeHandler(handler)
        trace.reset()
        for vs in servers:
            vs.stop()
        master.stop()


def _reports(bench, got: dict, verb: str) -> list[dict]:
    return bench.node.verb_reports("\n".join(got["lines"]), verb)


@pytest.fixture(scope="module")
def report(mended, bench) -> dict:
    reports = _reports(bench, mended["mixed"], "batch_rebuild")
    assert len(reports) == 1, mended["mixed"]["lines"]
    return reports[0]


@pytest.mark.parametrize("call", ["mixed", "fallen", "all_fallen", "shell"])
def test_every_lost_shard_comes_back_byte_for_byte(call, mended):
    got = mended[call]
    assert got["back"] == got["damage"]
    assert got["same_as_before"] and got["same_as_decode"]
    assert got["ecc_merged"] and got["survivors_untouched"]


def test_every_volume_reads_back_after_the_three_calls(mended):
    assert len(mended["read_back"]) == 5 and all(mended["read_back"].values())


def test_two_damage_signatures_are_two_groups_and_nobody_falls_through(mended, report, bench):
    assert (report["batch_volumes"], report["batch_groups"], report["fell_through"]) == (4, 2, 0)
    assert report["batch_passes"] == 1
    # two groups of two volumes, each in the rounds of the driver's rule,
    # in one pass; survivor bytes of all four volumes
    assert report["tiles"] == 2 * _rounds(2)
    assert report["survivor_bytes"] == 4 * 10 * SHARD_BYTES
    assert report["survivors"] == 10 and report["targets"] in (1, 2)
    assert report["mesh"]["arm"] == "bit-matmul" and not report.get("fallback")
    assert _reports(bench, mended["mixed"], "rebuild") == []
    crc_lines = [ln for ln in mended["mixed"]["lines"] if "rebuilt_crc32c=" in ln]
    assert len(crc_lines) == 4
    assert sum("rebuilt_crc32c=3:" in ln for ln in crc_lines) == 2
    assert sum("rebuilt_crc32c=5:" in ln for ln in crc_lines) == 2
    # the ONE report line follows the `.ecc` merges
    at = [i for i, ln in enumerate(mended["mixed"]["lines"]) if " report={" in ln]
    assert len(at) == 1 and at[0] > max(
        i for i, ln in enumerate(mended["mixed"]["lines"]) if "rebuilt_crc32c=" in ln)


def test_report_line_carries_the_split_the_lookups_and_the_waits(report):
    for field in NEW_FIELDS + WAIT_FIELDS + PHASE_FIELDS + (
            "publish_s", "reserve_s", "reserve_done_s", "program_traces", "ring_fresh_bytes",
            "tile_bytes"):
        assert field in report, field
    # the size the rule chose for a group of two volumes (ISSUE 39)
    assert report["tile_bytes"] == _tile(2)
    assert report["lookup_s"] > 0 and report["device_s"] > 0
    # as the mesh encode stage books them: the transfer inside the stage,
    # the launch all of device_s (each rounded to 1e-4 on the line)
    assert report["launch_s"] == pytest.approx(report["device_s"], abs=2e-4)
    assert report["h2d_s"] <= report["stage_s"] + 2e-4
    assert report["h2d_s"] + report["launch_s"] <= report["device_s"] + report["stage_s"] + 2e-4
    # the one pass's phases partition its wall
    assert sum(report[f] for f in PHASE_FIELDS) == pytest.approx(report["wall_s"], abs=7e-4)


def test_spans_of_the_batch_rebuild(mended, report):
    spans = mended["mixed"]["spans"]
    handler = [s for s in spans if s["name"] == "volume.ec_rebuild_batch"]
    roots = [s for s in spans if s["name"] == "ec_stream.rebuild_batch"]
    publish = [s for s in spans if s["name"] == "ec.publish"]
    # ONE root for the two groups: the call is one pass of the shell
    assert len(handler) == len(publish) == len(roots) == 1
    assert handler[0]["trace"] == "00000000000000ab" and handler[0]["parent"] == "000000cd"
    assert handler[0]["annot"]["fell_through"] == "0"
    assert float(handler[0]["annot"]["lookup_s"]) == report["lookup_s"]
    (root,) = roots
    assert root["parent"] == handler[0]["span"]
    assert (root["annot"]["batch_groups"], root["annot"]["batch_passes"],
            root["annot"]["batch_volumes"]) == ("2", "1", "4")
    assert (root["annot"]["tiles"], root["annot"]["survivors"]) == (str(2 * _rounds(2)), "10")
    # per-volume counts: the most targets of any volume of the pass
    assert root["annot"]["targets"] == "2"
    assert root["annot"]["tile_bytes"] == str(report["tile_bytes"])
    assert root["annot"]["survivor_bytes"] == str(4 * 10 * SHARD_BYTES)
    assert {"h2d_s", "launch_s"} | set(WAIT_FIELDS) <= set(root["stages_ms"])
    for field in ("h2d_s", "launch_s"):
        assert root["stages_ms"][field] == pytest.approx(report[field] * 1e3, abs=0.11)
    # handler, publish, and a root with its five phases: no span per
    # group or per round
    assert len(spans) == 2 + 6, sorted(s["name"] for s in spans)


def test_the_dispatcher_annotates_its_transfer_and_its_launch(mended):
    opened = mended["mixed"]["annotations"]
    h2d = [t for name, t in opened if name == "ec.h2d"]
    launch = [t for name, t in opened if name == "ec.launch"]
    group = [t for name, t in opened if name == "ec.group"]
    assert len(h2d) == len(launch) == 2 * _rounds(2)  # two groups' rounds
    # one `ec.group` a group, around its first launch: where the program changes
    assert len(group) == 2
    # the ONE dispatcher is the handler's thread, no pool thread
    pools = {t for name, t in opened if name in ("ec.read", "ec.write", "ec.writeback")}
    assert len(set(h2d + launch + group)) == 1 and not set(h2d) & pools


def test_a_shard_mounted_elsewhere_falls_through_beside_the_batch(mended, bench):
    """The shard the master names B for is a survivor held elsewhere:
    the volume rides the batch beside the two others, as a signature of
    its own, and only the shard that was lost is rebuilt. Its ten local
    survivors suffice, so nothing crosses the wire."""
    got = mended["fallen"]
    (batch,) = _reports(bench, got, "batch_rebuild")
    assert _reports(bench, got, "rebuild") == []
    assert (batch["batch_volumes"], batch["batch_groups"], batch["batch_passes"]) == (3, 2, 1)
    assert batch["fell_through"] == 0 and batch["lookup_s"] > 0
    assert batch["survivor_bytes_remote"] == 0
    assert batch["rebuilt_bytes"] == (2 + 2 + 1) * SHARD_BYTES
    assert not got["moved_shard_regenerated"]
    handler = [s for s in got["spans"] if s["name"] == "volume.ec_rebuild_batch"]
    assert len(handler) == 1 and handler[0]["annot"]["fell_through"] == "0"
    assert [s["parent"] for s in got["spans"] if s["name"] == "ec_stream.rebuild_batch"] == [
        handler[0]["span"]]
    assert not [s for s in got["spans"] if s["name"] == "ec_stream.rebuild"]


def test_a_call_whose_volumes_all_fall_through_still_leaves_its_line(mended, bench):
    """Nothing falls through now that the batch gathers survivors held
    elsewhere (the volume with a shard on B, alone in its call, is a
    batch of one), so the line's point is held where no volume is
    batched: a call with nothing to mend says so (`batch_volumes` 0) and
    is written all the same, so that a window never loses an
    operation's line."""
    (alone,) = _reports(bench, mended["all_fallen"], "batch_rebuild")
    assert (alone["batch_volumes"], alone["fell_through"]) == (1, 0)
    got = mended["idle"]
    lines = [ln for ln in got["lines"] if " report={" in ln]
    assert [re.search(r"ec\.\w+", ln).group(0) for ln in lines] == ["ec.batch_rebuild"]
    (batch,) = _reports(bench, got, "batch_rebuild")
    assert batch == {"batch_volumes": 0, "batch_groups": 0, "fell_through": 0,
                     "lookup_s": batch["lookup_s"], "publish_s": batch["publish_s"]}
    assert not [s for s in got["spans"] if s["name"] == "ec_stream.rebuild_batch"]


def test_the_shell_verb_finds_the_damage_makes_one_call_and_mounts(mended, bench):
    got = mended["shell"]
    batch = _reports(bench, got, "batch_rebuild")
    assert len(batch) == 1 and _reports(bench, got, "rebuild") == []
    assert (batch[0]["batch_volumes"], batch[0]["batch_groups"], batch[0]["fell_through"]) == (
        3, 2, 0)
    assert "batch-rebuilt ec shards for volumes" in got["out"]
    v = mended["vids"]
    for vid, ids in ((v[0], DRIVE), (v[1], DRIVE), (v[2], [5])):  # the dry run before it
        assert f"volume {vid}: missing shards {ids} (dry run; -force to rebuild)" in got["out"]
    assert got["mounted"]


# --- the benchmark's new files against the manifest and a real report line --------------

GIB = 1.25
METRICS = {
    "batch_rebuild_volumes_per_op": lambda rep: rep["batch_volumes"] / 1,
    "batch_rebuild_groups_per_op": lambda rep: rep["batch_groups"] / 1,
    "batch_rebuild_host_crc_s_per_gib": lambda rep: rep["compute_s"] / GIB,
}
GONE_ON_THE_PARENT = {  # the parent's line has every field but these
    "batch_rebuild_groups_per_op": ("batch_groups",),
}
JOINED = [
    "device_idle_pct.ec", "handler_overhead_pct", "dispatch_s_per_gib", "read_s_per_gib",
    "write_s_per_gib", "writeback_s_per_gib", "reserve_s_per_gib", "reserve_done_s_per_gib",
    "ring_fresh_bytes_per_gib", "reader_slot_wait_s_per_gib", "reader_queue_wait_s_per_gib",
    "first_tile_wait_s_per_gib", "dispatcher_tile_wait_s_per_gib",
    "dispatcher_window_wait_s_per_gib", "writer_work_wait_s_per_gib",
    "writer_latch_wait_s_per_gib", "dispatch_span_unbooked_pct", "rebuild_launches_per_gib",
    "rebuild_dispatcher_busy_pct", "rebuild_kernel_roofline",
]
OP_SECONDS = {  # as the chip's trace names the decode program's two operations (PR 38)
    '%copy = u32[4,10,131072]{2,1,0:T(8,128)S(1)} copy(u32[4,10,131072]{2,0,1:T(4,128)} '
    '%vols_u32.1), sharding={replicated}': 0.0049,
    '%swar_apply_u32_batch.1 = u32[4,2,131072]{2,1,0:T(2,128)} custom-call(u32[4,10,131072]'
    '{2,1,0:T(8,128)S(1)} %copy), custom_call_target="tpu_custom_call"': 0.0046,
}


def _observed(reports: list[dict]) -> dict:
    return {"reports": reports,
            "window": {"seconds": 2.0, "gib": GIB, "requests": len(reports)},
            "trace": {"busy_s": 0.02, "window_s": 2.0, "op_seconds": OP_SECONDS},
            "traced_work": {"rebuild_hbm_bytes": 3 * 4 * 12 * 26 * MIB},
            "device_kind": "TPU v5 lite", "rehearse": False}


def test_manifest_entries_of_the_cell(config):
    manifest = _json("BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert manifest["configs"].index(entry) == 5
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert "Erasure-Coding-for-warm-storage" in entry["source"]
    assert entry["source"] not in [c["source"] for c in manifest["configs"][:5]]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == list(config["reduced"]) == [
        "volumeSizeLimitMB", "volumes_per_drive", "drives", "nodes"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert manifest["workloads"].index(cell) == 5
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    assert sum(1 for w in manifest["workloads"] if w["chips"] == 4) == 1
    listing = {m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", [])}
    assert set(METRICS) | set(JOINED) <= listing
    for name in METRICS:  # this cell's, and the cells that joined them later
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"][0] == CELL, name
    # the SWAR kernel's own share of the roofline is NOT this cell's: the
    # mesh decode program re-lays its input out into on-chip memory first
    # (`%copy ... S(1)`), the kernel reads it from there, and the HBM floor
    # over the kernel's seconds alone read 104 % on the chip (PERF.md
    # section 6, PR 38); the whole program's share is the sound one
    assert "rebuild_swar_roofline" not in listing
    assert CELL in next(
        m for m in manifest["end_to_end"] if m["name"] == "ec_gbps")["workloads"]
    mix = _json("benchmark", "traffic", MIX + ".json")
    assert mix == {"generator": "batch_rebuild_loop", "rpc": "VolumeEcShardsBatchRebuild",
                   "volumes_per_call": 4, "concurrency": 1, "trace_ops": 3, "read_back": 28}


def test_configuration_is_batch_256ms_volumes_short_of_one_drives_shards(config):
    batch = _json("benchmark", "configs", "batch-256m.json")
    for key in ("code", "volumes", "needle_sizes"):
        assert config[key] == batch[key], key
    assert config["failure"]["lost_shards"] == DRIVE == [i for i in range(14) if i % 7 == 3]
    assert config["chips"] == 1 and len(config["guarantees"]) == 6
    assert len(config["volumes"]) == _json(
        "benchmark", "traffic", MIX + ".json")["volumes_per_call"]
    assert set(config["assumed"]) == {
        "placement", "lost_drive", "one_signature", "relose", "disk", "needle_sizes"}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_file_and_manifest_agree(name, bench, report):
    metric = bench.readers.load_metric(name)
    entry = next(m for m in _json("BENCHMARK.json")["per_layer"] if m["name"] == name)
    for key in ("name", "unit", "better", "layer", "moves", "source"):
        assert metric[key] == entry[key], key
    # a cell joins a metric by the manifest's list alone: the file's copy
    # is the list as the metric came
    assert metric["workloads"] == entry["workloads"][: len(metric["workloads"])] == [CELL]
    assert (entry["moves"], entry["layer"]) == ("ec_gbps", "stream driver")
    for spec in metric["num"] + metric.get("den", []):
        bench.readers.term(spec, _observed([report]))  # raises on a term no reader knows


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_reads_the_hand_computed_value(name, bench, report):
    got = bench.readers.read_metric(bench.readers.load_metric(name), _observed([report]))
    assert got == pytest.approx(METRICS[name](report), rel=1e-12)


@pytest.mark.parametrize("name", sorted(GONE_ON_THE_PARENT))
def test_metric_reads_nothing_from_a_parents_run(name, bench, report):
    """The parent's line prints no `batch_groups`: the metric is left
    out of its result line, never 0."""
    old = {k: v for k, v in report.items() if k not in GONE_ON_THE_PARENT[name]}
    assert bench.readers.read_metric(
        bench.readers.load_metric(name), _observed([old])) is None


@pytest.mark.parametrize("name", [
    "rebuild_dispatcher_busy_pct", "rebuild_launches_per_gib", "rebuild_kernel_roofline"])
def test_the_repair_cells_metrics_read_this_cells_line_and_trace(name, bench, report):
    """Joined by the manifest's list alone: the files' terms find the
    batch line's `tiles`, `h2d_s` and `launch_s`, the generator's
    `rebuild_hbm_bytes` and the batch kernel's name as they are."""
    got = bench.readers.read_metric(bench.readers.load_metric(name), _observed([report]))
    assert got is not None and got > 0
    if name.endswith("_roofline"):  # three operations' 3.9 GB through HBM, over 9.5 ms
        assert got == pytest.approx(100 * (3 * 4 * 12 * 26 * MIB / 819e9) / 0.0095)
    old = {k: v for k, v in report.items() if k not in ("h2d_s", "launch_s")}
    if name == "rebuild_dispatcher_busy_pct":  # the parent books neither
        assert bench.readers.read_metric(
            bench.readers.load_metric(name), _observed([old])) is None


def test_traced_work_of_a_drive_loss(bench):
    """What the generator hands the two rebuild rooflines: four volumes'
    shard files of 26 MiB, ten survivor rows read and two target rows
    written each: 1.31 GB, 1.6 ms at 819 GB/s."""
    work = 4 * bench.roofline_rebuild.rebuild_hbm_bytes(26 * MIB, 2)
    assert work == 1_308_622_848
    assert work / 819e9 == pytest.approx(1.598e-3, rel=1e-3)


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
        [sys.executable, "-c", code, "--workload", CELL, "--seed", str(2**31 + 38),
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
