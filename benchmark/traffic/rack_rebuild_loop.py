"""Traffic generator `rack_rebuild_loop`: the repair of the shards a lost
server held, on a rebuilder that streams most of the survivors from
other volume servers. `rebuild_loop`'s operation (lose, rebuild, mount;
its `call_rpc`, `window` and `check` run here as they are) on a
deployment of more than one server.

Parameters (the mix's JSON file): `rebuild_loop`'s.

The configuration names the servers (`placement`: server -> shard ids;
the node is `REBUILDER`, the server whose shards are
`failure.lost_shards` is never started, every other one is a host-only
peer, `harness/peers.py`) and the node's `budget.env`. The node inherits
this process's environment and `run.py` imports this module before it
starts the node, so the budget is stated here, at import, where the
environment does not state one already (a run for the record under
another budget sets the variable itself, and `setup` says so).

Set-up: `ec.encode` to its end on the node, the peers' shards moved to
them with the program's own verbs (`VolumeEcShardsCopy` with the `.ecx`
and `VolumeEcShardsMount` on the receiver, `VolumeEcShardsUnmount` +
`VolumeEcShardsDelete` on the node), the lost server's shards unmounted
and deleted, a wait until the master's `LookupEcVolume` names every
holder for its shards and nobody for the lost ones, then the untimed
repairs.

`check` compares the files where they lie: a directory of hard links
under `ctx.ref_dir` gathers every shard from its holder's directory
(the rebuilt ones and the `.ecc` from the node's), and the references
read that. A hard link is the file itself, mtime included.
"""

from __future__ import annotations

import os
import re
import shutil
import time

# the node's budget (configs/rack-rebuild-1g.json `budget.env`, which
# `setup` holds this to)
BUDGET_ENV = {"WEED_ARBITER": "0"}
for _key, _value in BUDGET_ENV.items():
    os.environ.setdefault(_key, _value)

from harness import reference, reference_rebuild  # noqa: E402
from harness.node import http_get, require, require_device_verb, verb_reports  # noqa: E402
from harness.peers import Peers  # noqa: E402
from traffic import rebuild_loop  # noqa: E402
from traffic.rebuild_loop import lost_shards, saved_shard  # noqa: E402

# (`seaweedfs_tpu` is imported inside the functions that dial: `run.py`
# imports this module before it rebuilds the package's native shims)
REBUILDER = "A"  # the server of `placement` that is the node
REMOTE_BYTES = re.compile(
    r'^weed_ec_repair_bytes_read_total\{source="remote"\} (\S+)$', re.M)


def holders(ctx) -> dict[str, list[int]]:
    """Peer name -> the shard ids it holds: every server of the
    placement but the node and the lost one."""
    lost = lost_shards(ctx)
    placement = {name: [int(i) for i in ids]
                 for name, ids in ctx.config["placement"].items() if name != "why"}
    require(sorted(i for ids in placement.values() for i in ids) == list(range(reference.TOTAL)),
            f"the placement does not deal the {reference.TOTAL} shards out once: {placement}")
    require(lost in placement.values(),
            f"no server of the placement holds exactly the lost shards {lost}")
    return {name: ids for name, ids in placement.items()
            if name != REBUILDER and ids != lost}


def shard_dirs(ctx) -> dict[int, str]:
    """Shard id -> the directory its file lies in: a peer's for what it
    holds, the node's for its own and for the rebuilt ones."""
    dirs = {sid: ctx.node.data for sid in range(reference.TOTAL)}
    for name, ids in holders(ctx).items():
        for sid in ids:
            dirs[sid] = ctx.peers[name].data
    return dirs


def gathered_base(ctx, vid: int) -> str:
    """The base path of a directory of hard links, made anew, that holds
    the volume's shard files from where they lie and the node's `.ecc`."""
    name = f"{ctx.collection[vid]}_{vid}"
    root = os.path.join(ctx.ref_dir, "gathered")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    links = {reference.shard_ext(sid): d for sid, d in shard_dirs(ctx).items()}
    links[".ecc"] = ctx.node.data
    for ext, directory in links.items():
        try:
            os.link(os.path.join(directory, name + ext), os.path.join(root, name + ext))
        except FileNotFoundError:
            pass  # a file that is missing where it should lie differs
    return os.path.join(root, name)


class Gathered:
    """`ctx` for `rebuild_loop.check`, whose every file is read through
    `base`: the gathered directory in the place of the node's."""

    def __init__(self, ctx):
        self._ctx = ctx
        self._bases = {vid: gathered_base(ctx, vid) for vid in ctx.vids}

    def base(self, vid: int) -> str:
        return self._bases[vid]

    def __getattr__(self, name):
        return getattr(self._ctx, name)


def remote_bytes_read(ctx) -> float:
    """The node's own count of survivor bytes its repairs fetched from
    other servers (0 before the first)."""
    text = http_get(f"http://{ctx.node.volume}/metrics").decode()
    m = REMOTE_BYTES.search(text)
    return float(m.group(1)) if m else 0.0


def located(ctx, vid: int) -> dict[int, list[str]]:
    """Shard id -> the servers the master names for it."""
    from seaweedfs_tpu.pb import master_pb2, rpc

    with rpc.dial(rpc.grpc_address(ctx.node.master)) as ch:
        resp = rpc.master_stub(ch).LookupEcVolume(
            master_pb2.LookupEcVolumeRequest(volume_id=vid), timeout=5)
    return {e.shard_id: sorted(loc.url for loc in e.locations)
            for e in resp.shard_id_locations if e.locations}


def spread(ctx, vid: int) -> None:
    """Move every peer's shards from the node to it, lose the lost
    server's, and wait for the master to have heard of all of it."""
    from seaweedfs_tpu.pb import rpc

    pb, node, collection = ctx.pb, ctx.volume_stub, ctx.collection[vid]

    def drop(ids: list[int]) -> None:
        node.VolumeEcShardsUnmount(
            pb.VolumeEcShardsUnmountRequest(volume_id=vid, shard_ids=ids), timeout=60)
        node.VolumeEcShardsDelete(pb.VolumeEcShardsDeleteRequest(
            volume_id=vid, collection=collection, shard_ids=ids), timeout=60)

    want = {sid: [ctx.node.volume] for sid in range(reference.TOTAL)}
    for name, ids in holders(ctx).items():
        peer = ctx.peers[name]
        with rpc.dial(rpc.grpc_address(peer.url)) as ch:
            stub = rpc.volume_stub(ch)
            stub.VolumeEcShardsCopy(pb.VolumeEcShardsCopyRequest(
                volume_id=vid, collection=collection, shard_ids=ids,
                copy_ecx_file=True, source_data_node=ctx.node.volume), timeout=600)
            stub.VolumeEcShardsMount(pb.VolumeEcShardsMountRequest(
                volume_id=vid, collection=collection, shard_ids=ids), timeout=60)
        drop(ids)
        want.update({sid: [peer.url] for sid in ids})
    drop(lost_shards(ctx))
    for sid in lost_shards(ctx):
        del want[sid]
    deadline = time.time() + 60
    while (got := located(ctx, vid)) != want:
        require(time.time() < deadline,
                f"the master names {got} for the shards of volume {vid}, not {want}")
        time.sleep(0.1)


def gathers_as_placed(ctx, rep: dict, shard_bytes: int) -> bool:
    """A report line of a repair that read ten survivors of
    `shard_bytes` each and rebuilt the lost shards."""
    return (rep.get("survivors") == reference.DATA
            and rep.get("targets") == len(lost_shards(ctx))
            and rep.get("survivor_bytes") == reference.DATA * shard_bytes)


def shard_bytes_of(ctx, vid: int) -> int:
    """The size of a shard file, read off one the node holds."""
    local = ctx.config["placement"][REBUILDER][0]
    return os.path.getsize(ctx.base(vid) + reference.shard_ext(local))


def setup(ctx) -> None:
    require(ctx.traffic.get("concurrency", 1) == 1,
            "rack_rebuild_loop runs its operations one after the other")
    require(ctx.traffic["rpc"] == "VolumeEcShardsRebuild", f"rpc: {ctx.traffic['rpc']}")
    require(len(ctx.vids) == 1, f"one volume is spread over the servers, not {len(ctx.vids)}")
    budget = ctx.config["budget"]["env"]
    require(budget == BUDGET_ENV, f"the configuration's budget {budget} is not {BUDGET_ENV}")
    ran_under = {key: os.environ.get(key) for key in budget}
    if ran_under != budget:
        ctx.note(f"NOT THE CELL: the node runs under {ran_under}, the configuration "
                 f"states {budget}; a run for the record only")
    pb, stub, lost = ctx.pb, ctx.volume_stub, lost_shards(ctx)
    (vid,), collection = ctx.vids, ctx.collection[ctx.vids[0]]
    # the peers come up beside the node's encode
    ctx.peers = Peers(ctx.node, holders(ctx))
    ctx.peers.start()
    stub.VolumeMarkReadonly(pb.VolumeMarkReadonlyRequest(volume_id=vid))
    os.link(ctx.base(vid) + ".dat", ctx.ref_dat(vid))
    stub.VolumeEcShardsGenerate(pb.VolumeEcShardsGenerateRequest(
        volume_id=vid, collection=collection), timeout=600)
    stub.VolumeEcShardsMount(pb.VolumeEcShardsMountRequest(
        volume_id=vid, collection=collection,
        shard_ids=list(range(reference.TOTAL))), timeout=60)
    stub.VolumeDelete(pb.VolumeDeleteRequest(volume_id=vid), timeout=60)
    ctx.peers.wait_up()
    spread(ctx, vid)
    ctx.node.new_log()
    for _ in range(rebuild_loop.WARM_PASSES):
        step = rebuild_loop.call_rpc(ctx, [vid])
        require(step["rebuilt"] == lost,
                f"a warm-up repair rebuilt shards {step['rebuilt']}, not the lost {lost}")
    reports = verb_reports(ctx.node.new_log(), rebuild_loop.REPORT_VERB)
    require(len(reports) == rebuild_loop.WARM_PASSES,
            f"the warm-up repairs left {len(reports)} ec.rebuild report line(s)")
    for rep in reports:
        require_device_verb(rep, ctx.rehearse)
    for sid in lost:
        shutil.copy2(ctx.base(vid) + reference.shard_ext(sid), saved_shard(ctx, vid, sid))


def cpu_seconds(ctx) -> dict[str, float]:
    """User + system seconds each server's process has used so far."""
    procs = {REBUILDER: ctx.node.proc, **{n: p.proc for n, p in ctx.peers.peers.items()}}
    out = {}
    for name, proc in procs.items():
        with open(f"/proc/{proc.pid}/stat") as f:
            fields = f.read().rpartition(")")[2].split()
        out[name] = (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")
    return out


def window(ctx, seconds: float, tracer) -> dict:
    before, cpu = remote_bytes_read(ctx), cpu_seconds(ctx)
    res = rebuild_loop.window(ctx, seconds, tracer)
    ctx.remote_bytes_gathered = remote_bytes_read(ctx) - before
    # which side of the wire works: a Python process whose one GIL is
    # the pace uses about one core
    ctx.note(f"CPU seconds of each server in the window's {res['window_s']:.3f} s: "
             + " ".join(f"{name} {now - cpu[name]:.2f}"
                        for name, now in cpu_seconds(ctx).items()))
    return res


def control(ctx, name: str) -> None:
    """`rebuild_loop`'s three. `cauchy` decodes the survivors from where
    they lie; the other two touch only files of the node's directory."""
    if name != "cauchy":
        return rebuild_loop.control(ctx, name)
    reference_rebuild.write_decoded(
        gathered_base(ctx, ctx.vids[0]), lost_shards(ctx), parity="cauchy")


def check(ctx) -> dict:
    """`rebuild_loop.check`'s eleven numbers over the files where they
    lie (a survivor in the node's directory that the placement puts
    elsewhere counts as rewritten: it was copied there), and
    `ops_wrong_gather`: report lines that do not say ten survivors, the
    lost shards as targets and ten shard files of survivor bytes, plus
    one if the node's remote-read counter did not grow over the window
    by exactly the peers' survivors' bytes for each completed repair."""
    try:
        out = rebuild_loop.check(Gathered(ctx))
        vid = ctx.vids[0]
        shard_bytes = shard_bytes_of(ctx, vid)
        out["ops_wrong_gather"] = sum(
            1 for rep in ctx.window_reports if not gathers_as_placed(ctx, rep, shard_bytes))
        done = sum(1 for _, _, ok, _ in ctx.op_log if ok)
        want = sum(len(ids) for ids in holders(ctx).values()) * shard_bytes * done
        if ctx.remote_bytes_gathered != want:
            ctx.note(f"the node fetched {ctx.remote_bytes_gathered} survivor bytes from "
                     f"other servers in the window, not {want}")
            out["ops_wrong_gather"] += 1
        out["survivors_rewritten"] += sum(
            1 for sid, directory in shard_dirs(ctx).items()
            if directory != ctx.node.data
            and os.path.exists(ctx.base(vid) + reference.shard_ext(sid)))
        return out
    finally:
        ctx.peers.stop()
