"""Traffic generator `rebuild_loop`: the repair of one lost shard of a
mounted EC volume, back to back. Unlike `grpc_loop` it changes the
node's state between calls, with the program's own verbs.

Parameters (the mix's JSON file):
  rpc          "VolumeEcShardsRebuild"
  concurrency  1: a repair scheduler or an operator's `ec.rebuild` mends
               one volume at a time on a node
  trace_ops    operations the traced slice covers in a `--trace 1` run
  read_back    needles per volume read back from the mounted EC volume

The configuration's `failure.lost_shards` names the shards an operation
loses. One operation, all of it inside the window (it is the time to
repair that an operator sees):
  (a) lose   VolumeEcShardsUnmount, then VolumeEcShardsDelete of the lost
             shards; their files must be gone
  (b) rebuild VolumeEcShardsRebuild; the response must name the lost
             shards and no other
  (c) mount  VolumeEcShardsMount of the rebuilt shards, as the shell's
             `ec.rebuild` finishes
The window stops at the first completion past `--seconds`. `ec_gbps` is
the EC volume's logical size (the sealed `.dat`'s bytes, kept at
`ctx.ref_dat`) per completed operation, summed, over the time from the
window's start to the last completion.
"""

from __future__ import annotations

import json
import os
import shutil
import time
import zlib

from harness import loader, reference, reference_rebuild, roofline_rebuild
from harness.node import require, require_device_verb, verb_reports

REPORT_VERB = "rebuild"
# The first repair traces and loads the decode program of this survivor
# set; the second still finds new ring memory and a cold page cache, as
# the encode cells' second pass does (grpc_loop.WARM_PASSES).
WARM_PASSES = 2
# a file's mtime is the kernel's coarse clock, a tick behind time_ns()
MTIME_SLACK_NS = 50_000_000


def lost_shards(ctx) -> list[int]:
    return [int(i) for i in ctx.config["failure"]["lost_shards"]]


def saved_shard(ctx, vid: int, sid: int) -> str:
    """Where a copy of the shard as it was before the window is kept."""
    return os.path.join(ctx.ref_dir, f"{ctx.collection[vid]}_{vid}{reference.shard_ext(sid)}")


def call_rpc(ctx, vids: list[int]) -> dict:
    """One operation on one volume: lose, rebuild, mount. Returns the
    seconds of the three steps and the shard ids the rebuild named.
    (A test that breaks the timed path does it here.)"""
    vid, lost = vids[0], lost_shards(ctx)
    pb, stub, collection = ctx.pb, ctx.volume_stub, ctx.collection[vids[0]]
    t0 = time.perf_counter()
    stub.VolumeEcShardsUnmount(
        pb.VolumeEcShardsUnmountRequest(volume_id=vid, shard_ids=lost), timeout=60)
    stub.VolumeEcShardsDelete(pb.VolumeEcShardsDeleteRequest(
        volume_id=vid, collection=collection, shard_ids=lost), timeout=60)
    for sid in lost:
        require(not os.path.exists(ctx.base(vid) + reference.shard_ext(sid)),
                f"shard {sid} of volume {vid} is still there after its delete")
    t1 = time.perf_counter()
    resp = stub.VolumeEcShardsRebuild(pb.VolumeEcShardsRebuildRequest(
        volume_id=vid, collection=collection), timeout=600)
    t2 = time.perf_counter()
    rebuilt = sorted(resp.rebuilt_shard_ids)
    stub.VolumeEcShardsMount(pb.VolumeEcShardsMountRequest(
        volume_id=vid, collection=collection, shard_ids=rebuilt), timeout=60)
    t3 = time.perf_counter()
    return {"lose_s": t1 - t0, "rebuild_s": t2 - t1, "mount_s": t3 - t2, "rebuilt": rebuilt}


def setup(ctx) -> None:
    """Take every volume through `ec.encode` to its end (seal, generate,
    mount all 14 shards, delete the normal volume), keep each `.dat` for
    the reference, run the untimed repairs (every shape and the one
    decode program the window uses), and keep a copy of each shard the
    window will lose as it was before it."""
    require(ctx.traffic.get("concurrency", 1) == 1,
            "rebuild_loop runs its operations one after the other")
    require(ctx.traffic["rpc"] == "VolumeEcShardsRebuild", f"rpc: {ctx.traffic['rpc']}")
    pb, stub, lost = ctx.pb, ctx.volume_stub, lost_shards(ctx)
    for vid in ctx.vids:
        collection = ctx.collection[vid]
        stub.VolumeMarkReadonly(pb.VolumeMarkReadonlyRequest(volume_id=vid))
        os.link(ctx.base(vid) + ".dat", ctx.ref_dat(vid))
        stub.VolumeEcShardsGenerate(pb.VolumeEcShardsGenerateRequest(
            volume_id=vid, collection=collection), timeout=600)
        stub.VolumeEcShardsMount(pb.VolumeEcShardsMountRequest(
            volume_id=vid, collection=collection,
            shard_ids=list(range(reference.TOTAL))), timeout=60)
        stub.VolumeDelete(pb.VolumeDeleteRequest(volume_id=vid), timeout=60)
    ctx.node.new_log()
    for vid in ctx.vids * WARM_PASSES:
        step = call_rpc(ctx, [vid])
        require(step["rebuilt"] == lost,
                f"a warm-up repair of volume {vid} rebuilt shards {step['rebuilt']}, "
                f"not the lost {lost}")
    reports = verb_reports(ctx.node.new_log(), REPORT_VERB)
    require(len(reports) == len(ctx.vids) * WARM_PASSES,
            f"the warm-up repairs left {len(reports)} ec.{REPORT_VERB} report line(s)")
    for rep in reports:
        require_device_verb(rep, ctx.rehearse)
    for vid in ctx.vids:
        for sid in lost:
            shutil.copy2(ctx.base(vid) + reference.shard_ext(sid), saved_shard(ctx, vid, sid))


def window(ctx, seconds: float, tracer) -> dict:
    lost = lost_shards(ctx)
    dat_bytes = {v: os.path.getsize(ctx.ref_dat(v)) for v in ctx.vids}
    survivor = next(i for i in range(reference.TOTAL) if i not in lost)
    op_hbm = {v: roofline_rebuild.rebuild_hbm_bytes(
        os.path.getsize(ctx.base(v) + reference.shard_ext(survivor)), len(lost))
        for v in ctx.vids}
    trace_from, trace_ops = 1, int(ctx.traffic.get("trace_ops", 3))
    ctx.op_log = []  # (started_ns, vid, ok, rebuilt shard ids)
    steps = []
    done_bytes = traced_hbm = 0
    ctx.window_started_ns = time.time_ns()
    t0 = time.perf_counter()
    while True:
        n = len(ctx.op_log)
        if n == trace_from:
            tracer.start()
        elif n == trace_from + trace_ops:
            tracer.stop()
        vid = ctx.vids[n % len(ctx.vids)]
        started = time.time_ns()
        try:
            step = call_rpc(ctx, [vid])
            ok, rebuilt = True, step["rebuilt"]
            steps.append(step)
            done_bytes += dat_bytes[vid]
            if tracer.running:
                traced_hbm += op_hbm[vid]
        except ctx.rpc_error as e:
            ok, rebuilt = False, None
            ctx.note(f"a step of the repair of volume {vid} failed: {e}")
        ctx.op_log.append((started, vid, ok, rebuilt))
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    tracer.stop()
    window_s = elapsed - tracer.paused_s
    starts = [t for t, _, _, _ in ctx.op_log]
    ctx.note("op starts, s from the first: "
             + " ".join(f"{(t - starts[0]) / 1e9:.3f}" for t in starts)
             + f"; last completion {elapsed:.3f}, traced pause {tracer.paused_s:.3f}")
    for key in ("lose_s", "rebuild_s", "mount_s"):
        ctx.note(f"{key} of each completed operation: "
                 + " ".join(f"{step[key]:.4f}" for step in steps))
    ctx.window_reports = verb_reports(ctx.node.new_log(), REPORT_VERB)
    for rep in ctx.window_reports:
        require_device_verb(rep, ctx.rehearse)
    return {
        "attempted": len(ctx.op_log),
        "failed": sum(1 for _, _, ok, _ in ctx.op_log if not ok),
        "metrics": {"ec_gbps": done_bytes / 1e9 / window_s},
        "window_s": window_s,
        "gib": done_bytes / 2**30,
        "requests": len(ctx.op_log),
        "reports": ctx.window_reports,
        "traced_work": {"rebuild_hbm_bytes": traced_hbm},
    }


def control(ctx, name: str) -> None:
    """The reference, or the state before the window, in the program's
    place, one stated guarantee broken: `cauchy` writes the decode of
    the survivors under another code's matrix over each rebuilt file,
    `crc32` puts zlib's CRC-32 of the rebuilt file where the `.ecc`
    promises CRC-32C, `not_rebuilt` puts the file of before the window
    back, with its old mtime: right bytes that no operation of the
    window wrote."""
    lost = lost_shards(ctx)
    for vid in ctx.vids:
        base = ctx.base(vid)
        if name == "cauchy":
            reference_rebuild.write_decoded(base, lost, parity="cauchy")
        elif name == "crc32":
            with open(base + ".ecc") as f:
                doc = json.load(f)
            for sid in lost:
                with open(base + reference.shard_ext(sid), "rb") as f:
                    doc["shards"][str(sid)]["crc"] = zlib.crc32(f.read())
            with open(base + ".ecc", "w") as f:
                json.dump(doc, f)
        elif name == "not_rebuilt":
            for sid in lost:
                shutil.copy2(saved_shard(ctx, vid, sid), base + reference.shard_ext(sid))
        else:
            raise ValueError(f"rebuild_loop has no control {name!r}")


def check(ctx) -> dict:
    """What the window's last repair of each volume left on disk (earlier
    ones wrote the same file and were deleted again: of them only the
    report line, the response and the count are held). The sealed `.dat`
    against the seed; the rebuilt file against the reference's DECODE of
    the ten survivors on disk; all 14 files and the `.ecc` against the
    reference's ENCODE of that `.dat`, so that a survivor that was
    written to, or a stale sidecar, shows; mtimes: the rebuilt file not
    older than the last operation's start, no survivor as new as the
    window; a sample of needles read back from the mounted EC volume.
    Every number's limit is 0."""
    lost = lost_shards(ctx)
    ok_ops = [(t, vid, rebuilt) for t, vid, ok, rebuilt in ctx.op_log if ok]
    out = {
        "ops_failed": len(ctx.op_log) - len(ok_ops),
        "ops_without_report": abs(len(ok_ops) - len(ctx.window_reports)),
        "ops_wrong_shards": sum(1 for _, _, rebuilt in ok_ops if rebuilt != lost),
        "dat_needles_differ": 0,
        "rebuilt_differs_from_decode": 0,
        "data_shards_differ": 0,
        "parity_shards_differ": 0,
        "ecc_crcs_differ": 0,
        "shards_not_rewritten": 0,
        "survivors_rewritten": 0,
        "ec_bodies_differ": 0,
    }
    for vid in ctx.vids:
        base = ctx.base(vid)
        out["dat_needles_differ"] += reference.dat_needles_differ(
            ctx.ref_dat(vid), ctx.loader.needle_digests(vid), loader.digest)
        out["rebuilt_differs_from_decode"] += reference_rebuild.rebuilt_differ(base, lost)
        for key, n in reference.check_shards(ctx.ref_dat(vid), base).items():
            out[key] += n
        last = max((t for t, v, _ in ok_ops if v == vid), default=None)
        for sid in range(reference.TOTAL):
            try:
                mtime = os.stat(base + reference.shard_ext(sid)).st_mtime_ns
            except OSError:
                mtime = None
            if sid in lost:
                if last is None or mtime is None or mtime < last - MTIME_SLACK_NS:
                    out["shards_not_rewritten"] += 1
            elif mtime is None or mtime >= ctx.window_started_ns - MTIME_SLACK_NS:
                out["survivors_rewritten"] += 1
        out["ec_bodies_differ"] += ctx.loader.bodies_differ(
            vid, int(ctx.traffic.get("read_back", 28)))
    return out
