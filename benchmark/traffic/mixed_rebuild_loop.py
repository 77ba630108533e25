"""Traffic generator `mixed_rebuild_loop`: `batch_rebuild_loop`'s
operation (lose, ONE `VolumeEcShardsBatchRebuild` of every volume,
mount) after a drive died under a placement that turns by volume, so
that each volume is short of a pair of shards of its own and one call
holds several damage signatures.

Parameters (the mix's JSON file): those of `batch_rebuild_loop`.

The configuration's `failure` names `drives` and `lost_drive`: shard i
of the v-th volume of the list lies on drive (i + v) mod `drives`, and
the dead drive held the shards i with (i + v) mod `drives` ==
`lost_drive`. Everything else, the steps of an operation, the window,
`ec_gbps` and the comparison, is `batch_rebuild_loop`'s, each volume
held to its own lost shards; the few lines that read one loss for all
volumes there are written here again per volume.
"""

from __future__ import annotations

import json
import os
import shutil
import time
import zlib

from harness import loader, reference, reference_rebuild, roofline_rebuild
from harness.node import require, require_device_verb, verb_reports
from traffic.batch_rebuild_loop import REPORT_VERB, SINGLE_VERB, shard_path
from traffic.rebuild_loop import MTIME_SLACK_NS, WARM_PASSES, saved_shard


def lost_of(ctx) -> dict[int, list[int]]:
    """Volume -> the shard ids the dead drive held of it."""
    failure = ctx.config["failure"]
    drives, dead = int(failure["drives"]), int(failure["lost_drive"])
    return {vid: [i for i in range(reference.TOTAL) if (i + v) % drives == dead]
            for v, vid in enumerate(ctx.vids)}


def signatures(ctx) -> int:
    """The damage signatures the call holds: one a distinct lost set
    (each volume's survivors are the first ten it has left)."""
    return len({tuple(ids) for ids in lost_of(ctx).values()})


def call_rpc(ctx, vids: list[int]) -> dict:
    """One operation on all of `vids`: lose each volume's own shards,
    ONE rebuild, mount. Returns the seconds of the three steps and, per
    volume, the lost shard ids whose files the verb left. (A test that
    breaks the timed path does it here.)"""
    pb, stub, lost = ctx.pb, ctx.volume_stub, lost_of(ctx)
    t0 = time.perf_counter()
    for vid in vids:
        stub.VolumeEcShardsUnmount(
            pb.VolumeEcShardsUnmountRequest(volume_id=vid, shard_ids=lost[vid]), timeout=60)
        stub.VolumeEcShardsDelete(pb.VolumeEcShardsDeleteRequest(
            volume_id=vid, collection=ctx.collection[vid], shard_ids=lost[vid]), timeout=60)
        for sid in lost[vid]:
            require(not os.path.exists(shard_path(ctx, vid, sid)),
                    f"shard {sid} of volume {vid} is still there after its delete")
    t1 = time.perf_counter()
    stub.VolumeEcShardsBatchRebuild(
        pb.VolumeEcShardsBatchGenerateRequest(volume_ids=vids), timeout=600)
    t2 = time.perf_counter()
    rebuilt = {vid: [sid for sid in lost[vid] if os.path.exists(shard_path(ctx, vid, sid))]
               for vid in vids}
    for vid in vids:
        stub.VolumeEcShardsMount(pb.VolumeEcShardsMountRequest(
            volume_id=vid, collection=ctx.collection[vid], shard_ids=rebuilt[vid]), timeout=60)
    t3 = time.perf_counter()
    return {"lose_s": t1 - t0, "rebuild_s": t2 - t1, "mount_s": t3 - t2, "rebuilt": rebuilt}


def all_back(ctx, rebuilt: dict[int, list[int]]) -> bool:
    lost = lost_of(ctx)
    return all(rebuilt.get(vid) == lost[vid] for vid in ctx.vids)


def setup(ctx) -> None:
    """`batch_rebuild_loop.setup` with each volume's own lost shards:
    every volume through `ec.encode` to its end, each `.dat` kept, the
    untimed operations (every shape and the decode program of every
    signature the window uses), and a copy of each shard the window
    will lose as it was before it."""
    require(ctx.traffic.get("concurrency", 1) == 1,
            "mixed_rebuild_loop runs its operations one after the other")
    require(ctx.traffic["rpc"] == "VolumeEcShardsBatchRebuild", f"rpc: {ctx.traffic['rpc']}")
    require(ctx.traffic["volumes_per_call"] == len(ctx.vids) >= 2,
            f"one call mends {ctx.traffic['volumes_per_call']} volumes, "
            f"the configuration has {len(ctx.vids)}")
    pb, stub = ctx.pb, ctx.volume_stub
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
    for _ in range(WARM_PASSES):
        step = call_rpc(ctx, list(ctx.vids))
        require(all_back(ctx, step["rebuilt"]),
                f"a warm-up operation left the lost shards {step['rebuilt']}, "
                f"not {lost_of(ctx)}")
    reports = verb_reports(ctx.node.new_log(), REPORT_VERB)
    require(len(reports) == WARM_PASSES,
            f"the warm-up operations left {len(reports)} ec.{REPORT_VERB} report line(s)")
    for rep in reports:
        require_device_verb(rep, ctx.rehearse)
    for vid, lost in lost_of(ctx).items():
        for sid in lost:
            shutil.copy2(shard_path(ctx, vid, sid), saved_shard(ctx, vid, sid))


def shard_bytes(ctx) -> dict[int, int]:
    """Volume -> the size of its shard files, read off a survivor."""
    lost = lost_of(ctx)
    return {vid: os.path.getsize(shard_path(ctx, vid, next(
        i for i in range(reference.TOTAL) if i not in lost[vid]))) for vid in ctx.vids}


def window(ctx, seconds: float, tracer) -> dict:
    """`batch_rebuild_loop.window`, with the traced HBM bytes of each
    volume's own targets."""
    vids, lost = list(ctx.vids), lost_of(ctx)
    op_bytes = sum(os.path.getsize(ctx.ref_dat(vid)) for vid in vids)
    op_hbm = sum(roofline_rebuild.rebuild_hbm_bytes(size, len(lost[vid]))
                 for vid, size in shard_bytes(ctx).items())
    trace_from, trace_ops = 1, int(ctx.traffic.get("trace_ops", 3))
    ctx.op_log = []  # (started_ns, ok, {vid: lost shard ids that were back})
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
        started = time.time_ns()
        try:
            step = call_rpc(ctx, vids)
            ok, rebuilt = True, step["rebuilt"]
            steps.append(step)
            done_bytes += op_bytes
            if tracer.running:
                traced_hbm += op_hbm
        except ctx.rpc_error as e:
            ok, rebuilt = False, None
            ctx.note(f"a step of the batch repair of volumes {vids} failed: {e}")
        ctx.op_log.append((started, ok, rebuilt))
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    tracer.stop()
    window_s = elapsed - tracer.paused_s
    starts = [t for t, _, _ in ctx.op_log]
    ctx.note("op starts, s from the first: "
             + " ".join(f"{(t - starts[0]) / 1e9:.3f}" for t in starts)
             + f"; last completion {elapsed:.3f}, traced pause {tracer.paused_s:.3f}")
    for key in ("lose_s", "rebuild_s", "mount_s"):
        ctx.note(f"{key} of each completed operation: "
                 + " ".join(f"{step[key]:.4f}" for step in steps))
    log = ctx.node.new_log()
    ctx.window_reports = verb_reports(log, REPORT_VERB)
    ctx.window_single_lines = len(verb_reports(log, SINGLE_VERB))
    for rep in ctx.window_reports:
        require_device_verb(rep, ctx.rehearse)
    return {
        "attempted": len(ctx.op_log),
        "failed": sum(1 for _, ok, _ in ctx.op_log if not ok),
        "metrics": {"ec_gbps": done_bytes / 1e9 / window_s},
        "window_s": window_s,
        "gib": done_bytes / 2**30,
        "requests": len(ctx.op_log),
        "reports": ctx.window_reports,
        "traced_work": {"rebuild_hbm_bytes": traced_hbm},
    }


def control(ctx, name: str) -> None:
    """`rebuild_loop`'s three, each volume with its own lost shards:
    `cauchy` (the decode of the survivors under another code's matrix
    over each rebuilt file), `crc32` (zlib's CRC-32 where the `.ecc`
    promises CRC-32C), `not_rebuilt` (the files of before the window put
    back, with their old mtimes)."""
    for vid, lost in lost_of(ctx).items():
        base = ctx.base(vid)
        if name == "cauchy":
            reference_rebuild.write_decoded(base, lost, parity="cauchy")
        elif name == "crc32":
            with open(base + ".ecc") as f:
                doc = json.load(f)
            for sid in lost:
                with open(shard_path(ctx, vid, sid), "rb") as f:
                    doc["shards"][str(sid)]["crc"] = zlib.crc32(f.read())
            with open(base + ".ecc", "w") as f:
                json.dump(doc, f)
        elif name == "not_rebuilt":
            for sid in lost:
                shutil.copy2(saved_shard(ctx, vid, sid), shard_path(ctx, vid, sid))
        else:
            raise ValueError(f"mixed_rebuild_loop has no control {name!r}")


def batched_as_stated(ctx, rep: dict, survivor_bytes: int) -> bool:
    """A report line of a call that mended all of the volumes, each from
    ten local survivors, as the call's signatures in ONE pass.
    `batch_groups`, `fell_through` and `batch_passes` are held where the
    line has them: a program that prints none of them (it mends a mixed
    call in a pass a signature) runs this cell too, and what fell
    through there still shows as `batch_volumes` short and an
    `ec.rebuild` line."""
    return (rep.get("batch_volumes") == len(ctx.vids)
            and rep.get("batch_groups", signatures(ctx)) == signatures(ctx)
            and rep.get("batch_passes", 1) == 1
            and rep.get("fell_through", 0) == 0
            and rep.get("survivors") == reference.DATA
            and rep.get("targets") == max(map(len, lost_of(ctx).values()))
            and rep.get("survivor_bytes") == survivor_bytes)


def check(ctx) -> dict:
    """`batch_rebuild_loop.check`'s twelve numbers, each volume held to
    its own lost shards; `ops_not_batched` counts report lines that do
    not say all the volumes, the call's signatures, one pass, ten
    survivors and the lost pair as targets, plus every `ec.rebuild`
    line in the window's log. Every number's limit is 0."""
    lost = lost_of(ctx)
    ok_ops = [(t, rebuilt) for t, ok, rebuilt in ctx.op_log if ok]
    survivor_bytes = reference.DATA * sum(shard_bytes(ctx).values())
    out = {
        "ops_failed": len(ctx.op_log) - len(ok_ops),
        "ops_without_report": abs(len(ok_ops) - len(ctx.window_reports)),
        "ops_wrong_shards": sum(1 for _, rebuilt in ok_ops if not all_back(ctx, rebuilt)),
        "ops_not_batched": ctx.window_single_lines + sum(
            1 for rep in ctx.window_reports
            if not batched_as_stated(ctx, rep, survivor_bytes)),
        "dat_needles_differ": 0,
        "rebuilt_differs_from_decode": 0,
        "data_shards_differ": 0,
        "parity_shards_differ": 0,
        "ecc_crcs_differ": 0,
        "shards_not_rewritten": 0,
        "survivors_rewritten": 0,
        "ec_bodies_differ": 0,
    }
    last = max((t for t, _ in ok_ops), default=None)
    for vid in ctx.vids:
        base = ctx.base(vid)
        out["dat_needles_differ"] += reference.dat_needles_differ(
            ctx.ref_dat(vid), ctx.loader.needle_digests(vid), loader.digest)
        out["rebuilt_differs_from_decode"] += reference_rebuild.rebuilt_differ(base, lost[vid])
        for key, n in reference.check_shards(ctx.ref_dat(vid), base).items():
            out[key] += n
        for sid in range(reference.TOTAL):
            try:
                mtime = os.stat(shard_path(ctx, vid, sid)).st_mtime_ns
            except OSError:
                mtime = None
            if sid in lost[vid]:
                if last is None or mtime is None or mtime < last - MTIME_SLACK_NS:
                    out["shards_not_rewritten"] += 1
            elif mtime is None or mtime >= ctx.window_started_ns - MTIME_SLACK_NS:
                out["survivors_rewritten"] += 1
        out["ec_bodies_differ"] += ctx.loader.bodies_differ(
            vid, int(ctx.traffic.get("read_back", 28)))
    return out
