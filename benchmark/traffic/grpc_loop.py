"""Traffic generator `grpc_loop`: one EC verb's gRPC handler, called
back to back on the configuration's sealed volumes.

Parameters (the mix's JSON file):
  rpc          "VolumeEcShardsGenerate" (one volume per call, the volumes
               in turn) or "VolumeEcShardsBatchGenerate" (all volumes in
               one call)
  concurrency  1: an operator's loop seals one thing at a time
  trace_ops    operations the traced slice covers in a `--trace 1` run
  read_back    needles per volume read back from the shards afterwards

Both handlers read the sealed `.dat`, write all 14 shard files durably
and leave the `.dat` in place, so the window repeats them on the same
volumes. The window stops at the first completion past `--seconds`, and
`ec_gbps` is the `.dat` bytes of all completed operations over the time
from the window's start to the last completion.
"""

from __future__ import annotations

import os
import time

from harness import loader, reference, roofline
from harness.node import require, require_device_verb, verb_reports

REPORT_VERB = {
    "VolumeEcShardsGenerate": "generate",
    "VolumeEcShardsBatchGenerate": "batch_generate",
}
# The second call on a volume still runs a quarter over the later ones
# (reader and dispatcher seconds fall from 0.8 and 1.2 through 0.4 and 0.8
# to 0.2 and 0.7: buffers and the page cache, on every run on the chip), so
# two untimed passes come before the window.
WARM_PASSES = 2
# a file's mtime is the kernel's coarse clock, a tick behind time_ns()
MTIME_SLACK_NS = 50_000_000


def operations(ctx) -> list[list[int]]:
    """The volume ids of each call of one pass over the volumes."""
    if ctx.traffic["rpc"] == "VolumeEcShardsBatchGenerate":
        return [list(ctx.vids)]
    return [[vid] for vid in ctx.vids]


def call_rpc(ctx, vids: list[int]) -> None:
    """One operation: one call. (The tests break the timed path here.)"""
    if ctx.traffic["rpc"] == "VolumeEcShardsBatchGenerate":
        ctx.volume_stub.VolumeEcShardsBatchGenerate(
            ctx.pb.VolumeEcShardsBatchGenerateRequest(volume_ids=vids), timeout=600
        )
    else:
        ctx.volume_stub.VolumeEcShardsGenerate(
            ctx.pb.VolumeEcShardsGenerateRequest(
                volume_id=vids[0], collection=ctx.collection[vids[0]]
            ),
            timeout=600,
        )


def setup(ctx) -> None:
    """Seal the volumes, keep each `.dat` for the reference, and run the
    untimed passes of the window's own operations: every shape it uses."""
    require(ctx.traffic.get("concurrency", 1) == 1,
            "grpc_loop runs its operations one after the other")
    for vid in ctx.vids:
        ctx.volume_stub.VolumeMarkReadonly(
            ctx.pb.VolumeMarkReadonlyRequest(volume_id=vid)
        )
        os.link(ctx.base(vid) + ".dat", ctx.ref_dat(vid))
    ctx.node.new_log()
    ops = operations(ctx)
    for vids in ops * WARM_PASSES:
        call_rpc(ctx, vids)
    verb = REPORT_VERB[ctx.traffic["rpc"]]
    reports = verb_reports(ctx.node.new_log(), verb)
    require(len(reports) == len(ops) * WARM_PASSES,
            f"the warm-up passes left {len(reports)} ec.{verb} report line(s)")
    for rep in reports:
        require_device_verb(rep, ctx.rehearse)


def window(ctx, seconds: float, tracer) -> dict:
    ops = operations(ctx)
    dat_bytes = {v: os.path.getsize(ctx.base(v) + ".dat") for v in ctx.vids}
    op_bytes = [sum(dat_bytes[v] for v in vids) for vids in ops]
    trace_from, trace_ops = 1, int(ctx.traffic.get("trace_ops", 3))
    ctx.op_log = []  # (started_ns, vids, ok)
    op_hbm = [sum(roofline.encode_hbm_bytes(dat_bytes[v]) for v in vids) for vids in ops]
    done_bytes = traced_hbm = 0
    t0 = time.perf_counter()
    while True:
        n = len(ctx.op_log)
        if n == trace_from:
            tracer.start()
        elif n == trace_from + trace_ops:
            tracer.stop()
        started = time.time_ns()
        try:
            call_rpc(ctx, ops[n % len(ops)])
            ok = True
            done_bytes += op_bytes[n % len(ops)]
            if tracer.running:
                traced_hbm += op_hbm[n % len(ops)]
        except ctx.rpc_error as e:
            ok = False
            ctx.note(f"{ctx.traffic['rpc']} failed: {e}")
        ctx.op_log.append((started, ops[n % len(ops)], ok))
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    tracer.stop()
    window_s = elapsed - tracer.paused_s
    starts = [t for t, _, _ in ctx.op_log]
    ctx.note("op starts, s from the first: "
             + " ".join(f"{(t - starts[0]) / 1e9:.3f}" for t in starts)
             + f"; last completion {elapsed:.3f}, traced pause {tracer.paused_s:.3f}")
    ctx.window_reports = verb_reports(ctx.node.new_log(), REPORT_VERB[ctx.traffic["rpc"]])
    for rep in ctx.window_reports:
        require_device_verb(rep, ctx.rehearse)
    return {
        "attempted": len(ctx.op_log),
        "failed": sum(1 for _, _, ok in ctx.op_log if not ok),
        "metrics": {"ec_gbps": done_bytes / 1e9 / window_s},
        "window_s": window_s,
        "gib": done_bytes / 2**30,
        "requests": len(ctx.op_log),
        "reports": ctx.window_reports,
        "traced_work": {"encode_hbm_bytes": traced_hbm},
    }


CONTROLS = {"cauchy": {"parity": "cauchy"}, "crc32": {"checksum": "crc32"}}


def control(ctx, name: str) -> None:
    """The reference in the program's place, one stated guarantee broken:
    `cauchy` writes another code's parity (the stated code no longer reads
    a needle back once a shard is lost), `crc32` publishes zlib's CRC-32
    where the `.ecc` promises CRC-32C."""
    for vid in ctx.vids:
        reference.write_shards(ctx.ref_dat(vid), ctx.base(vid), **CONTROLS[name])


def check(ctx) -> dict:
    """The sealed `.dat`, needle by needle, against the seed; what the
    window's last operation on each volume left on disk, against the
    reference's encode of that `.dat` (earlier operations wrote the same
    files and were overwritten: of them only the report line and the
    count are held); then the verb finished as `ec.batch` finishes it
    (mount the shards, delete the volume) and a sample of needles read
    back from the shards. Every number's limit is 0."""
    ok_ops = [(t, vids) for t, vids, ok in ctx.op_log if ok]
    out = {
        "ops_failed": len(ctx.op_log) - len(ok_ops),
        "ops_without_report": abs(len(ok_ops) - len(ctx.window_reports)),
        "dat_needles_differ": 0,
        "shards_not_rewritten": 0,
        "data_shards_differ": 0,
        "parity_shards_differ": 0,
        "ecc_crcs_differ": 0,
        "ec_bodies_differ": 0,
    }
    for vid in ctx.vids:
        base = ctx.base(vid)
        out["dat_needles_differ"] += reference.dat_needles_differ(
            ctx.ref_dat(vid), ctx.loader.needle_digests(vid), loader.digest)
        # the window's last operation on this volume rewrote every file
        last = max((t for t, vids in ok_ops if vid in vids), default=None)
        for i in range(reference.TOTAL):
            try:
                mtime = os.stat(base + reference.shard_ext(i)).st_mtime_ns
            except OSError:
                mtime = None
            if last is None or mtime is None or mtime < last - MTIME_SLACK_NS:
                out["shards_not_rewritten"] += 1
        for key, n in reference.check_shards(ctx.ref_dat(vid), base).items():
            out[key] += n
    for vid in ctx.vids:
        ctx.volume_stub.VolumeEcShardsMount(ctx.pb.VolumeEcShardsMountRequest(
            volume_id=vid, collection=ctx.collection[vid],
            shard_ids=list(range(reference.TOTAL))))
        ctx.volume_stub.VolumeDelete(ctx.pb.VolumeDeleteRequest(volume_id=vid))
        out["ec_bodies_differ"] += ctx.loader.bodies_differ(
            vid, int(ctx.traffic.get("read_back", 28)))
    return out
