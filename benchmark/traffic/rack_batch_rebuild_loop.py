"""Traffic generator `rack_batch_rebuild_loop`: the repair of every EC
volume a lost server held shards of, by ONE call on a surviving server
that streams most of each volume's survivors from the other racks.
`batch_rebuild_loop`'s operation (lose, ONE `VolumeEcShardsBatchRebuild`
of every volume, mount: its `call_rpc`) on `rack_rebuild_loop`'s
deployment (its peers, `spread` and budget).

Parameters (the mix's JSON file): those of `batch_rebuild_loop`.

The configuration names the servers as `rack_rebuild_loop`'s does
(`placement`, `failure.lost_shards`, `budget.env`); every volume lies
under the same placement. Set-up: `ec.encode` to its end on the node
for each volume, `rack_rebuild_loop.spread` for each (the peers' shards
moved to them with the program's own verbs, the lost server's deleted,
a wait until the master names every holder and nobody for the lost
shards), then the untimed operations.

The window is `batch_rebuild_loop`'s. Its report lines are the window's
`ec.batch_rebuild` lines and any `ec.rebuild` line: a program whose
batch takes local survivors only mends these volumes one after another
through the single-volume verb, leaves a batch line that mended nothing
(`batch_volumes` 0), and runs the cell all the same. Which path a
volume took is no correctness number: `batch_rebuild_volumes_per_op`
reads it.

`check` compares the files where they lie, a directory of hard links a
volume gathering every shard from its holder's directory (as
`rack_rebuild_loop.check` does for its one volume): `batch_rebuild_loop`'s
numbers but `ops_not_batched`, and `ops_wrong_gather` in its place.
"""

from __future__ import annotations

import os
import shutil
import time

from harness import reference, reference_rebuild, roofline_rebuild
from harness.node import require, require_device_verb, verb_reports
from harness.peers import Peers
from traffic import batch_rebuild_loop, rack_rebuild_loop, rebuild_loop
from traffic.batch_rebuild_loop import REPORT_VERB, SINGLE_VERB, all_back, call_rpc
from traffic.rack_rebuild_loop import (
    BUDGET_ENV, cpu_seconds, holders, remote_bytes_read, shard_dirs, spread,
)
from traffic.rebuild_loop import WARM_PASSES, lost_shards, saved_shard


def gathered_base(ctx, vid: int) -> str:
    """The base path of a directory of hard links, one a volume, made
    anew, that holds the volume's shard files from where they lie and
    the node's `.ecc`."""
    name = f"{ctx.collection[vid]}_{vid}"
    root = os.path.join(ctx.ref_dir, f"gathered-{vid}")
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
    """`ctx` for `batch_rebuild_loop.check`, whose every file is read
    through `base`: each volume's gathered directory in the place of the
    node's."""

    def __init__(self, ctx):
        self._ctx = ctx
        self._bases = {vid: gathered_base(ctx, vid) for vid in ctx.vids}

    def base(self, vid: int) -> str:
        return self._bases[vid]

    def __getattr__(self, name):
        return getattr(self._ctx, name)


def mended(rep: dict) -> int:
    """The volumes a report line's verb mended: a batch line's
    `batch_volumes`, a single-volume line's one."""
    return rep.get("batch_volumes", 1)


def reports_of(log: str) -> list[dict]:
    """The batch lines of `log` and the single-volume lines."""
    return verb_reports(log, REPORT_VERB) + verb_reports(log, SINGLE_VERB)


def worked(reports: list[dict]) -> list[dict]:
    """The lines whose verb mended a volume: a batch line that mended
    none ran no driver."""
    return [rep for rep in reports if mended(rep)]


def setup(ctx) -> None:
    require(ctx.traffic.get("concurrency", 1) == 1,
            "rack_batch_rebuild_loop runs its operations one after the other")
    require(ctx.traffic["rpc"] == "VolumeEcShardsBatchRebuild", f"rpc: {ctx.traffic['rpc']}")
    require(ctx.traffic["volumes_per_call"] == len(ctx.vids) >= 2,
            f"one call mends {ctx.traffic['volumes_per_call']} volumes, "
            f"the configuration has {len(ctx.vids)}")
    budget = ctx.config["budget"]["env"]
    require(budget == BUDGET_ENV, f"the configuration's budget {budget} is not {BUDGET_ENV}")
    ran_under = {key: os.environ.get(key) for key in budget}
    if ran_under != budget:
        ctx.note(f"NOT THE CELL: the node runs under {ran_under}, the configuration "
                 f"states {budget}; a run for the record only")
    pb, stub = ctx.pb, ctx.volume_stub
    # the peers come up beside the node's encodes
    ctx.peers = Peers(ctx.node, holders(ctx))
    ctx.peers.start()
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
    ctx.peers.wait_up()
    for vid in ctx.vids:
        spread(ctx, vid)
    ctx.node.new_log()
    for _ in range(WARM_PASSES):
        step = call_rpc(ctx, list(ctx.vids))
        require(all_back(ctx, step["rebuilt"]),
                f"a warm-up operation left the lost shards {step['rebuilt']}, "
                f"not {lost_shards(ctx)} of every volume")
    log = ctx.node.new_log()
    calls = verb_reports(log, REPORT_VERB)
    require(len(calls) == WARM_PASSES,
            f"the warm-up operations left {len(calls)} ec.{REPORT_VERB} report line(s)")
    for rep in worked(reports_of(log)):
        require_device_verb(rep, ctx.rehearse)
    for vid in ctx.vids:
        for sid in lost_shards(ctx):
            shutil.copy2(batch_rebuild_loop.shard_path(ctx, vid, sid), saved_shard(ctx, vid, sid))


def window(ctx, seconds: float, tracer) -> dict:
    """`batch_rebuild_loop.window`'s loop, with the report lines of
    `reports_of` and the node's count of survivor bytes it fetched from
    other servers over the window."""
    vids, lost = list(ctx.vids), lost_shards(ctx)
    op_bytes = sum(os.path.getsize(ctx.ref_dat(vid)) for vid in vids)
    op_hbm = sum(roofline_rebuild.rebuild_hbm_bytes(size, len(lost))
                 for size in batch_rebuild_loop.shard_bytes(ctx).values())
    trace_from, trace_ops = 1, int(ctx.traffic.get("trace_ops", 3))
    ctx.op_log = []  # (started_ns, ok, {vid: lost shard ids that were back})
    steps = []
    done_bytes = traced_hbm = 0
    remote_before, cpu = remote_bytes_read(ctx), cpu_seconds(ctx)
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
    ctx.remote_bytes_gathered = remote_bytes_read(ctx) - remote_before
    starts = [t for t, _, _ in ctx.op_log]
    ctx.note("op starts, s from the first: "
             + " ".join(f"{(t - starts[0]) / 1e9:.3f}" for t in starts)
             + f"; last completion {elapsed:.3f}, traced pause {tracer.paused_s:.3f}")
    for key in ("lose_s", "rebuild_s", "mount_s"):
        ctx.note(f"{key} of each completed operation: "
                 + " ".join(f"{step[key]:.4f}" for step in steps))
    # which side of the wire works (rack_rebuild_loop.window's note)
    ctx.note(f"CPU seconds of each server in the window's {window_s:.3f} s: "
             + " ".join(f"{name} {now - cpu[name]:.2f}"
                        for name, now in cpu_seconds(ctx).items()))
    log = ctx.node.new_log()
    ctx.window_reports = verb_reports(log, REPORT_VERB)
    ctx.window_single_lines = len(verb_reports(log, SINGLE_VERB))
    reports = reports_of(log)
    ctx.worked_reports = worked(reports)
    for rep in ctx.worked_reports:
        require_device_verb(rep, ctx.rehearse)
    ctx.note("volumes each call's batch mended: "
             + " ".join(str(rep.get("batch_volumes")) for rep in ctx.window_reports)
             + f"; single-volume lines: {ctx.window_single_lines}")
    return {
        "attempted": len(ctx.op_log),
        "failed": sum(1 for _, ok, _ in ctx.op_log if not ok),
        "metrics": {"ec_gbps": done_bytes / 1e9 / window_s},
        "window_s": window_s,
        "gib": done_bytes / 2**30,
        "requests": len(ctx.op_log),
        "reports": reports,
        "traced_work": {"rebuild_hbm_bytes": traced_hbm},
    }


def control(ctx, name: str) -> None:
    """`rebuild_loop`'s three, over every volume. `cauchy` decodes the
    survivors from where they lie; the other two touch only files of
    the node's directory."""
    if name != "cauchy":
        return rebuild_loop.control(ctx, name)
    for vid in ctx.vids:
        reference_rebuild.write_decoded(gathered_base(ctx, vid), lost_shards(ctx),
                                        parity="cauchy")


def gathers_as_placed(ctx, rep: dict, shard_bytes: int) -> bool:
    """A report line whose verb mended its volumes each from ten
    survivors of `shard_bytes`, six of them over the wire, with the lost
    shards as targets."""
    n = mended(rep)
    remote = sum(len(ids) for ids in holders(ctx).values())
    return (rep.get("survivors") == reference.DATA
            and rep.get("targets") == len(lost_shards(ctx))
            and rep.get("survivor_bytes") == n * reference.DATA * shard_bytes
            and rep.get("survivor_bytes_remote") == n * remote * shard_bytes)


def check(ctx) -> dict:
    """`batch_rebuild_loop.check`'s numbers over each volume's files
    where they lie (a survivor in the node's directory that the
    placement puts elsewhere counts as rewritten: it was copied there),
    but `ops_not_batched`, and `ops_wrong_gather`: report lines of
    `worked` that do not say ten survivors, the lost shards as targets,
    and ten shard files of survivor bytes and six of remote ones a
    volume, plus one if those lines did not mend every volume of every
    completed operation once, plus one if the node's remote-read
    counter did not grow over the window by exactly the peers'
    survivors' bytes for each volume of each completed operation. Every
    number's limit is 0."""
    try:
        out = batch_rebuild_loop.check(Gathered(ctx))
        del out["ops_not_batched"]  # which path mended is batch_rebuild_volumes_per_op's
        shard_bytes = rack_rebuild_loop.shard_bytes_of(ctx, ctx.vids[0])
        require(set(batch_rebuild_loop.shard_bytes(ctx).values()) == {shard_bytes},
                "the volumes' shard files differ in size")
        done = sum(1 for _, ok, _ in ctx.op_log if ok)
        out["ops_wrong_gather"] = sum(
            1 for rep in ctx.worked_reports if not gathers_as_placed(ctx, rep, shard_bytes))
        if sum(mended(rep) for rep in ctx.worked_reports) != len(ctx.vids) * done:
            ctx.note("the window's report lines mended "
                     f"{sum(mended(rep) for rep in ctx.worked_reports)} volumes, "
                     f"not {len(ctx.vids)} for each of {done} operations")
            out["ops_wrong_gather"] += 1
        remote = sum(len(ids) for ids in holders(ctx).values())
        want = len(ctx.vids) * remote * shard_bytes * done
        if ctx.remote_bytes_gathered != want:
            ctx.note(f"the node fetched {ctx.remote_bytes_gathered} survivor bytes from "
                     f"other servers in the window, not {want}")
            out["ops_wrong_gather"] += 1
        out["survivors_rewritten"] += sum(
            1 for vid in ctx.vids for sid, directory in shard_dirs(ctx).items()
            if directory != ctx.node.data
            and os.path.exists(ctx.base(vid) + reference.shard_ext(sid)))
        return out
    finally:
        ctx.peers.stop()
