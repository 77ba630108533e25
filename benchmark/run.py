#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A run is one new process: build the native shims, start ONE node child
(`-ec.codec tpu`) that alone owns the chip, fail unless the node's own
report says platform `tpu`, arm `swar` and the cell's device count, load
the configuration's data from the seed, let the cell's traffic generator
set up and warm the shapes it uses, measure for `--seconds`, compare what
the window produced with the plain reference, stop the node, and print
the result as the last line of standard output. `setup_s` is everything
from process start to the opening of the window.

The cell, its configuration and its traffic are found by name:
`BENCHMARK.json` -> `configs/<config>.json`, `traffic/<traffic>.json`
(whose `generator` names a module of `traffic/`), and with `--trace 1`
one `metrics/<name>.json` per per-layer metric. See README.md.

Not in the manifest's command: `--rehearse` (CPU sandbox, shrunk sizes,
every metric renamed `rehearsal.*`), `--control` and `--fault` (a run
that has to come out not correct), `--keep DIR`, `--selftest`.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import hashlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

from harness import readers, trace_reduce  # noqa: E402
from harness.loader import Loader  # noqa: E402
from harness.node import (  # noqa: E402
    BenchFailure, Node, build_native_shims, require, require_device_arm,
)

MIB = 1 << 20
# The node's directory (volumes, shard files, log, trace) lives in memory.
# The sandbox's only disk is a 9p share of its host's: the fsync of one
# operation's 1.44 GiB of shard files takes 0.02 to 0.9 s there with nothing
# else running, which was all of the spread of `ec_gbps` (PERF.md, section
# 6), and a run wrote 12 GiB to a host that keeps every block. One directory
# per checkout, so that two checkouts share nothing; emptied before a run
# and removed after it.
MEMORY_FS = "/dev/shm"


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Tracer:
    """Raises and lowers the flag file that the node's launcher watches.
    The time spent waiting for the profiler to start and stop is kept
    apart (`paused_s`): it is not the program's."""

    def __init__(self, workdir: str, enabled: bool):
        self.workdir, self.enabled = workdir, enabled
        self.running = False
        self.paused_s = 0.0
        self.slice_s = None

    def _wait_for(self, name: str, deadline_s: float) -> float:
        path = os.path.join(self.workdir, name)
        deadline = time.time() + deadline_s
        while not os.path.exists(path):
            require(time.time() < deadline, f"the node never wrote {name}")
            time.sleep(0.005)
        with open(path) as f:
            return float(f.read())

    def start(self) -> None:
        if not self.enabled or self.running or self.slice_s is not None:
            return
        t = time.perf_counter()
        with open(os.path.join(self.workdir, "trace.on"), "w"):
            pass
        self._t_started = self._wait_for("trace.started", 60)
        self.running = True
        self.paused_s += time.perf_counter() - t

    def stop(self) -> None:
        if not self.running:
            return
        t = time.perf_counter()
        os.remove(os.path.join(self.workdir, "trace.on"))
        self.slice_s = self._wait_for("trace.done", 300) - self._t_started
        self.running = False
        self.paused_s += time.perf_counter() - t


class Ctx:
    """What a traffic generator gets: the node, the loaded volumes, its
    own parameters, and the program's gRPC surface."""

    def __init__(self, args, node, loader, config, traffic, workdir):
        import grpc

        from seaweedfs_tpu.pb import rpc, volume_pb2

        self.seed, self.rehearse = args.seed, args.rehearse
        self.node, self.loader = node, loader
        self.config, self.traffic, self.workdir = config, traffic, workdir
        self.vids: list[int] = []
        self.collection: dict[int, str] = {}
        self.pb, self.rpc_error = volume_pb2, grpc.RpcError
        self.channel = rpc.dial(rpc.grpc_address(node.volume))
        self.volume_stub = rpc.volume_stub(self.channel)
        self.ref_dir = os.path.join(workdir, "ref")
        os.makedirs(self.ref_dir)

    def base(self, vid: int) -> str:
        """The node's base path of a volume's files."""
        return os.path.join(self.node.data, f"{self.collection[vid]}_{vid}")

    def ref_dat(self, vid: int) -> str:
        """Where the sealed `.dat` is kept (a hard link) for the reference."""
        return os.path.join(self.ref_dir, f"{self.collection[vid]}_{vid}.dat")

    def note(self, text: str) -> None:
        print(text, file=sys.stderr)


def load_volumes(ctx: Ctx) -> None:
    for vol in ctx.config["volumes"]:
        mib = vol["rehearse_mib"] if ctx.rehearse else vol["mib"]
        vid = ctx.loader.fill(vol["collection"], mib * MIB)
        ctx.vids.append(vid)
        ctx.collection[vid] = vol["collection"]


def per_layer(manifest: dict, cell: dict, obs: dict) -> dict:
    out = {}
    for entry in manifest["per_layer"]:
        if "workloads" in entry and cell["name"] not in entry["workloads"]:
            continue
        value = readers.read_metric(readers.load_metric(entry["name"]), obs)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def short_op_name(hlo: str) -> str:
    """`%fusion.1 u32[131072,14] fusion kCustom` from an operation's HLO text."""
    m = re.match(r"(%[\w.\-]+) = (\(?[\w]+\[[\d,]*\])[^ ]* ([\w\-]+)\(", hlo)
    if not m:
        return hlo[:80]
    kind = re.search(r"kind=(\w+)|custom_call_target=\"(\w+)\"", hlo)
    tail = " " + (kind.group(1) or kind.group(2)) if kind else ""
    return f"{m.group(1)} {m.group(2)} {m.group(3)}{tail}"


def breakdown(trace: dict, reports: list[dict]) -> dict:
    """The device operations that took most time, and the longest gaps
    between them. The tree writes no host annotations into the trace, so
    a gap is named by the stream driver's stage with most thread-seconds
    in the window (node log), or `host` where no verb reported."""
    pools = {k: sum(r.get(k, 0) for r in reports)
             for k in ("read_s", "stage_s", "device_s", "writeback_s", "write_s")}
    host = "host:" + max(pools, key=pools.get) if reports else "host"
    ops = sorted(trace["op_seconds"].items(), key=lambda kv: -kv[1])[:10]
    return {
        "device_ops": [[short_op_name(name), s] for name, s in ops],
        "idle_gaps": [[f"{host}@{start:.3f}s", s] for start, s in trace["gaps"]],
    }


def run(args) -> int:
    manifest = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in manifest["workloads"] if w["name"] == args.workload), None)
    require(cell is not None, f"no workload {args.workload!r} in BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, entry["file"])
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    generator = importlib.import_module("traffic." + traffic["generator"])
    if args.fault:
        importlib.import_module("tests.faults").plant(args.fault, generator)

    build_native_shims()
    require(os.path.isdir(MEMORY_FS) and os.access(MEMORY_FS, os.W_OK),
            f"no memory file system at {MEMORY_FS} for the node's directory")
    checkout = hashlib.sha1(ROOT.encode()).hexdigest()[:12]
    workdir = os.path.join(MEMORY_FS, f"tpu-weed-bench-{checkout}")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    node = Node(workdir, trace=bool(args.trace))
    try:
        return measure(args, manifest, cell, config, traffic, generator, node)
    except BenchFailure:
        sys.stderr.write(node.log_tail())
        raise
    finally:
        node.stop()
        if args.keep and os.path.exists(node.log_path):
            os.makedirs(args.keep, exist_ok=True)
            shutil.copy(node.log_path, os.path.join(
                args.keep, f"{cell['name']}-{args.seed}-t{args.trace}.node.log"))
        shutil.rmtree(workdir, ignore_errors=True)


def measure(args, manifest, cell, config, traffic, generator, node) -> int:
    workdir = node.workdir
    tracer = Tracer(workdir, enabled=bool(args.trace))
    report = require_device_arm(node.up().get("EcCodec") or {},
                                cell["chips"], args.rehearse)
    ctx = Ctx(args, node, Loader(node, args.seed, config["needle_sizes"]),
              config, traffic, workdir)
    load_volumes(ctx)
    generator.setup(ctx)
    require("jax" not in sys.modules, "the benchmark's parent imported jax")
    setup_s = time.time() - T_START
    res = generator.window(ctx, args.seconds, tracer)
    if args.control:
        generator.control(ctx, args.control)
    compared = generator.check(ctx)
    ctx.channel.close()
    rc = node.stop()
    require(rc == 0, f"node exited rc={rc} on SIGTERM")

    memory = load_json(workdir, "device_memory.json") if os.path.exists(
        os.path.join(workdir, "device_memory.json")) else {}
    device = {
        "platform": report["platform"],
        "kind": report["device_kind"],
        "count": report["device_count"],
        "memory_peak_bytes": memory.get("memory_peak_bytes") or 0,
    }
    metrics = {"setup_s": setup_s, **res["metrics"]}
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
    line = {
        "correct": all(v <= 0 for v in compared.values()),
        "attempted": res["attempted"],
        "failed": res["failed"],
    }
    if args.trace:
        xplane = trace_reduce.find_xplane(os.path.join(workdir, "trace"))
        require(xplane is not None and tracer.slice_s, "the traced run left no trace")
        trace = trace_reduce.reduce_trace(xplane, report["platform"], tracer.slice_s)
        require(trace["busy_s"] or args.rehearse,
                "no operation ran on a device plane in the traced slice")
        obs = {
            "reports": res.get("reports", []),
            "window": {"seconds": res["window_s"], "gib": res.get("gib"),
                       "requests": res["requests"]},
            "trace": trace, "traced_work": res.get("traced_work"),
            "device_kind": report["device_kind"], "rehearse": args.rehearse,
        }
        line["metrics"] = per_layer(manifest, cell, obs)
        device["busy_s"], device["window_s"] = trace["busy_s"] or 0.0, trace["window_s"]
        line["device"] = device
        line["breakdown"] = breakdown(trace, obs["reports"])
        if args.keep:
            with open(os.path.join(args.keep, f"{cell['name']}-{args.seed}.trace.json"), "w") as f:
                json.dump(trace, f, indent=1)
            shutil.copy(xplane, os.path.join(args.keep, f"{cell['name']}-{args.seed}.xplane.pb"))
    else:
        line["metrics"] = {k: {"value": v, "unit": units[k]} for k, v in metrics.items()}
        line["device"] = device
    if args.rehearse:  # a CPU number never stands under a device metric's name
        line["metrics"] = {"rehearsal." + k: v for k, v in line["metrics"].items()}
        line["rehearsal"] = True
    line["compared"] = {k: {"value": v, "limit": 0} for k, v in compared.items()}
    for k, v in compared.items():
        print(f"compared {k} = {v} (limit 0)", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU sandbox: shrunk volumes, platform cpu accepted, "
                         "metrics renamed rehearsal.*")
    ap.add_argument("--control", help="put the reference, one guarantee broken, "
                                      "in the program's place before the comparison")
    ap.add_argument("--fault", help="break the timed path (benchmark/tests/faults.py)")
    ap.add_argument("--keep", help="directory to copy the node's log and the trace to")
    ap.add_argument("--selftest", action="store_true")
    args = ap.parse_args(argv)
    if args.selftest:
        from selftest import selftest

        return selftest.main()
    if not args.workload:
        ap.error("--workload is required")
    try:
        return run(args)
    except BenchFailure as e:
        print(f"benchmark FAILED: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
