#!/usr/bin/env python3
"""chip_smoke.py — the EC main path on the attached TPU, in one command.

Starts a real node through the CLI entry point (`python -m seaweedfs_tpu
server … -ec.codec tpu`) as the ONE process that owns the chip, loads
seeded data over HTTP at a size a deployment would call real (one
≈1 GiB volume, four ≈256 MiB volumes), drives ec.encode / degraded GETs
/ ec.rebuild / scrub parity verify / ec.batch through the shell verbs
and HTTP routes an operator uses, and compares every shard file, `.ecc`
CRC and needle body with the numpy codec, byte for byte. This process
never imports JAX while the node lives: it needs numpy for the
reference and nothing else.

It cannot pass on the CPU or on a slower arm: right after start-up it
reads the node's own device report and fails unless that says platform
`tpu` and arm `swar`, and every verb must show, in the node's own log
line, the device driver and the SWAR kernel with `device_s > 0`.

    python chip_smoke.py [--seed N]      one chip, every phase
    python chip_smoke.py --chips 4       the (vol x stripe) mesh batch
                                         path and its comparison only

One JSON object per phase says what ran (seconds are smoke timings, not
benchmark numbers); the last line of stdout is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
Any failure names its phase on stderr and exits non-zero with no such
line.
"""

from __future__ import annotations

import argparse
import filecmp
import glob
import hashlib
import http.client
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

# the package is imported only after build_native_shims() has removed
# the shims it would otherwise load as it is imported

MIB = 1 << 20
TOTAL_SHARDS = 14
# 1 KiB … 4 MiB, off the power-of-two grid so needles straddle 1 MiB
# stripe blocks and 256 KiB decode tiles at odd offsets
BLOB_SIZES = (1024, 3 * 1024 + 17, 16 * 1024, 60 * 1024 + 5, 256 * 1024,
              MIB + 1, 4 * MIB)
SHELL_WRAPPER = (
    "import atexit, runpy, sys; "
    "atexit.register(lambda: print('JAX_LOADED=%s' % ('jax' in sys.modules), "
    "file=sys.stderr)); "
    "runpy.run_module('seaweedfs_tpu', run_name='__main__')"
)


class SmokeFailure(Exception):
    def __init__(self, phase: str, why: str):
        super().__init__(f"{phase}: {why}")
        self.phase = phase


def check(cond, phase: str, why: str) -> None:
    if not cond:
        raise SmokeFailure(phase, why)


def emit(phase: str, t0: float, **fields) -> None:
    """One JSON line per phase; also the point where this process
    proves it stayed off JAX while the node holds the chip."""
    check("jax" not in sys.modules, phase, "the smoke's parent imported jax")
    print(
        json.dumps(
            {"phase": phase, "ok": True,
             "smoke_seconds": round(time.time() - t0, 2), **fields}
        ),
        flush=True,
    )


def free_ports(n: int) -> list[int]:
    """n ports whose +10000 gRPC siblings are free too."""
    found: list[int] = []
    for port in range(23000, 29000, 7):
        try:
            for p in (port, port + 10000):
                with socket.socket() as s:
                    s.bind(("127.0.0.1", p))
        except OSError:
            continue
        found.append(port)
        if len(found) == n:
            return found
    raise SmokeFailure("start", "no free ports")


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def http_json(url: str, timeout: float = 30.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def http_get(url: str, timeout: float = 60.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


def ec_ext(shard_id: int) -> str:
    return f".ec{shard_id:02d}"


def digest(data: bytes) -> bytes:
    return hashlib.blake2b(data, digest_size=16).digest()


def blob(seed: int, vol: int, i: int, size: int) -> bytes:
    return np.random.default_rng((seed, vol, i)).bytes(size)


# --- native shims -------------------------------------------------------------


def build_native_shims() -> None:
    """Rebuild the C shims from their sources on THIS machine: the
    working tree may carry .so files git would not commit, and
    native/_build.py reuses any artifact newer than its source. A
    missing compiler is a finding, not a reason to serve through the
    Python arms in silence."""
    t0 = time.time()
    native_dir = os.path.join(HERE, "seaweedfs_tpu", "native")
    stale = glob.glob(os.path.join(native_dir, "*.so"))
    for so in stale:
        os.remove(so)
    check("seaweedfs_tpu" not in sys.modules, "native",
          "the package was imported before its shims were removed")
    from seaweedfs_tpu import native
    from seaweedfs_tpu.ec.codec import host_backend

    loaded = {
        "crc32c": native._lib is not None,
        "needle_ext": native.needle_ext is not None,
        "serve_ext": native.serve_ext is not None,
        "gf256": host_backend() == "native",
    }
    for name in ("crc32c", "needle_ext", "serve_ext"):
        check(loaded[name], "native", f"{name} did not build from its .c source")
    emit("native", t0, removed=len(stale), loaded=loaded)


# --- the node -----------------------------------------------------------------


class Node:
    """The all-in-one server child: the one process that touches JAX."""

    def __init__(self, workdir: str):
        self.workdir = workdir
        self.data = os.path.join(workdir, "data")
        os.makedirs(self.data)
        self.log_path = os.path.join(workdir, "node.log")
        self.proc: subprocess.Popen | None = None
        self._log_pos = 0

    def start(self) -> None:
        self.mport, self.vport, self.fport = free_ports(3)
        self.master = f"127.0.0.1:{self.mport}"
        self.volume = f"127.0.0.1:{self.vport}"
        cmd = [
            sys.executable, "-m", "seaweedfs_tpu", "server",
            "-dir", self.data,
            "-master.port", str(self.mport),
            "-volume.port", str(self.vport),
            "-volume.max", "32",
            "-filer", "-filer.port", str(self.fport),
            "-ec.codec", "tpu",
            # the smoke drives repair and scrub itself, at full speed
            "-repairInterval", "0",
            "-scrubInterval", "86400", "-scrubRate", "0",
        ]
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                cmd, cwd=HERE, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True,
            )

    def wait_up(self, deadline_s: float = 180.0) -> dict:
        """The node's /status once master and volume answer."""
        deadline = time.time() + deadline_s
        while time.time() < deadline:
            check(self.proc.poll() is None, "start",
                  f"node exited rc={self.proc.returncode}")
            try:
                http_get(f"http://{self.master}/stats/health", timeout=2)
                status = http_json(f"http://{self.volume}/status", timeout=2)
                http_json(f"http://{self.master}/dir/status", timeout=2)
                return status
            except (OSError, ValueError):
                time.sleep(0.25)
        raise SmokeFailure("start", "node did not come up")

    def status(self) -> dict:
        return http_json(f"http://{self.volume}/status")

    def new_log(self) -> str:
        """Log text the node wrote since the last call."""
        with open(self.log_path, "r", errors="replace") as f:
            f.seek(self._log_pos)
            text = f.read()
            self._log_pos = f.tell()
        return text

    def verb_reports(self, text: str, verb: str) -> list[dict]:
        """The node's own `ec.<verb> vid=… report={…}` lines."""
        return [
            json.loads(m.group(1))
            for m in re.finditer(
                rf"\] ec\.{verb} vid=.*? report=(\{{.*\}})\s*$", text, re.M
            )
        ]

    def shell(self, phase: str, script: str, timeout: float = 900.0) -> str:
        """`python -m seaweedfs_tpu shell -c …` as an operator runs it —
        an admin-side process, which must leave JAX unloaded."""
        proc = subprocess.run(
            [sys.executable, "-c", SHELL_WRAPPER, "shell",
             "-master", self.master, "-c", script],
            cwd=HERE, capture_output=True, text=True, timeout=timeout,
        )
        check(proc.returncode == 0, phase,
              f"shell rc={proc.returncode}: {proc.stderr[-800:]}")
        check("error:" not in proc.stdout, phase,
              f"`{script}` failed: {proc.stdout[-800:]}")
        check("JAX_LOADED=False" in proc.stderr, phase,
              f"the shell process loaded jax running `{script}`")
        return proc.stdout

    def stop(self) -> int | None:
        if self.proc is None:
            return None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                pass
        try:  # whatever is left of its process group goes too
            os.killpg(self.proc.pid, signal.SIGKILL)
        except OSError:
            pass
        return self.proc.wait(timeout=30)


def require_device_arm(report: dict, phase: str, chips: int) -> dict:
    """The node's device report must name the chip and the SWAR arm."""
    check(report.get("codec") == "tpu", phase, f"codec is {report}")
    check(report.get("platform") == "tpu", phase,
          f"the node's codec runs on platform {report.get('platform')!r}, "
          f"not on a TPU: {report}")
    check(report.get("arm") == "swar", phase, f"kernel arm is {report}")
    check(report.get("device_count") == chips, phase,
          f"node sees {report.get('device_count')} device(s), want {chips}")
    return report


def require_device_verb(rep: dict, phase: str) -> None:
    """One single-volume verb's log report: the stream driver's device
    arm, every tile through the fused SWAR+CRC program, device time
    booked."""
    check(rep.get("driver") == "stream-device", phase, f"driver: {rep}")
    arms = rep.get("arms") or {}
    check(arms.get("swar+crc", 0) > 0, phase, f"no swar+crc dispatch: {rep}")
    check(arms.get("bit-matmul", 0) == 0, phase, f"bit-matmul tiles ran: {rep}")
    check(rep.get("device_s", 0) > 0, phase, f"device_s is not > 0: {rep}")


# --- data ---------------------------------------------------------------------


class Loader:
    def __init__(self, node: Node, seed: int):
        self.node, self.seed = node, seed
        self.manifest: dict[int, list[tuple[str, int, bytes]]] = {}

    def fill(self, collection: str, target_bytes: int) -> int:
        """Grow exactly one volume for `collection` and fill it to
        ≈target_bytes over /dir/assign + POST. Returns its volume id."""
        grown = http_json(
            f"http://{self.node.master}/vol/grow?collection={collection}&count=1"
        )
        check(grown.get("count") == 1, "load", f"/vol/grow said {grown}")
        vol_idx = len(self.manifest)  # every volume its own seeded stream
        plan, total, i = [], 0, 0
        while total < target_bytes:
            size = BLOB_SIZES[i % len(BLOB_SIZES)] + (i * 131) % 509
            plan.append((i, size))
            total += size
            i += 1

        def put(item):
            i, size = item
            data = blob(self.seed, vol_idx, i, size)
            a = http_json(
                f"http://{self.node.master}/dir/assign?collection={collection}"
            )
            conn = http.client.HTTPConnection(a["url"], timeout=120)
            try:
                conn.request(
                    "POST", "/" + a["fid"], body=data,
                    headers={"Content-Type": "application/octet-stream"},
                )
                resp = conn.getresponse()
                body = resp.read()
            finally:
                conn.close()
            check(resp.status in (200, 201), "load",
                  f"POST {a['fid']} -> {resp.status} {body[:200]!r}")
            return a["fid"], size, digest(data)

        with ThreadPoolExecutor(max_workers=6) as pool:
            records = list(pool.map(put, plan))
        vids = {int(fid.split(",")[0]) for fid, _, _ in records}
        check(len(vids) == 1, "load", f"{collection} spread over volumes {vids}")
        vid = vids.pop()
        self.manifest[vid] = records
        return vid

    def sample(self, vid: int, n: int) -> list[tuple[str, int, bytes]]:
        """n needles of a volume, every size class represented."""
        records = self.manifest[vid]
        step = max(1, len(records) // n)
        # consecutive records cycle through BLOB_SIZES; a stride
        # coprime with the cycle length keeps every class in the sample
        while step % len(BLOB_SIZES) == 0:
            step += 1
        return records[::step][:n]

    def read_back(self, phase: str, vid: int, n: int) -> dict:
        got_bytes = 0
        for fid, size, want in self.sample(vid, n):
            body = http_get(f"http://{self.node.volume}/{fid}")
            check(len(body) == size and digest(body) == want, phase,
                  f"GET {fid}: body differs from what was written "
                  f"({len(body)} of {size} bytes)")
            got_bytes += size
        return {"needles": min(n, len(self.manifest[vid])), "bytes": got_bytes}


def seal_copy(node: Node, ref_dir: str, collection: str, vid: int) -> tuple[str, str]:
    """(node base, reference base): hard-link the volume's .dat beside
    the reference before the verbs delete the original."""
    from seaweedfs_tpu.storage.volume import volume_base_name

    base = volume_base_name(node.data, collection, vid)
    ref = os.path.join(ref_dir, f"{collection}_{vid}")
    os.link(base + ".dat", ref + ".dat")
    return base, ref


def compare_with_numpy(phase: str, base: str, ref: str) -> dict:
    """Encode the same .dat with the numpy `cpu` backend's classic loop
    (host only, in this process) and compare all 14 shard files and the
    node's `.ecc` CRCs with it."""
    from seaweedfs_tpu.ec import ec_files
    from seaweedfs_tpu.ec.codec import new_encoder

    t0 = time.time()
    st: dict = {}
    ec_files.write_ec_files(
        ref, rs=new_encoder(backend="cpu"), stats=st, want_crcs=True
    )
    check(st["driver"] == "classic", phase, "the reference left the classic loop")
    for i in range(TOTAL_SHARDS):
        ext = ec_ext(i)
        present = base + ext if os.path.exists(base + ext) else base + ext + ".bad"
        check(filecmp.cmp(present, ref + ext, shallow=False), phase,
              f"{os.path.basename(present)} differs from the numpy encode")
    with open(base + ".ecc") as f:
        ecc = json.load(f)["shards"]
    for i, crc in enumerate(st["shard_crcs"]):
        check(ecc[str(i)]["crc"] == crc, phase,
              f"{os.path.basename(base)}.ecc shard {i} crc differs from numpy's")
    return {
        "shard_bytes": os.path.getsize(ref + ".ec00"),
        "numpy_reference_seconds": round(time.time() - t0, 2),
    }


# --- phases -------------------------------------------------------------------


def phase_start(node: Node, chips: int, cache_dir: str) -> dict:
    t0 = time.time()
    entries = cache_entries(cache_dir)
    node.start()
    status = node.wait_up()
    report = require_device_arm(status.get("EcCodec") or {}, "device", chips)
    log = node.new_log()
    check("ec codec tpu: platform=tpu" in log, "device",
          "the node's log carries no start-up device report")
    emit("start", t0, node_report=report, compile_cache_dir=cache_dir,
         compile_cache_entries_before=entries, compile_cache_warm=entries > 0)
    return report


def phase_load(loader: Loader, volumes: list[tuple[str, int]]) -> list[int]:
    t0 = time.time()
    vids = [loader.fill(collection, size) for collection, size in volumes]
    read = [loader.read_back("load", vid, 24) for vid in vids]
    emit(
        "load", t0, volumes=vids,
        needles=[len(loader.manifest[v]) for v in vids],
        bytes=[sum(s for _, s, _ in loader.manifest[v]) for v in vids],
        read_back_before_seal=read,
    )
    return vids


def phase_encode(node: Node, base: str, ref: str, vid: int) -> None:
    t0 = time.time()
    out = node.shell("ec.encode", f"ec.encode -volumeId {vid}")
    check(f"ec encoded volume {vid}" in out, "ec.encode", out[-400:])
    reports = node.verb_reports(node.new_log(), "generate")
    check(len(reports) == 1, "ec.encode", f"{len(reports)} generate lines")
    require_device_verb(reports[0], "ec.encode")
    verb_s = round(time.time() - t0, 2)
    cmp = compare_with_numpy("ec.encode", base, ref)
    emit("ec.encode", t0, volume=vid, server_report=reports[0],
         verb_seconds=verb_s, **cmp)


def apply_calls(node: Node) -> dict:
    return node.status()["EcCodec"]["apply_calls"]


def degraded_reads(node: Node) -> float:
    text = http_get(f"http://{node.volume}/metrics").decode()
    m = re.search(r"^weed_ec_degraded_read_total\S*\s+([0-9.e+]+)", text, re.M)
    return float(m.group(1)) if m else 0.0


def phase_gets(node: Node, loader: Loader, vid: int, lost: tuple[int, int]) -> None:
    t0 = time.time()
    healthy = loader.read_back("get.healthy", vid, 56)
    check(degraded_reads(node) == 0, "get.healthy", "a healthy GET decoded")
    for sid in lost:
        r = http_json(
            f"http://{node.volume}/ec/quarantine?volumeId={vid}&shard={sid}"
        )
        check(r.get("quarantined") is True, "get.degraded", f"quarantine: {r}")
    calls0 = apply_calls(node)
    degraded = loader.read_back("get.degraded", vid, 56)
    calls1 = apply_calls(node)
    decodes = degraded_reads(node)
    check(decodes > 0, "get.degraded", "no GET took the reconstruct path")
    swar = calls1["swar"] - calls0["swar"]
    check(swar > 0, "get.degraded",
          f"no decode ran the SWAR kernel: {calls0} -> {calls1}")
    emit("gets", t0, volume=vid, removed_shards=list(lost), healthy=healthy,
         degraded=degraded, server_degraded_intervals=decodes,
         server_apply_calls={"swar": swar,
                             "bit-matmul": calls1["bit-matmul"] - calls0["bit-matmul"]})


def phase_rebuild(node: Node, base: str, ref: str, vid: int,
                  lost: tuple[int, int]) -> None:
    t0 = time.time()
    # the master learns of the unmounted shards by heartbeat
    deadline = time.time() + 60
    while True:
        dry = node.shell("ec.rebuild", f"ec.rebuild -volumeId {vid}")
        if f"missing shards {sorted(lost)}" in dry:
            break
        check(time.time() < deadline, "ec.rebuild",
              f"master never saw {sorted(lost)} missing: {dry[-300:]}")
        time.sleep(0.5)
    out = node.shell("ec.rebuild", f"ec.rebuild -volumeId {vid} -force")
    check(f"rebuilt shards {sorted(lost)} for volume {vid}" in out,
          "ec.rebuild", out[-400:])
    reports = node.verb_reports(node.new_log(), "rebuild")
    check(len(reports) == 1, "ec.rebuild", f"{len(reports)} rebuild lines")
    require_device_verb(reports[0], "ec.rebuild")
    for sid in lost:
        ext = ec_ext(sid)
        for other in (base + ext + ".bad", ref + ext):
            check(filecmp.cmp(base + ext, other, shallow=False), "ec.rebuild",
                  f"rebuilt {ext} differs from {os.path.basename(other)}")
        os.remove(base + ext + ".bad")
    emit("ec.rebuild", t0, volume=vid, rebuilt=sorted(lost),
         server_report=reports[0])


def scrub_row(node: Node, vid: int) -> dict:
    rows = http_json(f"http://{node.volume}/scrub/status")["Volumes"]
    for row in rows:
        if row["volume_id"] == vid and row["is_ec"]:
            return row
    return {"sweeps": 0, "corruptions_found": 0, "last_error": ""}


def wait_sweep_idle(node: Node, phase: str) -> None:
    deadline = time.time() + 300
    while http_json(f"http://{node.volume}/scrub/status")["SweepRunning"]:
        check(time.time() < deadline, phase, "sweep never finished")
        time.sleep(0.25)


def phase_scrub(node: Node, base: str, vid: int, seed: int) -> None:
    """Server-side parity re-verify through the EC volume's own codec
    (rs.encode -> tpu_apply_matrix's SWAR host-interop arm): clean,
    then one flipped byte localised to its shard. A fresh `.ecc`
    sidecar would route the sweep to its CRC pass instead, so a shard
    is touched first — the engine then falls back to parity, loudly."""
    t0 = time.time()
    ecc_mtime = os.stat(base + ".ecc").st_mtime_ns
    os.utime(base + ".ec00", ns=(ecc_mtime + 2_000_000_000,) * 2)
    calls0, sweeps0 = apply_calls(node), scrub_row(node, vid)["sweeps"]
    node.shell("scrub", f"scrub.trigger -volumeId {vid}")
    deadline = time.time() + 300
    while (row := scrub_row(node, vid))["sweeps"] == sweeps0:
        check(time.time() < deadline, "scrub",
              f"parity re-verify never completed: {row}")
        time.sleep(0.25)
    check(row["corruptions_found"] == 0 and not row["last_error"], "scrub",
          f"clean volume scrubbed dirty: {row}")
    wait_sweep_idle(node, "scrub")
    log = node.new_log()
    check("falling back to full parity re-verify" in log, "scrub",
          "the sweep did not take the parity path")
    calls1 = apply_calls(node)
    shard_bytes = os.path.getsize(base + ".ec00")
    tiles = -(-shard_bytes // (4 * MIB))
    swar_clean = calls1["swar"] - calls0["swar"]
    check(swar_clean >= tiles, "scrub",
          f"{swar_clean} SWAR applies for {tiles} parity tiles")
    check(calls1["bit-matmul"] == calls0["bit-matmul"], "scrub",
          "the parity verify took the bit-matmul arm")
    clean_s = round(time.time() - t0, 2)

    culprit = 1 + seed % 3  # a low data shard: few localisation hypotheses
    path = base + ec_ext(culprit)
    offset = (seed * 7919 + 12345) % shard_bytes
    with open(path, "r+b") as f:
        f.seek(offset)
        byte = f.read(1)
        f.seek(offset)
        f.write(bytes([byte[0] ^ 0x5A]))
    node.shell("scrub", f"scrub.trigger -volumeId {vid}")
    deadline = time.time() + 300
    while True:
        quarantined = node.status()["QuarantinedShards"].get(str(vid), [])
        if quarantined:
            break
        check(time.time() < deadline, "scrub",
              f"flipped byte never localised: {scrub_row(node, vid)}")
        time.sleep(0.25)
    check(quarantined == [culprit], "scrub",
          f"shard {culprit} was corrupted, {quarantined} quarantined")
    wait_sweep_idle(node, "scrub")
    log = node.new_log()
    check(f"quarantining corrupt shard {culprit} of vid {vid}" in log, "scrub",
          "no localisation line in the node's log")
    emit("scrub", t0, volume=vid, parity_tiles=tiles,
         clean_pass={"mismatches": 0, "server_swar_applies": swar_clean,
                     "smoke_seconds": clean_s},
         flipped={"shard": culprit, "offset": offset,
                  "localised_to": quarantined})


def phase_batch(node: Node, loader: Loader, pairs: list[tuple[int, str, str]],
                mesh: tuple[int, int]) -> None:
    """ec.batch over `pairs` of (vid, node base, reference base): one
    shard_map program per tile round over a (vol x stripe) mesh."""
    t0 = time.time()
    vids = [vid for vid, _, _ in pairs]
    out = node.shell("ec.batch", "ec.batch -volumeIds " + ",".join(map(str, vids)))
    for vid in vids:
        check(f"volume {vid} now serves from ec shards" in out, "ec.batch",
              out[-400:])
    reports = node.verb_reports(node.new_log(), "batch_generate")
    check(len(reports) == 1, "ec.batch", f"{len(reports)} batch_generate lines")
    rep = reports[0]
    m = rep.get("mesh") or {}
    check((m.get("vol"), m.get("stripe")) == mesh, "ec.batch",
          f"mesh is {m}, want vol x stripe = {mesh}")
    check(m.get("platform") == "tpu" and m.get("arm") == "swar", "ec.batch",
          f"mesh arm: {m}")
    check(m.get("devices_per_round") == mesh[0] * mesh[1], "ec.batch",
          f"only {m.get('devices_per_round')} device(s) held part of every "
          f"tile round: {m}")
    check(not rep.get("fallback") and rep.get("device_s", 0) > 0, "ec.batch",
          f"device arm did not run: {rep}")
    verb_s = round(time.time() - t0, 2)
    cmps = [compare_with_numpy("ec.batch", base, ref) for _, base, ref in pairs]
    reads = [loader.read_back("ec.batch", vid, 8) for vid in vids]
    emit("ec.batch", t0, volumes=vids, server_report=rep, verb_seconds=verb_s,
         shard_bytes=[c["shard_bytes"] for c in cmps],
         numpy_reference_seconds=sum(c["numpy_reference_seconds"] for c in cmps),
         ec_reads=reads)


def phase_ec_verify(node: Node, vid: int) -> None:
    """`ec.verify` runs its codec in the SHELL's process, while the
    node holds the chip: it must verify on a host codec and never load
    JAX (node.shell checks that)."""
    t0 = time.time()
    out = node.shell("ec.verify", f"ec.verify -volumeId {vid} -json")
    doc = json.loads(out[out.index("{"): out.rindex("}") + 1])
    check(doc["corrupt"] is False and doc["bytesPerShard"] > 0, "ec.verify",
          f"{doc}")
    emit("ec.verify", t0, volume=vid, bytes_per_shard=doc["bytesPerShard"],
         shell_process_loaded_jax=False)


# --- main ---------------------------------------------------------------------


def run(args, node: Node, ref_dir: str, cache_dir: str) -> dict:
    report = phase_start(node, args.chips, cache_dir)
    loader = Loader(node, args.seed)
    batch_bytes = args.batch_mib * MIB

    if args.chips == 4:
        # the mesh path and its comparison, and no other phase:
        # gcd(6, 4) = 2 -> vol=2 x stripe=2 (both axes and the CRC
        # all_gather real), then gcd(4, 4) = 4 -> vol=4 x stripe=1
        for n, mesh in ((6, (2, 2)), (4, (4, 1))):
            names = [(f"m{n}v{i}", batch_bytes) for i in range(n)]
            vids = phase_load(loader, names)
            pairs = [
                (vid, *seal_copy(node, ref_dir, name, vid))
                for vid, (name, _) in zip(vids, names)
            ]
            phase_batch(node, loader, pairs, mesh)
            for _, _, ref in pairs:
                for path in glob.glob(ref + ".*"):
                    os.remove(path)
        return report

    names = [("big", args.big_mib * MIB)] + [
        (f"b{i}", batch_bytes) for i in range(4)
    ]
    vids = phase_load(loader, names)
    big = vids[0]
    base, ref = seal_copy(node, ref_dir, "big", big)
    phase_encode(node, base, ref, big)
    lost = (args.seed % 10, 10 + args.seed % 4)  # one data, one parity shard
    phase_gets(node, loader, big, lost)
    phase_rebuild(node, base, ref, big, lost)
    phase_ec_verify(node, big)
    phase_scrub(node, base, big, args.seed)
    for path in glob.glob(ref + ".*"):
        os.remove(path)
    pairs = [
        (vid, *seal_copy(node, ref_dir, name, vid))
        for vid, (name, _) in zip(vids[1:], names[1:])
    ]
    phase_batch(node, loader, pairs, (1, 1))
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--big-mib", type=int, default=1024,
                    help="size of the single-volume ec.encode volume")
    ap.add_argument("--batch-mib", type=int, default=256,
                    help="size of each ec.batch volume")
    args = ap.parse_args()

    t0 = time.time()
    build_native_shims()
    from seaweedfs_tpu.ec.compile_cache import DEFAULT_DIR

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    ref_dir = os.path.join(workdir, "ref")
    os.makedirs(ref_dir)
    node = Node(workdir)
    try:
        report = run(args, node, ref_dir, cache_dir)
    except SmokeFailure:
        if os.path.exists(node.log_path):
            with open(node.log_path, "r", errors="replace") as f:
                sys.stderr.write("".join(f.readlines()[-60:]))
        raise
    finally:
        t_stop = time.time()
        rc = node.stop()
        out_dir = os.path.join(HERE, "chiprun_out")
        os.makedirs(out_dir, exist_ok=True)
        if os.path.exists(node.log_path):
            shutil.copy(node.log_path,
                        os.path.join(out_dir, f"chip_smoke_node_{os.getpid()}.log"))
        shutil.rmtree(workdir, ignore_errors=True)
    check(rc == 0, "stop", f"node exited rc={rc} on SIGTERM")
    emit("stop", t_stop, node_rc=rc,
         compile_cache_entries_after=cache_entries(cache_dir),
         total_smoke_seconds=round(time.time() - t0, 2))
    print(json.dumps({
        "ok": True,
        "device": {"platform": report["platform"],
                   "kind": report["device_kind"],
                   "count": report["device_count"]},
    }))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke FAILED in phase {e}", file=sys.stderr)
        sys.exit(1)
