"""The system under test as the benchmark drives it: ONE all-in-one node
child that alone owns the chip, its HTTP and gRPC surfaces, and the
refusal of any fallback. Copied from chip_smoke.py (PR 21), where each
piece was proven on the chip; later PRs may change chip_smoke.py, not
this.

This process never imports JAX while the node lives.
"""

from __future__ import annotations

import glob
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.request

HARNESS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HARNESS))

class BenchFailure(Exception):
    """The run cannot give a result: no chip, a fallback arm, a node
    that did not start. The process exits non-zero with no result line."""


def require(cond, why: str) -> None:
    if not cond:
        raise BenchFailure(why)


def free_ports(n: int) -> list[int]:
    """n ports whose +10000 gRPC siblings are free too, all of them below
    the kernel's ephemeral range (32768 up), where an outgoing connection
    of the load could take one between this look and the node's bind."""
    found: list[int] = []
    for port in range(12000, 19000, 7):
        try:
            for p in (port, port + 10000):
                with socket.socket() as s:
                    s.bind(("127.0.0.1", p))
        except OSError:
            continue
        found.append(port)
        if len(found) == n:
            return found
    raise BenchFailure("no free ports")


def http_get(url: str, timeout: float = 60.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


def http_json(url: str, timeout: float = 30.0) -> dict:
    return json.loads(http_get(url, timeout))


def build_native_shims() -> None:
    """Rebuild the C shims from their sources on THIS machine: a tree
    may carry .so files git would not commit, and native/_build.py
    reuses any artifact newer than its source. A shim that does not
    build is a failure, not a reason to serve through the Python arms."""
    for so in glob.glob(os.path.join(ROOT, "seaweedfs_tpu", "native", "*.so")):
        os.remove(so)
    require("seaweedfs_tpu" not in sys.modules,
            "the package was imported before its shims were removed")
    from seaweedfs_tpu import native

    for name, ok in (("crc32c", native._lib is not None),
                     ("needle_ext", native.needle_ext is not None),
                     ("serve_ext", native.serve_ext is not None)):
        require(ok, f"native shim {name} did not build from its .c source")


class Node:
    """The all-in-one server child: the one process that touches JAX."""

    def __init__(self, workdir: str, trace: bool):
        self.workdir, self.trace = workdir, trace
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
            sys.executable, os.path.join(HARNESS, "node_main.py"), "server",
            "-dir", self.data,
            "-master.port", str(self.mport),
            "-volume.port", str(self.vport),
            "-volume.max", "32",
            "-filer", "-filer.port", str(self.fport),
            "-ec.codec", "tpu",
            # no background repair or scrub inside a measured window
            "-repairInterval", "0",
            "-scrubInterval", "86400", "-scrubRate", "0",
        ]
        env = dict(os.environ, BENCH_WORK=self.workdir,
                   BENCH_TRACE="1" if self.trace else "0",
                   # where JAX_COMPILATION_CACHE_DIR is set the program
                   # leaves JAX's defaults alone, and those drop programs
                   # that compile in under a second: most of this path's
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                cmd, cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True,
            )

    def wait_up(self, deadline_s: float = 180.0) -> dict:
        """The node's /status once master and volume answer."""
        deadline = time.time() + deadline_s
        while time.time() < deadline:
            require(self.proc.poll() is None,
                    f"node exited rc={self.proc.returncode}: {self.log_tail()}")
            try:
                http_get(f"http://{self.master}/stats/health", timeout=2)
                status = http_json(f"http://{self.volume}/status", timeout=2)
                http_json(f"http://{self.master}/dir/status", timeout=2)
                return status
            except (OSError, ValueError):
                time.sleep(0.1)
        raise BenchFailure("node did not come up")

    def up(self, attempts: int = 3) -> dict:
        """Start the node and return its /status. A port lost between
        `free_ports` and the node's own bind costs another start, not
        the run."""
        while True:
            attempts -= 1
            self.start()
            try:
                return self.wait_up()
            except BenchFailure as e:
                self.stop()
                if not attempts or "bind" not in self.log_tail():
                    raise
                print(f"the node lost a port at start, starting again: {e}", file=sys.stderr)

    def new_log(self) -> str:
        """Log text the node wrote since the last call."""
        with open(self.log_path, "r", errors="replace") as f:
            f.seek(self._log_pos)
            text = f.read()
            self._log_pos = f.tell()
        return text

    def log_tail(self, lines: int = 40) -> str:
        if not os.path.exists(self.log_path):
            return ""
        with open(self.log_path, "r", errors="replace") as f:
            return "".join(f.readlines()[-lines:])

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


def verb_reports(text: str, verb: str) -> list[dict]:
    """The node's own `ec.<verb> vid=… report={…}` lines."""
    return [
        json.loads(m.group(1))
        for m in re.finditer(
            rf"\] ec\.{verb} vid=.*? report=(\{{.*\}})\s*$", text, re.M
        )
    ]


def require_device_arm(report: dict, chips: int, rehearse: bool) -> dict:
    """The node's device report must name the chip, the SWAR arm and the
    cell's device count. A rehearsal accepts the CPU and nothing else."""
    require(report.get("codec") == "tpu", f"codec is {report}")
    if rehearse:
        require(report.get("platform") == "cpu",
                f"--rehearse is for the CPU sandbox, the node reports {report}")
        return report
    require(report.get("platform") == "tpu",
            f"the node's codec runs on platform {report.get('platform')!r}, "
            f"not on a TPU: {report}")
    require(report.get("arm") == "swar", f"kernel arm is {report}")
    require(report.get("device_count") == chips,
            f"node sees {report.get('device_count')} device(s), want {chips}")
    return report


def require_device_verb(rep: dict, rehearse: bool) -> None:
    """One encode verb's log report: the stream driver's device arm (or
    the mesh driver with no fallback), every tile through the SWAR
    kernel, dispatcher time booked."""
    if rehearse:
        return
    if "mesh" in rep:
        m = rep["mesh"]
        require(m.get("platform") == "tpu" and m.get("arm") == "swar",
                f"mesh arm: {rep}")
        require(not rep.get("fallback"), f"the mesh driver fell back: {rep}")
    else:
        require(rep.get("driver") == "stream-device", f"driver: {rep}")
        arms = rep.get("arms") or {}
        require(arms.get("swar+crc", 0) > 0, f"no swar+crc dispatch: {rep}")
        require(arms.get("bit-matmul", 0) == 0, f"bit-matmul tiles ran: {rep}")
    require(rep.get("device_s", 0) > 0, f"device_s is not > 0: {rep}")
