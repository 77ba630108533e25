"""Host-only volume servers beside the node: the other racks of a
deployment whose shards lie on more than one server.

`harness/node.py` starts the ONE all-in-one node that owns the chip. A
peer is `python -m seaweedfs_tpu volume -mserver <the node's master>
-rack <its rack> -ec.codec native` with a `-dir` of its own under the
run's work directory, on the same machine, with `JAX_PLATFORMS=cpu` in
its environment: a second process that opens the chip while the node
holds it exits (PERF.md, PR 21), and a peer has no use for one. It
serves what any volume server serves; the cells use its EC verbs
(`VolumeEcShardsCopy`, `VolumeEcShardsMount`, `VolumeEcShardRead`).

`run.py` stops only the node, so the peers take care of their own end:
each is killed by the kernel when this process dies (PR_SET_PDEATHSIG),
and `atexit` stops whatever `Peers.stop` has not.

This process never imports JAX.
"""

from __future__ import annotations

import atexit
import ctypes
import os
import signal
import subprocess
import sys
import time

from harness.node import BenchFailure, ROOT, Node, free_ports, http_json, require

PR_SET_PDEATHSIG = 1


def _die_with_parent() -> None:
    """In the child, before exec: SIGKILL when the parent is gone,
    however it went."""
    ctypes.CDLL(None, use_errno=True).prctl(PR_SET_PDEATHSIG, signal.SIGKILL)


class Peer:
    """One host-only volume server that joins the node's master, in a
    rack of its own name."""

    def __init__(self, node: Node, name: str):
        self.node, self.name = node, name
        self.data = os.path.join(node.workdir, f"peer-{name}")
        os.makedirs(self.data)
        self.log_path = os.path.join(node.workdir, f"peer-{name}.log")
        self.proc: subprocess.Popen | None = None

    def start(self, port: int) -> None:
        self.url = f"127.0.0.1:{port}"
        cmd = [
            sys.executable, "-m", "seaweedfs_tpu", "volume",
            "-ip", "127.0.0.1", "-port", str(port),
            "-dir", self.data, "-max", "32",
            "-mserver", self.node.master,
            "-rack", self.name,
            "-ec.codec", "native",
            # as the node: no background scrub inside a measured window
            "-scrubInterval", "86400", "-scrubRate", "0",
        ]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                cmd, cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True, preexec_fn=_die_with_parent,
            )

    def log_tail(self, lines: int = 20) -> str:
        if not os.path.exists(self.log_path):
            return ""
        with open(self.log_path, "r", errors="replace") as f:
            return "".join(f.readlines()[-lines:])

    def wait_up(self, deadline_s: float = 90.0) -> None:
        """Its own /status answers, names a host codec and no device,
        and the node's master lists it under its rack."""
        deadline = time.time() + deadline_s
        status = None
        while time.time() < deadline:
            require(self.proc.poll() is None,
                    f"peer {self.name} exited rc={self.proc.returncode}: {self.log_tail()}")
            try:
                status = http_json(f"http://{self.url}/status", timeout=2)
                topo = http_json(f"http://{self.node.master}/dir/status", timeout=2)
            except (OSError, ValueError):
                time.sleep(0.1)
                continue
            if self.url in repr(topo):
                break
            time.sleep(0.1)
        else:
            raise BenchFailure(f"peer {self.name} did not join the master: {self.log_tail()}")
        codec = status.get("EcCodec") or {}
        require(codec.get("codec") == "native" and "platform" not in codec,
                f"peer {self.name} reports a device codec, not a host one: {codec}")

    def stop(self) -> None:
        if self.proc is None or self.proc.poll() is not None:
            return
        try:  # it leads a process group of its own
            os.killpg(self.proc.pid, signal.SIGKILL)
        except OSError:
            pass
        self.proc.wait(timeout=30)


class Peers:
    """The peers of one run, started together and stopped together."""

    def __init__(self, node: Node, names):
        self.peers = {name: Peer(node, name) for name in names}
        atexit.register(self.stop)

    def __getitem__(self, name: str) -> Peer:
        return self.peers[name]

    def start(self) -> None:
        """Start every peer; none is waited for. The node's own three
        ports are bound by now, so `free_ports` passes them over."""
        for peer, port in zip(self.peers.values(), free_ports(len(self.peers))):
            peer.start(port)

    def wait_up(self, attempts: int = 3) -> None:
        """Every peer up and in the master's topology. A port lost
        between `free_ports` and a peer's own bind costs another start
        of that peer, as it does the node."""
        for peer in self.peers.values():
            for left in reversed(range(attempts)):
                try:
                    peer.wait_up()
                    break
                except BenchFailure as e:
                    peer.stop()
                    if not left or "bind" not in peer.log_tail() + str(e):
                        raise
                    print(f"peer {peer.name} lost its port at start, starting again: {e}",
                          file=sys.stderr)
                    peer.start(free_ports(1)[0])

    def stop(self) -> None:
        for peer in self.peers.values():
            peer.stop()
