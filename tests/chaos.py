"""Cluster wiring for the weedchaos scenario suite (docs/CHAOS.md).

Used by tests/test_chaos.py: builders
for raft-HA master groups and proxied volume servers, an EC volume
seeded over the wire, and the write/read workloads the invariant
checkers audit. Everything here drives REAL servers over real
sockets — the point of the chaos plane is that no fault is simulated
below the syscall/wire level.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import threading
import time

from seaweedfs_tpu.analysis.chaos import ProxyPair
from seaweedfs_tpu.client import operation as op
from seaweedfs_tpu.client import retry as retry_mod


from seaweedfs_tpu.util.availability import free_port  # noqa: E402 — collision-hardened allocator


def wait_for(cond, timeout=45.0, interval=0.05) -> bool:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


def spawn_cli(*args, env_extra: dict | None = None):
    """A real `python -m seaweedfs_tpu ...` subprocess — the
    SIGSTOP/SIGKILL scenarios need a separate PROCESS, and
    `env_extra` selects the serving path (WEED_NATIVE_SERVE) per arm."""
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", WEED_EC_CODEC="cpu",
        **(env_extra or {}),
    )
    return subprocess.Popen(
        [
            sys.executable,
            "-m",
            "seaweedfs_tpu",
            *args,
        ],
        env=env,
        cwd="/root/repo",
        stdout=subprocess.DEVNULL,
        stderr=subprocess.STDOUT,
    )


def reap_procs(procs) -> None:
    """SIGCONT (for SIGSTOP scenarios) then kill+wait each process."""
    import signal

    for p in procs:
        try:
            p.send_signal(signal.SIGCONT)
        except OSError:
            pass
        try:
            p.kill()
            p.wait(timeout=10)
        except OSError:
            pass


def start_ha_masters(tmp_factory, n: int = 3, **kw):
    """n in-process MasterServers in one raft group; blocks until a
    leader is elected. Caller stops them."""
    from seaweedfs_tpu.server.master_server import MasterServer

    ports = [free_port() for _ in range(n)]
    peers = ",".join(f"127.0.0.1:{p}" for p in ports)
    masters = [
        MasterServer(
            port=p,
            volume_size_limit_mb=64,
            vacuum_interval=0,
            peers=peers,
            raft_dir=str(tmp_factory.mktemp(f"chaos_raft{p}")),
            **kw,
        )
        for p in ports
    ]
    for m in masters:
        m.start()
    assert wait_for(
        lambda: sum(1 for m in masters if m.is_leader) == 1
    ), "no raft leader elected"
    return masters


def master_addrs(masters) -> list[str]:
    return [f"127.0.0.1:{m.port}" for m in masters]


def start_volume_server(tmp_factory, masters_csv: str, tag: str, **kw):
    """One in-process VolumeServer heartbeating at `masters_csv`.
    Pass announce="host:port" to advertise a ChaosProxy pair instead
    of the bind address (the partition lever)."""
    from seaweedfs_tpu.server.volume_server import VolumeServer

    vs = VolumeServer(
        [str(tmp_factory.mktemp(f"chaos_{tag}"))],
        port=free_port(),
        master=masters_csv,
        heartbeat_interval=0.2,
        max_volume_counts=[100],
        ec_codec="cpu",
        scrub_interval=0,
        **kw,
    )
    vs.start()
    return vs


def proxied_volume_server(tmp_factory, masters_csv: str, tag: str, **kw):
    """A volume server the CLUSTER reaches only through a ChaosProxy
    pair (HTTP + gRPC ports faulted together): returns (vs, pair).
    pair.partition()/heal() then cuts/restores the node for every peer
    that dials its master-advertised address."""
    from seaweedfs_tpu.server.volume_server import VolumeServer

    port = free_port()
    pair = ProxyPair(f"127.0.0.1:{port}")
    vs = VolumeServer(
        [str(tmp_factory.mktemp(f"chaos_{tag}"))],
        port=port,
        master=masters_csv,
        heartbeat_interval=0.2,
        max_volume_counts=[100],
        ec_codec="cpu",
        scrub_interval=0,
        announce=pair.addr,
        **kw,
    )
    vs.start()
    return vs, pair


# ---------------------------------------------------------------------------
# workloads


def put_blob(masters: list[str], data: bytes, collection: str = "",
             policy=None) -> str:
    """assign (with policy-driven master failover) + upload; returns
    the fid. Raises on failure — callers count."""
    ar, _ = op.with_master_failover(
        masters, lambda m: op.assign(m, collection=collection), policy=policy
    )
    ur = op.upload(f"{ar.url}/{ar.fid}", data, jwt=ar.auth)
    if ur.error:
        raise RuntimeError(f"upload {ar.fid}: {ur.error}")
    return ar.fid


def read_blob(masters: list[str], fid: str, collection: str = "") -> bytes:
    """Locate via any live master and download one replica."""
    def locate(m):
        url = op.lookup_file_id(m, fid)
        return url

    url, _ = op.with_master_failover(masters, locate)
    q = f"?collection={collection}" if collection else ""
    data, _ = op.download(url + q, timeout=10)
    return data


def write_fan(
    masters: list[str],
    n_writers: int = 3,
    n_writes: int = 30,
    payload_fn=None,
    policy=None,
) -> dict:
    """Concurrent writer fan for scenarios: each writer loops
    assign+upload through master failover. Returns the invariant-
    checker report: acked {fid: payload}, failed count, requests_sent
    (first attempts + granted retries, for amplification audits)."""
    payload_fn = payload_fn or (lambda w, i: f"chaos w{w} i{i} ".encode() * 50)
    acked: dict[str, bytes] = {}
    lock = threading.Lock()
    failed = [0]
    duplicates = [0]
    retries_before = retry_mod.DEFAULT_BUDGET.spent

    def writer(w: int) -> None:
        for i in range(n_writes):
            data = payload_fn(w, i)
            try:
                fid = put_blob(masters, data, policy=policy)
            except Exception:  # noqa: BLE001 - counted, audited below
                with lock:
                    failed[0] += 1
                continue
            with lock:
                if fid in acked:
                    # two writers acked the SAME fid: a replayed
                    # assign double-applied — the no_double_apply
                    # invariant reads this counter (the acked dict's
                    # keys alone can't show it: the second insert
                    # silently overwrites)
                    duplicates[0] += 1
                acked[fid] = data

    threads = [
        threading.Thread(target=writer, args=(w,), daemon=True)
        for w in range(n_writers)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    attempts = n_writers * n_writes
    return {
        "acked": acked,
        "failed": failed[0],
        "duplicates": duplicates[0],
        "requests_sent": attempts + (retry_mod.DEFAULT_BUDGET.spent - retries_before),
    }


# ---------------------------------------------------------------------------
# EC seeding


def seed_ec_volume(master, collection: str, n: int = 8) -> tuple[int, dict]:
    """Write a keyset, seal + EC-encode + spread it over the live
    cluster via the shell verbs (the operator path). Returns
    (vid, {fid: payload})."""
    import io

    from seaweedfs_tpu.shell.command_env import CommandEnv
    from seaweedfs_tpu.shell.commands import do_ec_encode
    from seaweedfs_tpu.util.availability import write_keyset

    vid, keys, _src = write_keyset(
        master.port,
        collection,
        n=n,
        payload_fn=lambda i: (f"chaos ec {i} ".encode() * 1500)[: 12000 + i],
    )
    env = CommandEnv([f"127.0.0.1:{master.port}"])
    do_ec_encode(env, vid, collection, io.StringIO())
    return vid, keys


def registered_shards(master, vid: int) -> int:
    locs = master.topology.lookup_ec_shards(vid)
    if locs is None:
        return 0
    return sum(1 for nodes in locs.locations if nodes)
