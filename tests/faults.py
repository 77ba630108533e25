"""Fault-injection helpers for corruption end-to-end tests.

Bit-flips and truncations against the on-disk formats (.dat needle
records, .ec shard files, .ecx indexes) so scrub/repair tests inject
exactly the damage the subsystem claims to detect, plus a slow-replica
TCP proxy (QoS plane: the hedged-read A/B needs one replica reliably
slow without touching server code). Helpers return enough to RESTORE
the damage, because several suites share live cluster fixtures.
"""

from __future__ import annotations

import os

from seaweedfs_tpu.analysis.chaos import ChaosProxy
from seaweedfs_tpu.storage import types as t


class SlowReplicaProxy(ChaosProxy):
    """TCP proxy that delays one replica's RESPONSES by `delay_s`.

    Point a client's replica url at `proxy.addr` instead of the real
    volume server and every byte the server sends back is held for the
    delay before forwarding — the injected-slow-replica fault the
    hedged-read A/B (`git show 484f53f:BENCH_r09.json`) and the hedge
    tests drive. Requests pass through untouched, so the server does all its
    normal work; only the client-observed latency inflates. `delay_s`
    is mutable mid-run (`proxy.delay_s = 0` = transparent).

    Now a thin preset over the weedchaos fault library's ChaosProxy
    (analysis/chaos.py, docs/CHAOS.md), which generalizes this proxy
    to jitter/bandwidth/drop/blackhole/RST faults."""

    def __init__(self, target: str, delay_s: float = 0.25):
        super().__init__(target)
        self.response.latency_s = delay_s

    @property
    def delay_s(self) -> float:
        return self.response.latency_s

    @delay_s.setter
    def delay_s(self, value: float) -> None:
        self.response.latency_s = value

    @property
    def responses_delayed(self) -> int:
        return self.chunks_delayed


class DeadShard:
    """Quarantine one mounted shard of a LIVE EC volume mid-load — the
    degraded-read fault (docs/SCRUB.md): every later GET whose interval
    lands on the shard must reconstruct from survivors, exactly like a
    disk death under traffic. Uses the same rename-to-.bad quarantine
    the scrubber does, so the repair plane treats it as real damage.

    In-process servers: pass `volume_servers`; subprocess/CLI clusters:
    pass `addr` ("host:port") and the fault rides the /ec/quarantine
    operator route instead. `restore()` moves the .bad file back and
    remounts (in-process only), so suites sharing a cluster fixture can
    heal without a rebuild."""

    def __init__(self, vid: int, sid: int | None = None,
                 volume_servers=None, addr: str | None = None,
                 collection: str = ""):
        self.vid = vid
        self.collection = collection
        self.sid: int | None = sid
        self.addr = addr
        self._vs = None
        self._path: str | None = None
        if (volume_servers is None) == (addr is None):
            raise ValueError("pass exactly one of volume_servers / addr")
        if volume_servers is not None:
            for vs in volume_servers:
                ev = vs.store.find_ec_volume(vid)
                if ev is None:
                    continue
                ids = ev.shard_ids()
                if not ids:
                    continue
                if sid is None:
                    self.sid = ids[0]
                elif sid not in ids:
                    continue
                self._vs = vs
                self._path = ev.shards[self.sid].path
                break
            if self._vs is None:
                raise RuntimeError(
                    f"no server has a mounted shard of vid {vid}"
                    + (f" (wanted shard {sid})" if sid is not None else "")
                )

    def kill(self) -> int:
        """Quarantine the shard; returns the shard id killed."""
        if self._vs is not None:
            ev = self._vs.store.find_ec_volume(self.vid)
            assert ev is not None
            if not ev.quarantine_shard(self.sid, "fault: DeadShard"):
                raise RuntimeError(
                    f"shard {self.sid} of vid {self.vid} not quarantined"
                )
            return self.sid
        import json
        import urllib.request

        url = f"http://{self.addr}/ec/quarantine?volumeId={self.vid}"
        if self.sid is not None:
            url += f"&shard={self.sid}"
        with urllib.request.urlopen(url, timeout=10) as r:
            reply = json.loads(r.read())
        if not reply.get("quarantined"):
            raise RuntimeError(f"DeadShard via {self.addr}: {reply}")
        self.sid = reply["shard"]
        return self.sid

    def restore(self) -> None:
        """Undo (in-process only): move the forensic .bad copy back and
        remount, clearing the quarantine record."""
        if self._vs is None or self._path is None:
            raise RuntimeError("restore() needs in-process volume_servers")
        if os.path.exists(self._path + ".bad"):
            os.replace(self._path + ".bad", self._path)
        store = self._vs.store
        store.mount_ec_shards(self.vid, self.collection, [self.sid])


def flip_byte(path: str, offset: int, xor: int = 0xFF) -> int:
    """XOR one byte in place; returns the ORIGINAL byte value."""
    with open(path, "r+b") as f:
        f.seek(offset)
        orig = f.read(1)[0]
        f.seek(offset)
        f.write(bytes([orig ^ xor]))
    return orig


def restore_byte(path: str, offset: int, value: int) -> None:
    with open(path, "r+b") as f:
        f.seek(offset)
        f.write(bytes([value]))


def truncate_by(path: str, nbytes: int) -> int:
    """Chop `nbytes` off the file's tail; returns the new size."""
    size = os.path.getsize(path)
    new = max(0, size - nbytes)
    with open(path, "r+b") as f:
        f.truncate(new)
    return new


def find_ec_shard_path(volume_servers, collection: str, vid: int, sid: int):
    """(path, serving VolumeServer) for the MOUNTED copy of a shard.
    Mount state is checked first (via the store), not mere file
    existence: the encode/spread pipeline can leave an unmounted
    leftover shard file on the encoding node, and corrupting that
    dead copy instead of the served one makes a detection test pass
    or fail on spread order. Falls back to any on-disk file when no
    server has the shard mounted; (None, None) when absent."""
    for vs in volume_servers:
        ev = vs.store.find_ec_volume(vid)
        if ev is not None and sid in ev.shards:
            return ev.shards[sid].path, vs
    name = (
        f"{collection}_{vid}.ec{sid:02d}" if collection else f"{vid}.ec{sid:02d}"
    )
    for vs in volume_servers:
        for loc in vs.store.locations:
            p = os.path.join(loc.directory, name)
            if os.path.exists(p):
                return p, vs
    return None, None


def corrupt_needle_data(volume, needle_id: int, xor: int = 0x5A) -> tuple[str, int, int]:
    """Flip one byte inside a live needle's DATA region in the .dat so
    the CRC check fails on re-read. Returns (dat_path, offset, original
    byte) for restoration.

    v2/v3 record layout: 16-byte header, then u32 data_size, then data
    — so the first data byte sits at actual_offset + 20."""
    nv = volume.nm.get(needle_id)
    assert nv is not None and nv.size != t.TOMBSTONE_FILE_SIZE, (
        f"needle {needle_id} not live"
    )
    dat_path = volume.base_name + ".dat"
    offset = nv.actual_offset + t.NEEDLE_HEADER_SIZE + 4
    orig = flip_byte(dat_path, offset, xor)
    return dat_path, offset, orig


# --- leak checks for the EC stream pipeline ----------------------------------
# Counted by what the pipeline itself names and by where its files live:
# under xdist a worker's other tests start lazy threads and open sockets
# of their own, so a process-wide count before and after says nothing.

EC_STREAM_THREAD_PREFIX = "ec-stream-"


def ec_stream_threads() -> list[str]:
    """Names of the live pool threads of ec_stream's pipeline shell."""
    import threading

    return [
        th.name
        for th in threading.enumerate()
        if th.name.startswith(EC_STREAM_THREAD_PREFIX)
    ]


def fds_under(root) -> list[str]:
    """Paths under `root` that this process holds an open fd on."""
    root = os.path.realpath(str(root))
    held = []
    for name in os.listdir("/proc/self/fd"):
        try:
            path = os.readlink(f"/proc/self/fd/{name}")
        except OSError:  # closed since the listing (its own fd among them)
            continue
        if path == root or path.startswith(root + os.sep):
            held.append(path)
    return held


def ec_shards_less(
    base: str, nbytes: int, seed: int, lost, large: int, small: int
) -> None:
    """A seeded `.dat` and its whole shard set from the classic loop
    (made once), with the `lost` shards gone (again): what a rebuild
    driver starts from."""
    import numpy as np

    from seaweedfs_tpu.ec import ec_files
    from seaweedfs_tpu.ec.codec import new_encoder

    if not os.path.exists(base + ".dat"):
        rng = np.random.default_rng(seed)
        with open(base + ".dat", "wb") as f:
            f.write(rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes())
        ec_files.write_ec_files(
            base, rs=new_encoder(backend="cpu"), large_block_size=large,
            small_block_size=small,
        )
    for i in lost:
        if os.path.exists(base + ec_files.to_ext(i)):
            os.remove(base + ec_files.to_ext(i))
