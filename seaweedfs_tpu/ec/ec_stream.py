"""Pipelined host<->HBM streaming drivers for EC encode and rebuild.

Encode, rebuild and their batch forms are all `out = M * in`, streamed
through ONE pipeline shell (_Op): a reader pool fills slots of a
preallocated staging ring, ONE dispatcher (the caller's thread) hands
each slot to the codec stage, a writer pool fetches the result and
lands it with positioned writes at precomputed offsets. Every output
byte is written exactly once wherever its tile finishes, so completion
order never shows in the bytes: they match the synchronous drivers of
ec_files.py exactly.

The shell owns, once: the pools, their two bounded queues and the ring
(its memory borrowed from the process and given back: _KeptRing); the
stop flag's reach into every wait; the output files (opened on the
caller's thread, reserved by the writer pool behind a latch:
_Reservation); the five serial phases that partition wall_s
(_OP_PHASES), the pool stages' thread-seconds and the waits between
the stages (_WAIT_BUSY: who stood waiting for whom); the abort and
durability contract (_settle_outputs); the report line and the span.
A driver is a plan and its stage bodies, handed to _Op.run:

  plan      items (claimed in order by the readers), slot_bytes,
            outputs [(path, final size)], the span's name and bytes
  opened    () -> context manager: what ONE reader thread opens
  fill      (src, item, slot) -> staged       reader pool      read_s
  dispatch  (item, staged) -> handle          the dispatcher; books its
            own stage_s / device_s (h2d_s / launch_s) through op.book
  fetch     (item, staged, handle) -> result  writer pool, blocking
  checksum  (item, staged, result)            host CRC where the stage
            declined the fused one                             compute_s
  write     (fds, item, staged, result)       behind the latch write_s
  report    (stats, span, whole)              CRC fold, its own fields

Only the [4, N] parity crosses device->host on encode: the ten data
shard files are byte copies of the blocks read from the .dat, written
straight from the ring slot. Both rebuild drivers also take REMOTE
survivor readers (`remote_readers`: shard id -> read_into(offset,
row of the ring slot), one map a volume in the batch driver) and
gather through one helper (_Gather), which is how VolumeEcShardsRebuild
and VolumeEcShardsBatchRebuild overlap a rack-wide gather with
reconstruction.

Role match: the 256 KB-batch loops at reference
weed/storage/erasure_coding/ec_encoder.go:188-225 (encodeDatFile) and
:227-281 (rebuildEcFiles), rebuilt as a pooled pipelined driver.
"""

from __future__ import annotations

import contextlib
import errno
import functools
import itertools
import os
import sys
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, NamedTuple

import numpy as np

from seaweedfs_tpu import trace
from seaweedfs_tpu.ec import locate
from seaweedfs_tpu.stats.metrics import (
    EC_REPAIR_BYTES_READ,
    EC_REPAIR_BYTES_WRITTEN,
    EC_RING_FRESH_BYTES,
)

DATA_SHARDS = locate.DATA_SHARDS
PARITY_SHARDS = locate.PARITY_SHARDS
TOTAL_SHARDS = locate.TOTAL_SHARDS
LARGE_BLOCK_SIZE = locate.LARGE_BLOCK_SIZE
SMALL_BLOCK_SIZE = locate.SMALL_BLOCK_SIZE

# The unit the stream drivers' tiles are counted in: 1 MiB a shard,
# upstream's small block. A single-volume tile is four of it
# (VOLUME_TILE_BYTES); the batch drivers' round rule
# (batch_round_tile_bytes) floors at half of it, which is also the round
# of the host batch rebuild (BATCH_REBUILD_MIN_TILE_BYTES).
DEFAULT_TILE_BYTES = 1024 * 1024
# Per-shard bytes per tile of a single-volume encode or rebuild, whether
# a rebuild's survivors are local files or streamed from other servers:
# [10, 4 MiB] in, 40 MiB a ring slot, 480 MiB a kept ring. The ONE
# dispatcher thread pays about a millisecond to copy a tile to the
# device and another to launch it whatever the tile's size (0.90 + 0.83
# ms at 512 KiB a shard, 1.2 + 1.2 ms at 4 MiB on a v5e), so the 103 rows
# of a 1 GiB volume cost that thread 0.36 s in 206 tiles of 512 KiB and
# 0.07 s in 27 of 4 MiB (25 of four rows, one of two, one of one: an
# encode sends a run of rows as ONE tile, stream_write_ec_files). The
# reader pool's reads move 3.7 GB/s a thread in 4 MiB spans against 1.1
# in 512 KiB ones. Read through the cell rebuild-1data at 512 KiB, 1, 2
# and 4 MiB (2.08, 3.23, 4.60 and 5.51 GB/s of volume repaired) and
# through encode-1g at 2, 4 and 8 MiB (3.87, 4.45 and 2.98 GB/s of
# volume encoded, where one call a 1 MiB row read 2.8; at 8 MiB a fetch
# waits for 80 MiB of H2D): PERF.md section 6. A rack gather wants the
# same size for a reason of its own: one fetch per remote survivor and
# tile.
VOLUME_TILE_BYTES = 4 * DEFAULT_TILE_BYTES
# Dispatched-but-unfetched tiles queued toward the writer pool (the
# report line's pipeline_depth). Live host-tile bound: _INFLIGHT queued
# + one per writer thread (being fetched/written) + reader_threads + 2
# (read queue + the dispatcher's hands) — 10 tiles at the defaults.
_INFLIGHT = 3
# The most staging memory the process keeps between operations
# (_KeptRing). A ring is _ring_slots() slots (12 at the defaults) of the
# plan's slot_bytes: 12 x 40 MiB for the 4 MiB tiles of a single-volume
# encode or rebuild, 12 x 80 and 12 x 60 MiB for batch encodes of four
# volumes in rounds of 2 MiB a shard and of six in rounds of 1 MiB, 12 x
# 80 MiB for a batch rebuild of four volumes in rounds of 2 MiB a
# survivor (batch_round_tile_bytes sizes both batch drivers' rounds so
# that the ring stays under this), all kept; a ring
# beyond this (256 volumes a call are 2.5 GiB a slot) is allocated and
# freed by its operation.
_RING_KEEP_BYTES = 1 << 30
# Slots start this far apart in the ring's one allocation (a page), so
# every slot lies in its allocation as a slot of its own used to.
_SLOT_ALIGN = 4096


def pipeline_batch_limit() -> int:
    """Max volumes per mesh dispatch round on the batched encode path
    (WEED_EC_PIPELINE_BATCH, 0 = whole batch in one program). Caps
    staging-ring memory: one ring slot is batch x 10 x tile bytes."""
    try:
        return max(0, int(os.environ.get("WEED_EC_PIPELINE_BATCH", "0")))
    except ValueError:
        return 0


class _StagingRing:
    """N host staging buffers cycled reader -> dispatcher -> writer ->
    free, carved from ONE allocation that the operation borrows from the
    process (_KeptRing) and gives back when it has settled: the
    pipeline's host memory is bounded at slots x slot_bytes for the
    whole run, the allocator is out of the hot loop, and from a node's
    second operation on the readers, the H2D copy and the writers work
    on pages that are already mapped (six readers faulting in six new
    40 MiB slots at once made the first read into every slot four times
    as long as the later ones, PERF.md section 6, PR 33). Slot count =
    dispatch depth + one in-hand buffer per pool thread, so no stage
    ever stalls waiting for memory another stage is legitimately using.

    A slot holds whatever its last user left, an earlier operation's
    bytes included: a plan zero-pads what it does not read
    (_read_tile_into) and writes only what it read, as it already had to
    between two tiles of one operation."""

    def __init__(self, slots: int, slot_bytes: int):
        self.slots = max(2, slots)
        stride = -(-slot_bytes // _SLOT_ALIGN) * _SLOT_ALIGN
        # fresh_bytes: what of this ring was allocated for it (0: all of
        # it is memory an earlier operation used and gave back)
        self._arena, self.fresh_bytes = _RING.borrow(
            self.slots * stride
        )
        self._bufs = [
            self._arena[i * stride : i * stride + slot_bytes]
            for i in range(self.slots)
        ]
        self._free: queue.Queue = queue.Queue()
        for i in range(self.slots):
            self._free.put(i)

    def acquire(self, stop: threading.Event):
        """(slot id, flat uint8 buffer) or None when the pipeline
        aborted while waiting for a free slot."""
        i = _q_get(self._free, stop, "ec.wait.slot")
        if i is _STOPPED:
            return None
        return i, self._bufs[i]

    def release(self, slot_id: int) -> None:
        self._free.put(slot_id)

    def settle(self, whole: bool) -> None:
        """The operation's pools are joined: give the memory back to the
        process if the operation ran whole (every tile was fetched, so
        no transfer reads a slot any more), and drop it otherwise: an
        asynchronous H2D copy of an aborted operation may still be
        reading one, and what it reads must not become another
        operation's slot."""
        arena, self._arena, self._bufs = self._arena, None, []
        if whole:
            _RING.give_back(arena)


# Pool widths: the threads spend their time in GIL-released syscalls
# (preadv/pwritev), GIL-released C codec calls, or blocking device
# fetches, so a few of them keep the disks busy even on small hosts —
# but every extra thread costs GIL churn. Re-swept with the staging
# ring (`git show 484f53f:BENCH_r12.json`): the reader pool is the
# disk's IO queue, and a floor of 3 readers beat the old 2 even on a
# 1-CPU-quota host (1.24 vs 1.17 GB/s at the 1 MiB tile) because
# blocked preads cost no CPU; w=3 still beat w=2 and w=8.
DEFAULT_WRITER_THREADS = min(8, max(3, (os.cpu_count() or 2) + 1))
DEFAULT_READER_THREADS = min(6, max(3, (os.cpu_count() or 2) // 2))


def _ring_slots(writer_threads: int | None = None) -> int:
    """Slots of an operation's staging ring: the dispatched-but-unfetched
    window plus one buffer in hand a writer and one for the dispatcher."""
    return _INFLIGHT + (writer_threads or DEFAULT_WRITER_THREADS) + 1


# The smallest per-shard span a round of a batch driver reads: the
# 512 KiB every batch rebuild round was before the chip was asked
# (`git show 484f53f:BENCH_r12.json`, a CPU-sandbox record), and still
# what the host arm of the batch rebuild takes.
BATCH_REBUILD_MIN_TILE_BYTES = DEFAULT_TILE_BYTES // 2


def batch_round_tile_bytes(volumes: int, slots: int) -> int:
    """Per-shard bytes a volume's share of one round of a mesh batch
    driver may carry (a survivor's span in the batch rebuild, a data
    shard's run of blocks in the batch encode), from what the driver can
    see: the volumes it stacks a round and the slots of its ring. The
    largest power of two from BATCH_REBUILD_MIN_TILE_BYTES to
    VOLUME_TILE_BYTES whose ring (slots x volumes x 10 x tile) the
    process still keeps (_RING_KEEP_BYTES): at 12 slots 4 MiB for one or
    two volumes, 2 MiB for three or four, 1 MiB for five to eight, and
    512 KiB beyond, where no size in range fits.

    A batch rebuild's round is volumes x 10 preads whatever their size
    (a batch encode's is one preadv a volume), and on a host
    whose pools share one interpreter lock a pread's price is mostly the
    call's: 0.97 ms at 512 KiB, 1.19 at 1 MiB, 1.42 at 2 MiB on a v5e's
    host, so four volumes' 1,040 MiB of survivors cost the reader pool
    2.01, 1.24 and 0.74 thread-seconds. Read through the cell
    batch-rebuild-2lost (four volumes, twelve slots) at 512 KiB, 1, 2
    and 4 MiB: 2.56, 3.53, 4.51 and 0.79 GB/s of volume repaired in 52,
    26, 13 and 7 rounds. The last is what the bound is for: a 1.9 GiB
    ring is over _RING_KEEP_BYTES, so every call allocates it and its
    six readers fault 160 MiB slots in (the first round lands after
    0.85 s, ring_fresh_bytes 2,013,265,920 a call). PERF.md section 6,
    PR 39."""
    tile = VOLUME_TILE_BYTES
    while (
        tile > BATCH_REBUILD_MIN_TILE_BYTES
        and slots * volumes * DATA_SHARDS * tile > _RING_KEEP_BYTES
    ):
        tile //= 2
    return tile


_EOF = object()  # end-of-stream marker flowing through the queues
_STOPPED = object()  # returned by _q_get when the pipeline aborted

_Q_TICK = 0.2  # seconds between stop-flag checks while blocked
_NO_WAIT = contextlib.nullcontext()


def _standing(wait: str | None):
    """The blocking part of a wait in a profiler trace, under its
    `ec.wait.*` name; a wait nobody named leaves no event."""
    return trace.annotation(wait) if wait else _NO_WAIT


def _q_put(
    q: queue.Queue, item, stop: threading.Event, wait: str | None = None
) -> bool:
    """put() that gives up when the pipeline aborts (a dead consumer
    must not leave the producer blocked forever). It tries without
    blocking first, and `wait` names only the blocking call in a
    profiler trace (ec.wait.*): a put that finds room leaves no event."""
    if stop.is_set():
        return False
    try:
        q.put_nowait(item)
        return True
    except queue.Full:
        pass
    with _standing(wait):
        while not stop.is_set():
            try:
                q.put(item, timeout=_Q_TICK)
                return True
            except queue.Full:
                continue
    return False


def _q_get(q: queue.Queue, stop: threading.Event, wait: str | None = None):
    """get() under the same rules: _STOPPED when the pipeline aborted,
    and no trace event for an item that was already there."""
    if stop.is_set():
        return _STOPPED
    try:
        return q.get_nowait()
    except queue.Empty:
        pass
    with _standing(wait):
        while not stop.is_set():
            try:
                return q.get(timeout=_Q_TICK)
            except queue.Empty:
                continue
    return _STOPPED


class _Pipeline:
    """Reader/writer pool threads around the caller's dispatch loop,
    with first-error propagation and deadlock-free shutdown."""

    def __init__(self):
        self.stop = threading.Event()
        self.errors: list[BaseException] = []
        self._threads: list[threading.Thread] = []

    def spawn(self, fn, role: str) -> None:
        def run():
            try:
                fn()
            except BaseException as e:  # noqa: BLE001 — re-raised on join
                self.errors.append(e)
                self.stop.set()

        t = threading.Thread(
            target=run,
            daemon=True,
            name=f"ec-stream-{role}-{len(self._threads)}",
        )
        t.start()
        self._threads.append(t)

    def finish(self, caller_error: bool = False) -> None:
        """Join the stage threads; re-raise the first stage error."""
        if caller_error:
            self.stop.set()
        for t in self._threads:
            t.join()
        if not caller_error and self.errors:
            raise self.errors[0]


class _Reservation:
    """One operation's output files, reserved by its writer pool behind
    a count-down latch. Every writer thread starts by claiming
    (fd, size) pairs from ONE shared iterator and preallocating each
    (`reserve`), then enters its ordinary loop; `wait` stands directly
    before a writer's pwritevs, so no shard byte of the operation is
    written until every file of the operation has its final extent —
    what the handler's serial loop guaranteed, without the readers, the
    dispatcher and the device waiting for it. ENOSPC from a reservation
    is a stage error like any other (_Pipeline.spawn), and because of
    the latch it still fails before the first write. `wait` obeys the
    pipeline's stop flag the way _q_get does, so another stage's error
    cannot leave a writer parked here."""

    def __init__(self, files: list[tuple[int, int]], stop: threading.Event):
        self._files = iter(files)
        self._left = len(files)
        self._stop = stop
        self._lock = threading.Lock()
        self._open = threading.Event()
        # the clock sample at which the last file was reserved (0.0: the
        # latch never opened)
        self.opened_at = 0.0

    def reserve(self, book: Callable[[str, float], None]) -> None:
        while not self._stop.is_set():
            with self._lock:
                claim = next(self._files, None)
            if claim is None:
                return
            t0 = time.perf_counter()
            with trace.annotation("ec.reserve"):
                _preallocate(*claim)
            t1 = time.perf_counter()
            book("reserve_s", t1 - t0)
            with self._lock:
                self._left -= 1
                if not self._left:
                    self.opened_at = t1
                    self._open.set()

    def wait(self) -> bool:
        """True once every file is reserved; False when the pipeline
        aborted first. Only a wait that blocks is an event in a profiler
        trace (ec.wait.latch)."""
        if self._open.is_set():
            return True
        with trace.annotation("ec.wait.latch"):
            while not self._open.is_set():
                if self._stop.is_set():
                    return False
                self._open.wait(_Q_TICK)
        return True


# --- raw-fd IO primitives ---------------------------------------------------


def _preallocate(fd: int, size: int) -> None:
    """Reserve the file's exact final extent up front so ENOSPC fails
    before any shard byte is written and close() has no deferred work.
    posix_fallocate allocates real blocks where the filesystem supports
    it; anything it can't do degrades to ftruncate (sparse extent —
    every byte is positioned-written exactly once anyway)."""
    if size <= 0:
        return
    try:
        os.posix_fallocate(fd, 0, size)
        return
    except OSError as e:
        if e.errno == errno.ENOSPC:
            raise
    os.ftruncate(fd, size)


def _pwrite_full(fd: int, buf, offset: int) -> None:
    """Positioned write of the whole buffer, restarting cleanly across
    short writes (pwritev can short-write on signals / rlimits; a silent
    short write would corrupt the shard)."""
    mv = memoryview(buf).cast("B")
    written = 0
    while written < len(mv):
        w = os.pwritev(fd, [mv[written:]], offset + written)
        if w <= 0:
            raise OSError(errno.EIO, f"short pwritev at {offset + written}")
        written += w


def _pread_into(fd: int, view, offset: int) -> int:
    """Positioned read filling `view` (a writable uint8 buffer); stops
    early only at EOF. Returns bytes read."""
    return _preadv_into(fd, [view], offset)


# The most buffers one preadv takes (Linux's IOV_MAX)
_IOV_MAX = 1024


def _preadv_into(fd: int, views: list, offset: int) -> int:
    """Positioned scatter read filling `views` (writable uint8 buffers)
    in order from ONE run of the file at `offset`, restarting cleanly
    across short reads; stops early only at EOF. One syscall for up to
    _IOV_MAX buffers. Returns bytes read."""
    mvs = [memoryview(v).cast("B") for v in views]
    got = j = 0
    while j < len(mvs):
        r = os.preadv(fd, mvs[j : j + _IOV_MAX], offset + got)
        if r == 0:
            break
        got += r
        while j < len(mvs) and r >= len(mvs[j]):
            r -= len(mvs[j])
            j += 1
        if r:
            mvs[j] = mvs[j][r:]
    return got


@contextlib.contextmanager
def _opened(paths: list[str]):
    """Read-only fds of `paths` for ONE reader thread (each thread owns
    its fds: positioned reads, no seek state shared across the pool)."""
    fds: list[int] = []
    try:
        for path in paths:
            fds.append(os.open(path, os.O_RDONLY))
        yield fds
    finally:
        for fd in fds:
            os.close(fd)


def _survivor_choice(
    base: str, present: list[bool], missing: list[int], remote: dict
) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """(survivors, targets) of one volume's rebuild, for both rebuild
    drivers. A shard readable remotely (a key of `remote`) exists in the
    cluster: it can serve as a survivor but must not be rebuilt here.
    Local survivors first (free reads), topped up from remote holders;
    the decode matrix keeps the chosen set in ascending order, and any
    10-of-14 subset reconstructs identical bytes. At least one survivor
    must be local: its file size fixes the tile walk."""
    targets = tuple(i for i in missing if i not in remote)
    local_ids = [i for i, p in enumerate(present) if p]
    remote_ids = sorted(i for i in remote if not present[i])
    if targets and len(local_ids) + len(remote_ids) < DATA_SHARDS:
        raise ValueError(
            f"too few shard files to rebuild {base}: "
            f"{len(local_ids) + len(remote_ids)} of {DATA_SHARDS}"
        )
    if targets and not local_ids:
        raise ValueError(
            f"rebuild of {base} needs at least one local survivor (its "
            "size fixes the shard length)"
        )
    return tuple(sorted((local_ids + remote_ids)[:DATA_SHARDS])), targets


class _Gather:
    """The survivor bytes of one rebuild into its ring slots, for both
    rebuild drivers: the one place rebuild bytes cross a disk or the
    network, so the truncation check and the repair accounting live
    here. Volume v's survivors are `survivors_of[v]`, each read from its
    local shard file or, where `remote_of[v]` has a reader for it (shard
    id -> read_into(offset, dest) -> bytes received, `dest` the row of
    the slot the span belongs in: the reader fills it where it lies),
    fetched from the server that holds it. A count short of the row is
    a truncated survivor.

    `opened()` is what ONE reader thread holds: read-only fds, opened
    as it first meets a volume (positioned reads, no seek state shared
    across the pool), and a fetch pool of min(remote survivors, 10)
    threads, so that a tile's remote spans fan out instead of costing
    one round trip each, one after the other (a lone remote survivor is
    read on the reader's own thread). Each remote fetch is one
    `ec.remote_read` annotation on the thread that makes it, booked
    under remote_read_s through `book`; every survivor byte read is
    counted local or remote on weed_ec_repair_bytes_read_total.
    `books()`, once the pools are joined, gives the report line's
    remote fields: survivor_bytes_remote, and what `remote_report()`
    (the caller's readers: arbiter_wait_s, remote_fetches,
    remote_fetches_dataplane) has to add."""

    def __init__(
        self,
        bases: list[str],
        survivors_of: list[tuple[int, ...]],
        remote_of: list[dict[int, Callable[[int, np.ndarray], int]]],
        book: Callable[[str, float], None],
        remote_report: Callable[[], dict] | None = None,
    ):
        from seaweedfs_tpu.ec.ec_files import to_ext

        self._survivors = survivors_of
        self._remote = remote_of
        # [volume] {shard id: path} of the survivors read from disk
        self._paths = [
            {s: base + to_ext(s) for s in survivors if s not in remote}
            for base, survivors, remote in zip(bases, survivors_of, remote_of)
        ]
        self._book = book
        self._remote_report = remote_report
        self._fetchers = min(DATA_SHARDS, max(
            (len(s) - len(p) for s, p in zip(survivors_of, self._paths)),
            default=0,
        ))
        # bytes of every remote fetch (append is GIL-atomic)
        self._remote_bytes: list[int] = []
        self._read_local = EC_REPAIR_BYTES_READ.labels("local")
        self._read_remote = EC_REPAIR_BYTES_READ.labels("remote")

    @contextlib.contextmanager
    def opened(self):
        fds: dict[int, dict[int, int]] = {}
        pool = (
            ThreadPoolExecutor(max_workers=self._fetchers)
            if self._fetchers > 1
            else None
        )
        try:
            yield fds, pool
        finally:
            if pool is not None:
                # wait for in-flight remote fetches: they write into
                # ring slots, the caller closes the readers' connections
                # right after the driver returns, and a fetch still
                # running on a pool thread would see its wire yanked
                # (and leak the thread past return)
                pool.shutdown(wait=True, cancel_futures=True)
            for vol_fds in fds.values():
                for fd in vol_fds.values():
                    os.close(fd)

    def _fetch(self, v: int, sid: int, off: int, row: np.ndarray) -> int:
        t0 = time.perf_counter()
        with trace.annotation("ec.remote_read"):
            got = self._remote[v][sid](off, row)
        self._book("remote_read_s", time.perf_counter() - t0)
        return got

    def read(self, src, reads: list[tuple[int, int, np.ndarray]]) -> None:
        """Fill each (v, off, tile [10, step]) of `reads` with volume
        v's survivor bytes at off: every remote span of them is asked
        for first, the local ones are read while those travel."""
        fds, pool = src
        remote = [
            (n, j, sid)
            for n, (v, _, _) in enumerate(reads)
            for j, sid in enumerate(self._survivors[v])
            if sid not in self._paths[v]
        ]
        futures = {}
        if pool is not None:
            futures = {
                (n, j): pool.submit(self._fetch, reads[n][0], sid, reads[n][1],
                                    reads[n][2][j])
                for n, j, sid in remote
            }
        for v, off, tile in reads:
            vol_fds = fds.get(v)
            if vol_fds is None:
                vol_fds = fds[v] = {}
                for sid, path in self._paths[v].items():
                    vol_fds[sid] = os.open(path, os.O_RDONLY)
            for j, sid in enumerate(self._survivors[v]):
                if sid in vol_fds:
                    got = _pread_into(vol_fds[sid], tile[j], off)
                    self._read_local.inc(got)
                    _check_span(sid, got, tile, off, self._paths[v][sid])
        for n, j, sid in remote:
            v, off, tile = reads[n]
            fut = futures.get((n, j))
            got = fut.result() if fut is not None else self._fetch(v, sid, off, tile[j])
            self._read_remote.inc(got)
            self._remote_bytes.append(got)
            _check_span(sid, got, tile, off, "remote")

    def books(self) -> dict:
        out = {"survivor_bytes_remote": sum(self._remote_bytes)}
        if self._remote_report is not None:
            out.update(self._remote_report())
        return out


def _not_local(remote: dict | None, present: list[bool]) -> dict:
    """The readers of `remote` for shards with no local file: a survivor
    on this server's disk is read from there."""
    return {i: r for i, r in (remote or {}).items() if not present[i]}


def _check_span(sid: int, got: int, tile: np.ndarray, off: int, where: str) -> None:
    if got != tile.shape[1]:
        raise ValueError(
            f"ec shard {sid} truncated: expected {tile.shape[1]} at {off} ({where})"
        )


def _create_empty(paths: list[str], durable: bool) -> None:
    """The outputs of an operation with nothing to stream: empty files,
    fsynced when durable, so the caller's publish can never outlive
    files a crash could drop."""
    from seaweedfs_tpu.util import durable as _durable

    for path in paths:
        open(path, "wb").close()
        if durable:
            _durable.fsync_path(path)


def _settle_outputs(
    fds: list[int], paths: list[str], durable: bool, whole: bool
) -> OSError | None:
    """The abort and durability contract of every operation's output
    files, for the shell and for the inline host arm. fsync each fd only
    if the operation is whole (crash contract, weedcrash, docs/ANALYSIS.md
    v3: an operation acked to its caller must survive power loss, so the
    bytes are pinned before the fds close and the ack leaves). A FAILED
    fsync must fail the operation (swallowing it would ack bytes that
    never reached disk), but only after every fd is closed: the first
    one is kept and RETURNED for the caller to raise. On any failure
    EVERY output file is removed: shard_presence treats any existing
    .ecNN as a valid shard, so full-size garbage would read as a
    complete volume to a later rebuild, or be skipped by a retry."""
    fsync_err: OSError | None = None
    for fd in fds:
        try:
            if durable and whole:
                try:
                    os.fsync(fd)
                except OSError as e:
                    if fsync_err is None:
                        fsync_err = e
            os.close(fd)
        except OSError:
            pass
    if not whole or fsync_err is not None:
        for path in paths:
            try:
                os.remove(path)
            except OSError:
                pass
    return fsync_err


def _charge(busy: dict, lock: threading.Lock, key: str, dt: float) -> None:
    """Accumulate per-stage busy seconds across pool threads (a stage
    total can legitimately exceed wall — it is thread-seconds)."""
    with lock:
        busy[key] += dt


# The serial phases of one operation, all on the handler's thread (it is
# the dispatcher): span and annotation name -> report field. They
# partition wall_s (trace.Phases): head (open the output files, spawn
# the pools, first read; the writers reserve the files meanwhile, see
# _Reservation) | dispatch (first dispatch -> last
# dispatch returned) | drain (-> the last writer's fetch returned:
# nothing left to send, the host waits for the device and the D2H) |
# write_tail (-> the pools joined) | flush (the fsync + close loop).
_OP_PHASES = {
    "ec.op.head": "head_s",
    "ec.op.dispatch": "dispatch_span_s",
    "ec.op.drain": "drain_s",
    "ec.op.write_tail": "write_tail_s",
    "ec.op.flush": "flush_s",
}

# What the device stages book, beside the pool stages and in
# thread-seconds like them: the dispatcher's time in the transfer call
# and in the jitted call. In the single-volume drivers (encode and
# rebuild) h2d_s + launch_s is device_s; the batch drivers' transfer
# (encode and rebuild) is part of their stage_s and launch_s ==
# device_s. Host stage pairs book neither.
_DEVICE_BUSY = {"h2d_s": 0.0, "launch_s": 0.0}

# What the single-volume rebuild books for its rack gather, a pool
# stage like read_s: the fetch pool's thread-seconds inside the remote
# readers' calls (each one span of one survivor over the wire, whatever
# the caller's reader waits for before it moves bytes included). Inside
# read_s, which books a tile's ten reads together, local and remote; 0.0
# where every survivor is local.
_GATHER_BUSY = {"remote_read_s": 0.0}

# What every operation books for its output files' reservation
# (_Reservation): reserve_s is a pool stage like read_s, the writer
# pool's thread-seconds inside _preallocate; reserve_done_s is one
# clock sample, wall seconds from the operation's clock start to the
# moment the last file was reserved and the first shard write could go
# (0.0 on an operation that aborted before that).
_RESERVE_BUSY = {"reserve_s": 0.0, "reserve_done_s": 0.0}

# Where a thread of the shell stands waiting for another, booked where
# it blocks (two clock samples a wait) and named `ec.wait.*` in a
# profiler trace only when the call did block. A pool's thread-seconds
# beside its busy stages; the dispatcher's wall seconds, which with its
# time inside the plan's dispatch call close on dispatch_span_s (the
# residue is the loop's own statements). The pool whose waits are near
# zero is the pace.
#   slot_wait_s        readers   ring.acquire: a free ring slot (the
#                                writers give them back)
#   read_q_wait_s      readers   room in the read queue (the dispatcher
#                                is behind)
#   first_tile_wait_s  dispatcher  tile 0 off the read queue: the part
#                                of head_s that is the first read
#   tile_wait_s        dispatcher  tiles 1..n-1 off the read queue (the
#                                readers, or the ring behind them)
#   dispatch_call_s    dispatcher  the plan's dispatch(item, staged) as
#                                the shell sees it, tile 0's included
#   window_wait_s      dispatcher  room in the in-flight window for
#                                tiles 0..n-2 (the writers are behind);
#                                the last tile's put lies in drain_s
#   work_wait_s        writers   a launched tile off the write queue,
#                                the wait for _EOF included
#   latch_wait_s       writers   _Reservation.wait, result in hand
_WAIT_BUSY = {
    "slot_wait_s": 0.0, "read_q_wait_s": 0.0, "first_tile_wait_s": 0.0,
    "tile_wait_s": 0.0, "dispatch_call_s": 0.0, "window_wait_s": 0.0,
    "work_wait_s": 0.0, "latch_wait_s": 0.0,
}


def _book_reserve_done(busy: dict, reservation, wall0: float) -> None:
    if reservation is not None and reservation.opened_at:
        busy["reserve_done_s"] = reservation.opened_at - wall0


def _close_phases(phases, busy: dict) -> float:
    """End the operation's last phase and book all five into `busy`
    (0.0 for one an aborted operation never entered); returns the
    closing clock sample, which is the end of wall_s."""
    end = phases.close()
    for name, field in _OP_PHASES.items():
        busy[field] = phases.seconds.get(name, 0.0)
    return end


class _Op:
    """The one pipeline shell (module docstring): one operation's pools,
    queues, ring, output files, phases, books and close-out. Made
    before the stage bodies, which book through `book`; `run` then
    drives them and returns when the operation is settled."""

    def __init__(
        self, span: str, device_stage: bool = False,
        extra_busy: dict | None = None,
    ):
        self.span = span
        # the stage traces and launches this process's device programs
        # (program_traces is reported, 0 in steady state)
        self.device_stage = device_stage
        # per-stage busy thread-seconds: read | stage (host staging
        # prep) | device (async dispatch) | writeback (device drain /
        # D2H) or compute (host codec, host CRC) | write — how e2e
        # numbers stay attributable and reader/device/writer overlap is
        # provable per run; the queue, ring and latch waits between the
        # stages are booked apart from them (_WAIT_BUSY)
        self.busy = {
            "read_s": 0.0,
            "stage_s": 0.0,
            "device_s": 0.0,
            "writeback_s": 0.0,
            "compute_s": 0.0,
            "write_s": 0.0,
            **(extra_busy or {}),
            **_RESERVE_BUSY,
            **_WAIT_BUSY,
        }
        self._lock = threading.Lock()
        self.book = functools.partial(_charge, self.busy, self._lock)
        self._traces0 = _program_traces() if device_stage else 0

    def run(
        self,
        *,
        nbytes: int,
        items: list,
        slot_bytes: int,
        outputs: list[tuple[str, int]],
        opened: Callable[[], "contextlib.AbstractContextManager"],
        fill: Callable,
        dispatch: Callable,
        fetch: Callable,
        write: Callable,
        report: Callable[[dict, "object", bool], None],
        checksum: Callable | None = None,
        prepare: Callable | None = None,
        fetch_charges: str = "writeback_s",
        stats: dict | None = None,
        durable: bool = False,
        reader_threads: int | None = None,
        writer_threads: int | None = None,
    ) -> None:
        """`prepare(item) -> item` runs on the reader's thread before it
        takes a slot, outside every stage's clock. `checksum` is skipped
        when None. `report(stats, span, whole)` adds the driver's own
        fields; `whole` says every byte was written and, if asked,
        fsynced."""
        busy, book = self.busy, self.book
        writer_threads = writer_threads or DEFAULT_WRITER_THREADS
        reader_threads = reader_threads or DEFAULT_READER_THREADS
        pipe = _Pipeline()
        read_q: queue.Queue = queue.Queue(maxsize=max(2, reader_threads))
        write_q: queue.Queue = queue.Queue(maxsize=_INFLIGHT)
        # every in-flight tile lives in one of these slots: the window
        # plus the buffers pool threads legitimately hold. The memory is
        # the process's where it is free (_KeptRing)
        ring = _StagingRing(_ring_slots(writer_threads), slot_bytes)
        claims, claim_lock = iter(items), threading.Lock()
        fds: list[int] = []  # opened inside the try: no leak on ENOSPC
        reservation: _Reservation | None = None
        # the latest clock sample at which a writer's fetch returned
        # (under the books' lock): where ec.op.drain ends, read once the
        # pools are joined
        last_fetch = [0.0]
        wall0 = time.perf_counter()
        # tracing plane: the operation is one span whose stages are the
        # pool busy totals and whose children are the serial phases
        # (inherits the scrub/repair plane tag when the caller's context
        # carries one); entered manually because the body below already
        # owns the try/finally structure
        sp = trace.span(self.span, nbytes=nbytes)
        sp.__enter__()
        phases = trace.Phases("ec.op.head", wall0)

        def reader():
            slot_wait = q_wait = 0.0
            try:
                with opened() as src:
                    while True:
                        with claim_lock:
                            item = next(claims, None)
                        if item is None:
                            return
                        if prepare is not None:
                            item = prepare(item)
                        ta = time.perf_counter()
                        got = ring.acquire(pipe.stop)
                        if got is None:
                            slot_wait += time.perf_counter() - ta
                            return
                        slot_id, buf = got
                        t0 = time.perf_counter()
                        slot_wait += t0 - ta
                        with trace.annotation("ec.read"):
                            staged = fill(src, item, buf)
                        book("read_s", time.perf_counter() - t0)
                        ta = time.perf_counter()
                        put = _q_put(
                            read_q, (item, slot_id, staged), pipe.stop,
                            "ec.wait.read_q",
                        )
                        q_wait += time.perf_counter() - ta
                        if not put:
                            ring.release(slot_id)
                            return
            finally:
                book("slot_wait_s", slot_wait)
                book("read_q_wait_s", q_wait)

        def writer():
            work_wait = latch_wait = 0.0
            try:
                reservation.reserve(book)
                while True:
                    ta = time.perf_counter()
                    got = _q_get(write_q, pipe.stop, "ec.wait.work")
                    work_wait += time.perf_counter() - ta
                    if got is _EOF or got is _STOPPED:
                        return
                    item, slot_id, staged, handle = got
                    t0 = time.perf_counter()
                    result = fetch(item, staged, handle)
                    t1 = time.perf_counter()
                    with self._lock:
                        last_fetch[0] = max(last_fetch[0], t1)
                    if checksum is not None:
                        checksum(item, staged, result)
                    t2 = time.perf_counter()
                    reserved = reservation.wait()
                    tw = time.perf_counter()
                    latch_wait += tw - t2
                    if not reserved:
                        return
                    with trace.annotation("ec.write"):
                        write(fds, item, staged, result)
                    t3 = time.perf_counter()
                    ring.release(slot_id)
                    book(fetch_charges, t1 - t0)
                    book("compute_s", t2 - t1)
                    book("write_s", t3 - tw)
            finally:
                book("work_wait_s", work_wait)
                book("latch_wait_s", latch_wait)

        # the dispatcher's own waits and its time in the plan's call:
        # locals of the pacing thread, booked once when the loop is over
        first_tile_wait = tile_wait = dispatch_call = window_wait = 0.0
        ok = False
        try:
            for path, _ in outputs:
                fds.append(
                    os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
                )
            reservation = _Reservation(
                [(fd, size) for fd, (_, size) in zip(fds, outputs)], pipe.stop
            )
            # writers first: they reserve the files beside the first reads
            for _ in range(writer_threads):
                pipe.spawn(writer, "writer")
            for _ in range(min(reader_threads, len(items))):
                pipe.spawn(reader, "reader")
            last = len(items) - 1
            for n in range(len(items)):
                ta = time.perf_counter()
                got = _q_get(read_q, pipe.stop, "ec.wait.tile")
                if got is _STOPPED:
                    break
                item, slot_id, staged = got
                t0 = time.perf_counter()
                if n == 0:
                    first_tile_wait = t0 - ta
                    phases.to("ec.op.dispatch", t0)
                else:
                    tile_wait += t0 - ta
                work = (item, slot_id, staged, dispatch(item, staged))
                if n == last:
                    # the last call's end is the span's: one sample for
                    # both, and this tile's put lies in ec.op.drain
                    t1 = phases.to("ec.op.drain")
                    put = _q_put(write_q, work, pipe.stop)
                else:
                    t1 = time.perf_counter()
                    put = _q_put(write_q, work, pipe.stop, "ec.wait.window")
                    window_wait += time.perf_counter() - t1
                dispatch_call += t1 - t0
                if not put:
                    break
            for _ in range(writer_threads):
                if not _q_put(write_q, _EOF, pipe.stop):
                    break
            ok = True
        finally:
            try:
                pipe.finish(caller_error=not ok)  # may re-raise a stage error
            finally:
                phases.to("ec.op.write_tail", (ok and last_fetch[0]) or None)
                phases.to("ec.op.flush")
                whole = ok and not pipe.errors
                ring.settle(whole)
                fsync_err = _settle_outputs(
                    fds, [path for path, _ in outputs], durable, whole
                )
                try:
                    if fsync_err is not None:
                        raise fsync_err
                finally:
                    # an error surfacing mid-stream must not skip the
                    # stats nor leak any fd (each reader closes its own
                    # in its thread). Raw preallocated fds: nothing
                    # buffered remains, so flush_s measures only the
                    # fsync + close syscalls
                    end = _close_phases(phases, busy)
                    _book_reserve_done(busy, reservation, wall0)
                    # the pools are joined: the books are this thread's
                    busy["first_tile_wait_s"] = first_tile_wait
                    busy["tile_wait_s"] = tile_wait
                    busy["dispatch_call_s"] = dispatch_call
                    busy["window_wait_s"] = window_wait
                    out: dict = {}
                    _finish_stats(
                        out, busy, wall0, reader_threads, writer_threads, end
                    )
                    out["pipeline_depth"] = _INFLIGHT
                    out["ring_slots"] = ring.slots
                    out["ring_fresh_bytes"] = ring.fresh_bytes
                    report(out, sp, whole and fsync_err is None)
                    _trace_stages(sp, busy)
                    sp.annotate("ring_fresh_bytes", ring.fresh_bytes)
                    if self.device_stage:
                        _report_traces(out, sp, self._traces0)
                    if stats is not None:
                        stats.update(out)
                    # a stage error re-raised by pipe.finish() is live
                    # in this finally; hand it to the span so a failed
                    # drive is distinguishable from a clean one in
                    # /debug/traces
                    sp.__exit__(*sys.exc_info())


def _trace_stages(sp, busy: dict) -> None:
    """The operation's booked seconds on its span, under the report
    line's own field names (one vocabulary: what an operator reads at
    /debug/traces is what the node's `ec.<verb> report=` line says).
    Pool stages are thread-seconds; the phases are the span's children."""
    sp.add_stages({k: v for k, v in busy.items() if k.endswith("_s")})


def _finish_stats(
    stats: dict,
    busy: dict,
    wall0: float,
    reader_threads: int = 1,
    writer_threads: int = 1,
    end: float | None = None,
) -> None:
    """Per-stage busy thread-seconds + wall. The PIPELINE stages
    (read/dispatch/fetch/write) run in thread POOLS, so a stage's Σ can
    exceed wall (overlap across threads) — the wall a stage explains is
    its total divided by its pool width. The serial phases of the shell
    (_OP_PHASES, flush_s among them) are different: they partition the
    wall, given the clock sample `end` that closed the last of them."""
    wall = (time.perf_counter() if end is None else end) - wall0
    stats.update({k: round(v, 4) for k, v in busy.items()})
    stats["wall_s"] = round(wall, 4)
    stats["reader_threads"] = reader_threads
    stats["writer_threads"] = writer_threads


# --- codec stage factories --------------------------------------------------


def local_encode_fns(rs, want_crcs: bool = False) -> tuple[Callable, Callable]:
    """(parity_fn, fetch_fn) for a host ReedSolomon backend.

    Unlike the TPU pair — where parity_fn dispatches async device work
    — a host codec has no async engine, so parity_fn just hands the
    tile through and fetch_fn runs the actual matrix apply IN THE
    WRITER POOL. The native SIMD shim releases the GIL inside its C
    call, so W writer threads encode W tiles concurrently instead of
    serializing the codec on the dispatcher thread (measured: the
    single-thread native encode rate was the whole pipeline's cap).

    fetch_fn.charges = "compute_s": the matrix apply is HOST codec
    work, not a device drain — without the tag the stage breakdown
    would book the whole encode as writer-pool writeback time.

    want_crcs=True makes fetch_fn return (parity, [k+p] CRC-32C) pairs
    (codec.parity_with_crc) — the same fused-CRC stage contract the
    device pairs serve on-chip."""

    if want_crcs:

        def fetch_fn(tile: np.ndarray):
            return rs.parity_with_crc(tile)

    else:

        def fetch_fn(tile: np.ndarray):
            return rs._apply(rs.parity_rows, tile)

    fetch_fn.charges = "compute_s"
    return (lambda tile: tile), fetch_fn


def local_rebuild_fns(rs, want_crcs: bool = False) -> tuple[Callable, Callable]:
    """(rebuild_fn, fetch_fn) over a host ReedSolomon backend, with the
    inverted-survivor decode rows cached on the codec (rs.decode_rows)
    and the decode itself deferred to the writer pool (see
    local_encode_fns — including the compute_s charge tag and the
    want_crcs (rebuilt, crcs) contract)."""

    def rebuild_fn(survivors, targets, tile: np.ndarray):
        return (tuple(survivors), tuple(targets), tile)

    if want_crcs:
        from seaweedfs_tpu.util.crc import crc32c

        def fetch_fn(handle):
            survivors, targets, tile = handle
            rebuilt = rs._apply(rs.decode_rows(survivors, targets), tile)
            return rebuilt, [
                crc32c(np.ascontiguousarray(row).tobytes()) for row in rebuilt
            ]

    else:

        def fetch_fn(handle):
            survivors, targets, tile = handle
            return rs._apply(rs.decode_rows(survivors, targets), tile)

    fetch_fn.charges = "compute_s"
    return rebuild_fn, fetch_fn


# --- encode driver ----------------------------------------------------------


def stream_write_ec_files(
    base_file_name: str,
    tile_bytes: int | None = None,
    large_block_size: int = LARGE_BLOCK_SIZE,
    small_block_size: int = SMALL_BLOCK_SIZE,
    parity_fn: Callable[[np.ndarray], "object"] | None = None,
    fetch_fn: Callable[["object"], np.ndarray] | None = None,
    stats: dict | None = None,
    writer_threads: int | None = None,
    reader_threads: int | None = None,
    durable: bool = False,
    want_crcs: bool = False,
) -> None:
    """Pipelined .dat → .ec00…13, byte-identical to write_ec_files.

    durable=True fsyncs every shard fd before returning — the ordering
    the generate verbs need so the .ecx publish that follows can imply
    "shard bytes are on disk" after a crash (weedcrash finding,
    docs/ANALYSIS.md v3: the writer pool's pwritev stream is otherwise
    entirely page-cache-resident when the .ecx lands).

    parity_fn([10, W] u8 host tile) must *dispatch* the parity
    computation and return an opaque handle immediately; fetch_fn turns
    the handle into a [4, W] u8 numpy array — or a
    ([4, W] u8, [14] CRC-32C) pair when the stage computed fused
    shard CRCs (blocking; called concurrently from the writer pool, so
    both must be thread-safe). Row i of a tile is shard i's next W bytes
    (shard-major): a run of `rows` full rows of the .dat is ONE tile of
    W = rows x block, and a sub-block tile of the large tier one of W =
    its step. The defaults run the SWAR kernel on the attached TPU. The
    indirection keeps the pipeline logic testable on CPU hosts (tests
    inject a numpy parity_fn and still exercise tiling/offsets/write
    paths).

    want_crcs=True lands a 14-entry `shard_crcs` list in `stats`: the
    standard CRC-32C of every finished shard FILE, folded from the
    per-tile CRCs the stage pair returns (util/crc.crc32c_combine).
    Tiles whose stage pair declined the fused CRC (injected test fns,
    widths the fused kernel does not take) are checksummed host-side in
    the writer pool and charged to compute_s — the contract holds either
    way. `tiles` (the stage calls the dispatcher made) and `tile_bytes`
    (the per-shard bytes a tile may carry) land in `stats` and on the
    root span.

    Host staging buffers live in a _StagingRing of
    _INFLIGHT + writer_threads + 1 slots (the dispatched-but-unfetched
    window plus the buffers pool threads legitimately hold while
    working), so pipeline memory is bounded and allocator churn stays
    out of the hot loop."""
    if (parity_fn is None) != (fetch_fn is None):
        raise ValueError("parity_fn and fetch_fn must be injected together")
    device_stage = parity_fn is None
    op = _Op(
        "ec_stream.encode", device_stage, _DEVICE_BUSY if device_stage else None
    )
    if device_stage:
        parity_fn, fetch_fn = _tpu_encode_fns(want_crcs=want_crcs, book=op.book)
    tile_bytes = tile_bytes or VOLUME_TILE_BYTES

    dat_path = base_file_name + ".dat"
    dat_size = os.path.getsize(dat_path)
    from seaweedfs_tpu.ec.ec_files import to_ext

    # tiles and their shard-file output offsets, precomputed: each tile
    # contributes exactly `width` bytes per shard in generation order,
    # so positioned writes land it wherever it finishes
    tiles = _encode_tiles(
        dat_size, tile_bytes, large_block_size, small_block_size
    )
    widths = [step * rows for _, _, _, step, rows in tiles]
    out_offs = list(itertools.accumulate(widths, initial=0))
    shard_bytes = out_offs.pop()
    # per-tile shard CRCs, filled by the writer pool (index writes are
    # GIL-atomic), folded into whole-file CRCs after the join
    tile_crcs: list = [None] * len(tiles)
    launches = 0

    def fill(src, k, buf):
        # one [10, width] ring-slot prefix per tile, read straight into
        # it (no bytes objects, no shared seek position across the
        # pool), zero-padded past EOF like read_dat_tile. The codec
        # takes it as it lies and the writers land each of its rows with
        # one pwrite, so the bytes are copied exactly once between disk
        # reads and writes.
        (fd,) = src
        tile = buf[: DATA_SHARDS * widths[k]].reshape(DATA_SHARDS, widths[k])
        _read_encode_tile(fd, dat_size, tiles[k], tile)
        return tile

    def dispatch(k, tile):
        nonlocal launches
        t0 = time.perf_counter()
        handle = parity_fn(tile)  # one async dispatch a tile
        op.book("device_s", time.perf_counter() - t0)
        launches += 1
        return handle

    def fetch(k, tile, handle):
        """(parity [4, width], 14 CRCs or None)."""
        got = fetch_fn(handle)
        return got if isinstance(got, tuple) else (got, None)

    def checksum(k, tile, result):
        # a tile for which the stage declined the fused CRC (injected
        # pair / unsupported width): table-CRC the written bytes here
        from seaweedfs_tpu.util.crc import crc32c

        parity, crcs = result
        if crcs is None:
            crcs = [crc32c(row.tobytes()) for row in tile] + [
                crc32c(np.ascontiguousarray(row).tobytes()) for row in parity
            ]
        tile_crcs[k] = crcs

    def write(fds, k, tile, result):
        parity, off = result[0], out_offs[k]
        for i in range(DATA_SHARDS):
            _pwrite_full(fds[i], tile[i], off)
        for p in range(PARITY_SHARDS):
            _pwrite_full(
                fds[DATA_SHARDS + p], np.ascontiguousarray(parity[p]), off
            )

    def report(out, sp, whole):
        out["driver"] = _driver_name(device_stage)
        if hasattr(parity_fn, "arms"):
            out["arms"] = dict(parity_fn.arms)
        out["tiles"] = launches
        out["tile_bytes"] = tile_bytes
        sp.annotate("tiles", launches)
        sp.annotate("tile_bytes", tile_bytes)
        if want_crcs and whole:
            out["shard_crcs"] = _fold_encode_crcs(widths, tile_crcs)

    op.run(
        nbytes=dat_size,
        items=list(range(len(tiles))),
        slot_bytes=DATA_SHARDS * max(widths, default=tile_bytes),
        outputs=[
            (base_file_name + to_ext(i), shard_bytes)
            for i in range(TOTAL_SHARDS)
        ],
        opened=lambda: _opened([dat_path]),
        fill=fill,
        dispatch=dispatch,
        fetch=fetch,
        checksum=checksum if want_crcs else None,
        write=write,
        report=report,
        fetch_charges=getattr(fetch_fn, "charges", "writeback_s"),
        stats=stats,
        durable=durable,
        reader_threads=reader_threads,
        writer_threads=writer_threads,
    )


def _fold_encode_crcs(widths: list[int], tile_crcs: list) -> list[int]:
    """Whole-shard-file CRC-32C per shard from the per-tile CRCs: fold
    in tile generation order with crc32c_combine (tiles land on disk in
    ANY order — positioned writes — but the fold is over the recorded
    CRCs, so completion order is irrelevant here too)."""
    from seaweedfs_tpu.util.crc import crc32c_combine

    crcs = [0] * TOTAL_SHARDS
    for width, tile in zip(widths, tile_crcs):
        for i in range(TOTAL_SHARDS):
            crcs[i] = crc32c_combine(crcs[i], int(tile[i]), width)
    return crcs


def _encode_tiles(
    dat_size: int, tile_bytes: int, large: int, small: int
) -> list[tuple[int, int, int, int, int]]:
    """(row_off, block, batch_off, step, rows) tiles of the single-volume
    encode, in generation order. A run of consecutive FULL rows (the
    whole small-block tier once tile_bytes >= small_block_size) is cut
    into tiles of a power-of-two count of rows, the largest first, at
    most tile_bytes // block a tile: 103 rows of 1 MiB under 4 MiB tiles
    are 25 x 4 + 2 + 1, so every tile's width is a power of two where the
    block is, the lane counts the fused CRC takes (crc_kernel.
    crc_supported). A sub-block tile of the large tier is one tile of
    rows == 1, as ec_files.iter_ec_tiles yields it."""
    from seaweedfs_tpu.ec.ec_files import iter_ec_tiles

    runs: list[list[int]] = []
    for row_off, block, batch_off, step in iter_ec_tiles(
        dat_size, tile_bytes, large, small
    ):
        prev = runs[-1] if runs else None
        if (
            prev is not None
            and batch_off == 0 and step == block
            and prev[2] == 0 and prev[3] == prev[1] == block
            and prev[0] + prev[4] * block * DATA_SHARDS == row_off
        ):
            prev[4] += 1  # the next full row, contiguous in the .dat
        else:
            runs.append([row_off, block, batch_off, step, 1])
    tiles = []
    for row_off, block, batch_off, step, rows in runs:
        most = max(1, tile_bytes // block)
        most = 1 << (most.bit_length() - 1)
        while rows:
            n = min(most, 1 << (rows.bit_length() - 1))
            tiles.append((row_off, block, batch_off, step, n))
            row_off += n * block * DATA_SHARDS
            rows -= n
    return tiles


def _read_encode_tile(
    fd: int, dat_size: int, tile: tuple[int, int, int, int, int],
    dest: np.ndarray,
) -> None:
    """Fill dest [10, rows x step] with one tile of _encode_tiles, read
    from the .dat: a run of full rows with ONE preadv, shard-major
    (_read_rows_into), a sub-block tile of the large tier one read a
    shard (_read_tile_into)."""
    row_off, block, batch_off, step, _rows = tile
    if batch_off == 0 and step == block:
        _read_rows_into(fd, dat_size, row_off, step, dest)
    else:
        _read_tile_into(fd, dat_size, row_off, block, batch_off, step, dest)


# --- rebuild driver ---------------------------------------------------------


def stream_rebuild_ec_files(
    base_file_name: str,
    tile_bytes: int | None = None,
    rebuild_fn: Callable[[tuple[int, ...], tuple[int, ...], np.ndarray], "object"]
    | None = None,
    fetch_fn: Callable[["object"], np.ndarray] | None = None,
    stats: dict | None = None,
    remote_readers: dict[int, Callable[[int, np.ndarray], int]] | None = None,
    remote_report: Callable[[], dict] | None = None,
    writer_threads: int | None = None,
    reader_threads: int | None = None,
    session=None,
    durable: bool = False,
    want_crcs: bool = False,
) -> list[int]:
    """Pipelined shard rebuild, byte-identical to rebuild_ec_files.

    rebuild_fn(survivors, targets, [10, step] u8) dispatches
    reconstruction of `targets` from the survivor tile and returns a
    handle; fetch_fn blocks it into [len(targets), step] u8 — or a
    ([len(targets), step] u8, [len(targets)] CRC-32C) pair when the
    stage fused the Castagnoli pass (called from the writer pool —
    both must be thread-safe).

    want_crcs=True lands `shard_crcs` in `stats`: a {shard id: CRC-32C
    of the whole rebuilt file} dict folded from per-range CRCs
    (device-fused where the stage supports the shape, host table CRC
    for donated ranges and odd tails, charged to compute_s).

    remote_readers maps shard id → read_into(offset, dest) -> bytes
    received for survivors that live on OTHER nodes, `dest` the
    [g_len] u8 row of the ring slot the span belongs in: the reader
    fills it where it lies (the gather neither joins nor copies), and
    a count short of len(dest) is a truncated survivor. The reader
    pool pulls their tiles over the wire in parallel with local preadv
    and the decode, and shards readable remotely are treated as
    present (not rebuilt). At least one survivor must be local — its
    file size fixes the tile walk. The gather is _Gather's, the batch
    driver's too: each remote fetch is one `ec.remote_read` annotation
    on the thread that makes it and is booked under remote_read_s;
    remote_report(), called once the pools are joined, gives what the
    caller's readers have to add to the report line and the root
    span's attributes (the volume server's: arbiter_wait_s,
    remote_fetches, remote_fetches_dataplane).

    `session` (an ec.repair_session.RebuildSession) is the repair-
    bandwidth-frugal hookup: tiles degraded serving already decoded are
    consumed as donations, so the reader gathers survivors only for the
    GAPS — range-aligned sub-shard reads instead of the naive whole-
    range k-gather — and the reader yields to in-flight degraded
    gathers between tiles (serving never starves behind repair). Every
    survivor byte gathered is counted local-vs-remote on
    weed_ec_repair_bytes_read_total, every rebuilt byte written on
    weed_ec_repair_bytes_written_total.

    `durable=True` fsyncs the rebuilt shard files before returning
    (the weedcrash contract for the generate/rebuild verbs: an acked
    shard set survives a crash — docs/ANALYSIS.md v3)."""
    if (rebuild_fn is None) != (fetch_fn is None):
        raise ValueError("rebuild_fn and fetch_fn must be injected together")
    device_stage = rebuild_fn is None
    op = _Op(
        "ec_stream.rebuild",
        device_stage,
        {**(_DEVICE_BUSY if device_stage else {}), **_GATHER_BUSY},
    )
    if device_stage:
        rebuild_fn, fetch_fn = _tpu_rebuild_fns(want_crcs=want_crcs, book=op.book)
    tile_bytes = tile_bytes or VOLUME_TILE_BYTES
    fold_spans = device_stage and want_crcs

    from seaweedfs_tpu.ec.ec_files import shard_presence, to_ext

    present, local_missing = shard_presence(base_file_name)
    remote_readers = _not_local(remote_readers, present)
    survivors, targets = _survivor_choice(
        base_file_name, present, local_missing, remote_readers
    )
    if not targets:
        return []
    shard_size = os.path.getsize(
        base_file_name + to_ext(next(i for i in survivors if present[i]))
    )
    # (range offset, range length, [crc per target]) from the writer
    # pool, folded into whole-file CRCs after the join (append is
    # GIL-atomic; order restored by sorting on offset)
    crc_ranges: list[tuple[int, int, list[int]]] = []
    # survivor bytes of every gather, from the reader pool (append is
    # GIL-atomic too), and the dispatcher's own count of its launches
    gathered: list[int] = []
    # rebuilt bytes of every write, from the writer pool
    written: list[int] = []
    launches = 0
    n_remote = sum(1 for i in survivors if not present[i])
    gather = _Gather(
        [base_file_name], [survivors], [remote_readers], op.book, remote_report
    )

    def claim(offset):
        """(offset, covered, gaps) of the tile at `offset`."""
        step = min(tile_bytes, shard_size - offset)
        if session is None:
            return offset, [], [(offset, step)]
        # serve-first arbitration: degraded GET gathers in flight own
        # the disks/links; repair waits (bounded)
        session.yield_to_serving()
        return (offset, *session.consume(offset, step))

    def fill(src, item, buf):
        # parts: ("don", off, {target: bytes}) ride through as bytes;
        # ("raw", off, [k, n] tile) get decoded. Only the gaps pay
        # survivor reads — donated ranges moved zero new bytes
        # (arXiv:2205.11015's partial-repair shape). Gap tiles
        # sub-allocate contiguous views out of the tile's ring slot
        # (Σ gap bytes ≤ step, so they fit).
        _, covered, gaps = item
        parts: list = [("don", d_off, per_t) for d_off, per_t in covered]
        reads = []
        cur = 0
        for gap in gaps:
            # the device stage folds the CRC of a power-of-two span in
            # its program; a short tile (the 3 MiB tail of a 103 MiB
            # shard under 4 MiB tiles) goes as such spans
            spans = _pow2_spans(*gap, tile_bytes) if fold_spans else [gap]
            for g_off, g_len in spans:
                tile = buf[cur : cur + DATA_SHARDS * g_len].reshape(
                    DATA_SHARDS, g_len
                )
                cur += DATA_SHARDS * g_len
                reads.append((0, g_off, tile))
                parts.append(("raw", g_off, tile))
                gathered.append(DATA_SHARDS * g_len)
        gather.read(src, reads)
        return parts

    def dispatch(item, parts):
        nonlocal launches
        t0 = time.perf_counter()
        parts = [
            (
                ("h", off, rebuild_fn(survivors, targets, payload))
                if kind == "raw"
                else (kind, off, payload)
            )
            for kind, off, payload in parts
        ]
        op.book("device_s", time.perf_counter() - t0)
        launches += sum(1 for kind, _, _ in parts if kind == "h")
        return parts

    def fetch(item, _parts, parts):
        """[(kind, off, payload, crcs or None)]: handles fetched."""
        fetched = []
        for kind, off, payload in parts:
            crcs = None
            if kind == "h":
                payload = fetch_fn(payload)
                if isinstance(payload, tuple):
                    payload, crcs = payload
            fetched.append((kind, off, payload, crcs))
        return fetched

    def rows_of(kind, payload) -> list:
        """One fetched part's buffers, one per target in their order."""
        if kind == "don":
            return [payload[i] for i in targets]
        return [np.ascontiguousarray(payload[j]) for j in range(len(targets))]

    def checksum(item, _parts, fetched):
        # donated ranges and declined-fused tiles: table-CRC the bytes
        # being written
        from seaweedfs_tpu.util.crc import crc32c

        for kind, off, payload, crcs in fetched:
            rows = rows_of(kind, payload)
            if crcs is None:
                crcs = [crc32c(bytes(row)) for row in rows]
            crc_ranges.append((off, len(rows[0]), [int(c) for c in crcs]))

    def write(fds, item, _parts, fetched):
        for kind, off, payload, _ in fetched:
            for fd, row in zip(fds, rows_of(kind, payload)):
                _pwrite_full(fd, row, off)
                EC_REPAIR_BYTES_WRITTEN.inc(len(row))
                written.append(len(row))

    def report(out, sp, whole):
        out["driver"] = _driver_name(device_stage)
        if hasattr(rebuild_fn, "arms"):
            out["arms"] = dict(rebuild_fn.arms)
        if want_crcs and whole:
            out["shard_crcs"] = _fold_rebuild_crcs(targets, crc_ranges)
        shape = _rebuild_shape(launches, survivors, targets, sum(gathered))
        # the gather's split, in what the repair counters took: of the
        # survivors and their bytes those that crossed the wire, and the
        # rebuilt bytes written (weed_ec_repair_bytes_written_total)
        shape["remote_survivors"] = n_remote
        shape["rebuilt_bytes"] = sum(written)
        shape.update(gather.books())
        out.update(shape)
        for key, value in shape.items():
            sp.annotate(key, value)
        if session is not None:
            out["donated_bytes"] = session.donated_bytes
            out["used_donated_bytes"] = session.used_donated_bytes
            out["serve_yields"] = session.yields
            sp.annotate("donated_bytes", session.used_donated_bytes)
            sp.annotate("serve_yields", session.yields)

    op.run(
        nbytes=shard_size * max(1, len(targets)),
        items=list(range(0, shard_size, tile_bytes)),
        # gap gathers sub-allocate contiguous [k, g_len] views out of
        # one flat slot per tile
        slot_bytes=DATA_SHARDS * tile_bytes,
        outputs=[(base_file_name + to_ext(i), shard_size) for i in targets],
        opened=gather.opened,
        prepare=claim,
        fill=fill,
        dispatch=dispatch,
        fetch=fetch,
        checksum=checksum if want_crcs else None,
        write=write,
        report=report,
        fetch_charges=getattr(fetch_fn, "charges", "writeback_s"),
        stats=stats,
        durable=durable,
        reader_threads=reader_threads,
        writer_threads=writer_threads,
    )
    return list(targets)


def _pow2_spans(off: int, length: int, tile_bytes: int) -> list[tuple[int, int]]:
    """(offset, length) spans covering [off, off + length), each a power
    of two (what crc_kernel.crc_supported takes) from the largest down,
    for as long as a span is an eighth of a tile or more; what is left
    below that rides as one last span of any length. A whole tile is one
    span; 3 MiB under 4 MiB tiles are 2 MiB + 1 MiB."""
    spans = []
    while length:
        span = 1 << (length.bit_length() - 1)
        if span * 8 < tile_bytes:
            span = length
        spans.append((off, span))
        off, length = off + span, length - span
    return spans


def _fold_rebuild_crcs(
    targets: tuple[int, ...], crc_ranges: list[tuple[int, int, list[int]]]
) -> dict[int, int]:
    """{target shard id: whole-file CRC-32C} from the writer pool's
    per-range CRCs: ranges land in any order (positioned writes), so
    sort by offset and fold with crc32c_combine."""
    from seaweedfs_tpu.util.crc import crc32c_combine

    acc = {i: 0 for i in targets}
    for _off, length, crcs in sorted(crc_ranges, key=lambda r: r[0]):
        for j, i in enumerate(targets):
            acc[i] = crc32c_combine(acc[i], crcs[j], length)
    return acc


# --- default TPU kernel stages ---------------------------------------------


def _swar_ok(step: int) -> bool:
    from seaweedfs_tpu.ec.codec_tpu import _SWAR_MIN_BYTES, _on_tpu

    return step % 1024 == 0 and step >= _SWAR_MIN_BYTES and _on_tpu()


def _fetch(handle) -> np.ndarray:
    """Block a dispatched kernel handle into a host uint8 array — or
    (uint8 array, crc uint32 array) when the dispatch fused the CRC
    pass (the driver splits on the tuple). One annotation, the wait for
    the program and the copy together, as writeback_s books them: a
    host-side sample between the two costs the batch cell 4 to 8 % of
    its throughput (PERF.md section 6, PR 26); a device trace tells them
    apart, where the program's last operation ends inside the
    annotation."""
    import jax

    out, swar, fused_crc = handle
    with trace.annotation("ec.writeback"):
        if fused_crc:
            dev, crcs = out
            host = np.asarray(jax.device_get(dev))
            return host.view(np.uint8), np.asarray(jax.device_get(crcs))
        host = np.asarray(jax.device_get(out))
    return host.view(np.uint8) if swar else host


def _crc_ok(step: int, want_crcs: bool) -> bool:
    from seaweedfs_tpu.ec import crc_kernel

    return want_crcs and crc_kernel.crc_supported(step)


def _driver_name(device_stage: bool) -> str:
    """stats["driver"] of the single-volume stream drivers: which side
    of the staging ring applies the matrix — the self-provisioned
    device stage pair, or one the caller injected (a host codec)."""
    return "stream-device" if device_stage else "stream-host"


def _new_arms() -> dict:
    """Dispatches per kernel arm, counted by the device stage pair on
    the dispatcher thread and published as stats["arms"] — how a
    caller sees that the SWAR kernel ran and not the bit-matmul the
    same code takes off-TPU or on an odd tail."""
    return {"swar+crc": 0, "swar": 0, "bit-matmul": 0}


_KEPT: dict[tuple, object] = {}
_KEPT_LOCK = threading.Lock()


def _kept(key: tuple, build: Callable[[], "object"]):
    """The process's one object for `key`, built under a lock the first
    time it is asked for (gRPC handler threads run EC verbs
    concurrently) and handed out without one from then on."""
    obj = _KEPT.get(key)
    if obj is None:
        with _KEPT_LOCK:
            obj = _KEPT.get(key)
            if obj is None:
                obj = _KEPT[key] = build()
    return obj


class _KeptRing:
    """The process's kept staging memory: ONE flat allocation that an
    operation borrows for its ring (_StagingRing) and gives back settled,
    so that the memory outlives the operation the way the programs do.
    It belongs to one operation at a time: gRPC handler threads run EC
    verbs concurrently (an encode beside a repair), and one that finds
    it lent out, or too small, allocates its own as every operation did
    before. The largest allocation that comes back is the one kept, so
    what is kept grows to the largest ring the node has run, up to
    _RING_KEEP_BYTES; a larger ring is never kept. No knob: the decision
    is made from the requested bytes."""

    def __init__(self):
        self._lock = threading.Lock()
        self._arena: np.ndarray | None = None  # None: none yet, or lent out

    def borrow(self, nbytes: int) -> tuple[np.ndarray, int]:
        """(flat uint8 memory of at least nbytes, the bytes of it that
        were allocated now: 0 when it is the kept memory)."""
        with self._lock:
            arena, self._arena = self._arena, None
        if arena is not None:
            if arena.size >= nbytes:
                return arena, 0
            self.give_back(arena)  # too small for this one: leave it
        EC_RING_FRESH_BYTES.inc(nbytes)
        return np.empty(nbytes, dtype=np.uint8), nbytes

    def give_back(self, arena: np.ndarray) -> None:
        if arena.size > _RING_KEEP_BYTES:
            return
        with self._lock:
            if self._arena is None or self._arena.size < arena.size:
                self._arena = arena


# the process's one, beside the kept programs
_RING = _KeptRing()


class _DevicePrograms:
    """The single-chip stage pairs' device programs: ONE TpuCodecKernels
    and one jitted program per kernel arm of encode and of rebuild, kept
    for the life of the process. A jax.jit object carries its own cache
    of traced and loaded programs, so an operation that made new ones
    traced, lowered and re-loaded a program the process ran a second
    before (0.5 s of a 1.2 s encode of 1 GiB on a v5e, PERF.md section
    6, PR 27); these are traced once per tile shape (and per survivor
    set, the rebuild programs' static arguments) and then only launched.
    Each body counts its traces (codec_tpu.counted_jit).

    Use after construction takes no lock: jax.jit objects are
    thread-safe, and two threads that miss one of the kernels' decode
    row dicts at once both fill it with the same matrix. What now lives
    as long as the process is bounded by the distinct (survivors,
    targets) sets it has rebuilt, at most 15,015 for RS(10,4) (1,001
    survivor sets by the 15 subsets of the other four): per set a 4x10
    coefficient matrix, on the bit-matmul arm its 32x80 bit matrix
    (2.6 KB, on the host), and one compiled program, which JAX's own
    bounded caches hold."""

    def __init__(self):
        from seaweedfs_tpu.ec.codec_tpu import TpuCodecKernels, counted_jit

        self.kern = kern = TpuCodecKernels(DATA_SHARDS, PARITY_SHARDS)
        # no donate_argnums: no output has the [10, n32] tile's shape, so
        # XLA cannot reuse its buffer ("Some donated buffers were not
        # usable" on the chip, PR 21); the tile is freed once the kernel
        # has read it either way, because nothing keeps a reference
        self.encode_u32 = counted_jit(kern.encode_u32)
        # fused encode+CRC program (ec/crc_kernel.py rides the same
        # dispatch): parity AND all 14 per-row CRCs come back from one
        # device pass, so the host never re-reads parity bytes to
        # checksum them
        self.encode_u32_crc = counted_jit(kern.encode_u32_crc)
        self.encode = counted_jit(kern.encode)
        self.reconstruct_u32 = counted_jit(
            kern.reconstruct_u32, static_argnums=(0, 1)
        )
        self.reconstruct_u32_crc = counted_jit(
            kern.reconstruct_u32_crc, static_argnums=(0, 1)
        )
        self.reconstruct = counted_jit(kern.reconstruct, static_argnums=(0, 1))


def _device_programs() -> _DevicePrograms:
    """The process's _DevicePrograms for everything a trace of them
    reads that is not an argument: the code's shape, the kernel arm
    (_on_tpu) and the XOR schedule flag (swar_apply_matrix_u32 reads it
    while tracing). The key only keeps apart programs that differ."""
    from seaweedfs_tpu.ec.codec_tpu import _on_tpu
    from seaweedfs_tpu.ec.schedule import schedule_enabled

    return _kept(
        ("chip", DATA_SHARDS, PARITY_SHARDS, _on_tpu(), schedule_enabled()),
        _DevicePrograms,
    )


def _program_traces() -> int:
    """codec_tpu.program_traces() of the calling thread (imported here:
    only a device stage may pull JAX in)."""
    from seaweedfs_tpu.ec.codec_tpu import program_traces

    return program_traces()


def _report_traces(stats: dict | None, sp, traces0: int) -> None:
    """program_traces of the operation that read `traces0` at its start
    on this, its dispatcher's, thread: into its stats (the node's report
    line) and onto its root span. 0 in steady state."""
    n = _program_traces() - traces0
    if stats is not None:
        stats["program_traces"] = n
    sp.annotate("program_traces", n)


def _tpu_encode_fns(want_crcs: bool, book: Callable[[str, float], None]):
    """(parity_fn, fetch_fn) on the attached device. `book(field, dt)`
    takes the device fields (_DEVICE_BUSY) of the driver's `busy`. The
    programs are the process's (_device_programs); the closures, the
    arm counts and the booking are this operation's."""
    import jax.numpy as jnp

    progs = _device_programs()
    arms = _new_arms()

    def parity_fn(tile: np.ndarray):
        t0 = time.perf_counter()
        swar = _swar_ok(tile.shape[1])
        fused_crc = swar and _crc_ok(tile.shape[1], want_crcs)
        if fused_crc:
            arm, program = "swar+crc", progs.encode_u32_crc
        elif swar:
            arm, program = "swar", progs.encode_u32
        else:
            arm, program = "bit-matmul", progs.encode
        with trace.annotation("ec.h2d"):
            # async H2D; the SWAR arms take the byte stream 4 per lane
            dev = jnp.asarray(tile.view(np.uint32) if swar else tile)
        t1 = time.perf_counter()
        with trace.annotation("ec.launch"):
            out = program(dev)  # async dispatch
        t2 = time.perf_counter()
        arms[arm] += 1
        book("h2d_s", t1 - t0)
        book("launch_s", t2 - t1)
        return out, swar, fused_crc

    parity_fn.arms = arms
    return parity_fn, _fetch


def _tpu_rebuild_fns(want_crcs: bool, book: Callable[[str, float], None]):
    """(rebuild_fn, fetch_fn) on the attached device, over the same kept
    programs as _tpu_encode_fns and booking the same device fields."""
    import jax.numpy as jnp

    progs = _device_programs()
    arms = _new_arms()

    def rebuild_fn(survivors, targets, tile: np.ndarray):
        t0 = time.perf_counter()
        swar = _swar_ok(tile.shape[1])
        fused_crc = swar and _crc_ok(tile.shape[1], want_crcs)
        if fused_crc:
            arm, program = "swar+crc", progs.reconstruct_u32_crc
        elif swar:
            arm, program = "swar", progs.reconstruct_u32
        else:
            arm, program = "bit-matmul", progs.reconstruct
        with trace.annotation("ec.h2d"):
            dev = jnp.asarray(tile.view(np.uint32) if swar else tile)
        t1 = time.perf_counter()
        with trace.annotation("ec.launch"):
            out = program(tuple(survivors), tuple(targets), dev)
        t2 = time.perf_counter()
        arms[arm] += 1
        book("h2d_s", t1 - t0)
        book("launch_s", t2 - t1)
        return out, swar, fused_crc

    rebuild_fn.arms = arms
    return rebuild_fn, _fetch


def _read_rows_into(
    fd: int, dat_size: int, row_off: int, step: int, dest: np.ndarray
) -> None:
    """Fill dest [10, rows x step] with `rows` whole rows of the .dat from
    row_off, shard-major: row r's block i lands at dest[i, r*step :
    (r+1)*step], so each row of dest is one shard's run of blocks. The
    rows lie contiguous in the .dat, so ONE preadv whose buffers list
    those places in file order reads them all; zero-padded past EOF (and
    past a truncated .dat's end) like read_dat_tile."""
    rows = dest.shape[1] // step
    places = [
        dest[i, r * step : (r + 1) * step]
        for r in range(rows)
        for i in range(DATA_SHARDS)
    ]
    n = max(0, min(len(places) * step, dat_size - row_off))
    full, part = divmod(n, step)
    got = _preadv_into(
        fd, places[:full] + ([places[full][:part]] if part else []), row_off
    ) if n else 0
    full, part = divmod(got, step)
    if full < len(places):
        places[full][part:] = 0
        for place in places[full + 1 :]:
            place[:] = 0


def _read_tile_into(
    fd: int, dat_size: int, row_off: int, block: int, batch_off: int,
    step: int, dest: np.ndarray,
) -> None:
    """Fill dest [10, step] (ring-slot views) with one volume's sub-block
    tile of the .dat, zero-padded past EOF: one read a shard, since a
    sub-block tile's ten blocks lie a block apart in the .dat. Both
    encode drivers take their runs of full rows with one preadv instead
    (_read_rows_into), strided rows of a slot included."""
    for i in range(DATA_SHARDS):
        row = dest[i]
        off = row_off + i * block + batch_off
        n = max(0, min(step, dat_size - off))
        if n < step:
            row[n:] = 0
        if n:
            got = _pread_into(fd, row[:n], off)
            if got < n:
                row[got:n] = 0


# --- mesh-batched encode driver ---------------------------------------------


def stream_write_ec_files_batch(
    base_file_names: list[str],
    codec=None,
    tile_bytes: int | None = None,
    large_block_size: int = LARGE_BLOCK_SIZE,
    small_block_size: int = SMALL_BLOCK_SIZE,
    stats: dict | None = None,
    durable: bool = False,
    want_crcs: bool = False,
    reader_threads: int | None = None,
    writer_threads: int | None = None,
) -> None:
    """Pipelined batch-of-volumes encode through the mesh codec: N
    sealed .dat files → N shard sets, byte-identical per volume to
    write_ec_files, with disk reads, host staging, the sharded device
    program (parallel/mesh_codec.encode_batch_u32[_crc] under
    shard_map), device drain, and shard pwritevs all overlapped by the
    same staging-ring pipeline the single-volume driver runs. This is
    how a batch of SMALL volumes saturates one chip (the per-volume
    dispatch was latency-bound) and a mesh of chips splits the stream
    axis of large ones.

    codec=None self-provisions a MeshCodec whose 'vol' axis is the gcd
    of batch size and device count (any batch shards cleanly); when
    jax itself is unavailable the whole batch falls back to the
    single-volume host-codec driver per volume — byte-identical, just
    unbatched. WEED_EC_PIPELINE_BATCH caps volumes per dispatch round
    (ring memory = slots x batch x 10 x tile bytes).

    A round holds, for each volume, one tile of the single-volume
    encode's cut (_encode_tiles): a run of full rows of a power-of-two
    count, laid out shard-major, read with ONE preadv and written with
    ONE pwrite a shard file. `tile_bytes` is the most per-shard bytes a
    volume's tile may carry; left None it follows from the volumes a
    round stacks and the ring the process keeps (batch_round_tile_bytes:
    2 MiB, two rows of 1 MiB, for four volumes under twelve slots; 1 MiB
    for six). `tiles` (the rounds the dispatcher sent, summed over the
    WEED_EC_PIPELINE_BATCH chunks) and `tile_bytes` land in `stats` and
    on the root span.

    want_crcs=True lands `shard_crcs` in stats: one 14-entry CRC-32C
    list per volume (fused on-mesh where a volume's tile fills the
    round's width — including the stripe-axis CRC composition
    collective — host table CRC for a narrower tile)."""
    if not base_file_names:
        return
    limit = pipeline_batch_limit()
    if limit and len(base_file_names) > limit:
        all_crcs: list = []
        for i in range(0, len(base_file_names), limit):
            chunk_stats: dict = {}
            stream_write_ec_files_batch(
                base_file_names[i : i + limit],
                # each chunk self-provisions a mesh that fits ITS size
                # (gcd sizing): a caller codec built for the WHOLE
                # batch need not divide a chunk — passing it through
                # would brick the verb the moment the memory-cap knob
                # splits the batch unevenly
                codec=None,
                tile_bytes=tile_bytes,
                large_block_size=large_block_size,
                small_block_size=small_block_size,
                stats=chunk_stats,
                durable=durable,
                want_crcs=want_crcs,
                reader_threads=reader_threads,
                writer_threads=writer_threads,
            )
            if want_crcs:
                all_crcs.extend(chunk_stats.get("shard_crcs", []))
            if stats is not None:
                for k, v in chunk_stats.items():
                    if isinstance(v, float):
                        # stage seconds accumulate across chunks
                        stats[k] = round(stats.get(k, 0.0) + v, 4)
                    elif k in ("program_traces", "ring_fresh_bytes", "tiles"):
                        stats[k] = stats.get(k, 0) + v
                    elif k != "shard_crcs":
                        # structural fields (pipeline_depth, mesh,
                        # ring_slots, thread counts): last chunk's
                        # values — dropping them would break every
                        # consumer the docs promise them to
                        stats[k] = v
        if stats is not None:
            stats["batch_volumes"] = len(base_file_names)
            if want_crcs:
                stats["shard_crcs"] = all_crcs
        return
    if codec is None:
        try:
            codec = _default_mesh_codec(len(base_file_names))
        except ImportError:
            # no jax at all: the host-codec single-volume pipeline is
            # the byte-identical fallback seam
            from seaweedfs_tpu.ec.codec import new_encoder

            rs = new_encoder()
            all_crcs = []
            for base in base_file_names:
                s: dict = {}
                parity_fn, fetch_fn = local_encode_fns(rs, want_crcs=want_crcs)
                stream_write_ec_files(
                    base,
                    tile_bytes=tile_bytes,
                    large_block_size=large_block_size,
                    small_block_size=small_block_size,
                    parity_fn=parity_fn,
                    fetch_fn=fetch_fn,
                    stats=s,
                    durable=durable,
                    want_crcs=want_crcs,
                )
                if want_crcs:
                    all_crcs.append(s.get("shard_crcs"))
            if stats is not None:
                stats["fallback"] = "host"
                if want_crcs:
                    stats["shard_crcs"] = all_crcs
            return
    _stream_batch_chunk(
        base_file_names, codec, tile_bytes, large_block_size,
        small_block_size, stats, durable, want_crcs, reader_threads,
        writer_threads,
    )


def _default_mesh_codec(batch: int):
    """The process's MeshCodec over all devices with the 'vol' axis
    sized to gcd(batch, devices) so any batch shards cleanly (the
    BatchGenerate verb's mesh recipe, owned by the driver). The SAME
    object for the same mesh, because its jitted programs live on it
    (see _DevicePrograms): one on one chip, at most three on four
    (the gcd is 1, 2 or 4). The schedule flag is in the key because the
    mesh programs' SWAR kernels read it while tracing."""
    import math

    import jax

    from seaweedfs_tpu.ec.schedule import schedule_enabled
    from seaweedfs_tpu.parallel import MeshCodec, make_mesh

    devices = jax.devices()
    vol_axis = math.gcd(batch, len(devices))
    stripe = len(devices) // vol_axis
    return _kept(
        (
            "mesh", vol_axis, stripe, tuple(d.id for d in devices),
            schedule_enabled(),
        ),
        lambda: MeshCodec(make_mesh(devices, stripe=stripe)),
    )


class _HostBatchCodec:
    """Marker codec routing the batch REBUILD driver to its host arm
    (_rebuild_batch_pass_host) on hosts whose only jax devices are
    CPU: the SWAR Pallas kernels run interpreted there, orders of
    magnitude under the host RS backends, so the batch win must come
    from the host side instead — ONE shared pipeline (one thread-pool
    spin-up, one staging ring, per-(volume, tile) work items) for the
    whole call, where the serial path pays the driver's fixed cost
    once per volume. Byte-identical to the per-volume path: same
    cached decode rows, same survivor order, bytewise GF math."""

    def __init__(self, rs):
        self.rs = rs


def _mesh_width(codec, b: int, max_step: int) -> tuple[int, int, int]:
    """(vol axis, stripe axis, tile width) of a batch of b volumes on
    the codec's mesh: ONE static width for every round (finished volumes
    ride as zero-step entries whose output is discarded), rounded so the
    u32 lane count splits over the stripe axis in whole SWAR-friendly
    chunks — shapes stay static, the mesh program compiles once."""
    vol_axis, stripe = codec.mesh.devices.shape
    if b % vol_axis:
        raise ValueError(
            f"batch of {b} volumes does not shard over the mesh's "
            f"{vol_axis}-way 'vol' axis"
        )
    gran = 4 * 1024 * stripe
    return vol_axis, stripe, -(-max_step // gran) * gran


def _stream_batch_chunk(
    bases: list[str], codec, tile_bytes, large_block_size, small_block_size,
    stats, durable, want_crcs, reader_threads, writer_threads,
) -> None:
    from seaweedfs_tpu.ec.ec_files import shard_file_size, to_ext

    b = len(bases)
    # the round's per-shard bytes a volume follow from what the driver
    # can see, the volumes it stacks and the ring the process keeps, by
    # the batch rebuild's rule: no caller passes a size
    tile_bytes = tile_bytes or batch_round_tile_bytes(
        b, _ring_slots(writer_threads)
    )
    sizes = [os.path.getsize(base + ".dat") for base in bases]
    # each volume's tiles as the single-volume encode cuts them: runs of
    # full rows in power-of-two counts, read shard-major with ONE preadv
    tiles = [
        _encode_tiles(size, tile_bytes, large_block_size, small_block_size)
        for size in sizes
    ]
    rounds = max((len(ts) for ts in tiles), default=0)
    if not rounds:
        # all .dat files empty: 14 empty shard files each
        _create_empty(
            [base + to_ext(i) for base in bases for i in range(TOTAL_SHARDS)],
            durable,
        )
        if stats is not None and want_crcs:
            stats["shard_crcs"] = [[0] * TOTAL_SHARDS for _ in bases]
        return
    # per-shard bytes of each volume's tile a round (rows x step; 0 once
    # the volume is done)
    widths = [
        [(ts[r][3] * ts[r][4] if r < len(ts) else 0) for ts in tiles]
        for r in range(rounds)
    ]
    vol_axis, stripe, width = _mesh_width(
        codec, b, max(w for row in widths for w in row)
    )
    # fused CRC needs power-of-two lanes per device (crc_kernel); a
    # volume's narrower tile (w < width) is host-checksummed regardless
    fused_crc = want_crcs and codec.crc_supported(width)
    out_offs = []  # [rounds][volume] output offset
    acc = [0] * b
    for r in range(rounds):
        out_offs.append(list(acc))
        for v in range(b):
            acc[v] += widths[r][v]
    round_crcs: list = [None] * rounds
    # fewest mesh devices that held part of a round's batch: the
    # sharding can silently land everything on device 0
    held = vol_axis * stripe
    launches = 0
    op = _Op("ec_stream.encode_batch", True, _DEVICE_BUSY)

    def fill(fds, r, buf):
        buf3 = buf[: b * DATA_SHARDS * width].reshape(b, DATA_SHARDS, width)
        for v in range(b):
            if r >= len(tiles[v]):
                continue  # volume done: zero-width, output discarded
            _read_encode_tile(
                fds[v], sizes[v], tiles[v][r], buf3[v, :, : widths[r][v]]
            )
        return buf3

    def dispatch(r, buf3):
        nonlocal held, launches
        t0 = time.perf_counter()
        # staging: the u32 lane view is free host-side; device_put
        # lays the batch out P('vol', None, 'stripe') over the mesh
        with trace.annotation("ec.h2d"):
            vols = codec.shard_volumes(buf3.view(np.uint32))
        th = time.perf_counter()
        held = min(held, codec.devices_holding(vols))
        t1 = time.perf_counter()
        with trace.annotation("ec.launch"):
            handle = (
                codec.encode_batch_u32_crc(vols)
                if fused_crc
                else codec.encode_batch_u32(vols)
            )
        t2 = time.perf_counter()
        op.book("stage_s", t1 - t0)
        op.book("device_s", t2 - t1)
        # the transfer is part of the stage; the launch is all of device_s
        op.book("h2d_s", th - t0)
        op.book("launch_s", t2 - t1)
        launches += 1
        return handle

    def fetch(r, buf3, handle):
        """(parity [b, 4, width], fused CRCs [b, 14] or None)."""
        import jax

        with trace.annotation("ec.writeback"):
            crcs = None
            if fused_crc:
                handle, crcs_dev = handle
                crcs = np.asarray(jax.device_get(crcs_dev))
            parity = (
                np.asarray(jax.device_get(handle))
                .view(np.uint8)
                .reshape(b, PARITY_SHARDS, width)
            )
        return parity, crcs

    def checksum(r, buf3, result):
        from seaweedfs_tpu.util.crc import crc32c

        parity, crcs = result
        vol_crcs: list = [None] * b
        for v in range(b):
            w = widths[r][v]
            if not w:
                continue
            if crcs is not None and w == width:
                vol_crcs[v] = [int(c) for c in crcs[v]]
            else:
                # a narrower tile: the fused CRC would cover the padded
                # width; table-CRC the written bytes
                vol_crcs[v] = [
                    crc32c(buf3[v, i, :w].tobytes())
                    for i in range(DATA_SHARDS)
                ] + [
                    crc32c(np.ascontiguousarray(parity[v, p, :w]).tobytes())
                    for p in range(PARITY_SHARDS)
                ]
        round_crcs[r] = vol_crcs

    def write(fds, r, buf3, result):
        # ONE pwrite a shard file a round: the volume's rows x step bytes
        parity = result[0]
        for v in range(b):
            w = widths[r][v]
            if not w:
                continue
            off, first = out_offs[r][v], v * TOTAL_SHARDS
            for i in range(DATA_SHARDS):
                _pwrite_full(fds[first + i], buf3[v, i, :w], off)
            for p in range(PARITY_SHARDS):
                _pwrite_full(
                    fds[first + DATA_SHARDS + p],
                    np.ascontiguousarray(parity[v, p, :w]),
                    off,
                )

    def report(out, sp, whole):
        out["batch_volumes"] = b
        out["mesh"] = {**codec.report(), "devices_per_round": held}
        # the same count as a number of its own: a metric term reads a
        # top-level field, not a nested dict
        out["mesh_devices"] = held
        out["tiles"] = launches
        out["tile_bytes"] = tile_bytes
        if want_crcs and whole:
            out["shard_crcs"] = [
                list(crcs.values())
                for crcs in _fold_round_crcs(
                    b, range(TOTAL_SHARDS), widths, round_crcs
                )
            ]
        sp.annotate("mesh", f"{vol_axis}x{stripe}")
        sp.annotate("mesh_devices", held)
        sp.annotate("batch_volumes", b)
        sp.annotate("tiles", launches)
        sp.annotate("tile_bytes", tile_bytes)

    op.run(
        nbytes=sum(sizes),
        items=list(range(rounds)),
        slot_bytes=b * DATA_SHARDS * width,
        outputs=[
            (
                base + to_ext(i),
                shard_file_size(size, large_block_size, small_block_size),
            )
            for base, size in zip(bases, sizes)
            for i in range(TOTAL_SHARDS)
        ],
        opened=lambda: _opened([base + ".dat" for base in bases]),
        fill=fill,
        dispatch=dispatch,
        fetch=fetch,
        checksum=checksum if want_crcs else None,
        write=write,
        report=report,
        stats=stats,
        durable=durable,
        reader_threads=reader_threads,
        writer_threads=writer_threads,
    )


def _fold_round_crcs(
    b: int, ids, step_of: list[list[int]], round_crcs: list
) -> list[dict[int, int]]:
    """Per-volume {shard id: whole-file CRC-32C} from the batch writer
    pools' per-round records (one CRC per id of `ids`, in their order),
    folded in round order."""
    from seaweedfs_tpu.util.crc import crc32c_combine

    out = []
    for v in range(b):
        acc = dict.fromkeys(ids, 0)
        for r, vol_crcs in enumerate(round_crcs):
            step = step_of[r][v]
            if not step or vol_crcs is None or vol_crcs[v] is None:
                continue
            for t, sid in enumerate(ids):
                acc[sid] = crc32c_combine(acc[sid], vol_crcs[v][t], step)
        out.append(acc)
    return out


def stream_rebuild_ec_files_batch(
    base_file_names: list[str],
    codec=None,
    tile_bytes: int | None = None,
    stats: dict | None = None,
    durable: bool = False,
    want_crcs: bool = False,
    reader_threads: int | None = None,
    writer_threads: int | None = None,
    remote_readers: list[dict[int, Callable[[int, np.ndarray], int]]] | None = None,
    remote_report: Callable[[], dict] | None = None,
) -> list[list[int]]:
    """Rebuild N volumes' missing shard files through ONE sharded mesh
    program per tile round — the rebuild-side sibling of
    stream_write_ec_files_batch. The RepairScheduler's common case is a
    node loss surfacing many small EC volumes missing the SAME shard
    ids at once; rebuilding them one dispatch per volume is
    latency-bound exactly like the small-volume encode was. Here each
    tile round stacks one [k, W] survivor tile per volume into a
    [B, k, W/4]-lane batch laid out P('vol', None, 'stripe') and runs
    parallel/mesh_codec.reconstruct_batch_u32 once.

    Volumes are grouped by their (survivors, targets) signature — each
    group one decode program — and a mixed-damage call is still ONE
    pass of the pipeline shell: its items are (chunk, round), group
    after group, so the pools, the ring, the reservation and the
    head-to-flush phases are paid once a call, and the program changes
    once a chunk (a chunk is a group, or a WEED_EC_PIPELINE_BATCH slice
    of one). Output bytes per volume are identical to rebuild_ec_files
    over the same survivor choice (RS determinism).

    `remote_readers`, one map a volume beside base_file_names, gives
    the readers of survivors that live on OTHER servers, as the
    single-volume driver's `remote_readers` (a rack loss: four of a
    volume's survivors local, six fetched). A volume's survivors are
    chosen as that driver chooses them (_survivor_choice: local first,
    topped up from remote holders, ascending), a shard readable
    remotely is never a target, and a round's gather of every volume's
    slot is that driver's (_Gather: the fetch pool, `ec.remote_read`,
    remote_read_s, the repair byte counters, the truncation check).
    remote_report(), once the pools are joined, adds the readers' own
    books (arbiter_wait_s, remote_fetches, remote_fetches_dataplane,
    summed over the call's volumes) to the report line and the root
    span, beside survivor_bytes_remote and rebuilt_bytes. The repair
    session (donations from degraded GETs) stays with the single-volume
    driver.

    want_crcs=True lands `shard_crcs` in stats: one {rebuilt shard id:
    whole-file CRC-32C} dict per volume, in base_file_names order
    (host table CRCs per round — reconstruct has no fused CRC tier —
    folded with crc32c_combine). Returns the per-volume rebuilt id
    lists in base_file_names order; volumes with nothing missing
    return [].

    `tile_bytes` is an override for tests and sweeps. Left None, the
    mesh arm sizes the round from the widest chunk's volumes and the
    ring the process keeps (batch_round_tile_bytes: 2 MiB a survivor
    row for four volumes, which the chip read at 4.5 GB/s of volume
    repaired where 512 KiB read 2.6: PERF.md section 6, PR 39), and the
    host arm (no accelerator, or no jax) takes
    BATCH_REBUILD_MIN_TILE_BYTES (512 KiB: the number of a record
    taken on a host without a chip, `git show 484f53f:BENCH_r12.json`). The benchmark cells `batch-rebuild-2lost` (one signature),
    `batch-rebuild-mixed` (seven) and `rack-batch-rebuild` (one, six
    remote survivors a volume) run the mesh arm on the chip
    (`rebuild_launches_per_gib`, `read_s_per_gib`,
    `ring_fresh_bytes_per_gib`, `batch_rebuild_passes_per_op`,
    `remote_read_s_per_gib`): a change of the rule is judged there.

    `stats` gets the pass's books: its stage seconds, `tiles` (rounds
    or work items of every chunk), `survivor_bytes`, `tile_bytes`,
    `mesh` (the last chunk's), `survivors` and `targets` (per-volume
    counts: the most targets of any volume), `batch_volumes`,
    `batch_groups` (damage signatures: decode programs the call ran),
    `batch_passes` (1; 0 where there was nothing to stream) and the
    gather's remote fields; the ONE root span carries the same.

    `durable=True` fsyncs every rebuilt shard before returning; a
    failed call, a remote fetch that fails or comes back short among
    the causes, removes ALL its volumes' target files (the abort
    contract scrub relies on: no partial rebuilt shard survives)."""
    from seaweedfs_tpu.ec.ec_files import shard_presence

    results: list[list[int]] = [[] for _ in base_file_names]
    if not base_file_names:
        return results
    remote_of = list(remote_readers or [{} for _ in base_file_names])
    groups: dict[tuple, list[int]] = {}
    for i, base in enumerate(base_file_names):
        present, missing = shard_presence(base)
        remote_of[i] = _not_local(remote_of[i], present)
        sig = _survivor_choice(base, present, missing, remote_of[i])
        if sig[1]:
            groups.setdefault(sig, []).append(i)

    if not groups:
        if stats is not None:
            stats["batch_volumes"] = len(base_file_names)
            stats["batch_groups"] = 0
            stats["batch_passes"] = 0
            if want_crcs:
                stats["shard_crcs"] = [{} for _ in base_file_names]
        return results

    if codec is None:
        codec = _default_batch_codec(max(len(idxs) for idxs in groups.values()))
    limit = pipeline_batch_limit()
    # group-major: a group's volumes in chunks of at most `limit`, each
    # chunk one decode program over its rounds, all of them ONE pass
    chunks = [
        (idxs[i : i + (limit or len(idxs))], survivors, targets)
        for (survivors, targets), idxs in groups.items()
        for i in range(0, len(idxs), limit or len(idxs))
    ]
    order = [i for idxs, _, _ in chunks for i in idxs]
    pass_stats: dict = {}
    _rebuild_batch_pass(
        [
            ([base_file_names[i] for i in idxs], survivors, targets)
            for idxs, survivors, targets in chunks
        ],
        codec, tile_bytes, pass_stats, durable, want_crcs,
        reader_threads, writer_threads, len(groups),
        [remote_of[i] for i in order], remote_report,
    )
    for idxs, _, targets in chunks:
        for i in idxs:
            results[i] = list(targets)
    if stats is not None:
        # the pass's volumes in its order -> base_file_names order
        crcs = dict(zip(order, pass_stats.pop("shard_crcs", None) or []))
        stats.update(pass_stats)
        stats["batch_volumes"] = len(base_file_names)
        stats["batch_groups"] = len(groups)
        if want_crcs:
            stats["shard_crcs"] = [
                crcs.get(i, {}) for i in range(len(base_file_names))
            ]
    return results


def _default_batch_codec(batch: int):
    """The batch rebuild's codec where the caller names none: the mesh
    codec on an accelerator; with none (or no jax), the host arm, since
    the interpreted Pallas kernels lose to the host backends by orders
    of magnitude (same grouping and gather, one matrix apply a work
    item)."""
    try:
        import jax

        on_host = all(d.platform == "cpu" for d in jax.devices())
    except ImportError:
        on_host = True
    if not on_host:
        return _default_mesh_codec(batch)
    from seaweedfs_tpu.ec.codec import new_encoder

    try:
        return _HostBatchCodec(new_encoder(backend="native"))
    except (ImportError, ValueError):
        return _HostBatchCodec(new_encoder(backend="cpu"))


def _rebuild_shape(tiles: int, survivors, targets, survivor_bytes: int) -> dict:
    """The shape of one rebuild on its report line: decode tiles
    dispatched (rounds or work items in the batch drivers), the survivor
    set and the targets by count, and the survivor bytes gathered, local
    and remote: what weed_ec_repair_bytes_read_total took."""
    return {
        "tiles": tiles,
        "survivors": len(survivors),
        "targets": len(targets),
        "survivor_bytes": survivor_bytes,
    }


def _report_batch_rebuild(
    out: dict, sp, volumes: int, groups: int, tile_bytes: int, shape: dict
) -> None:
    """A batch rebuild pass's shape on its stats and on its root span,
    under the same names: the volumes it stacked, the one pass it made
    of them, the call's damage signatures, the per-survivor bytes of a
    round (or work item) its arm took, and _rebuild_shape's four."""
    shape = {
        "batch_volumes": volumes, "batch_passes": 1,
        "tile_bytes": tile_bytes, **shape,
    }
    out.update(shape)
    sp.annotate("batch_groups", groups)
    for key, value in shape.items():
        sp.annotate(key, value)


class _BatchChunk(NamedTuple):
    """One (survivors, targets)-homogeneous chunk of a mesh pass: its
    volumes are the pass's v0 .. v0 + b - 1, stacked [b, 10, width] a
    round and decoded by ONE program of `codec`. step_of[r][v] is the
    bytes volume v has in round r (0: done, its output discarded)."""

    v0: int
    b: int
    survivors: tuple[int, ...]
    targets: tuple[int, ...]
    codec: object
    width: int
    step_of: list[list[int]]
    opens_group: bool  # its signature is not the previous chunk's


def _rebuild_batch_pass(
    chunks: list[tuple[list[str], tuple[int, ...], tuple[int, ...]]],
    codec, tile_bytes, stats, durable, want_crcs,
    reader_threads, writer_threads, groups: int,
    remotes: list[dict], remote_report: Callable[[], dict] | None,
) -> None:
    """Every chunk of a call, (bases, survivors, targets) each, through
    ONE pass of the shell: the rebuild-side mirror of _stream_batch_chunk
    over items (chunk, round), in chunk order, so the decode program
    changes once a chunk. Each round reads [k, step] survivor tiles per
    volume of its chunk into a [b, k, W] staging slot (the slot is sized
    for the widest chunk), runs the chunk's reconstruct_batch_u32 once,
    and pwrites the rebuilt target rows. One reservation and one latch
    for every output file of the call, and the call's abort contract:
    any failure removes every target file of every volume. `groups` is
    the call's count of damage signatures, for the root span; `remotes`
    the volumes' remote readers in the pass's order, for _Gather. Stats
    get the volumes' rebuilt-shard CRCs in the pass's volume order."""
    from seaweedfs_tpu.ec.ec_files import to_ext

    bases = [base for chunk_bases, _, _ in chunks for base in chunk_bases]
    survivors_of = [s for chunk_bases, s, _ in chunks for _ in chunk_bases]
    targets_of = [t for chunk_bases, _, t in chunks for _ in chunk_bases]
    # a local survivor's file size is the volume's shard length
    sizes = [
        os.path.getsize(base + to_ext(next(i for i in survivors if i not in remote)))
        for base, survivors, remote in zip(bases, survivors_of, remotes)
    ]
    outputs = [
        (base + to_ext(t), size)
        for base, size, targets in zip(bases, sizes, targets_of)
        for t in targets
    ]
    # where each volume's target files start among the outputs (and fds)
    out_at = list(itertools.accumulate(map(len, targets_of), initial=0))
    host = isinstance(codec, _HostBatchCodec)
    if not any(sizes):
        # all-empty shard sets: rebuilt targets are empty files too
        _create_empty([path for path, _ in outputs], durable)
        if stats is not None:
            stats["batch_volumes"] = len(bases)
            stats["batch_passes"] = 0
            if host:
                stats["codec_arm"] = "host"
            if want_crcs:
                stats["shard_crcs"] = [
                    dict.fromkeys(targets, 0) for targets in targets_of
                ]
        return
    if host:
        # the host arm's slots are [10, tile] a (volume, tile) work item
        # and its number comes from hosts without a chip: the fine tile
        # (more preads in flight, cache-resident spans) stays
        return _rebuild_batch_pass_host(
            bases, codec.rs, survivors_of, targets_of, sizes, outputs,
            out_at, tile_bytes or BATCH_REBUILD_MIN_TILE_BYTES,
            stats, durable, want_crcs, reader_threads, writer_threads, groups,
            remotes, remote_report,
        )
    # the round's span follows from what the driver can see, the widest
    # chunk it stacks and the ring the process keeps: no caller passes a
    # size
    tile_bytes = tile_bytes or batch_round_tile_bytes(
        max(len(chunk_bases) for chunk_bases, _, _ in chunks),
        _ring_slots(writer_threads),
    )
    plans: list[_BatchChunk] = []
    v0 = 0
    for chunk_bases, survivors, targets in chunks:
        b = len(chunk_bases)
        chunk_sizes = sizes[v0 : v0 + b]
        step_of = [
            [max(0, min(tile_bytes, size - r * tile_bytes)) for size in chunk_sizes]
            for r in range(max(-(-size // tile_bytes) for size in chunk_sizes))
        ]
        # a caller's codec is honoured where the chunk shards over its
        # vol axis; else the chunk takes a mesh that fits its size
        chunk_codec = codec
        if b % codec.mesh.devices.shape[0]:
            chunk_codec = _default_mesh_codec(b)
        _, _, width = _mesh_width(
            chunk_codec, b, max((s for row in step_of for s in row), default=0)
        )
        plans.append(_BatchChunk(
            v0, b, survivors, targets, chunk_codec, width, step_of,
            not plans or (plans[-1].survivors, plans[-1].targets)
            != (survivors, targets),
        ))
        v0 += b
    held = min(p.codec.mesh.devices.size for p in plans)  # see _stream_batch_chunk
    items = [(c, r) for c, p in enumerate(plans) for r in range(len(p.step_of))]
    round_crcs: list[list] = [[None] * len(p.step_of) for p in plans]
    op = _Op("ec_stream.rebuild_batch", True, {**_DEVICE_BUSY, **_GATHER_BUSY})
    gather = _Gather(bases, survivors_of, remotes, op.book, remote_report)
    # rebuilt bytes of every write, from the writer pool
    written: list[int] = []

    def fill(src, item, buf):
        c, r = item
        p = plans[c]
        buf3 = buf[: p.b * DATA_SHARDS * p.width].reshape(
            p.b, DATA_SHARDS, p.width
        )
        # a volume that is done has no read: its output is discarded
        gather.read(src, [
            (p.v0 + v, r * tile_bytes, buf3[v, :, :step])
            for v, step in enumerate(p.step_of[r])
            if step
        ])
        return buf3

    def dispatch(item, buf3):
        nonlocal held
        c, r = item
        p = plans[c]
        t0 = time.perf_counter()
        with trace.annotation("ec.h2d"):
            vols = p.codec.shard_volumes(buf3.view(np.uint32))
        th = time.perf_counter()
        held = min(held, p.codec.devices_holding(vols))
        # where the program changes: the first launch of each group
        group = (
            trace.annotation("ec.group")
            if p.opens_group and r == 0
            else contextlib.nullcontext()
        )
        t1 = time.perf_counter()
        with group, trace.annotation("ec.launch"):
            handle = p.codec.reconstruct_batch_u32(p.survivors, p.targets, vols)
        t2 = time.perf_counter()
        op.book("stage_s", t1 - t0)
        op.book("device_s", t2 - t1)
        # as the mesh encode stage: the transfer is part of the stage,
        # the launch is all of device_s
        op.book("h2d_s", th - t0)
        op.book("launch_s", t2 - t1)
        return handle

    def fetch(item, buf3, handle):
        import jax

        p = plans[item[0]]
        with trace.annotation("ec.writeback"):
            return (
                np.asarray(jax.device_get(handle))
                .view(np.uint8)
                .reshape(p.b, len(p.targets), p.width)
            )

    def checksum(item, buf3, rebuilt):
        # no fused CRC tier for reconstruct: host table CRC the rebuilt
        # rows
        from seaweedfs_tpu.util.crc import crc32c

        c, r = item
        p = plans[c]
        round_crcs[c][r] = [
            [
                crc32c(np.ascontiguousarray(rebuilt[v][t, :step]).tobytes())
                for t in range(len(p.targets))
            ]
            if step
            else None
            for v, step in enumerate(p.step_of[r])
        ]

    def write(fds, item, buf3, rebuilt):
        c, r = item
        p = plans[c]
        for v, step in enumerate(p.step_of[r]):
            if not step:
                continue
            at = out_at[p.v0 + v]
            for t in range(len(p.targets)):
                _pwrite_full(
                    fds[at + t],
                    np.ascontiguousarray(rebuilt[v][t, :step]),
                    r * tile_bytes,
                )
                EC_REPAIR_BYTES_WRITTEN.inc(step)
                written.append(step)

    def report(out, sp, whole):
        last = plans[-1].codec
        out["mesh"] = {**last.report(), "devices_per_round": held}
        sp.annotate("mesh", "x".join(map(str, last.mesh.devices.shape)))
        _report_batch_rebuild(out, sp, len(bases), groups, tile_bytes, {
            **_rebuild_shape(
                len(items), plans[0].survivors, max(targets_of, key=len),
                DATA_SHARDS * sum(sizes),
            ),
            "rebuilt_bytes": sum(written), **gather.books(),
        })
        if want_crcs and whole:
            out["shard_crcs"] = [
                crcs
                for c, p in enumerate(plans)
                for crcs in _fold_round_crcs(
                    p.b, p.targets, p.step_of, round_crcs[c]
                )
            ]

    op.run(
        nbytes=sum(s * len(t) for s, t in zip(sizes, targets_of)),
        items=items,
        slot_bytes=max(p.b * DATA_SHARDS * p.width for p in plans),
        outputs=outputs,
        opened=gather.opened,
        fill=fill,
        dispatch=dispatch,
        fetch=fetch,
        checksum=checksum if want_crcs else None,
        write=write,
        report=report,
        stats=stats,
        durable=durable,
        reader_threads=reader_threads,
        writer_threads=writer_threads,
    )


# At or below this many (volume, tile) work items the host arm skips
# the thread pipeline entirely: on small batches every queue handoff
# and Thread.start costs a scheduler wakeup (milliseconds on a busy
# single-CPU host) that dwarfs the native-codec work it brokers.
_HOST_INLINE_TILES = 16


def _rebuild_batch_pass_host(
    bases: list[str], rs, survivors_of: list[tuple[int, ...]],
    targets_of: list[tuple[int, ...]], sizes: list[int],
    outputs: list[tuple[str, int]], out_at: list[int], tile_bytes: int,
    stats, durable, want_crcs, reader_threads, writer_threads, groups: int,
    remotes: list[dict], remote_report: Callable[[], dict] | None,
) -> None:
    """Host arm of the batch rebuild: one shared pipeline whose work
    items are per-(volume, tile) survivor gathers, decoded in the
    writer pool with each signature's single cached decode-rows matrix.
    Slots stay at the single-volume driver's [k, tile] size (cache-
    resident on small hosts — an all-volumes-per-round slot measurably
    loses CPU to memory traffic), and the stream crosses volume and
    signature boundaries without the per-volume spawn/drain the serial
    path pays. Same abort contract as the mesh arm.

    At or below _HOST_INLINE_TILES items there are no pools at all: one
    staging buffer, one pass over the work list on the caller's thread.
    Many-small-volumes repair is latency-bound on fixed costs, so the
    win there is paying ONE set of them for the whole batch and none of
    the pipeline's per-handoff scheduler wakeups."""
    # flat (volume, offset) work list: the pipeline streams straight
    # through volume boundaries, no drain between them
    items = [
        (v, off)
        for v in range(len(bases))
        for off in range(0, sizes[v], tile_bytes)
    ]
    decode = {
        sig: rs.decode_rows(*sig) for sig in set(zip(survivors_of, targets_of))
    }
    rows = [decode[sig] for sig in zip(survivors_of, targets_of)]
    # (volume, offset, step, [crc per target]); append is GIL-atomic,
    # order restored by sorting at fold time
    crc_parts: list[tuple[int, int, int, list[int]]] = []
    written: list[int] = []
    pooled = len(items) > _HOST_INLINE_TILES
    op = _Op("ec_stream.rebuild_batch", extra_busy=_GATHER_BUSY) if pooled else None
    # inline: the one thread's books, and the fetch pool's remote_read_s
    busy = {"read_s": 0.0, "compute_s": 0.0, "write_s": 0.0, **_GATHER_BUSY}
    book = op.book if pooled else functools.partial(_charge, busy, threading.Lock())
    gather = _Gather(bases, survivors_of, remotes, book, remote_report)

    def fill(src, item, buf):
        v, off = item
        step = min(tile_bytes, sizes[v] - off)
        tile = buf[: DATA_SHARDS * step].reshape(DATA_SHARDS, step)
        gather.read(src, [(v, off, tile)])
        return tile

    def fetch(item, tile, _handle):
        return rs._apply(rows[item[0]], tile)

    def checksum(item, tile, rebuilt):
        from seaweedfs_tpu.util.crc import crc32c

        crc_parts.append((*item, tile.shape[1], [
            crc32c(np.ascontiguousarray(rebuilt[t]).tobytes())
            for t in range(len(targets_of[item[0]]))
        ]))

    def write(fds, item, tile, rebuilt):
        v, off = item
        for t in range(len(targets_of[v])):
            _pwrite_full(
                fds[out_at[v] + t],
                np.ascontiguousarray(rebuilt[t]),
                off,
            )
            EC_REPAIR_BYTES_WRITTEN.inc(tile.shape[1])
            written.append(tile.shape[1])

    def report(out, sp, whole):
        out["codec_arm"] = "host"
        _report_batch_rebuild(out, sp, len(bases), groups, tile_bytes, {
            **_rebuild_shape(
                len(items), survivors_of[0], max(targets_of, key=len),
                DATA_SHARDS * sum(sizes),
            ),
            "rebuilt_bytes": sum(written), **gather.books(),
        })
        if want_crcs and whole:
            out["shard_crcs"] = _fold_host_batch_crcs(targets_of, crc_parts)

    nbytes = sum(s * len(t) for s, t in zip(sizes, targets_of))
    if pooled:
        return op.run(
            nbytes=nbytes,
            items=items,
            slot_bytes=DATA_SHARDS * tile_bytes,
            outputs=outputs,
            opened=gather.opened,
            fill=fill,
            dispatch=lambda item, tile: None,  # the writers decode
            fetch=fetch,
            checksum=checksum if want_crcs else None,
            write=write,
            report=report,
            fetch_charges="compute_s",
            stats=stats,
            durable=durable,
            reader_threads=reader_threads,
            writer_threads=writer_threads,
        )
    wall0 = time.perf_counter()
    buf = np.empty(DATA_SHARDS * tile_bytes, dtype=np.uint8)
    fds: list[int] = []
    ok = False
    with trace.span(
        "ec_stream.rebuild_batch", nbytes=nbytes
    ) as sp, gather.opened() as src:
        try:
            for path, size in outputs:
                fds.append(
                    os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
                )
                _preallocate(fds[-1], size)
            for item in items:
                t0 = time.perf_counter()
                tile = fill(src, item, buf)
                t1 = time.perf_counter()
                rebuilt = fetch(item, tile, None)
                if want_crcs:
                    checksum(item, tile, rebuilt)
                t2 = time.perf_counter()
                write(fds, item, tile, rebuilt)
                t3 = time.perf_counter()
                busy["read_s"] += t1 - t0
                busy["compute_s"] += t2 - t1
                busy["write_s"] += t3 - t2
            ok = True
        finally:
            tc0 = time.perf_counter()
            fsync_err = _settle_outputs(
                fds, [path for path, _ in outputs], durable, ok
            )
            try:
                if fsync_err is not None:
                    raise fsync_err
            finally:
                busy["flush_s"] = time.perf_counter() - tc0
                out: dict = {}
                _finish_stats(out, busy, wall0, 1, 1)
                report(out, sp, ok and fsync_err is None)
                out["host_inline"] = True
                if stats is not None:
                    stats.update(out)
                _trace_stages(sp, busy)


def _fold_host_batch_crcs(
    targets_of: list[tuple[int, ...]],
    crc_parts: list[tuple[int, int, int, list[int]]],
) -> list[dict[int, int]]:
    """Per-volume {rebuilt shard id: whole-file CRC} folded from the
    writer pool's per-tile records in offset order."""
    from seaweedfs_tpu.util.crc import crc32c_combine

    out = [dict.fromkeys(targets, 0) for targets in targets_of]
    for v, off, step, crcs in sorted(crc_parts):
        for t, tid in enumerate(targets_of[v]):
            out[v][tid] = crc32c_combine(out[v][tid], crcs[t], step)
    return out
