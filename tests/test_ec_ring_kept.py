"""The staging ring's memory outlives the operation (ISSUE 33,
docs/CODEC.md "Staging ring"): an operation borrows its ring from the
process's one holder (ec_stream._KeptRing) and gives it back settled, so
a node's second operation reads, copies and writes on pages that are
already mapped. `ring_fresh_bytes` on the report line says what an
operation had to allocate anew.

Host arm under JAX_PLATFORMS=cpu, injected numpy stages on the
single-volume drivers. What is asserted is bytes, counts and identity of
memory, never a time. Every expected byte comes from the classic serial
driver (the kill switch), which has no ring."""

import os
import shutil
import threading

import numpy as np
import pytest

from seaweedfs_tpu.ec import ec_files, ec_stream
from seaweedfs_tpu.ec.codec import new_encoder
from seaweedfs_tpu.stats.metrics import EC_RING_FRESH_BYTES
from seaweedfs_tpu.util.crc import crc32c
from tests.faults import ec_stream_threads, fds_under

LARGE = 64 * 1024
SMALL = 16 * 1024
LOST = (2, 11)  # what the rebuild drivers rebuild: a data and a parity shard
WRITERS, READERS = 3, 2
SLOTS = ec_stream._INFLIGHT + WRITERS + 1


@pytest.fixture(autouse=True)
def fresh_process(monkeypatch):
    """Each test starts as a process that has run no operation."""
    monkeypatch.setattr(ec_stream, "_RING", ec_stream._KeptRing())


def _kept() -> np.ndarray | None:
    return ec_stream._RING._arena


def _classic(base: str, nbytes: int, seed: int) -> list[bytes]:
    """A seeded `.dat` at `base` and what the classic serial loop makes
    of it, in a directory of its own: the 14 shard files' bytes."""
    rng = np.random.default_rng(seed)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes())
    ref = os.path.join(os.path.dirname(base), "classic-" + os.path.basename(base))
    shutil.copy(base + ".dat", ref + ".dat")
    os.environ["WEED_EC_PIPELINE"] = "0"
    try:
        ec_files.write_ec_files(
            ref, rs=new_encoder(backend="cpu"), large_block_size=LARGE,
            small_block_size=SMALL,
        )
    finally:
        os.environ.pop("WEED_EC_PIPELINE", None)
    return [
        open(ref + ec_files.to_ext(i), "rb").read()
        for i in range(ec_files.TOTAL_SHARDS)
    ]


def _survivors_only(base: str, shards: list[bytes]) -> None:
    """The classic shard set at `base`, less the LOST shards."""
    for i, data in enumerate(shards):
        path = base + ec_files.to_ext(i)
        if i in LOST:
            if os.path.exists(path):
                os.remove(path)
        elif not os.path.exists(path):
            with open(path, "wb") as f:
                f.write(data)


def _check(base: str, want: list[bytes], crcs, ids=range(ec_files.TOTAL_SHARDS)):
    """The operation's files are the classic driver's byte for byte, and
    the CRCs it reported (what the handler publishes as the `.ecc`) are
    those of the files."""
    crcs = dict(zip(ids, crcs)) if isinstance(crcs, list) else crcs
    for i in ids:
        with open(base + ec_files.to_ext(i), "rb") as f:
            got = f.read()
        assert got == want[i], f"{base} shard {i}"
        assert crcs[i] == crc32c(want[i]), f"{base} crc {i}"


class _Volumes:
    """`sizes` seeded volumes under tmp_path, the classic shard sets
    beside them, and the four drivers over them. Every driver call
    returns its stats; `check` holds its outputs to the classic ones."""

    def __init__(self, tmp_path, tag: str, sizes: list[int], seed: int):
        self.bases = [str(tmp_path / f"{tag}{i}") for i in range(len(sizes))]
        self.want = [
            _classic(base, size, seed + i)
            for i, (base, size) in enumerate(zip(self.bases, sizes))
        ]

    def single(self, tile: int = SMALL) -> dict:
        stats: dict = {}
        parity_fn, fetch_fn = ec_stream.local_encode_fns(
            new_encoder(backend="cpu"), want_crcs=True
        )
        ec_stream.stream_write_ec_files(
            self.bases[0], tile_bytes=tile, large_block_size=LARGE,
            small_block_size=SMALL, parity_fn=parity_fn, fetch_fn=fetch_fn,
            stats=stats, want_crcs=True, writer_threads=WRITERS,
            reader_threads=READERS,
        )
        _check(self.bases[0], self.want[0], stats["shard_crcs"])
        return stats

    def batch(self, tile: int = SMALL) -> dict:
        stats: dict = {}
        ec_stream.stream_write_ec_files_batch(
            self.bases, tile_bytes=tile, large_block_size=LARGE,
            small_block_size=SMALL, stats=stats, want_crcs=True,
            writer_threads=WRITERS, reader_threads=READERS,
        )
        for base, want, crcs in zip(self.bases, self.want, stats["shard_crcs"]):
            _check(base, want, crcs)
        return stats

    def rebuild(self, tile: int = SMALL) -> dict:
        stats: dict = {}
        _survivors_only(self.bases[0], self.want[0])
        rebuild_fn, fetch_fn = ec_stream.local_rebuild_fns(
            new_encoder(backend="cpu"), want_crcs=True
        )
        rebuilt = ec_stream.stream_rebuild_ec_files(
            self.bases[0], tile_bytes=tile, rebuild_fn=rebuild_fn,
            fetch_fn=fetch_fn, stats=stats, want_crcs=True,
            writer_threads=WRITERS, reader_threads=READERS,
        )
        assert rebuilt == list(LOST)
        _check(self.bases[0], self.want[0], stats["shard_crcs"], LOST)
        return stats

    def rebuild_batch(self, tile: int = SMALL // 2) -> dict:
        # tiles fine enough for more than _HOST_INLINE_TILES work items:
        # the host arm then runs its pools, through the shell
        stats: dict = {}
        for base, want in zip(self.bases, self.want):
            _survivors_only(base, want)
        rebuilt = ec_stream.stream_rebuild_ec_files_batch(
            self.bases, tile_bytes=tile, stats=stats, want_crcs=True,
            writer_threads=WRITERS, reader_threads=READERS,
        )
        assert rebuilt == [list(LOST)] * len(self.bases)
        assert "host_inline" not in stats
        for base, want, crcs in zip(self.bases, self.want, stats["shard_crcs"]):
            _check(base, want, crcs, LOST)
        return stats


DRIVERS = ("single", "batch", "rebuild", "rebuild_batch")
# a tail round with step < width in every volume, and in the batch one
# volume shorter than the other by whole rounds
SIZES = [10 * SMALL * 12 + 77, 10 * SMALL * 7 + 5]


def _ring_bytes(stats: dict, slot_bytes: int) -> int:
    assert stats["ring_slots"] == SLOTS
    return SLOTS * -(-slot_bytes // ec_stream._SLOT_ALIGN) * ec_stream._SLOT_ALIGN


@pytest.mark.parametrize("driver", DRIVERS)
def test_second_operation_runs_on_the_first_ones_memory(driver, tmp_path):
    vols = _Volumes(tmp_path, "v", SIZES, seed=33)
    first = getattr(vols, driver)()
    assert first["ring_fresh_bytes"] > 0
    kept = _kept()
    assert kept is not None and kept.size == first["ring_fresh_bytes"]
    second = getattr(vols, driver)()  # checks its bytes and CRCs itself
    assert second["ring_fresh_bytes"] == 0
    assert _kept() is kept  # the same allocation came back
    assert not ec_stream_threads() and not fds_under(tmp_path)


def test_fresh_bytes_are_the_rings_slots(tmp_path):
    vols = _Volumes(tmp_path, "v", SIZES[:1], seed=34)
    stats = vols.single()
    assert stats["ring_fresh_bytes"] == _ring_bytes(stats, 10 * SMALL)
    assert _kept().size == stats["ring_fresh_bytes"]


@pytest.mark.parametrize("driver", DRIVERS)
def test_another_volumes_bytes_in_the_kept_memory_change_no_output(
    driver, tmp_path
):
    """A LARGER ring first (three volumes, tiles twice as wide), every
    byte of what it gave back set to 0xFF, then other volumes of other
    sizes through a smaller ring carved from it: a slot's first use now
    starts from another operation's bytes, not from new zero pages."""
    big = _Volumes(tmp_path, "big", [10 * SMALL * 9 + 1] * 3, seed=50)
    assert big.batch(tile=2 * SMALL)["ring_fresh_bytes"] > 0
    kept = _kept()
    kept.fill(0xFF)
    vols = _Volumes(tmp_path, "v", SIZES, seed=60)
    stats = getattr(vols, driver)()  # checks its bytes and CRCs itself
    assert stats["ring_fresh_bytes"] == 0
    assert _kept() is kept and kept.size > _ring_bytes(stats, 0)
    # and it did run on that memory: the operation's own bytes are in it
    assert not (kept == 0xFF).all()


def test_a_larger_request_grows_what_is_kept(tmp_path):
    small = _Volumes(tmp_path, "s", SIZES[:1], seed=70)
    large = _Volumes(tmp_path, "l", SIZES, seed=71)
    a = small.single()
    b = large.batch(tile=2 * SMALL)
    assert 0 < a["ring_fresh_bytes"] < b["ring_fresh_bytes"]
    assert _kept().size == b["ring_fresh_bytes"]  # the larger one is kept
    assert small.single()["ring_fresh_bytes"] == 0
    assert large.batch(tile=2 * SMALL)["ring_fresh_bytes"] == 0
    assert _kept().size == b["ring_fresh_bytes"]


def test_concurrent_operations_use_disjoint_memory(tmp_path, monkeypatch):
    """Two operations from two threads, held at a barrier until both
    have their rings: one has the kept memory, the other allocated its
    own, no byte is shared, and both leave the classic driver's files."""
    warm = _Volumes(tmp_path, "w", SIZES[:1], seed=80)
    assert warm.single()["ring_fresh_bytes"] > 0
    kept = _kept()
    both = threading.Barrier(2, timeout=60)
    arenas: list[np.ndarray] = []
    real_init = ec_stream._StagingRing.__init__

    def init(self, slots, slot_bytes):
        real_init(self, slots, slot_bytes)
        arenas.append(self._arena)
        both.wait()

    monkeypatch.setattr(ec_stream._StagingRing, "__init__", init)
    ops = [
        _Volumes(tmp_path, "a", SIZES[:1], seed=81),
        _Volumes(tmp_path, "b", SIZES[1:], seed=82),
    ]
    box: dict = {}

    def run(name, vols):
        try:
            box[name] = vols.single()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            box[name] = e

    threads = [
        threading.Thread(target=run, args=(n, v), name=f"op-{n}")
        for n, v in zip("ab", ops)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    for name in "ab":
        if isinstance(box.get(name), BaseException):
            raise box[name]
    assert len(arenas) == 2 and not np.shares_memory(arenas[0], arenas[1])
    assert sum(a is kept for a in arenas) == 1
    fresh = sorted(box[n]["ring_fresh_bytes"] for n in "ab")
    assert fresh[0] == 0 and fresh[1] > 0
    assert _kept() is not None  # one of the two came back
    assert not ec_stream_threads() and not fds_under(tmp_path)


READ_FN = {"single": "_preadv_into", "batch": "_read_tile_into"}


@pytest.mark.parametrize("driver", sorted(READ_FN))
def test_an_aborted_operation_does_not_give_its_ring_back(
    driver, tmp_path, monkeypatch
):
    vols = _Volumes(tmp_path, "v", SIZES, seed=90)
    assert getattr(vols, driver)()["ring_fresh_bytes"] > 0
    kept = _kept()
    real, calls = getattr(ec_stream, READ_FN[driver]), [0]

    def read(*args):
        calls[0] += 1
        if calls[0] == 4:
            raise OSError(5, "read failed")
        return real(*args)

    monkeypatch.setattr(ec_stream, READ_FN[driver], read)
    with pytest.raises(OSError, match="read failed"):
        getattr(vols, driver)()
    monkeypatch.setattr(ec_stream, READ_FN[driver], real)
    # it ran on the kept memory, and that memory is gone with it: a
    # transfer of the aborted operation may still be reading a slot
    assert _kept() is None
    assert not ec_stream_threads() and not fds_under(tmp_path)
    after = getattr(vols, driver)()
    assert after["ring_fresh_bytes"] > 0
    assert _kept() is not None and _kept() is not kept
    assert getattr(vols, driver)()["ring_fresh_bytes"] == 0
    assert not ec_stream_threads() and not fds_under(tmp_path)


def test_the_aborted_operations_own_line_still_carries_the_field(
    tmp_path, monkeypatch
):
    vols = _Volumes(tmp_path, "v", SIZES[:1], seed=91)
    first = vols.single()
    monkeypatch.setattr(
        ec_stream, "_pwrite_full",
        lambda *a: (_ for _ in ()).throw(OSError(28, "disk full")),
    )
    stats: dict = {}
    parity_fn, fetch_fn = ec_stream.local_encode_fns(new_encoder(backend="cpu"))
    with pytest.raises(OSError, match="disk full"):
        ec_stream.stream_write_ec_files(
            vols.bases[0], tile_bytes=SMALL, large_block_size=LARGE,
            small_block_size=SMALL, parity_fn=parity_fn, fetch_fn=fetch_fn,
            stats=stats, writer_threads=WRITERS, reader_threads=READERS,
        )
    assert stats["ring_fresh_bytes"] == 0 and first["ring_fresh_bytes"] > 0
    assert _kept() is None


@pytest.mark.parametrize("over", [True, False])
def test_a_ring_over_the_bound_is_not_kept(over, tmp_path, monkeypatch):
    """The keep-or-not decision is made from the requested bytes: a
    ring one byte over the module's bound is allocated and freed by its
    operation as before, one exactly at the bound is kept."""
    vols = _Volumes(tmp_path, "v", SIZES[:1], seed=92)
    ring = _ring_bytes({"ring_slots": SLOTS}, 10 * SMALL)
    monkeypatch.setattr(ec_stream, "_RING_KEEP_BYTES", ring - 1 if over else ring)
    first, second = vols.single(), vols.single()
    assert first["ring_fresh_bytes"] == ring
    assert second["ring_fresh_bytes"] == (ring if over else 0)
    assert (_kept() is None) == over


def test_a_ring_too_small_for_the_request_stays_kept_for_the_next(tmp_path):
    """A request larger than what is kept leaves the kept memory where
    it is while it runs (a concurrent smaller operation can still take
    it) and replaces it when it comes back."""
    small = _Volumes(tmp_path, "s", SIZES[:1], seed=93)
    small.single()
    kept = _kept()
    got, fresh = ec_stream._RING.borrow(kept.size + 1)
    assert fresh == kept.size + 1 and got is not kept and _kept() is kept
    ec_stream._RING.give_back(got)
    assert _kept() is got


def test_metrics_count_the_fresh_bytes(tmp_path):
    vols = _Volumes(tmp_path, "v", SIZES[:1], seed=94)
    before = EC_RING_FRESH_BYTES.value()
    first = vols.single()
    assert EC_RING_FRESH_BYTES.value() - before == first["ring_fresh_bytes"] > 0
    vols.single()
    assert EC_RING_FRESH_BYTES.value() - before == first["ring_fresh_bytes"]
    assert any(
        ln.startswith("weed_ec_ring_fresh_bytes_total ")
        for ln in EC_RING_FRESH_BYTES.render()
    )


def test_root_span_carries_the_field(tmp_path):
    from seaweedfs_tpu import trace

    vols = _Volumes(tmp_path, "v", SIZES[:1], seed=95)
    trace.reset()
    try:
        for want_fresh in (True, False):
            stats = vols.single()
            root = max(
                (s for s in trace.debug_payload(n=256)["recent"]
                 if s["name"] == "ec_stream.encode"),
                key=lambda s: s["start"],
            )
            assert root["annot"]["ring_fresh_bytes"] == str(stats["ring_fresh_bytes"])
            assert (stats["ring_fresh_bytes"] > 0) == want_fresh
    finally:
        trace.reset()


def test_chunked_batch_adds_the_fresh_bytes_up(tmp_path, monkeypatch):
    """WEED_EC_PIPELINE_BATCH splits a batch into operations of their
    own; the verb's line carries the sum of what they allocated."""
    vols = _Volumes(tmp_path, "v", SIZES + SIZES[:1], seed=96)
    monkeypatch.setenv("WEED_EC_PIPELINE_BATCH", "2")
    stats = vols.batch()
    # the first chunk (two volumes) allocated, the second (one) fits in it
    assert stats["ring_fresh_bytes"] == _ring_bytes(stats, 2 * 10 * SMALL)
    assert vols.batch()["ring_fresh_bytes"] == 0


def test_the_holder_lends_its_memory_to_one_borrower_at_a_time():
    """More borrowers than cores on a shortened switch interval: whoever
    holds memory writes its own mark over it and finds it again after
    yielding, so a second borrower of the same memory would show; and
    what was lent comes back, the holder ends with the largest."""
    import sys
    import time

    holder = ec_stream._RING
    threads_n, rounds = 4 * (os.cpu_count() or 2), 200
    held: set[int] = set()
    held_lock = threading.Lock()
    errors: list[str] = []
    deadline = time.monotonic() + 60

    def work(n: int) -> None:
        for r in range(rounds):
            if time.monotonic() > deadline:
                errors.append("timed out")
                return
            arena, fresh = holder.borrow(4096 + 64 * ((n + r) % 5))
            with held_lock:
                if id(arena) in held:
                    errors.append(f"thread {n} got memory that is lent out")
                held.add(id(arena))
            arena[:8] = n
            time.sleep(0)
            if not (arena[:8] == n).all():
                errors.append(f"thread {n} lost its mark")
            if fresh not in (0, arena.size):
                errors.append(f"fresh bytes {fresh} of {arena.size}")
            with held_lock:
                held.discard(id(arena))
            holder.give_back(arena)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [
            threading.Thread(target=work, args=(n,)) for n in range(threads_n)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(90)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:3]
    assert not held and _kept().size == 4096 + 64 * 4
