"""The output files' reservation runs in the writer pool (ISSUE 29,
docs/CODEC.md "Reserving the shard files"; every driver since ISSUE 30,
whose one pipeline shell owns it): the shell opens the files on the
handler's thread, the writer threads preallocate them from one shared
iterator beside the first reads, and a latch keeps every shard write
behind the last reservation.

Host arm under JAX_PLATFORMS=cpu; each check runs on the single-volume
and the batch driver of encode and of rebuild. What is asserted is
order, counts, errors and
bookkeeping, never a device time. Every driver call carries a time
limit of its own: a writer parked on the latch would otherwise hang the
run, not fail it."""

import errno
import os
import re
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import ec_files, ec_stream
from seaweedfs_tpu.ec.codec import new_encoder
from tests.faults import ec_shards_less, ec_stream_threads, fds_under

LARGE = 64 * 1024
SMALL = 16 * 1024
PHASE_FIELDS = ("head_s", "dispatch_span_s", "drain_s", "write_tail_s", "flush_s")
SHARD = re.compile(r"\.ec\d\d$")
HANDLER = "op-handler"  # the thread that calls the driver: its dispatcher


def _make_dat(base: str, nbytes: int, seed: int) -> None:
    rng = np.random.default_rng(seed)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes())


def _single(tmp_path, stats: dict) -> list[str]:
    base = str(tmp_path / "v")
    if not os.path.exists(base + ".dat"):
        _make_dat(base, 10 * SMALL * 12 + 77, seed=29)
    parity_fn, fetch_fn = ec_stream.local_encode_fns(
        new_encoder(backend="cpu"), want_crcs=True
    )
    ec_stream.stream_write_ec_files(
        base, tile_bytes=SMALL, large_block_size=LARGE, small_block_size=SMALL,
        parity_fn=parity_fn, fetch_fn=fetch_fn, stats=stats, want_crcs=True,
        writer_threads=3, reader_threads=2,
    )
    return [base]


def _batch(tmp_path, stats: dict) -> list[str]:
    bases = [str(tmp_path / f"b{i}") for i in range(2)]
    for i, base in enumerate(bases):
        if not os.path.exists(base + ".dat"):
            # two volumes of different sizes: two reservation sizes
            _make_dat(base, 10 * SMALL * (6 + 3 * i) + i, seed=i)
    ec_stream.stream_write_ec_files_batch(
        bases, tile_bytes=SMALL, large_block_size=LARGE, small_block_size=SMALL,
        stats=stats, want_crcs=True, writer_threads=3, reader_threads=2,
    )
    return bases


LOST = (2, 11)  # what the rebuild drivers rebuild: a data and a parity shard


def _lose(base: str, nbytes: int, seed: int) -> None:
    ec_shards_less(base, nbytes, seed, LOST, LARGE, SMALL)


def _rebuild(tmp_path, stats: dict) -> list[str]:
    base = str(tmp_path / "r")
    _lose(base, 10 * SMALL * 12 + 77, seed=30)
    rebuild_fn, fetch_fn = ec_stream.local_rebuild_fns(
        new_encoder(backend="cpu"), want_crcs=True
    )
    rebuilt = ec_stream.stream_rebuild_ec_files(
        base, tile_bytes=SMALL, rebuild_fn=rebuild_fn, fetch_fn=fetch_fn,
        stats=stats, want_crcs=True, writer_threads=3, reader_threads=2,
    )
    assert rebuilt == list(LOST)
    return [base]


def _rebuild_batch(tmp_path, stats: dict) -> list[str]:
    bases = [str(tmp_path / f"rb{i}") for i in range(2)]
    for i, base in enumerate(bases):
        _lose(base, 10 * SMALL * (6 + 3 * i) + i, seed=40 + i)
    # tiles fine enough for more than _HOST_INLINE_TILES work items: the
    # host arm then runs its pools, through the shell
    rebuilt = ec_stream.stream_rebuild_ec_files_batch(
        bases, tile_bytes=SMALL // 2, stats=stats, want_crcs=True,
        writer_threads=3, reader_threads=2,
    )
    assert rebuilt == [list(LOST)] * 2 and "host_inline" not in stats
    return bases


DRIVERS = {
    "single": _single, "batch": _batch,
    "rebuild": _rebuild, "rebuild_batch": _rebuild_batch,
}
# the function through which each driver's reader pool reads its input
READ_FN = {
    "single": "_preadv_into", "batch": "_read_tile_into",
    "rebuild": "_pread_into", "rebuild_batch": "_pread_into",
}
NAMES = {
    "single": ["v"], "batch": ["b0", "b1"],
    "rebuild": ["r"], "rebuild_batch": ["rb0", "rb1"],
}


def _outputs(driver: str, tmp_path) -> list[str]:
    """The files the driver's operation opens, reserves and writes."""
    ids = LOST if driver.startswith("rebuild") else range(ec_files.TOTAL_SHARDS)
    return [
        str(tmp_path / name) + ec_files.to_ext(i)
        for name in NAMES[driver] for i in ids
    ]


def _within(seconds: float, fn, *args):
    """fn(*args) on a thread of its own, held to a time limit."""
    box: dict = {}

    def run():
        try:
            box["value"] = fn(*args)
        except BaseException as e:  # noqa: BLE001 — handed to the caller below
            box["error"] = e

    t = threading.Thread(target=run, daemon=True, name=HANDLER)
    t.start()
    t.join(seconds)
    assert not t.is_alive(), f"the operation did not end within {seconds} s"
    if "error" in box:
        raise box["error"]
    return box["value"]


def _shard_path(fd: int) -> str | None:
    path = os.readlink(f"/proc/self/fd/{fd}")
    return path if SHARD.search(path) else None


class _Syscalls:
    """os.posix_fallocate and os.pwritev of shard files, in the order
    they happened: a reservation is booked when it RETURNS, a write when
    it is ENTERED, so `write before the last reservation` cannot hide
    behind the recorder's own timing."""

    def __init__(self, monkeypatch, before_reserve=None):
        self.events: list[tuple[str, str, int]] = []  # (kind, path, size)
        self._lock = threading.Lock()
        self._started = 0
        real_fallocate, real_pwritev = os.posix_fallocate, os.pwritev

        def fallocate(fd, offset, length):
            path = _shard_path(fd)
            if path and before_reserve:
                with self._lock:
                    self._started += 1
                    nth = self._started
                before_reserve(nth)
            real_fallocate(fd, offset, length)
            if path:
                self._book("reserve", path, offset + length)

        def pwritev(fd, buffers, offset, *a):
            path = _shard_path(fd)
            if path:
                self._book("write", path, offset)
            return real_pwritev(fd, buffers, offset, *a)

        monkeypatch.setattr(os, "posix_fallocate", fallocate)
        monkeypatch.setattr(os, "pwritev", pwritev)

    def _book(self, kind: str, path: str, size: int) -> None:
        with self._lock:
            self.events.append((kind, path, size))

    def count(self, kind: str) -> int:
        with self._lock:
            return sum(1 for k, _, _ in self.events if k == kind)


def _no_output_left(driver: str, tmp_path) -> bool:
    return not any(os.path.exists(p) for p in _outputs(driver, tmp_path))


# --- (1) order and counts -----------------------------------------------------


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_no_shard_byte_is_written_before_the_last_reservation(
    driver, tmp_path, monkeypatch
):
    # programs compiled first, then a slow reservation: writers that
    # have fetched a tile long before the last file is reserved must
    # stand at the latch, not write
    (tmp_path / "warm").mkdir()
    _within(60, DRIVERS[driver], tmp_path / "warm", {})
    if driver.startswith("rebuild"):
        _within(60, DRIVERS[driver], tmp_path, {})  # the shard sets, unrecorded
    calls = _Syscalls(monkeypatch, before_reserve=lambda n: time.sleep(0.01))
    _within(60, DRIVERS[driver], tmp_path, {})
    kinds = [k for k, _, _ in calls.events]
    last_reserve = max(i for i, k in enumerate(kinds) if k == "reserve")
    first_write = kinds.index("write")
    assert last_reserve < first_write
    # once per file per operation, at the file's final size
    reserved = {p: s for k, p, s in calls.events if k == "reserve"}
    outputs = _outputs(driver, tmp_path)
    assert kinds.count("reserve") == len(reserved) == len(outputs)
    for path in outputs:
        want = ec_files.shard_file_size(
            os.path.getsize(path[:-5] + ".dat"), LARGE, SMALL
        )
        assert want > 0
        assert reserved[path] == want == os.path.getsize(path), path


# --- (2) ENOSPC from a pool thread --------------------------------------------


@pytest.mark.parametrize("kth", ["first", "last"])
@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_enospc_from_a_reservation_fails_before_any_write(
    driver, kth, tmp_path, monkeypatch
):
    files = len(_outputs(driver, tmp_path))
    k = 1 if kth == "first" else files
    raised_on: list[str] = []

    def full_disk(n):
        if n == k:
            time.sleep(0.05)  # the other writers stand at the latch by now
            raised_on.append(threading.current_thread().name)
            raise OSError(errno.ENOSPC, "No space left on device")

    # a clean run first: the programs are compiled, and a rebuild
    # driver's shard sets are written before the recorder is on
    _within(60, DRIVERS[driver], tmp_path, {})
    assert not ec_stream_threads() and not fds_under(tmp_path)
    with monkeypatch.context() as patched:
        calls = _Syscalls(patched, before_reserve=full_disk)
        with pytest.raises(OSError) as err:
            _within(60, DRIVERS[driver], tmp_path, {})
    assert err.value.errno == errno.ENOSPC
    assert raised_on and raised_on[0] != HANDLER
    assert calls.count("write") == 0
    assert calls.count("reserve") < files
    assert _no_output_left(driver, tmp_path)
    # no pool thread and no fd of the operation outlives it (counted by
    # the pipeline's own thread names and by this test's directory: an
    # xdist worker's other threads and sockets come and go)
    assert not ec_stream_threads() and not fds_under(tmp_path)

    # the next operation on the same files succeeds, byte for byte the
    # classic driver's
    stats: dict = {}
    bases = _within(60, DRIVERS[driver], tmp_path, stats)
    rs = new_encoder(backend="cpu")
    for base in bases:
        ref = base + "-classic"
        os.link(base + ".dat", ref + ".dat")
        ec_files.write_ec_files(
            ref, rs=rs, large_block_size=LARGE, small_block_size=SMALL
        )
        for i in range(ec_files.TOTAL_SHARDS):
            with open(base + ec_files.to_ext(i), "rb") as got, \
                    open(ref + ec_files.to_ext(i), "rb") as want:
                assert got.read() == want.read(), (base, i)


# --- (3) another stage's error while writers stand at the latch ---------------


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_reader_error_frees_writers_parked_on_the_latch(
    driver, tmp_path, monkeypatch
):
    """One reservation is still in flight, the other writers have fetched
    a tile and stand at the latch, then a reader raises: the operation
    ends within two _Q_TICKs of the error, with the reader's error."""
    files = len(_outputs(driver, tmp_path))
    if driver.startswith("rebuild"):
        _within(60, DRIVERS[driver], tmp_path, {})  # the shard sets, unpatched
    parked, reader_raised = threading.Event(), threading.Event()
    raised_at: list[float] = []

    def hold_the_last(n):
        if n == files:
            reader_raised.wait(20)

    _Syscalls(monkeypatch, before_reserve=hold_the_last)
    real_wait = ec_stream._Reservation.wait

    def wait(self):
        parked.set()
        return real_wait(self)

    monkeypatch.setattr(ec_stream._Reservation, "wait", wait)
    real_read = getattr(ec_stream, READ_FN[driver])
    first_reader: list[int] = []
    gate = threading.Lock()

    def read(*args):
        with gate:
            if not first_reader:
                first_reader.append(threading.get_ident())
        if first_reader[0] == threading.get_ident():
            time.sleep(0.002)  # leaves tiles for the reader that fails
            return real_read(*args)
        assert parked.wait(20), "no writer reached the latch"
        raised_at.append(time.perf_counter())
        reader_raised.set()
        raise RuntimeError("read failed")

    monkeypatch.setattr(ec_stream, READ_FN[driver], read)
    with pytest.raises(RuntimeError, match="read failed"):
        _within(30, DRIVERS[driver], tmp_path, {})
    assert time.perf_counter() - raised_at[0] < 2 * ec_stream._Q_TICK
    assert _no_output_left(driver, tmp_path)


# --- (4) what the operation books ---------------------------------------------


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_reservation_is_booked_and_is_not_in_the_head(
    driver, tmp_path, monkeypatch
):
    """50 ms a file, and the LAST file's reservation does not begin until
    the handler's thread has dispatched its first tile: a reservation on
    the handler's thread (in the head) could never see that."""
    from seaweedfs_tpu import trace

    if driver.startswith("rebuild"):
        _within(60, DRIVERS[driver], tmp_path, {})  # the shard sets, unpatched
    files = len(_outputs(driver, tmp_path))
    dispatched = threading.Event()
    saw_dispatch: list[bool] = []
    real_to = trace.Phases.to

    def to(self, name, at=None):
        if name == "ec.op.dispatch":
            dispatched.set()
        return real_to(self, name, at)

    def slow(n):
        if n == files:
            saw_dispatch.append(dispatched.wait(20))
        time.sleep(0.05)

    monkeypatch.setattr(trace.Phases, "to", to)
    _Syscalls(monkeypatch, before_reserve=slow)
    stats: dict = {}
    _within(60, DRIVERS[driver], tmp_path, stats)
    assert saw_dispatch == [True]
    assert stats["reserve_s"] >= 0.05 * files * 0.9  # thread-seconds of the pool
    assert 0.05 * files / 3 * 0.9 <= stats["reserve_done_s"] <= stats["wall_s"]
    assert stats["head_s"] < stats["reserve_done_s"]
    assert sum(stats[f] for f in PHASE_FIELDS) == pytest.approx(
        stats["wall_s"], abs=3.5e-4
    )


# --- (5) the report line, and the benchmark's two metrics that read it --------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRICS = {"reserve_s_per_gib": "reserve_s", "reserve_done_s_per_gib": "reserve_done_s"}
CELLS = ["encode-1g", "batch-encode-256m", "batch-encode-x4"]


@pytest.fixture
def bench_harness(monkeypatch):
    """benchmark/harness as benchmark/run.py imports it."""
    import importlib

    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmark"))
    return (importlib.import_module("harness.readers"),
            importlib.import_module("harness.node"))


def _node_reports(node, verb: str, stats: dict) -> list[dict]:
    """`stats` as the node writes them on its `ec.<verb> report=` line,
    read back by the benchmark's own parser."""
    import logging

    from seaweedfs_tpu.server.volume_server import VolumeServer

    lines: list[str] = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    logger = logging.getLogger("seaweedfs_tpu")
    logger.addHandler(handler)
    try:
        VolumeServer._log_ec_verb(verb, [1], stats)
    finally:
        logger.removeHandler(handler)
    return node.verb_reports("I] " + lines[-1], verb)


def _parents_reports(node) -> list[dict]:
    """Report lines of a program that has neither PR 29's nor PR 33's fields."""
    with open(os.path.join(REPO, "benchmark", "selftest", "node_log_phases.txt")) as f:
        return node.verb_reports(f.read(), "generate")


WINDOW = {"seconds": 1.0, "gib": 0.5, "requests": 1}


@pytest.mark.parametrize("name", sorted(METRICS))
@pytest.mark.parametrize("driver,verb", [("single", "generate"), ("batch", "batch_generate")])
def test_report_line_feeds_the_benchmark_metric(
    driver, verb, name, tmp_path, bench_harness
):
    """The node's report line carries the field, the metric file reads
    it through the readers that were there, and a report line of a
    program without the field (the parent's) makes it read nothing."""
    import json

    readers, node = bench_harness
    metric = readers.load_metric(name)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    for key in ("name", "unit", "better", "layer", "moves", "source"):
        assert metric[key] == entry[key], key
    # a cell joins a metric by the manifest's list (run.py:per_layer); the
    # file's copy is the three cells of ISSUE 29, and the repair cells of
    # ISSUEs 32 and 34 were appended to the manifest alone
    assert metric["workloads"] == CELLS and entry["moves"] == "ec_gbps"
    assert entry["workloads"][:5] == CELLS + ["rebuild-1data", "rack-rebuild-4lost"]

    stats: dict = {}
    _within(60, DRIVERS[driver], tmp_path, stats)
    reports = _node_reports(node, verb, stats)
    assert len(reports) == 1 and reports[0][METRICS[name]] == stats[METRICS[name]] > 0
    obs = {"reports": reports, "window": WINDOW, "trace": None}
    assert readers.read_metric(metric, obs) == pytest.approx(stats[METRICS[name]] / 0.5)
    obs["reports"] = _parents_reports(node)
    assert obs["reports"] and readers.read_metric(metric, obs) is None


ALL_CELLS = CELLS + ["rebuild-1data"]


@pytest.mark.parametrize("driver,verb", [
    ("single", "generate"), ("batch", "batch_generate"),
    ("rebuild", "rebuild"), ("rebuild_batch", "rebuild"),
])
def test_report_line_feeds_the_ring_metric(
    driver, verb, tmp_path, bench_harness, monkeypatch
):
    """ISSUE 33's one metric, `ring_fresh_bytes_per_gib`: the file is its
    manifest entry word for word and lists all four cells, the report
    line of an operation that allocated its ring reads its bytes, that of
    one that ran on kept memory reads 0 (a value, not nothing), and a
    line of a program without the field (the parent's) reads nothing."""
    import json

    readers, node = bench_harness
    name = "ring_fresh_bytes_per_gib"
    metric = readers.load_metric(name)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    for key in ("name", "unit", "better", "layer", "moves", "source"):
        assert metric[key] == entry[key], key
    # the file's copy of the list is this PR's four cells; a later cell
    # joins by the manifest's list alone (run.py:per_layer)
    assert metric["workloads"] == ALL_CELLS
    assert entry["workloads"][:4] == ALL_CELLS
    assert (entry["unit"], entry["better"], entry["moves"], entry["layer"]) == (
        "bytes/GiB", "lower", "ec_gbps", "stream driver")
    assert (metric["num"], metric["den"]) == (["report:ring_fresh_bytes"], ["window:gib"])

    # a process before its first operation
    monkeypatch.setattr(ec_stream, "_RING", ec_stream._KeptRing())
    for fresh in (True, False):
        stats: dict = {}
        _within(60, DRIVERS[driver], tmp_path, stats)
        assert (stats["ring_fresh_bytes"] > 0) == fresh
        reports = _node_reports(node, verb, stats)
        assert len(reports) == 1
        assert reports[0]["ring_fresh_bytes"] == stats["ring_fresh_bytes"]
        obs = {"reports": reports, "window": WINDOW, "trace": None}
        assert readers.read_metric(metric, obs) == stats["ring_fresh_bytes"] / 0.5
    obs["reports"] = _parents_reports(node)
    assert obs["reports"] and readers.read_metric(metric, obs) is None
