"""The one pipeline shell under the EC stream drivers (ISSUE 30,
ec_stream._Op), driven directly with a toy plan: what every driver gets
from it whatever its bodies do. Nothing here touches a codec or JAX."""

import contextlib
import os
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import ec_stream
from tests.faults import ec_stream_threads, fds_under

CHUNK = 4096
ITEMS = 24
PHASE_FIELDS = ("head_s", "dispatch_span_s", "drain_s", "write_tail_s", "flush_s")
BODIES = ("opened", "prepare", "fill", "dispatch", "fetch", "checksum", "write")
WAIT_FIELDS = tuple(ec_stream._WAIT_BUSY)
SLOW = 0.05


def _payload(item: int) -> np.ndarray:
    return ((np.arange(CHUNK) * (item + 3) + item) % 251).astype(np.uint8)


def _run(
    tmp_path, stats: dict, fail_in: str | None = None, durable: bool = False,
    slow: str | None = None,
):
    """Two output files of ITEMS chunks: `a` takes what fill staged, `b`
    its complement from fetch. Later items are fetched sooner, so tiles
    finish out of order. `fail_in` names the body that raises on item 5
    (`opened`: when a reader thread opens its inputs); `slow` the body
    that sleeps SLOW seconds on every item."""
    outs = [str(tmp_path / "toy.a"), str(tmp_path / "toy.b")]
    seen: dict = {"order": [], "reports": [], "opened": 0, "closed": 0}
    src_path = str(tmp_path / "toy.src")
    with open(src_path, "wb") as f:
        f.write(b"x")

    def boom(body: str, item: int = 5, at: int = 5) -> None:
        if fail_in == body and item == at:
            raise RuntimeError(f"{body} failed")
        if slow == body:
            time.sleep(SLOW)

    @contextlib.contextmanager
    def opened():
        boom("opened")
        fd = os.open(src_path, os.O_RDONLY)
        seen["opened"] += 1
        try:
            yield fd
        finally:
            os.close(fd)
            seen["closed"] += 1

    def prepare(item):
        boom("prepare", item)
        return item

    def fill(src, item, buf):
        boom("fill", item)
        staged = buf[:CHUNK]
        staged[:] = _payload(item)
        return staged

    def dispatch(item, staged):
        boom("dispatch", item)
        return item

    def fetch(item, staged, handle):
        assert handle == item
        boom("fetch", item)
        time.sleep(0.0005 * (ITEMS - item))
        return 255 - staged

    def checksum(item, staged, result):
        boom("checksum", item)

    def write(fds, item, staged, result):
        boom("write", item)
        seen["order"].append(item)
        ec_stream._pwrite_full(fds[0], staged, item * CHUNK)
        ec_stream._pwrite_full(fds[1], result, item * CHUNK)

    def report(out, sp, whole):
        seen["reports"].append(whole)
        out["toy"] = True

    op = ec_stream._Op("ec_stream.toy")
    try:
        op.run(
            nbytes=ITEMS * CHUNK, items=list(range(ITEMS)), slot_bytes=CHUNK,
            outputs=[(p, ITEMS * CHUNK) for p in outs], opened=opened,
            prepare=prepare, fill=fill, dispatch=dispatch, fetch=fetch,
            checksum=checksum, write=write, report=report, stats=stats,
            durable=durable, reader_threads=3, writer_threads=4,
        )
    finally:
        seen["outs"] = outs
    return seen


def test_out_of_order_tiles_land_byte_exact(tmp_path):
    stats: dict = {}
    seen = _run(tmp_path, stats, durable=True)
    want = np.concatenate([_payload(i) for i in range(ITEMS)])
    assert open(seen["outs"][0], "rb").read() == want.tobytes()
    assert open(seen["outs"][1], "rb").read() == (255 - want).tobytes()
    assert sorted(seen["order"]) == list(range(ITEMS))
    assert seen["order"] != list(range(ITEMS))  # completion order is not item order
    assert seen["reports"] == [True] and stats["toy"] is True
    assert seen["opened"] == seen["closed"] == 3
    # the shell's own fields, whatever the plan
    assert stats["pipeline_depth"] == ec_stream._INFLIGHT
    assert stats["ring_slots"] == ec_stream._INFLIGHT + 4 + 1
    assert (stats["reader_threads"], stats["writer_threads"]) == (3, 4)
    assert sum(stats[f] for f in PHASE_FIELDS) == pytest.approx(
        stats["wall_s"], abs=3.5e-4
    )
    assert stats["reserve_s"] >= 0 and 0 < stats["reserve_done_s"] <= stats["wall_s"]
    assert "program_traces" not in stats  # no device stage was declared
    for field in WAIT_FIELDS:
        assert stats[field] >= 0, field
    assert not ec_stream_threads() and not fds_under(tmp_path)


@pytest.mark.parametrize("body", BODIES)
def test_an_error_in_any_body_aborts_the_operation_whole(body, tmp_path):
    stats: dict = {}
    with pytest.raises(RuntimeError, match=f"^{body} failed$"):
        _run(tmp_path, stats, fail_in=body)
    # every output gone, whatever was written; nothing left running or open
    assert not os.path.exists(tmp_path / "toy.a")
    assert not os.path.exists(tmp_path / "toy.b")
    assert not ec_stream_threads() and not fds_under(tmp_path)
    # and the close-out still ran: the fields are there and add up
    assert stats["toy"] is True
    assert sum(stats[f] for f in PHASE_FIELDS) == pytest.approx(
        stats["wall_s"], abs=3.5e-4
    )
    # the waits too: 0.0 or what was booked until the abort, never missing
    for field in WAIT_FIELDS:
        assert 0 <= stats[field] <= 12 * stats["wall_s"] + 1e-3, field


# Which waits carry ONE slow stage, and which stay near 0 (ISSUE 36):
# three readers, ONE dispatcher, four writers, a ring of eight slots, a
# read queue of three and an in-flight window of three; every body but
# the slow one takes microseconds (fetch: 0.5 to 12 ms an item).
SLOW_STAGES = {
    # the readers are the pace: the dispatcher and the writers starve,
    # nobody waits for memory or for room downstream
    "fill": (("tile_wait_s", "work_wait_s"), ("slot_wait_s", "window_wait_s")),
    # the writers are the pace: the window fills, then the ring
    "write": (("slot_wait_s", "window_wait_s"), ("work_wait_s",)),
    # the latch: two writers reserve, two stand with results in hand,
    # and behind them the window fills
    "_preallocate": (("latch_wait_s", "window_wait_s"), ()),
    # the dispatcher is the pace: the readers queue up behind it, the
    # writers idle in front of it
    "dispatch": (("read_q_wait_s", "work_wait_s"), ("slot_wait_s", "window_wait_s")),
}


@pytest.mark.parametrize("stage", sorted(SLOW_STAGES))
def test_one_slow_stage_shows_in_its_waits_and_in_no_other(
    stage, tmp_path, monkeypatch
):
    carry, spare = SLOW_STAGES[stage]
    if stage == "_preallocate":
        real = ec_stream._preallocate

        def slow_preallocate(fd, size):
            time.sleep(10 * SLOW)
            real(fd, size)

        monkeypatch.setattr(ec_stream, "_preallocate", slow_preallocate)
    stats: dict = {}
    _run(tmp_path, stats, slow=None if stage == "_preallocate" else stage)
    # the slow stage costs ITEMS x SLOW over its pool (0.5 s of reserve):
    # 0.3 to 1.2 s of wall; a wait that carries it holds a good part of
    # that, one that does not holds scheduling noise, and the writers'
    # also their wait through the head for the first tile
    for field in carry:
        assert stats[field] >= 3 * SLOW, (field, stats)
    for field in spare:
        noise = 2 * SLOW + (4 * stats["head_s"] if field == "work_wait_s" else 0)
        assert stats[field] <= noise, (field, stats)
    if stage != "_preallocate":
        assert stats["latch_wait_s"] <= 2 * SLOW, stats
    span = stats["tile_wait_s"] + stats["dispatch_call_s"] + stats["window_wait_s"]
    assert span == pytest.approx(stats["dispatch_span_s"], rel=0.02, abs=2e-3)


def test_a_failed_fsync_fails_the_operation_after_closing_every_fd(
    tmp_path, monkeypatch
):
    synced: list[int] = []
    real_fsync = os.fsync

    def fsync(fd):
        synced.append(fd)
        if len(synced) == 1:
            raise OSError(5, "fsync failed")
        real_fsync(fd)

    monkeypatch.setattr(os, "fsync", fsync)
    stats: dict = {}
    with pytest.raises(OSError, match="fsync failed"):
        _run(tmp_path, stats, durable=True)
    assert len(synced) == 2  # the second file was still synced and closed
    assert not os.path.exists(tmp_path / "toy.a")
    assert not os.path.exists(tmp_path / "toy.b")
    assert not fds_under(tmp_path) and stats["toy"] is True
