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


def _payload(item: int) -> np.ndarray:
    return ((np.arange(CHUNK) * (item + 3) + item) % 251).astype(np.uint8)


def _run(tmp_path, stats: dict, fail_in: str | None = None, durable: bool = False):
    """Two output files of ITEMS chunks: `a` takes what fill staged, `b`
    its complement from fetch. Later items are fetched sooner, so tiles
    finish out of order. `fail_in` names the body that raises on item 5
    (`opened`: when a reader thread opens its inputs)."""
    outs = [str(tmp_path / "toy.a"), str(tmp_path / "toy.b")]
    seen: dict = {"order": [], "reports": [], "opened": 0, "closed": 0}
    src_path = str(tmp_path / "toy.src")
    with open(src_path, "wb") as f:
        f.write(b"x")

    def boom(body: str, item: int = 5, at: int = 5) -> None:
        if fail_in == body and item == at:
            raise RuntimeError(f"{body} failed")

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
