"""The single-volume encode sends each run of full rows to its codec
stage as ONE shard-major tile (`ec_stream._encode_tiles`,
`_read_rows_into`): row i of a [10, rows x block] tile is shard i's run
of blocks, read with one `preadv` in file order and written with one
`pwrite` a shard; runs are cut into power-of-two counts of rows, the
widths the fused CRC takes. Shard files and `shard_crcs` are held byte
for byte to the classic serial driver and to the table CRC of each file,
on an injected host stage and on the device stage (the CPU backend's
bit-matmul arm). What is asserted is bytes, CRCs and counts, never a
time."""

import json
import os
import shutil

import numpy as np
import pytest

from seaweedfs_tpu.ec import ec_files, ec_stream
from seaweedfs_tpu.ec.codec import new_encoder
from seaweedfs_tpu.util.crc import crc32c
from tests.test_ec_reserve import (  # noqa: F401 — bench_harness is a fixture
    REPO, WINDOW, _node_reports, _parents_reports, bench_harness,
)

SMALL = 4096
LARGE = 128 * SMALL  # a large row is 5 MiB: 103 small rows stay small
TILE = 4 * SMALL  # at most four rows a tile
ROW = 10 * SMALL
# .dat size -> the rows of each full-row tile, in order
SHAPES = {
    "one-row": (ROW, [1]),
    "three-rows": (3 * ROW, [2, 1]),
    # a 1 GiB volume's 103 rows under 4 MiB tiles, scaled down
    "103-rows": (103 * ROW, [4] * 25 + [2, 1]),
    # seven rows, the last cut inside shard 2's block
    "last-row-cut": (6 * ROW + 2 * SMALL + 100, [4, 2, 1]),
    # one super-tile whose last row ends inside shard 7's block
    "eof-in-super-tile": (3 * ROW + 7 * SMALL + 11, [4]),
    # two large rows as sub-block tiles, then six small rows
    "large-tier": (2 * 10 * LARGE + 5 * ROW + 7, [4, 2]),
}
STAGES = ("host", "device")


def _make_dat(base: str, nbytes: int, seed: int) -> None:
    rng = np.random.default_rng(seed)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes())


def _shards(base: str) -> list[bytes]:
    out = []
    for i in range(ec_files.TOTAL_SHARDS):
        with open(base + ec_files.to_ext(i), "rb") as f:
            out.append(f.read())
    return out


def _classic(tmp_path, base: str, block: int = SMALL, **sizes) -> list[bytes]:
    ref = str(tmp_path / ("ref-" + os.path.basename(base)))
    shutil.copy(base + ".dat", ref + ".dat")
    ec_files.write_ec_files(
        ref, rs=new_encoder(backend="cpu"), buffer_size=block,
        **(sizes or dict(large_block_size=LARGE, small_block_size=SMALL)),
    )
    return _shards(ref)


def _encode(base: str, stage: str, tile: int | None = TILE, **sizes) -> dict:
    fns = {}
    if stage == "host":
        parity_fn, fetch_fn = ec_stream.local_encode_fns(
            new_encoder(backend="cpu"), want_crcs=True
        )
        fns = dict(parity_fn=parity_fn, fetch_fn=fetch_fn)
    stats: dict = {}
    ec_stream.stream_write_ec_files(
        base, tile_bytes=tile, stats=stats, want_crcs=True,
        **(sizes or dict(large_block_size=LARGE, small_block_size=SMALL)),
        **fns,
    )
    return stats


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_tiles_are_power_of_two_runs_of_rows(shape):
    size, rows = SHAPES[shape]
    tiles = ec_stream._encode_tiles(size, TILE, LARGE, SMALL)
    full = [t for t in tiles if t[2] == 0 and t[3] == t[1]]
    assert [t[4] for t in full] == rows
    # each run of rows starts where the one before it ended in the .dat
    for (off, block, _, _, n), nxt in zip(full, full[1:]):
        assert nxt[0] == off + n * block * 10
    sub = [t for t in tiles if t not in full]
    assert all(t[4] == 1 for t in sub)
    # a large row's blocks are read as 32 sub-block tiles each
    assert len(sub) == (64 if shape == "large-tier" else 0)
    widths = sum(step * n for _, _, _, step, n in tiles)
    assert widths == ec_files.shard_file_size(size, LARGE, SMALL)


def test_a_gib_volume_is_27_tiles_of_the_repairs_shapes():
    """The cell's volume: 103 rows of 1 MiB under the default tile."""
    size = 1_074_470_000  # a sealed .dat of 1.0007 GiB
    tiles = ec_stream._encode_tiles(
        size, ec_stream.VOLUME_TILE_BYTES, ec_stream.LARGE_BLOCK_SIZE,
        ec_stream.SMALL_BLOCK_SIZE,
    )
    widths = [step * n for _, _, _, step, n in tiles]
    assert len(tiles) == 27
    assert widths == [4 << 20] * 25 + [2 << 20, 1 << 20]


@pytest.mark.parametrize("stage", STAGES)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_shard_files_and_crcs_match_the_classic_driver(shape, stage, tmp_path):
    size, _ = SHAPES[shape]
    base = str(tmp_path / "v")
    _make_dat(base, size, seed=len(shape))
    want = _classic(tmp_path, base)
    stats = _encode(base, stage)
    assert _shards(base) == want
    assert stats["shard_crcs"] == [crc32c(b) for b in want]
    assert stats["tiles"] == len(ec_stream._encode_tiles(size, TILE, LARGE, SMALL))
    assert stats["tile_bytes"] == TILE
    if stage == "device":
        assert sum(stats["arms"].values()) == stats["tiles"]
    # a second operation over the same files on the kept ring
    again = _encode(base, stage)
    assert _shards(base) == want and again["shard_crcs"] == stats["shard_crcs"]


def test_one_preadv_per_super_tile(tmp_path, monkeypatch):
    """103 rows are 27 tiles: 27 reads, each ONE syscall for its rows'
    forty blocks (four rows x ten shards, in the order of the .dat)."""
    size, _ = SHAPES["103-rows"]
    base = str(tmp_path / "v")
    _make_dat(base, size, seed=103)
    want = _classic(tmp_path, base)
    calls: list[int] = []
    real = os.preadv

    def preadv(fd, buffers, offset):
        calls.append(len(buffers))  # list.append is atomic under the GIL
        return real(fd, buffers, offset)

    monkeypatch.setattr(os, "preadv", preadv)
    stats = _encode(base, "host")
    monkeypatch.undo()
    assert _shards(base) == want
    assert len(calls) == stats["tiles"] == 27
    assert sorted(calls) == sorted([40] * 25 + [20, 10])


def test_a_tile_of_more_blocks_than_one_preadv_takes(tmp_path, monkeypatch):
    """A run of 256 rows of 4 KiB is one tile of 2,560 blocks: read in
    calls of at most _IOV_MAX buffers, each resuming where the last one
    stopped, and the bytes are the classic driver's."""
    sizes = dict(large_block_size=ec_stream.LARGE_BLOCK_SIZE, small_block_size=SMALL)
    base = str(tmp_path / "v")
    _make_dat(base, 300 * ROW - 9, seed=300)
    want = _classic(tmp_path, base, **sizes)
    calls: list[int] = []
    real = os.preadv

    def preadv(fd, buffers, offset):
        calls.append(len(buffers))
        return real(fd, buffers, offset)

    monkeypatch.setattr(os, "preadv", preadv)
    stats = _encode(base, "host", tile=256 * SMALL, **sizes)
    monkeypatch.undo()
    assert _shards(base) == want
    assert stats["shard_crcs"] == [crc32c(b) for b in want]
    assert stats["tiles"] == 4  # 256 + 32 + 8 + 4 rows
    assert sorted(calls) == sorted([1024, 1024, 512, 320, 80, 40])


def test_the_default_tile_is_the_single_volume_repairs(tmp_path):
    """tile_bytes=None: VOLUME_TILE_BYTES a shard, here 32 rows of
    128 KiB at most; a volume of 22 such rows is tiles of 16, 4 and 2."""
    block = 128 * 1024
    sizes = dict(large_block_size=ec_stream.LARGE_BLOCK_SIZE, small_block_size=block)
    base = str(tmp_path / "v")
    _make_dat(base, 21 * 10 * block + 5, seed=22)
    want = _classic(tmp_path, base, block, **sizes)
    stats = _encode(base, "host", tile=None, **sizes)
    assert stats["tile_bytes"] == ec_stream.VOLUME_TILE_BYTES == 32 * block
    assert stats["tiles"] == 3
    assert _shards(base) == want


def test_report_line_feeds_the_launch_metric(tmp_path, bench_harness):
    """The node's `ec.generate` line carries `tiles` (the sum of its
    arms) with `tile_bytes` beside it, the metric file is its manifest
    entry and reads tiles per GiB, and a line of a program without the
    field (the parent's) makes it read nothing."""
    readers, node = bench_harness
    name = "encode_launches_per_gib"
    metric = readers.load_metric(name)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    for key in ("name", "unit", "better", "layer", "moves", "source", "workloads"):
        assert metric[key] == entry[key], key
    assert entry["workloads"] == ["encode-1g"] and entry["moves"] == "ec_gbps"
    # appended after every metric before it; the batch encode's rounds
    # metric follows it
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-2:] == [name, "batch_encode_rounds_per_gib"]
    assert manifest["per_layer"][-2] == entry
    assert (metric["num"], metric["den"]) == (["report:tiles"], ["window:gib"])

    base = str(tmp_path / "v")
    _make_dat(base, SHAPES["103-rows"][0], seed=7)
    stats = _encode(base, "device")
    reports = _node_reports(node, "generate", stats)
    assert len(reports) == 1
    rep = reports[0]
    assert rep["tiles"] == sum(rep["arms"].values()) == 27
    assert rep["tile_bytes"] == TILE
    obs = {"reports": reports, "window": WINDOW, "trace": None}
    assert readers.read_metric(metric, obs) == 27 / WINDOW["gib"]
    obs["reports"] = _parents_reports(node)
    assert obs["reports"] and readers.read_metric(metric, obs) is None

