"""The batch encode stacks, for each volume of a round, one tile of the
single-volume encode's cut (`ec_stream._encode_tiles`): a run of full
rows of a power-of-two count, laid out shard-major, read with ONE
`preadv` (`_read_rows_into`) and written with one `pwrite` a shard file.
Its width comes from the rule both batch drivers share
(`ec_stream.batch_round_tile_bytes`: the volumes a round stacks and the
ring the process keeps). Shard files and `shard_crcs` are held byte for
byte to the classic serial driver and to the table CRC of each file, on
the mesh codec the driver provisions for itself (the CPU backend here).
What is asserted is bytes, CRCs and counts, never a time."""

import json
import os

import numpy as np
import pytest

from seaweedfs_tpu.ec import ec_stream
from seaweedfs_tpu.util.crc import crc32c
from tests.test_ec_encode_tile import _classic, _make_dat, _shards
from tests.test_ec_reserve import (  # noqa: F401 — bench_harness is a fixture
    REPO, WINDOW, _node_reports, _parents_reports, bench_harness,
)

SMALL = 4096
LARGE = 64 * SMALL  # a large row is 2.5 MiB of .dat: 64 small rows
TILE = 2 * SMALL  # two small rows a round
ROW = 10 * SMALL
SIZES = dict(large_block_size=LARGE, small_block_size=SMALL)
MIB = 1 << 20
SLOTS = 12  # the chip's host: eight writers
# .dat sizes of each batch
BATCHES = {
    "one": [5 * ROW + 77],
    "four-unequal": [
        26 * ROW,
        21 * ROW,  # five rows short
        9 * ROW + 3 * SMALL + 5,  # ends inside shard 3's block
        0,  # empty
    ],
    "four-large-tier": [
        2 * 10 * LARGE + 3 * ROW + 5,  # two large rows, then small ones
        7 * ROW,
        7 * ROW + 1,
        ROW - 9,  # one row, cut short
    ],
    "six": [
        2 * 10 * LARGE + ROW,
        26 * ROW,
        13 * ROW + SMALL,
        3 * ROW,
        ROW + 1,
        0,
    ],
}


def _volumes(tmp_path, sizes: list[int], seed: int) -> list[str]:
    bases = []
    for i, size in enumerate(sizes):
        bases.append(str(tmp_path / f"b{i}"))
        _make_dat(bases[-1], size, seed=seed + i)
    return bases


def _batch(bases: list[str], tile: int | None = TILE, **kw) -> dict:
    stats: dict = {}
    ec_stream.stream_write_ec_files_batch(
        bases, tile_bytes=tile, stats=stats, want_crcs=True, **SIZES, **kw
    )
    return stats


def _rounds(sizes: list[int], tile: int) -> int:
    return max(
        len(ec_stream._encode_tiles(size, tile, LARGE, SMALL)) for size in sizes
    )


@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_shard_files_and_crcs_match_the_classic_driver(batch, tmp_path):
    sizes = BATCHES[batch]
    bases = _volumes(tmp_path, sizes, seed=len(batch))
    want = [_classic(tmp_path, base, **SIZES) for base in bases]
    stats = _batch(bases)
    for base, files, crcs in zip(bases, want, stats["shard_crcs"]):
        assert _shards(base) == files
        assert crcs == [crc32c(f) for f in files]
    assert stats["tiles"] == _rounds(sizes, TILE)
    assert stats["tile_bytes"] == TILE
    # a second operation over the same files on the kept ring
    again = _batch(bases)
    for base, files in zip(bases, want):
        assert _shards(base) == files
    assert again["shard_crcs"] == stats["shard_crcs"]


@pytest.mark.parametrize("volumes", [1, 4, 6])
def test_the_default_round_is_the_rule(volumes, tmp_path):
    """tile_bytes=None: the rule's width for the volumes under the ring
    of eight writers' twelve slots, here runs of up to 512 and 256 rows
    of 4 KiB; each volume's .dat ends inside a row."""
    tile = ec_stream.batch_round_tile_bytes(volumes, SLOTS)
    sizes = [(40 + 7 * v) * ROW + 11 * v + 3 for v in range(volumes)]
    bases = _volumes(tmp_path, sizes, seed=100 + volumes)
    want = [_classic(tmp_path, base, **SIZES) for base in bases]
    stats = _batch(bases, tile=None, writer_threads=8)
    assert stats["tile_bytes"] == tile
    assert stats["tiles"] == _rounds(sizes, tile)
    for base, files, crcs in zip(bases, want, stats["shard_crcs"]):
        assert _shards(base) == files
        assert crcs == [crc32c(f) for f in files]


@pytest.mark.parametrize(
    "volumes,tile,rounds",
    [(1, 4 * MIB, 7), (4, 2 * MIB, 13), (6, MIB, 26), (8, MIB, 26), (9, MIB // 2, 52)],
)
def test_the_rule_for_the_cells_volumes(volumes, tile, rounds):
    """At twelve slots: four volumes of 26 rows of 1 MiB (a sealed
    .dat of 256 MiB) go in 13 rounds of two rows, six in 26 of one."""
    assert ec_stream.batch_round_tile_bytes(volumes, SLOTS) == tile
    size = 256 * MIB + 12345  # 26 rows, the last cut short
    tiles = ec_stream._encode_tiles(
        size, tile, ec_stream.LARGE_BLOCK_SIZE, ec_stream.SMALL_BLOCK_SIZE
    )
    assert len(tiles) == rounds
    assert sum(step * n for _, _, _, step, n in tiles) == 26 * MIB


def test_one_preadv_per_volume_and_round(tmp_path, monkeypatch):
    """Every round of full rows is ONE read a volume, of its rows' blocks
    in the order of the .dat (two rows x ten shards), and one write a
    shard file: 13 rounds of four volumes of 26, 26, 20 and 25 rows."""
    sizes = [26 * ROW, 26 * ROW, 20 * ROW, 25 * ROW]
    bases = _volumes(tmp_path, sizes, seed=26)
    want = [_classic(tmp_path, base, **SIZES) for base in bases]
    reads: list[int] = []
    writes: list[int] = []
    real_read, real_write = os.preadv, os.pwritev

    def preadv(fd, buffers, offset):
        reads.append(len(buffers))  # list.append is atomic under the GIL
        return real_read(fd, buffers, offset)

    def pwritev(fd, buffers, offset):
        writes.append(sum(len(b) for b in buffers))
        return real_write(fd, buffers, offset)

    monkeypatch.setattr(os, "preadv", preadv)
    monkeypatch.setattr(os, "pwritev", pwritev)
    stats = _batch(bases)
    monkeypatch.undo()
    for base, files in zip(bases, want):
        assert _shards(base) == files
    tiles = [
        t for size in sizes for t in ec_stream._encode_tiles(size, TILE, LARGE, SMALL)
    ]
    assert stats["tiles"] == 13
    # 13 + 13 + 10 + 12 runs of two rows and one of one
    assert [t[4] for t in tiles] == [2] * 48 + [1]
    assert sorted(reads) == [10] + [20] * 48
    assert sorted(writes) == [SMALL] * 14 + [TILE] * 14 * 48


def test_report_line_feeds_the_rounds_metric(tmp_path, bench_harness):
    """The node's `ec.batch_generate` line carries `tiles` (the rounds
    the dispatcher sent) with `tile_bytes` beside it, the metric file is
    its manifest entry and reads rounds per GiB, and a line of a program
    without the field (the parent's) makes it read nothing."""
    readers, node = bench_harness
    name = "batch_encode_rounds_per_gib"
    metric = readers.load_metric(name)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    for key in ("name", "unit", "better", "layer", "moves", "source", "workloads"):
        assert metric[key] == entry[key], key
    assert entry["workloads"] == ["batch-encode-256m", "batch-encode-x4"]
    assert entry["moves"] == "ec_gbps" and manifest["per_layer"][-1] == entry
    assert (metric["num"], metric["den"]) == (["report:tiles"], ["window:gib"])

    bases = _volumes(tmp_path, [26 * ROW, 26 * ROW - 5, 25 * ROW, 26 * ROW], seed=4)
    stats = _batch(bases)
    reports = _node_reports(node, "batch_generate", stats)
    assert len(reports) == 1
    rep = reports[0]
    assert rep["tiles"] == 13 and rep["tile_bytes"] == TILE
    obs = {"reports": reports, "window": WINDOW, "trace": None}
    assert readers.read_metric(metric, obs) == 13 / WINDOW["gib"]
    obs["reports"] = _parents_reports(node)
    assert obs["reports"] and readers.read_metric(metric, obs) is None


def test_chunks_sum_their_rounds(tmp_path, monkeypatch):
    """WEED_EC_PIPELINE_BATCH splits a batch into chunks, each a pass of
    its own: the call's `tiles` is the sum of their rounds, not the last
    chunk's, and the bytes are the classic driver's."""
    monkeypatch.setenv("WEED_EC_PIPELINE_BATCH", "2")
    sizes = [6 * ROW, 3 * ROW + 1, 9 * ROW, 2 * ROW, 4 * ROW - 7]
    bases = _volumes(tmp_path, sizes, seed=55)
    want = [_classic(tmp_path, base, **SIZES) for base in bases]
    stats = _batch(bases)
    chunks = [sizes[i : i + 2] for i in range(0, len(sizes), 2)]
    assert [_rounds(c, TILE) for c in chunks] == [3, 5, 2]
    assert stats["tiles"] == 10 and stats["tile_bytes"] == TILE
    assert stats["batch_volumes"] == 5
    for base, files, crcs in zip(bases, want, stats["shard_crcs"]):
        assert _shards(base) == files
        assert crcs == [crc32c(f) for f in files]


def test_read_rows_fills_strided_rows_of_a_slot(tmp_path):
    """A volume's rows in a batch slot are strided views (the slot is
    [volumes, 10, width]); one preadv fills them in file order and
    zero-pads past the .dat's end, leaving the rest of the slot alone."""
    base = str(tmp_path / "v")
    size = 3 * ROW - 100
    _make_dat(base, size, seed=3)
    with open(base + ".dat", "rb") as f:
        data = np.frombuffer(f.read(), dtype=np.uint8)
    slot = np.full((2, 10, 5 * SMALL), 0xA5, dtype=np.uint8)
    fd = os.open(base + ".dat", os.O_RDONLY)
    try:
        ec_stream._read_rows_into(fd, size, 0, SMALL, slot[1, :, : 4 * SMALL])
    finally:
        os.close(fd)
    padded = np.zeros(4 * ROW, dtype=np.uint8)
    padded[:size] = data
    rows = padded.reshape(4, 10, SMALL)  # [row, shard, block byte]
    assert np.array_equal(
        slot[1, :, : 4 * SMALL], rows.transpose(1, 0, 2).reshape(10, 4 * SMALL)
    )
    assert (slot[0] == 0xA5).all() and (slot[1, :, 4 * SMALL :] == 0xA5).all()
