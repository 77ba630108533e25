"""The local repair's tile (ISSUE 35): the single-volume rebuild driver
at the tile sizes the chip was read at, on shard files that no tile
divides, through its own device stage and through an injected host
stage; and the default it takes when nobody passes a size.

CPU backend (the device stage then takes its bit-matmul arm): what is
asserted is bytes, CRCs and counts, never a time. Every expected byte
comes from the classic serial `rebuild_ec_files` of the numpy codec."""

import os

import numpy as np
import pytest

from seaweedfs_tpu.ec import ec_files, ec_stream
from seaweedfs_tpu.ec.codec import new_encoder
from seaweedfs_tpu.util.crc import crc32c
from tests.test_ec_rebuild_cell import _copy_without

KIB, MIB = 1 << 10, 1 << 20
# no tile size below divides it, and its tail is under an eighth of each
SHARD_BYTES = 9 * MIB + 4 * KIB + 12
TILES = (512 * KIB, MIB, 2 * MIB, 4 * MIB)
# one data shard, one parity shard, one of each
LOSSES = ((3,), (12,), (3, 12))
STAGES = ("device", "host")


def _write_shard_set(base: str, shard_bytes: int, seed: int) -> None:
    """Ten seeded data shards and the numpy codec's four parity shards."""
    rng = np.random.default_rng(seed)
    data = [rng.integers(0, 256, shard_bytes, dtype=np.uint8) for _ in range(10)]
    for i, shard in enumerate(new_encoder(backend="cpu").encode(data + [None] * 4)):
        np.asarray(shard).tofile(base + ec_files.to_ext(i))


@pytest.fixture(scope="module")
def shard_set(tmp_path_factory) -> str:
    base = str(tmp_path_factory.mktemp("tile") / "t_35")
    _write_shard_set(base, SHARD_BYTES, seed=3500)
    return base


@pytest.fixture(scope="module")
def classic(shard_set, tmp_path_factory) -> dict:
    """{lost: {shard id: the file the classic serial loop rebuilds}}."""
    want = {}
    for lost in LOSSES:
        copy = _copy_without(shard_set, lost, tmp_path_factory.mktemp("classic"))
        stats: dict = {}
        rebuilt = ec_files.rebuild_ec_files(
            copy, rs=new_encoder(backend="cpu"), stats=stats
        )
        assert rebuilt == list(lost) and stats["driver"] == "classic"
        want[lost] = {
            i: open(copy + ec_files.to_ext(i), "rb").read() for i in lost
        }
        for i in lost:  # the classic loop gives back the encoder's own files
            assert want[lost][i] == open(shard_set + ec_files.to_ext(i), "rb").read()
    return want


def _stage(name: str) -> dict:
    if name == "device":
        return {}
    rebuild_fn, fetch_fn = ec_stream.local_rebuild_fns(
        new_encoder(backend="cpu"), want_crcs=True
    )
    return {"rebuild_fn": rebuild_fn, "fetch_fn": fetch_fn}


def _launches(shard_bytes: int, tile: int, stage: str) -> int:
    """The device stage launches a tile as its power-of-two spans (it
    folds their CRCs in its program); an injected stage takes it whole."""
    tiles = [
        (off, min(tile, shard_bytes - off)) for off in range(0, shard_bytes, tile)
    ]
    if stage == "host":
        return len(tiles)
    return sum(len(ec_stream._pow2_spans(off, n, tile)) for off, n in tiles)


@pytest.mark.parametrize("stage", STAGES)
@pytest.mark.parametrize("lost", LOSSES, ids=lambda lost: "-".join(map(str, lost)))
@pytest.mark.parametrize("tile", TILES, ids=lambda t: f"{t // KIB}k")
def test_rebuild_at_each_tile_size_equals_the_classic_loop(
    tile, lost, stage, shard_set, classic, tmp_path
):
    copy = _copy_without(shard_set, lost, tmp_path)
    stats: dict = {}
    rebuilt = ec_stream.stream_rebuild_ec_files(
        copy, tile_bytes=tile, stats=stats, durable=True, want_crcs=True,
        **_stage(stage),
    )
    assert rebuilt == list(lost)
    assert stats["driver"] == "stream-" + stage
    for sid in lost:
        with open(copy + ec_files.to_ext(sid), "rb") as f:
            got = f.read()
        assert got == classic[lost][sid], sid
        assert stats["shard_crcs"][sid] == crc32c(got), sid
    assert stats["tiles"] == _launches(SHARD_BYTES, tile, stage)
    assert (stats["survivors"], stats["targets"]) == (10, len(lost))
    assert stats["survivor_bytes"] == 10 * SHARD_BYTES
    assert stats["rebuilt_bytes"] == len(lost) * SHARD_BYTES
    if stage == "device":
        assert sum(stats["arms"].values()) == stats["tiles"]


# --- the default -------------------------------------------------------------------


@pytest.fixture()
def fresh_ring(monkeypatch):
    """The test starts as a process that has run no operation."""
    monkeypatch.setattr(ec_stream, "_RING", ec_stream._KeptRing())


@pytest.mark.parametrize("stage", STAGES)
def test_default_tile_of_a_local_rebuild(stage, fresh_ring, tmp_path):
    """No tile_bytes and no remote_readers: the module's local default,
    whichever stage applies the matrix (ISSUE 35 keeps one size for the
    device stage and for host codecs)."""
    tile = ec_stream.VOLUME_TILE_BYTES
    shard_bytes = 2 * tile + tile // 2 + tile // 4 + 12
    base = str(tmp_path / "d_35")
    _write_shard_set(base, shard_bytes, seed=3501)
    want = open(base + ec_files.to_ext(3), "rb").read()

    def rebuild() -> dict:
        os.remove(base + ec_files.to_ext(3))
        stats: dict = {}
        assert ec_stream.stream_rebuild_ec_files(
            base, stats=stats, want_crcs=True, **_stage(stage)
        ) == [3]
        assert open(base + ec_files.to_ext(3), "rb").read() == want
        assert stats["shard_crcs"][3] == crc32c(want)
        return stats

    first = rebuild()
    # whole tiles and the tail's spans: 2 + (1/2, 1/4, the last 12 bytes)
    assert first["tiles"] == -(-shard_bytes // tile) + (2 if stage == "device" else 0)
    ring = first["ring_slots"] * 10 * tile
    assert first["ring_fresh_bytes"] == ring <= ec_stream._RING_KEEP_BYTES
    second = rebuild()
    assert second["tiles"] == first["tiles"]
    assert second["ring_fresh_bytes"] == 0
