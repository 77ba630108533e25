"""The batch rebuild's round tile (ISSUE 39): the mesh arm of
`stream_rebuild_ec_files_batch` sizes the span a survivor `pread` moves
from the volumes it stacks a round and the staging ring the process
keeps (`ec_stream.batch_rebuild_tile_bytes`), where it took the 512 KiB
of a CPU-sandbox sweep whatever the batch. The rule as a pure function;
the mesh arm at every size the rule can choose, through a MeshCodec over
CPU devices, held to `rebuild_ec_files`' bytes and CRCs; the kept ring
under the chosen size; the host arm, which keeps 512 KiB.

Everything runs on the CPU backend: what is asserted is bytes, counts
and the report's fields, never a time."""

import os

import numpy as np
import pytest

from seaweedfs_tpu.ec import ec_files, ec_stream
from seaweedfs_tpu.ec.codec import new_encoder

from faults import ec_shards_less

KIB, MIB = 1 << 10, 1 << 20
LARGE = 1 << 30
SMALL = 16 * KIB  # a shard file is whole rows of this
LOST = (3, 10)  # the drive-loss configuration's: a data and a parity shard
SIZES = [512 * KIB, MIB, 2 * MIB, 4 * MIB]
SLOTS = 12  # _INFLIGHT + 8 writers + 1: the ring of the chip's host
WRITERS = 8


@pytest.fixture(autouse=True)
def own_ring(monkeypatch):
    """A kept ring of the test's own, so that what a call here keeps (up
    to 960 MiB of address space) goes with the test."""
    monkeypatch.setattr(ec_stream, "_RING", ec_stream._KeptRing())


# --- the rule -----------------------------------------------------------------


@pytest.mark.parametrize("volumes,expected", [
    (1, 4 * MIB), (2, 4 * MIB), (3, 2 * MIB), (4, 2 * MIB), (5, MIB), (8, MIB),
    (9, 512 * KIB), (16, 512 * KIB), (256, 512 * KIB),
])
def test_rule_takes_the_largest_tile_whose_ring_is_kept(volumes, expected):
    """At twelve slots, the numbers ISSUE 39 and the comments state; and
    what makes them so: a power of two in [512 KiB, 4 MiB], the largest
    whose ring the process keeps, never below 512 KiB where none fits."""
    tile = ec_stream.batch_rebuild_tile_bytes(volumes, SLOTS)
    assert tile == expected and tile in SIZES
    assert SIZES[0] == ec_stream.BATCH_REBUILD_MIN_TILE_BYTES
    assert SIZES[-1] == ec_stream.REBUILD_TILE_BYTES
    fits = [t for t in SIZES
            if SLOTS * volumes * ec_stream.DATA_SHARDS * t <= ec_stream._RING_KEEP_BYTES]
    assert tile == (max(fits) if fits else SIZES[0])


def test_rule_reads_the_slots_of_the_callers_ring():
    # a narrower writer pool is a smaller ring: four volumes fit 4 MiB
    assert ec_stream._ring_slots(WRITERS) == SLOTS
    assert ec_stream._ring_slots(2) == ec_stream._INFLIGHT + 3
    assert ec_stream._ring_slots() == ec_stream._ring_slots(
        ec_stream.DEFAULT_WRITER_THREADS)
    assert ec_stream.batch_rebuild_tile_bytes(4, ec_stream._ring_slots(2)) == 4 * MIB


# --- the mesh arm at every size the rule can choose ---------------------------------


def _mesh_codec(vol: int = 1, stripe: int = 1):
    """A MeshCodec over CPU devices (the CPU's own default is the host
    arm): the program the chip runs, on its bit-matmul arm."""
    import jax

    from seaweedfs_tpu.parallel import MeshCodec, make_mesh

    return MeshCodec(make_mesh(jax.devices()[: vol * stripe], stripe=stripe))


def _rows(nbytes: int) -> int:
    """The size of a shard file of at least nbytes: whole rows."""
    return -(-nbytes // SMALL) * SMALL


def _shard_bytes(volumes: int, tile: int) -> list[int]:
    """Unequal volumes: the first a round and a short tail, the others
    parts of one round, none a multiple of the tile."""
    sizes = [_rows(tile + 3 * SMALL)] + [
        _rows(tile * (v + 1) // (volumes + 1)) for v in range(volumes - 1)
    ]
    assert all(s % tile for s in sizes) and len(set(sizes)) == volumes
    return sizes


def _volumes(tmp_path, shard_bytes: list[int], seed: int) -> list[str]:
    bases = [str(tmp_path / f"v{i}") for i in range(len(shard_bytes))]
    for i, (base, size) in enumerate(zip(bases, shard_bytes)):
        ec_shards_less(base, 10 * size - 7, seed + i, LOST, LARGE, SMALL)
        assert os.path.getsize(base + ec_files.to_ext(0)) == size
    return bases


def _rebuilt(base: str) -> dict[int, np.ndarray]:
    return {i: np.fromfile(base + ec_files.to_ext(i), dtype=np.uint8) for i in LOST}


def _reference(bases: list[str]) -> tuple[list[dict], list[dict]]:
    """What `rebuild_ec_files` leaves of each volume, bytes and CRCs;
    the files it rebuilt are removed again."""
    want, crcs = [], []
    for base in bases:
        stats: dict = {}
        assert ec_files.rebuild_ec_files(
            base, rs=new_encoder(backend="cpu"), stats=stats, want_crcs=True
        ) == list(LOST)
        want.append(_rebuilt(base))
        crcs.append(stats["shard_crcs"])
        for i in LOST:
            os.remove(base + ec_files.to_ext(i))
    return want, crcs


def _check(bases: list[str], stats: dict, want: list[dict], crcs: list[dict]) -> None:
    for base, w, got_crcs, want_crcs in zip(bases, want, stats["shard_crcs"], crcs):
        got = _rebuilt(base)
        for i in LOST:
            assert np.array_equal(got[i], w[i]), (base, i)
        assert got_crcs == want_crcs


@pytest.mark.parametrize("volumes,mesh", [
    (1, (1, 1)), (3, (1, 1)), (4, (2, 2)), (5, (1, 1)), (9, (1, 1)),
], ids=["1vol-4MiB", "3vol-2MiB", "4vol-2MiB-2x2", "5vol-1MiB", "9vol-512KiB"])
def test_mesh_arm_at_the_rules_size_is_rebuild_ec_files_byte_for_byte(
        volumes, mesh, tmp_path):
    tile = ec_stream.batch_rebuild_tile_bytes(volumes, SLOTS)
    shard_bytes = _shard_bytes(volumes, tile)
    bases = _volumes(tmp_path, shard_bytes, seed=390 + volumes)
    want, crcs = _reference(bases)
    stats: dict = {}
    rebuilt = ec_stream.stream_rebuild_ec_files_batch(
        bases, codec=_mesh_codec(*mesh), stats=stats, want_crcs=True,
        writer_threads=WRITERS,
    )
    assert rebuilt == [list(LOST)] * volumes
    _check(bases, stats, want, crcs)
    assert stats["tile_bytes"] == tile and "codec_arm" not in stats
    assert stats["mesh"]["vol"] * stats["mesh"]["stripe"] == mesh[0] * mesh[1]
    # two rounds: the first volume's whole tile, then its tail alone
    assert stats["tiles"] == 2 == -(-max(shard_bytes) // tile)
    assert stats["survivor_bytes"] == 10 * sum(shard_bytes)
    assert stats["ring_slots"] == SLOTS
    # a slot is the round's [volumes, 10, tile]: the ring the rule sized
    assert stats["ring_fresh_bytes"] == SLOTS * volumes * 10 * tile
    assert stats["ring_fresh_bytes"] <= ec_stream._RING_KEEP_BYTES


@pytest.mark.parametrize("tile", SIZES)
def test_mesh_arm_under_an_explicit_tile(tile, tmp_path):
    """`tile_bytes=` stays what it was, an override for tests and sweeps:
    the same two volumes at each of the four sizes, whatever the rule
    would say."""
    shard_bytes = [_rows(MIB + 5 * SMALL), _rows(512 * KIB + SMALL)]
    bases = _volumes(tmp_path, shard_bytes, seed=39)
    want, crcs = _reference(bases)
    stats: dict = {}
    ec_stream.stream_rebuild_ec_files_batch(
        bases, codec=_mesh_codec(), tile_bytes=tile, stats=stats, want_crcs=True,
        writer_threads=2, reader_threads=2,
    )
    _check(bases, stats, want, crcs)
    assert stats["tile_bytes"] == tile
    assert stats["tiles"] == -(-shard_bytes[0] // tile)


# --- the kept ring under the chosen size ----------------------------------------------


def test_second_four_volume_call_at_the_default_runs_on_the_kept_ring(tmp_path):
    """The cell's shape in small: four volumes, the driver's own pools
    and the rule's tile. The second call allocates nothing, and both
    lines say which size the rule chose."""
    shard_bytes = [35 * SMALL, 34 * SMALL, 33 * SMALL, 21 * SMALL]
    bases = _volumes(tmp_path, shard_bytes, seed=139)
    want, crcs = _reference(bases)
    chosen = ec_stream.batch_rebuild_tile_bytes(4, ec_stream._ring_slots())
    seen = []
    for _ in range(2):
        stats: dict = {}
        ec_stream.stream_rebuild_ec_files_batch(
            bases, codec=_mesh_codec(), stats=stats, want_crcs=True
        )
        _check(bases, stats, want, crcs)
        seen.append(stats)
        for base in bases:
            for i in LOST:
                os.remove(base + ec_files.to_ext(i))
    assert [s["tile_bytes"] for s in seen] == [chosen, chosen]
    assert [s["tiles"] for s in seen] == [1, 1]  # files under a tile: one round
    # the round's width follows the largest file, not the tile
    assert seen[0]["ring_fresh_bytes"] == seen[0]["ring_slots"] * 4 * 10 * 35 * SMALL
    assert seen[1]["ring_fresh_bytes"] == 0


# --- the host arm ---------------------------------------------------------------------------


def test_host_arm_still_takes_512_kib(tmp_path):
    """No codec on a host whose devices are CPUs: (volume, tile) work
    items of 512 KiB, four volumes or not."""
    shard_bytes = [_rows(MIB + 3 * SMALL), MIB, 30 * SMALL, _rows(512 * KIB + SMALL)]
    bases = _volumes(tmp_path, shard_bytes, seed=239)
    want, crcs = _reference(bases)
    stats: dict = {}
    ec_stream.stream_rebuild_ec_files_batch(bases, stats=stats, want_crcs=True)
    _check(bases, stats, want, crcs)
    assert stats["codec_arm"] == "host" and "mesh" not in stats
    assert stats["tile_bytes"] == 512 * KIB == ec_stream.BATCH_REBUILD_MIN_TILE_BYTES
    assert stats["tiles"] == sum(-(-s // (512 * KIB)) for s in shard_bytes)
