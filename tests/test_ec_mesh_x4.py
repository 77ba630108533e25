"""The four-chip EC node (ISSUE 28): six sealed volumes through the batch
stream driver on a vol=2 x stripe=2 mesh, held byte for byte to the
benchmark's plain reference (`benchmark/harness/reference.py`, imported
read-only: numpy and google_crc32c, nothing of the program), and the
mesh recipe that gives a batch of six that mesh on four devices.

Four of the CPU backend's virtual devices stand for the chips: what is
asserted is bytes, CRCs and bookkeeping, never a device time."""

import importlib.util
import os

import jax
import numpy as np
import pytest

from seaweedfs_tpu import trace
from seaweedfs_tpu.ec import ec_files, ec_stream
from seaweedfs_tpu.parallel import MeshCodec, make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIB = 1 << 20
ROW = 10 * MIB  # one stripe row of the `.dat` at upstream's 1 MiB blocks
# unequal sizes: five volumes of one row, each with a short last row
# that the reader pads with zeros, and one of two rows whose second
# holds 4,321 bytes, so that the other five ride its last rounds as
# zero-step entries
DAT_BYTES = (300 * 1024 + 5, MIB + 1, 5 * MIB // 2, ROW + 4321, 777_777, 5 * MIB)
# 256 KiB tiles split over the stripe axis into power-of-two lanes: the
# fused CRC and its all_gather; 384 KiB tiles leave a short tail round
# per row (384, 384, 256), whose CRCs the writers compute on the host
TILES = {"fused-crc-gather": 256 * 1024, "short-tail-round": 384 * 1024}


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "bench_harness_reference",
        os.path.join(REPO, "benchmark", "harness", "reference.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def codec():
    return MeshCodec(make_mesh(jax.devices()[:4], stripe=2))


@pytest.fixture(scope="module")
def volumes(tmp_path_factory, reference):
    """(base, the reference's 14 shards, their CRC-32Cs) per volume, on
    seeded data."""
    root = tmp_path_factory.mktemp("x4")
    out = []
    for i, nbytes in enumerate(DAT_BYTES):
        base = str(root / f"x{i}_{i + 1}")
        rng = np.random.default_rng(2800 + i)
        with open(base + ".dat", "wb") as f:
            f.write(rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes())
        shards = reference.encode(reference.read_rows(base + ".dat"))
        out.append((base, shards, [reference.crc32c(s) for s in shards]))
    return out


def _encode(volumes, codec, tile_bytes: int) -> dict:
    stats: dict = {}
    ec_stream.stream_write_ec_files_batch(
        [base for base, _, _ in volumes], codec=codec, tile_bytes=tile_bytes,
        stats=stats, durable=True, want_crcs=True,
    )
    return stats


@pytest.mark.parametrize("tiles", sorted(TILES))
def test_mesh_2x2_batch_equals_the_plain_reference(tiles, volumes, codec):
    stats = _encode(volumes, codec, TILES[tiles])
    assert codec.crc_supported(TILES[tiles]) == (tiles == "fused-crc-gather")
    for (base, shards, crcs), got_crcs in zip(volumes, stats["shard_crcs"]):
        for i, want in enumerate(shards):
            got = np.fromfile(base + ec_files.to_ext(i), dtype=np.uint8)
            assert np.array_equal(got, want), (base, i)
        assert got_crcs == crcs, base
    mesh = stats["mesh"]
    assert (mesh["vol"], mesh["stripe"]) == (2, 2)
    assert mesh["devices_per_round"] == 4
    assert stats["mesh_devices"] == 4
    assert stats["batch_volumes"] == 6
    assert "fallback" not in stats
    # a second operation launches the kept programs and traces nothing
    again = _encode(volumes, codec, TILES[tiles])
    assert again["program_traces"] == 0
    assert again["shard_crcs"] == stats["shard_crcs"]


def test_root_span_carries_the_mesh(volumes, codec):
    trace.reset()
    try:
        _encode(volumes, codec, TILES["fused-crc-gather"])
        roots = [
            s for s in trace.debug_payload(n=64)["recent"]
            if s["name"] == "ec_stream.encode_batch"
        ]
    finally:
        trace.reset()
    assert len(roots) == 1
    annot = roots[0]["annot"]
    assert annot["mesh"] == "2x2"
    assert annot["mesh_devices"] == "4"
    assert annot["batch_volumes"] == "6"


@pytest.mark.parametrize(
    "batch,vol,stripe", [(6, 2, 2), (8, 4, 1), (256, 4, 1), (1, 1, 4)]
)
def test_default_mesh_on_four_devices(batch, vol, stripe, monkeypatch):
    """gcd(batch, devices) volumes wide: six volumes are the batch that
    gets both axes; a multiple of four gets no stripe axis at all."""
    four = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: four)
    monkeypatch.setattr(ec_stream, "_KEPT", {})
    mesh = ec_stream._default_mesh_codec(batch).mesh
    assert (mesh.shape["vol"], mesh.shape["stripe"]) == (vol, stripe)
    assert mesh.devices.size == 4
