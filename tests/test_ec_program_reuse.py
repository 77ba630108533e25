"""The EC drivers' device programs are built once per process and every
operation reuses them (ISSUE 27, docs/CODEC.md): the stage factories
take their jitted programs from one holder, `_default_mesh_codec` hands
out one MeshCodec per mesh, and `program_traces` on an operation's
report counts how often JAX traced a program body during it.

Everything runs on the CPU backend, on the bit-matmul arm: what is
asserted is counts, identities and bytes, never a time."""

import errno
import os
import shutil
import threading

import numpy as np
import pytest

from seaweedfs_tpu import trace
from seaweedfs_tpu.ec import codec_tpu, ec_files, ec_stream
from seaweedfs_tpu.ec.codec import new_encoder
from seaweedfs_tpu.stats.metrics import EC_PROGRAM_TRACES

LARGE = 64 * 1024
SMALL = 16 * 1024
SIZES = dict(large_block_size=LARGE, small_block_size=SMALL)


@pytest.fixture(autouse=True)
def fresh_holder(monkeypatch):
    """Each test starts with nothing kept, whatever earlier tests of the
    worker traced, and leaves the worker's own holder as it found it."""
    monkeypatch.setattr(ec_stream, "_KEPT", {})


def _make_dat(base: str, nbytes: int, seed: int = 0) -> None:
    rng = np.random.default_rng(seed)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes())


def _shards(base: str) -> list[bytes]:
    out = []
    for i in range(14):
        with open(base + ec_files.to_ext(i), "rb") as f:
            out.append(f.read())
    return out


def _host_reference(tmp_path, base: str) -> tuple[list[bytes], list[int]]:
    """Shard files and whole-file CRCs of `base`.dat by the host codec's
    serial classic driver."""
    ref = str(tmp_path / ("ref-" + os.path.basename(base)))
    shutil.copy(base + ".dat", ref + ".dat")
    stats: dict = {}
    ec_files.write_ec_files(
        ref, rs=new_encoder(backend="cpu"), buffer_size=SMALL, stats=stats,
        want_crcs=True, **SIZES,
    )
    assert stats["driver"] == "classic"
    return _shards(ref), stats["shard_crcs"]


def _encode(base: str, tile_bytes: int = SMALL) -> dict:
    stats: dict = {}
    ec_stream.stream_write_ec_files(
        base, tile_bytes=tile_bytes, stats=stats, want_crcs=True, **SIZES
    )
    assert stats["driver"] == "stream-device"
    return stats


def _rebuild(base: str, lost: list[int]) -> dict:
    for i in lost:
        os.remove(base + ec_files.to_ext(i))
    stats: dict = {}
    rebuilt = ec_stream.stream_rebuild_ec_files(
        base, tile_bytes=SMALL, stats=stats, want_crcs=True
    )
    assert rebuilt == lost
    return stats


# --- (a) the single-volume encode ---------------------------------------------


def test_second_encode_traces_nothing(tmp_path):
    base = str(tmp_path / "v")
    _make_dat(base, 10 * SMALL * 6 + 77)
    want_files, want_crcs = _host_reference(tmp_path, base)
    total0 = EC_PROGRAM_TRACES.value()
    first = _encode(base)
    assert first["program_traces"] == 1  # one tile shape, one arm
    assert _shards(base) == want_files and first["shard_crcs"] == want_crcs
    second = _encode(base)
    assert second["program_traces"] == 0
    assert _shards(base) == want_files and second["shard_crcs"] == want_crcs
    assert EC_PROGRAM_TRACES.value() - total0 == 1
    assert second["arms"] == first["arms"] == {
        "swar+crc": 0, "swar": 0, "bit-matmul": 7,
    }


def test_a_new_tile_shape_traces_once_more(tmp_path):
    base = str(tmp_path / "v")
    _make_dat(base, 10 * SMALL * 4)
    assert _encode(base)["program_traces"] == 1
    assert _encode(base, tile_bytes=SMALL // 2)["program_traces"] == 1
    assert _encode(base, tile_bytes=SMALL // 2)["program_traces"] == 0
    assert _encode(base)["program_traces"] == 0


def test_traces_on_the_root_span(tmp_path):
    base = str(tmp_path / "v")
    _make_dat(base, 10 * SMALL * 2)
    trace.reset()
    try:
        _encode(base)
        _encode(base)
        roots = [
            s for s in trace.debug_payload(n=64)["recent"]
            if s["name"] == "ec_stream.encode"
        ]
    finally:
        trace.reset()
    # the ring lists the newest first
    assert [s["annot"]["program_traces"] for s in roots] == ["0", "1"]


def test_host_stage_pair_reports_no_traces(tmp_path):
    """A host codec runs no device program: the field is the device
    stage's, like h2d_s and launch_s."""
    base = str(tmp_path / "v")
    _make_dat(base, 10 * SMALL * 2)
    parity_fn, fetch_fn = ec_stream.local_encode_fns(new_encoder(backend="cpu"))
    stats: dict = {}
    ec_stream.stream_write_ec_files(
        base, tile_bytes=SMALL, parity_fn=parity_fn, fetch_fn=fetch_fn,
        stats=stats, **SIZES,
    )
    assert "program_traces" not in stats and not ec_stream._KEPT


# --- (b) the batch encode and its mesh codec ----------------------------------


def test_second_batch_encode_traces_nothing(tmp_path):
    bases = []
    for i in range(4):
        bases.append(str(tmp_path / f"b{i}"))
        _make_dat(bases[-1], 10 * SMALL * 3 + i, seed=i)
    want = [_host_reference(tmp_path, b) for b in bases]
    reports = []
    for _ in range(2):
        stats: dict = {}
        ec_stream.stream_write_ec_files_batch(
            bases, tile_bytes=SMALL, stats=stats, want_crcs=True, **SIZES
        )
        reports.append(stats)
        for base, crcs, (files, ref_crcs) in zip(
            bases, stats["shard_crcs"], want
        ):
            assert _shards(base) == files and crcs == ref_crcs
    assert [r["program_traces"] for r in reports] == [1, 0]
    assert reports[0]["mesh"] == reports[1]["mesh"]


def test_default_mesh_codec_is_one_object_per_mesh():
    import jax

    n = len(jax.devices())
    assert n == 8  # conftest's virtual CPU mesh
    four = ec_stream._default_mesh_codec(4)
    assert ec_stream._default_mesh_codec(4) is four
    assert ec_stream._default_mesh_codec(12) is four  # gcd(12, 8) is 4 too
    eight = ec_stream._default_mesh_codec(8)
    assert eight is not four
    assert four.report()["vol"] == 4 and eight.report()["vol"] == 8
    # a program built on the kept codec is the one the next caller gets
    assert (
        ec_stream._default_mesh_codec(4)._encode_crc_sharded
        is four._encode_crc_sharded
    )


def test_a_callers_own_codec_is_kept(tmp_path):
    from seaweedfs_tpu.parallel import MeshCodec, make_mesh

    mine = MeshCodec(make_mesh(stripe=2))
    bases = []
    for i in range(4):
        bases.append(str(tmp_path / f"b{i}"))
        _make_dat(bases[-1], 10 * SMALL * 2, seed=i)
    stats: dict = {}
    ec_stream.stream_write_ec_files_batch(
        bases, codec=mine, tile_bytes=SMALL, stats=stats, **SIZES
    )
    assert stats["program_traces"] == 1
    assert "_encode_crc_sharded" not in vars(mine)  # no CRCs asked for
    assert len(mine._sharded_u32_cache) == 1
    assert not ec_stream._KEPT  # nothing was provisioned beside it


def test_chunked_batch_sums_its_chunks_traces(tmp_path, monkeypatch):
    """WEED_EC_PIPELINE_BATCH splits a batch into chunks, each with a
    mesh that fits it: the verb's one report adds their counts up."""
    monkeypatch.setenv("WEED_EC_PIPELINE_BATCH", "2")
    bases = []
    for i in range(3):
        bases.append(str(tmp_path / f"b{i}"))
        _make_dat(bases[-1], 10 * SMALL * 2, seed=i)
    reports = []
    for _ in range(2):
        stats: dict = {}
        ec_stream.stream_write_ec_files_batch(
            bases, tile_bytes=SMALL, stats=stats, want_crcs=True, **SIZES
        )
        reports.append(stats["program_traces"])
    # chunks of 2 and 1 volumes: a vol=2 mesh and a vol=1 mesh
    assert reports == [2, 0]


# --- (c) the rebuilds ----------------------------------------------------------


def test_rebuild_traces_once_per_survivor_set(tmp_path):
    base = str(tmp_path / "v")
    _make_dat(base, 10 * SMALL * 5 + 3)
    _encode(base)
    want = _shards(base)
    seen = []
    for lost in ([3], [3], [0, 11], [0, 11], [3]):
        stats = _rebuild(base, lost)
        assert _shards(base) == want, lost
        assert stats["arms"]["bit-matmul"] > 0
        seen.append(stats["program_traces"])
    # a survivor set is a static argument of its program: the first
    # rebuild of each set traces once, no repeat traces again
    assert seen == [1, 0, 1, 0, 0]


def test_batch_rebuild_traces_once_per_survivor_set(tmp_path):
    from seaweedfs_tpu.parallel import MeshCodec, make_mesh

    bases = []
    for i in range(2):
        bases.append(str(tmp_path / f"b{i}"))
        _make_dat(bases[-1], 10 * SMALL * 2 + i, seed=i)
        _encode(bases[-1])
    want = [_shards(b) for b in bases]
    codec = MeshCodec(make_mesh(stripe=4))  # CPU hosts default to the host arm
    seen = []
    for lost in ([5], [5], [1, 12]):
        for base in bases:
            for i in lost:
                os.remove(base + ec_files.to_ext(i))
        stats: dict = {}
        ec_stream.stream_rebuild_ec_files_batch(
            bases, codec=codec, tile_bytes=SMALL, stats=stats
        )
        assert [_shards(b) for b in bases] == want, lost
        seen.append(stats["program_traces"])
    assert seen == [1, 0, 1]


def test_aborted_operation_leaves_the_programs_usable(tmp_path, monkeypatch):
    """ENOSPC in the writer pool fails one operation; the next one runs
    on the same kept programs, traces nothing and writes the same
    bytes (encode and rebuild share the holder)."""
    base = str(tmp_path / "v")
    _make_dat(base, 10 * SMALL * 4)
    _encode(base)
    want = _shards(base)
    progs = ec_stream._device_programs()

    def no_space(*_):
        raise OSError(errno.ENOSPC, "No space left on device")

    with monkeypatch.context() as mp:
        mp.setattr(ec_stream, "_pwrite_full", no_space)
        with pytest.raises(OSError, match="No space left"):
            _encode(base)
    assert not os.path.exists(base + ec_files.to_ext(0))
    assert _encode(base)["program_traces"] == 0 and _shards(base) == want

    assert _rebuild(base, [2])["program_traces"] == 1
    os.remove(base + ec_files.to_ext(2))
    with monkeypatch.context() as mp:
        mp.setattr(ec_stream, "_pwrite_full", no_space)
        with pytest.raises(OSError, match="No space left"):
            ec_stream.stream_rebuild_ec_files(base, tile_bytes=SMALL)
    assert not os.path.exists(base + ec_files.to_ext(2))
    stats: dict = {}
    assert ec_stream.stream_rebuild_ec_files(
        base, tile_bytes=SMALL, stats=stats
    ) == [2]
    assert stats["program_traces"] == 0 and _shards(base) == want
    assert ec_stream._device_programs() is progs


# --- (d) concurrent operations -------------------------------------------------


def test_concurrent_encodes_share_programs_and_keep_their_own_counts(tmp_path):
    """Two handler threads at once: one on a tile shape the process has
    traced, one on a new shape. Each report carries the tiles and the
    traces of ITS operation, and both ran on the one holder."""
    warm, cold = str(tmp_path / "warm"), str(tmp_path / "cold")
    _make_dat(warm, 10 * SMALL * 6, seed=1)
    _make_dat(cold, 10 * SMALL * 4, seed=2)
    want = {b: _host_reference(tmp_path, b) for b in (warm, cold)}
    assert _encode(warm)["program_traces"] == 1
    progs = ec_stream._device_programs()
    total0 = EC_PROGRAM_TRACES.value()

    gate = threading.Barrier(2)
    reports: dict = {}
    errors: list = []

    def run(base, tile_bytes):
        try:
            gate.wait(timeout=30)
            reports[base] = _encode(base, tile_bytes=tile_bytes)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [
        threading.Thread(target=run, args=(warm, SMALL)),
        threading.Thread(target=run, args=(cold, SMALL // 2)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert not errors, errors
    assert reports[warm]["program_traces"] == 0
    assert reports[cold]["program_traces"] == 1
    assert reports[warm]["arms"]["bit-matmul"] == 6
    assert reports[cold]["arms"]["bit-matmul"] == 8
    assert EC_PROGRAM_TRACES.value() - total0 == 1
    for base in (warm, cold):
        assert _shards(base) == want[base][0]
        assert reports[base]["shard_crcs"] == want[base][1]
    assert ec_stream._device_programs() is progs
    assert len(ec_stream._KEPT) == 1


def test_holder_is_built_once_under_contention():
    built = []
    gate = threading.Barrier(8)

    def build():
        built.append(threading.get_ident())
        return object()

    got = []

    def ask():
        gate.wait(timeout=30)
        got.append(ec_stream._kept(("test",), build))

    threads = [threading.Thread(target=ask) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    assert len(built) == 1 and len(got) == 8
    assert all(g is got[0] for g in got)


# --- (e) the key ---------------------------------------------------------------


def test_schedule_flag_keys_the_holder_and_the_mesh_codec(tmp_path, monkeypatch):
    base = str(tmp_path / "v")
    _make_dat(base, 10 * SMALL * 3)
    want_files, want_crcs = _host_reference(tmp_path, base)
    assert _encode(base)["program_traces"] == 1
    progs, mesh = ec_stream._device_programs(), ec_stream._default_mesh_codec(4)
    with monkeypatch.context() as mp:
        mp.setenv("WEED_EC_SCHEDULE", "0")
        other = ec_stream._device_programs()
        assert other is not progs and other.kern is not progs.kern
        assert ec_stream._default_mesh_codec(4) is not mesh
        # traced anew under the flag, on the other entry
        assert _encode(base)["program_traces"] == 1
        assert _shards(base) == want_files
    # the patch undone: the first entry again, and nothing of the other
    assert ec_stream._device_programs() is progs
    assert ec_stream._default_mesh_codec(4) is mesh
    again = _encode(base)
    assert again["program_traces"] == 0
    assert _shards(base) == want_files and again["shard_crcs"] == want_crcs


def test_kernel_arm_keys_the_holder(monkeypatch):
    progs = ec_stream._device_programs()
    with monkeypatch.context() as mp:
        mp.setattr(codec_tpu, "_on_tpu", lambda: True)
        on_chip = ec_stream._device_programs()
        assert on_chip is not progs
        assert on_chip.encode_u32_crc is not progs.encode_u32_crc
    assert ec_stream._device_programs() is progs
