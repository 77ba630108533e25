"""Device-resident EC streaming pipeline (docs/CODEC.md): staging
ring, fused CRC32-C, mesh batch arm, routing by backend, stage
accounting, and tile-cache scan resistance.

Everything runs on the CPU backend (tier-1 is JAX_PLATFORMS=cpu): the
stream drivers are held byte- and CRC-identical to the classic loop,
which the numpy `cpu` backend takes as the reference."""

import os

import numpy as np
import pytest

from seaweedfs_tpu.ec import ec_files, ec_stream
from seaweedfs_tpu.ec.codec import new_encoder
from seaweedfs_tpu.ec.tile_cache import TileCache
from seaweedfs_tpu.util.crc import crc32c, crc32c_combine

# small two-tier geometry: fast, still exercises large-tier striding,
# super-tile coalescing, and the zero-padded tail
LARGE = 64 * 1024
SMALL = 16 * 1024


def _make_dat(path: str, nbytes: int, seed: int = 0) -> bytes:
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes()
    with open(path + ".dat", "wb") as f:
        f.write(data)
    return data


def _shards(base: str) -> list[bytes]:
    return [
        open(base + ec_files.to_ext(i), "rb").read()
        for i in range(ec_files.TOTAL_SHARDS)
    ]


def _write_classic(base: str, rs, want_crcs=False, stats=None):
    """The serial reference driver: the classic loop, which write_ec_files
    takes for the numpy `cpu` backend."""
    ec_files.write_ec_files(
        base, rs=rs, large_block_size=LARGE, small_block_size=SMALL,
        stats=stats, want_crcs=want_crcs,
    )


# ---------------------------------------------------------------------------
class TestCrcKernel:
    def test_rows_match_host_crc32c(self):
        from seaweedfs_tpu.ec import crc_kernel

        rng = np.random.default_rng(0)
        for n32 in (1, 4, 64, 1024):
            x = rng.integers(0, 2**32, (3, n32), dtype=np.uint32)
            got = np.asarray(crc_kernel.crc32c_rows(x))
            for r in range(3):
                assert int(got[r]) == crc32c(x[r].tobytes())

    def test_leading_batch_dims(self):
        from seaweedfs_tpu.ec import crc_kernel

        rng = np.random.default_rng(1)
        x = rng.integers(0, 2**32, (2, 5, 64), dtype=np.uint32)
        got = np.asarray(crc_kernel.crc32c_rows(x))
        for i in range(2):
            for j in range(5):
                assert int(got[i, j]) == crc32c(x[i, j].tobytes())

    @pytest.mark.parametrize("fill", ["zeros", "ones", "random"])
    @pytest.mark.parametrize("lead", [(14,), (4, 14), (3, 14)])
    @pytest.mark.parametrize("n32", [1, 2, 64, 128, 256, 4096, 131072])
    def test_rows_match_host_crc32c_every_dispatched_shape(self, n32, lead, fill):
        """Every shape a cell or a rebuild dispatches, on both sides of
        the block width (128 lanes), against the host's table CRC."""
        import jax

        from seaweedfs_tpu.ec import crc_kernel

        if fill == "random":
            rng = np.random.default_rng(n32 + len(lead))
            x = rng.integers(0, 2**32, lead + (n32,), dtype=np.uint32)
        else:
            word = 0 if fill == "zeros" else 0xFFFFFFFF
            x = np.full(lead + (n32,), word, dtype=np.uint32)
        got = np.asarray(jax.jit(crc_kernel.crc32c_rows)(x))  # as the programs run it
        assert got.shape == lead and got.dtype == np.uint32
        rows = x.reshape(-1, n32)
        want = [crc32c(rows[r].tobytes()) for r in range(rows.shape[0])]
        assert got.reshape(-1).tolist() == want

    def test_fold_lowers_without_gather_or_strided_slice(self):
        """The mechanism, where no speed can be read: at the encode tile
        [14, 262144] the fold's lowering holds no gather and no slice
        with a stride (the strided halving it replaced held 72 gathers;
        on the TPU each re-lays the tile out)."""
        import re

        import jax

        from seaweedfs_tpu.ec import crc_kernel

        x = jax.ShapeDtypeStruct((14, 262144), np.uint32)
        text = jax.jit(crc_kernel.crc_lin_rows).lower(x).as_text()
        assert "gather" not in text
        slices = [ln for ln in text.splitlines() if "stablehlo.slice" in ln]
        assert len(slices) == 2 * 11 + 1  # the ladder's halves, and c[..., 0]
        for ln in slices:  # `[0:14, 0:1024]`; a stride prints as a third field
            bounds = re.search(r"\[([^\]]*)\]", ln).group(1)
            assert all(dim.count(":") == 1 for dim in bounds.split(",")), ln
        # one block contraction + eleven ladder rounds
        assert text.count("stablehlo.dot_general") == 12

    @pytest.mark.parametrize("n32", [64, 128, 1024, 65536])
    def test_block_operator_and_ladder_match_host_combine(self, n32):
        """The block matmul gives the raw CRC of each block of lanes, and
        the ladder over them is the host's crc32c_combine over the same
        split, block after block."""
        from seaweedfs_tpu.ec import crc_kernel

        lanes = min(crc_kernel._BLOCK_LANES, n32)
        rng = np.random.default_rng(n32)
        x = rng.integers(0, 2**32, (3, n32), dtype=np.uint32)
        blocks = np.asarray(crc_kernel._block_crcs(x, lanes))
        assert blocks.shape == (3, n32 // lanes)
        for r in range(3):
            parts = x[r].reshape(-1, lanes)
            folded = 0  # crc32c(b"")
            for k in range(parts.shape[0]):
                part = parts[k].tobytes()
                assert int(blocks[r, k]) == crc_kernel._raw_transit(part, 0)
                folded = crc32c_combine(folded, crc32c(part), len(part))
            lin = crc_kernel._fold_halves(blocks[r], 4 * lanes)
            got = int(np.asarray(crc_kernel.finalize_rows(lin, n32 * 4)))
            assert got == folded == crc32c(x[r].tobytes())

    def test_non_power_of_two_rejected(self):
        from seaweedfs_tpu.ec import crc_kernel

        assert not crc_kernel.crc_supported(12)  # 3 lanes
        assert not crc_kernel.crc_supported(6)  # partial lane
        assert crc_kernel.crc_supported(4096)
        with pytest.raises(ValueError):
            crc_kernel.crc_lin_rows(np.zeros((1, 3), dtype=np.uint32))

    def test_combine_matches_concatenation(self):
        rng = np.random.default_rng(2)
        for la, lb in ((0, 5), (7, 0), (13, 40), (4096, 100)):
            a = rng.integers(0, 256, la, dtype=np.uint8).tobytes()
            b = rng.integers(0, 256, lb, dtype=np.uint8).tobytes()
            assert crc32c_combine(crc32c(a), crc32c(b), lb) == crc32c(a + b)

    def test_fused_encode_crc_matches_host(self):
        from seaweedfs_tpu.ec.codec_tpu import TpuCodecKernels

        kern = TpuCodecKernels(10, 4)
        rng = np.random.default_rng(3)
        data = rng.integers(0, 256, (10, 4096), dtype=np.uint8)
        parity, crcs = kern.encode_u32_crc(data.view(np.uint32))
        parity_h = np.asarray(parity).view(np.uint8)
        rs = new_encoder(backend="cpu")
        want = rs.encode([data[i].copy() for i in range(10)] + [None] * 4)
        crcs_h = np.asarray(crcs)
        for i in range(4):
            assert np.array_equal(parity_h[i], want[10 + i])
        full = np.concatenate([data, parity_h], axis=0)
        for i in range(14):
            assert int(crcs_h[i]) == crc32c(full[i].tobytes())

    def test_fused_reconstruct_crc_matches_host(self):
        from seaweedfs_tpu.ec.codec_tpu import TpuCodecKernels

        kern = TpuCodecKernels(10, 4)
        rng = np.random.default_rng(4)
        data = rng.integers(0, 256, (10, 4096), dtype=np.uint8)
        parity = np.asarray(
            kern.encode_u32_crc(data.view(np.uint32))[0]
        ).view(np.uint8)
        all_shards = np.concatenate([data, parity], axis=0)
        survivors = tuple(range(2, 12))
        targets = (0, 1)
        tile = all_shards[list(survivors)]
        rebuilt, crcs = kern.reconstruct_u32_crc(
            survivors, targets, tile.view(np.uint32)
        )
        rebuilt_h = np.asarray(rebuilt).view(np.uint8)
        for j, t in enumerate(targets):
            assert np.array_equal(rebuilt_h[j], all_shards[t])
            assert int(np.asarray(crcs)[j]) == crc32c(all_shards[t].tobytes())


# ---------------------------------------------------------------------------
class TestPipelinedEncode:
    @pytest.mark.parametrize("nbytes", [10 * SMALL * 3 + 777, 10 * LARGE + 5])
    def test_bytes_and_crcs_match_serial(self, tmp_path, nbytes):
        rs = new_encoder(backend="cpu")
        piped = str(tmp_path / "p")
        serial = str(tmp_path / "s")
        data = _make_dat(piped, nbytes)
        with open(serial + ".dat", "wb") as f:
            f.write(data)
        _write_classic(serial, rs, want_crcs=True, stats=(sstats := {}))
        parity_fn, fetch_fn = ec_stream.local_encode_fns(rs, want_crcs=True)
        pstats: dict = {}
        ec_stream.stream_write_ec_files(
            piped, large_block_size=LARGE, small_block_size=SMALL,
            parity_fn=parity_fn, fetch_fn=fetch_fn, stats=pstats,
            want_crcs=True,
        )
        for i, (pb, sb) in enumerate(zip(_shards(piped), _shards(serial))):
            assert pb == sb, f"shard {i}"
            assert pstats["shard_crcs"][i] == crc32c(pb) == sstats["shard_crcs"][i]

    def test_stage_buckets_and_compute_charge(self, tmp_path):
        """Satellite fix: host-codec time lands in compute_s, not in
        the writer pool's writeback bucket."""
        rs = new_encoder(backend="cpu")
        base = str(tmp_path / "v")
        _make_dat(base, 10 * SMALL * 4)
        parity_fn, fetch_fn = ec_stream.local_encode_fns(rs)
        assert fetch_fn.charges == "compute_s"
        stats: dict = {}
        ec_stream.stream_write_ec_files(
            base, large_block_size=LARGE, small_block_size=SMALL,
            parity_fn=parity_fn, fetch_fn=fetch_fn, stats=stats,
        )
        for key in ("read_s", "stage_s", "device_s", "writeback_s",
                    "compute_s", "write_s", "pipeline_depth", "ring_slots"):
            assert key in stats, key
        assert stats["compute_s"] > 0  # the numpy encode ran somewhere
        assert stats["writeback_s"] == 0  # and NOT booked as D2H drain

    def test_injected_plain_fns_still_get_crcs(self, tmp_path):
        """A stage pair that never heard of CRCs (the test-injection
        contract) still yields shard_crcs — host fallback in the
        writer pool."""
        rs = new_encoder(backend="cpu")
        base = str(tmp_path / "v")
        _make_dat(base, 10 * SMALL * 2 + 99)

        def fetch(tile):
            return rs._apply(rs.parity_rows, tile)

        stats: dict = {}
        ec_stream.stream_write_ec_files(
            base, large_block_size=LARGE, small_block_size=SMALL,
            parity_fn=lambda t: t, fetch_fn=fetch, stats=stats,
            want_crcs=True,
        )
        for i, sb in enumerate(_shards(base)):
            assert stats["shard_crcs"][i] == crc32c(sb)

    def test_depth_knob(self, tmp_path, monkeypatch):
        """The window is the constant _INFLIGHT: the write queue holds
        that many dispatched tiles and the ring one slot more than the
        window plus the writer pool."""
        queues: list[int] = []
        real_queue = ec_stream.queue.Queue

        def spy(maxsize=0):
            queues.append(maxsize)
            return real_queue(maxsize)

        monkeypatch.setattr(ec_stream.queue, "Queue", spy)
        rs = new_encoder(backend="cpu")
        base = str(tmp_path / "v")
        _make_dat(base, 10 * SMALL * 2)
        parity_fn, fetch_fn = ec_stream.local_encode_fns(rs)
        stats: dict = {}
        ec_stream.stream_write_ec_files(
            base, large_block_size=LARGE, small_block_size=SMALL,
            parity_fn=parity_fn, fetch_fn=fetch_fn, stats=stats,
            writer_threads=4, reader_threads=2,
        )
        assert ec_stream._INFLIGHT == 3
        assert stats["pipeline_depth"] == ec_stream._INFLIGHT
        assert stats["ring_slots"] == ec_stream._INFLIGHT + 4 + 1
        # read queue (one per reader), write queue, the ring's free list
        assert queues == [2, ec_stream._INFLIGHT, 0]

    def test_kill_switch_routes_serial(self, tmp_path):
        """Routing reads the backend and nothing else: a `native` codec
        goes through the stream driver, the numpy `cpu` codec takes the
        classic loop (the reference), and both give the same bytes and
        CRCs."""
        rs = new_encoder(backend="cpu")
        assert not ec_files._stream_host_codec(rs)
        assert not ec_files._use_stream_driver(rs)
        native = new_encoder(backend="cpu")
        native._backend_name = "native"  # pretend: routing looks at the name
        assert ec_files._stream_host_codec(native)
        assert not ec_files._use_stream_driver(native)
        classic, piped = str(tmp_path / "c"), str(tmp_path / "p")
        data = _make_dat(classic, 10 * SMALL * 2 + 123)
        with open(piped + ".dat", "wb") as f:
            f.write(data)
        cstats: dict = {}
        pstats: dict = {}
        for base, codec, stats in ((classic, rs, cstats), (piped, native, pstats)):
            ec_files.write_ec_files(
                base, rs=codec, large_block_size=LARGE, small_block_size=SMALL,
                stats=stats, want_crcs=True,
            )
        assert "encode_s" in cstats  # the classic driver's bucket
        assert "device_s" not in cstats
        assert "pipeline_depth" in pstats  # the stream driver ran
        for i, (cb, pb) in enumerate(zip(_shards(classic), _shards(piped))):
            assert cb == pb, f"shard {i}"
            assert cstats["shard_crcs"][i] == pstats["shard_crcs"][i] == crc32c(cb)


# ---------------------------------------------------------------------------
class TestPipelinedRebuild:
    def test_rebuild_crcs_match_files(self, tmp_path):
        rs = new_encoder(backend="cpu")
        base = str(tmp_path / "v")
        _make_dat(base, 10 * SMALL * 3 + 4321)
        parity_fn, fetch_fn = ec_stream.local_encode_fns(rs)
        ec_stream.stream_write_ec_files(
            base, large_block_size=LARGE, small_block_size=SMALL,
            parity_fn=parity_fn, fetch_fn=fetch_fn,
        )
        want0 = open(base + ec_files.to_ext(0), "rb").read()
        os.remove(base + ec_files.to_ext(0))
        os.remove(base + ec_files.to_ext(12))
        rebuild_fn, rfetch = ec_stream.local_rebuild_fns(rs, want_crcs=True)
        stats: dict = {}
        rebuilt = ec_stream.stream_rebuild_ec_files(
            base, rebuild_fn=rebuild_fn, fetch_fn=rfetch, stats=stats,
            want_crcs=True,
        )
        assert sorted(rebuilt) == [0, 12]
        assert open(base + ec_files.to_ext(0), "rb").read() == want0
        for i in (0, 12):
            got = open(base + ec_files.to_ext(i), "rb").read()
            assert stats["shard_crcs"][i] == crc32c(got)

    def test_classic_rebuild_crcs(self, tmp_path, monkeypatch):
        rs = new_encoder(backend="cpu")
        base = str(tmp_path / "v")
        _make_dat(base, 10 * SMALL * 2)
        _write_classic(base, rs)
        os.remove(base + ec_files.to_ext(3))
        stats: dict = {}
        rebuilt = ec_files.rebuild_ec_files(
            base, rs=rs, stats=stats, want_crcs=True
        )
        assert rebuilt == [3]
        got = open(base + ec_files.to_ext(3), "rb").read()
        assert stats["shard_crcs"][3] == crc32c(got)


# ---------------------------------------------------------------------------
class TestMeshBatchPipeline:
    def test_batch_matches_serial_per_volume(self, tmp_path):
        """The mesh batch arm (CPU mesh = the byte-identical fallback
        tier) against the serial classic driver, odd sizes included;
        fused CRCs against the files on disk."""
        rs = new_encoder(backend="cpu")
        bases, refs = [], []
        for v in range(3):
            base = str(tmp_path / f"v{v}")
            ref = str(tmp_path / f"r{v}")
            data = _make_dat(base, 10 * SMALL * (v + 1) + 101 * v, seed=v)
            with open(ref + ".dat", "wb") as f:
                f.write(data)
            _write_classic(ref, rs)
            bases.append(base)
            refs.append(ref)
        stats: dict = {}
        ec_stream.stream_write_ec_files_batch(
            bases, large_block_size=LARGE, small_block_size=SMALL,
            stats=stats, want_crcs=True,
        )
        assert stats["batch_volumes"] == 3
        for v in range(3):
            for i, (gb, wb) in enumerate(zip(_shards(bases[v]), _shards(refs[v]))):
                assert gb == wb, f"v{v} shard {i}"
                assert stats["shard_crcs"][v][i] == crc32c(gb)

    def test_batch_limit_knob_chunks(self, tmp_path, monkeypatch):
        monkeypatch.setenv("WEED_EC_PIPELINE_BATCH", "2")
        assert ec_stream.pipeline_batch_limit() == 2
        rs = new_encoder(backend="cpu")
        bases, refs = [], []
        for v in range(3):
            base = str(tmp_path / f"v{v}")
            ref = str(tmp_path / f"r{v}")
            data = _make_dat(base, 10 * SMALL + 7 * v, seed=10 + v)
            with open(ref + ".dat", "wb") as f:
                f.write(data)
            _write_classic(ref, rs)
            bases.append(base)
            refs.append(ref)
        stats: dict = {}
        ec_stream.stream_write_ec_files_batch(
            bases, large_block_size=LARGE, small_block_size=SMALL,
            stats=stats, want_crcs=True,
        )
        assert len(stats["shard_crcs"]) == 3
        # structural fields survive the chunk merge (the dryrun and
        # bench consumers read them on every run)
        assert stats["batch_volumes"] == 3
        assert "pipeline_depth" in stats and "mesh" in stats
        for v in range(3):
            for gb, wb in zip(_shards(bases[v]), _shards(refs[v])):
                assert gb == wb

    def test_empty_volumes(self, tmp_path):
        bases = []
        for v in range(2):
            base = str(tmp_path / f"e{v}")
            open(base + ".dat", "wb").close()
            bases.append(base)
        stats: dict = {}
        ec_stream.stream_write_ec_files_batch(
            bases, stats=stats, want_crcs=True
        )
        for base in bases:
            for i in range(14):
                assert os.path.getsize(base + ec_files.to_ext(i)) == 0
        assert stats["shard_crcs"] == [[0] * 14, [0] * 14]

    def test_routing_via_write_ec_files_batch(self, tmp_path):
        """ec_files.write_ec_files_batch is the pipelined batch driver:
        the same bytes and CRCs as the classic write_ec_files on the
        numpy `cpu` backend."""
        rs = new_encoder(backend="cpu")
        piped = str(tmp_path / "p")
        classic = str(tmp_path / "c")
        data = _make_dat(piped, 10 * SMALL * 2 + 55)
        with open(classic + ".dat", "wb") as f:
            f.write(data)
        st_p: dict = {}
        ec_files.write_ec_files_batch(
            [piped], large_block_size=LARGE, small_block_size=SMALL,
            stats=st_p, want_crcs=True,
        )
        assert "pipeline_depth" in st_p  # pipelined arm ran
        st_c: dict = {}
        _write_classic(classic, rs, want_crcs=True, stats=st_c)
        assert "encode_s" in st_c  # classic loop ran
        for gb, wb in zip(_shards(piped), _shards(classic)):
            assert gb == wb
        assert list(st_p["shard_crcs"][0]) == list(st_c["shard_crcs"])

    def test_mesh_fused_crc_with_stripe_collective(self):
        """encode_batch_u32_crc on a vol×stripe mesh: the stripe-axis
        CRC composition (all_gather + Z-shift fold) must equal the
        host CRC of the full concatenated stream."""
        jax = pytest.importorskip("jax")
        from seaweedfs_tpu.parallel import MeshCodec, make_mesh

        devs = jax.devices()
        if len(devs) < 8:
            pytest.skip("needs 8 virtual devices")
        codec = MeshCodec(make_mesh(devs[:8]))  # 4 x 2
        assert codec.crc_supported(32 * 1024)
        assert not codec.crc_supported(32 * 1024 + 8)
        rng = np.random.default_rng(7)
        vols = rng.integers(0, 256, (4, 10, 32 * 1024), dtype=np.uint8)
        u32 = codec.shard_volumes(vols.view(np.uint32))
        parity, crcs = codec.encode_batch_u32_crc(u32)
        parity_h = np.asarray(parity).view(np.uint8)
        crcs_h = np.asarray(crcs)
        full = np.concatenate([vols, parity_h], axis=1)
        for v in range(4):
            for i in range(14):
                assert int(crcs_h[v, i]) == crc32c(full[v, i].tobytes())
        layout = codec.batch_layout(4, 32 * 1024)
        assert layout == {
            "vol": 4, "stripe": 2, "devices": 8,
            "per_device_volumes": 1, "per_device_bytes": 16 * 1024,
        }


# ---------------------------------------------------------------------------
class TestTileCacheScanResistance:
    def test_scan_does_not_churn_protected(self):
        """ROADMAP satellite: a sequential scan (one-touch puts) must
        not evict the promoted hot set."""
        c = TileCache(capacity_bytes=8 * 100, tile_bytes=4096)
        assert c.scan_resistant
        # hot set: put + second-touch get -> protected
        for off in (0, 4096):
            c.put(0, off, b"h" * 100)
            assert c.get(0, off) is not None
        # scan: 50 one-touch tiles, never touched again
        for i in range(50):
            c.put(1, i * 4096, b"s" * 100)
        assert c.get(0, 0) is not None, "scan churned the hot set"
        assert c.get(0, 4096) is not None
        assert c.total_bytes <= 8 * 100

    def test_plain_lru_churns_under_knob(self, monkeypatch):
        """WEED_EC_TILE_SCAN=0: the pre-PR behavior, where the same
        scan evicts everything — the regression control."""
        monkeypatch.setenv("WEED_EC_TILE_SCAN", "0")
        c = TileCache(capacity_bytes=8 * 100, tile_bytes=4096)
        assert not c.scan_resistant
        for off in (0, 4096):
            c.put(0, off, b"h" * 100)
            assert c.get(0, off) is not None
        for i in range(50):
            c.put(1, i * 4096, b"s" * 100)
        assert c.get(0, 0) is None  # plain LRU: scanned straight through
        assert c.get(0, 4096) is None

    def test_probation_bounded_small(self):
        c = TileCache(capacity_bytes=64 << 20, tile_bytes=256 * 1024)
        assert c.probation_bytes_cap == (64 << 20) // 8

    def test_second_touch_promotes(self):
        c = TileCache(capacity_bytes=4 * 100, tile_bytes=4096)
        c.put(0, 0, b"x" * 100)
        assert c.get(0, 0) is not None  # promotes
        assert (0, 0) in c._protected
        assert (0, 0) not in c._probation

    def test_covers_and_snapshot_span_probation(self):
        c = TileCache(capacity_bytes=1 << 20, tile_bytes=4096)
        c.put(3, 0, b"x" * 4096)  # probationary only
        assert c.covers(3, 100, 200)
        snap = c.snapshot(3)
        assert snap == [(0, b"x" * 4096)]

    def test_protected_reput_updates_in_place(self):
        c = TileCache(capacity_bytes=1 << 20, tile_bytes=4096)
        c.put(0, 0, b"a" * 100)
        c.get(0, 0)  # promote
        c.put(0, 0, b"b" * 200)
        assert c.get(0, 0) == b"b" * 200
        assert c.total_bytes == 200
