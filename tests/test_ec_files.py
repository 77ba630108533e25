"""Striping/shard-file tests, modeled on the reference's ec_test.go:
encode the reference's checked-in volume fixture with small block sizes,
then (a) byte-compare striped shard reads against the original .dat for
every needle, and (b) drop shard subsets and verify rebuild equality.
"""

import os
import random
import shutil

import numpy as np
import pytest

from seaweedfs_tpu.ec import ec_files, locate
from seaweedfs_tpu.ec.codec import new_encoder
from seaweedfs_tpu.storage import idx as idx_codec
from seaweedfs_tpu.storage import types as t

# ec_test.go:15-18 — tiny block sizes so the fixture exercises both tiers
LARGE = 10000
SMALL = 100


class TestLocateData:
    def test_pinned_single_interval(self):
        # ec_test.go:187 TestLocateData
        intervals = locate.locate_data(LARGE, SMALL, 10 * LARGE + 1, 10 * LARGE, 1)
        assert len(intervals) == 1
        iv = intervals[0]
        assert (iv.block_index, iv.inner_block_offset, iv.size, iv.is_large_block) == (
            0,
            0,
            1,
            False,
        )

    def test_spanning_intervals_cover_range(self):
        dat_size = 10 * LARGE + 1
        offset = 10 * LARGE // 2 + 100
        size = dat_size - offset
        intervals = locate.locate_data(LARGE, SMALL, dat_size, offset, size)
        assert sum(iv.size for iv in intervals) == size
        # intervals must be contiguous in .dat space: re-derive offsets
        cursor = offset
        for iv in intervals:
            again = locate.locate_data(LARGE, SMALL, dat_size, cursor, iv.size)
            assert again[0] == iv
            cursor += iv.size

    def test_shard_id_and_offset_roundtrip(self):
        dat_size = 3 * 10 * LARGE + 2345
        rng = random.Random(5)
        for _ in range(100):
            offset = rng.randrange(dat_size)
            size = rng.randrange(1, min(5 * SMALL, dat_size - offset) + 1)
            for iv in locate.locate_data(LARGE, SMALL, dat_size, offset, size):
                shard_id, shard_off = iv.to_shard_id_and_offset(LARGE, SMALL)
                assert 0 <= shard_id < 10
                assert 0 <= shard_off


class TestRowCounts:
    def test_strict_greater_quirk(self):
        # exactly one full large row goes through the small tier
        assert ec_files.shard_row_counts(10 * LARGE, LARGE, SMALL) == (0, 100)
        assert ec_files.shard_row_counts(10 * LARGE + 1, LARGE, SMALL) == (1, 1)
        assert ec_files.shard_row_counts(0, LARGE, SMALL) == (0, 0)
        assert ec_files.shard_row_counts(1, LARGE, SMALL) == (0, 1)

    def test_shard_file_size(self):
        assert ec_files.shard_file_size(10 * LARGE + 1, LARGE, SMALL) == LARGE + SMALL


@pytest.fixture(scope="session")
def encoded_fixture(tmp_path_factory, reference_root):
    """The reference's binary volume fixture (1.dat/1.idx — real
    artifacts written by the reference implementation) encoded ONCE with
    the CPU backend; tests copy the results instead of re-encoding."""
    root = tmp_path_factory.mktemp("encoded")
    for ext in (".dat", ".idx"):
        shutil.copyfile(
            reference_root / f"weed/storage/erasure_coding/1{ext}",
            root / f"1{ext}",
        )
    base = str(root / "1")
    _encode_fixture(base)
    return base


@pytest.fixture()
def fixture_volume(tmp_path, encoded_fixture):
    """Per-test scratch copy of the pre-encoded fixture volume."""
    src = os.path.dirname(encoded_fixture)
    for name in os.listdir(src):
        shutil.copyfile(os.path.join(src, name), tmp_path / name)
    return str(tmp_path / "1")


def _encode_fixture(base, backend="cpu", buffer_size=2000):
    rs = new_encoder(backend=backend)
    ec_files.write_ec_files(
        base,
        rs=rs,
        buffer_size=buffer_size,
        large_block_size=LARGE,
        small_block_size=SMALL,
    )


class TestEncodeFixture:
    def test_striped_reads_match_dat(self, fixture_volume):
        # validateFiles (ec_test.go:63-121): every needle's bytes read
        # through the striping must equal the .dat bytes.
        dat = open(fixture_volume + ".dat", "rb").read()
        idx_data = open(fixture_volume + ".idx", "rb").read()
        checked = 0
        for key, offset_units, size in idx_codec.iter_entries(idx_data):
            if size == t.TOMBSTONE_FILE_SIZE or offset_units == 0:
                continue
            offset = t.units_to_offset(offset_units)
            from seaweedfs_tpu.storage.needle import get_actual_size

            span = get_actual_size(size, 3)
            got = ec_files.read_shard_intervals(
                fixture_volume, offset, span, len(dat), LARGE, SMALL
            )
            assert got == dat[offset : offset + span], f"needle {key} mismatch"
            checked += 1
        assert checked > 200

    def test_shard_sizes(self, fixture_volume):
        dat_size = os.path.getsize(fixture_volume + ".dat")
        expect = ec_files.shard_file_size(dat_size, LARGE, SMALL)
        for i in range(14):
            assert os.path.getsize(fixture_volume + ec_files.to_ext(i)) == expect

    def test_tpu_backend_identical_files(self, fixture_volume, tmp_path):
        cpu_shards = [
            open(fixture_volume + ec_files.to_ext(i), "rb").read() for i in range(14)
        ]
        # re-encode with the TPU backend and a different buffer size
        _encode_fixture(fixture_volume, backend="tpu", buffer_size=500)
        for i in range(14):
            tpu_bytes = open(fixture_volume + ec_files.to_ext(i), "rb").read()
            assert tpu_bytes == cpu_shards[i], f"shard {i} differs"

    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_rebuild_missing_shards(self, fixture_volume, seed):
        # ec_test.go:141-172: drop a random subset (≤4), rebuild, compare.
        originals = {
            i: open(fixture_volume + ec_files.to_ext(i), "rb").read()
            for i in range(14)
        }
        rng = random.Random(seed)
        missing = rng.sample(range(14), rng.randint(1, 4))
        for i in missing:
            os.remove(fixture_volume + ec_files.to_ext(i))
        rebuilt = ec_files.rebuild_ec_files(fixture_volume)
        assert sorted(rebuilt) == sorted(missing)
        for i in range(14):
            got = open(fixture_volume + ec_files.to_ext(i), "rb").read()
            assert got == originals[i], f"shard {i} not restored"

    def test_rebuild_too_few_raises(self, fixture_volume):
        for i in range(5):
            os.remove(fixture_volume + ec_files.to_ext(i))
        with pytest.raises(ValueError, match="too few"):
            ec_files.rebuild_ec_files(fixture_volume)

    def test_rebuild_noop_when_complete(self, fixture_volume):
        assert ec_files.rebuild_ec_files(fixture_volume) == []


class TestEcx:
    def test_sorted_and_complete(self, fixture_volume):
        ec_files.write_sorted_file_from_idx(fixture_volume)
        ecx = open(fixture_volume + ".ecx", "rb").read()
        keys, offsets, sizes = idx_codec.entries_as_arrays(ecx)
        assert np.all(np.diff(keys.astype(np.int64)) > 0), "keys must ascend strictly"
        idx_data = open(fixture_volume + ".idx", "rb").read()
        live = {}
        for key, off, size in idx_codec.iter_entries(idx_data):
            if off != 0 and size != t.TOMBSTONE_FILE_SIZE:
                live[key] = (off, size)
        assert set(int(k) for k in keys) == set(live)

    def test_delete_of_out_of_order_insert_removed(self, tmp_path):
        # reference CompactMap: out-of-order inserts land in `overflow`,
        # and Delete removes overflow entries entirely
        base = str(tmp_path / "oo")
        entries = (
            idx_codec.pack_entry(10, 1, 100)
            + idx_codec.pack_entry(4, 2, 200)  # out of order -> overflow
            + idx_codec.pack_entry(4, 0, t.TOMBSTONE_FILE_SIZE)
            + idx_codec.pack_entry(10, 0, t.TOMBSTONE_FILE_SIZE)
        )
        with open(base + ".idx", "wb") as f:
            f.write(entries)
        ec_files.write_sorted_file_from_idx(base)
        got = list(idx_codec.iter_entries(open(base + ".ecx", "rb").read()))
        assert got == [(10, 1, t.TOMBSTONE_FILE_SIZE)]

    def test_delete_of_zero_size_entry_is_noop(self, tmp_path):
        base = str(tmp_path / "zz")
        entries = (
            idx_codec.pack_entry(3, 5, 0)  # live zero-size needle
            + idx_codec.pack_entry(3, 0, t.TOMBSTONE_FILE_SIZE)
        )
        with open(base + ".idx", "wb") as f:
            f.write(entries)
        ec_files.write_sorted_file_from_idx(base)
        got = list(idx_codec.iter_entries(open(base + ".ecx", "rb").read()))
        assert got == [(3, 5, 0)]

    def test_delete_entries_tombstone(self, tmp_path):
        base = str(tmp_path / "2")
        entries = (
            idx_codec.pack_entry(5, 10, 100)
            + idx_codec.pack_entry(3, 20, 200)
            + idx_codec.pack_entry(5, 0, t.TOMBSTONE_FILE_SIZE)  # delete 5
            + idx_codec.pack_entry(9, 0, t.TOMBSTONE_FILE_SIZE)  # delete unknown
        )
        with open(base + ".idx", "wb") as f:
            f.write(entries)
        ec_files.write_sorted_file_from_idx(base)
        ecx = open(base + ".ecx", "rb").read()
        got = list(idx_codec.iter_entries(ecx))
        assert got == [(3, 20, 200), (5, 10, t.TOMBSTONE_FILE_SIZE)]

    def test_idx_from_ecx_roundtrip(self, tmp_path):
        base = str(tmp_path / "3")
        with open(base + ".idx", "wb") as f:
            f.write(idx_codec.pack_entry(1, 5, 50) + idx_codec.pack_entry(2, 9, 90))
        ec_files.write_sorted_file_from_idx(base)
        # simulate a journaled delete of needle 2
        with open(base + ".ecj", "wb") as f:
            f.write(t.needle_id_to_bytes(2))
        ec_files.write_idx_file_from_ec_index(base)
        got = list(idx_codec.iter_entries(open(base + ".idx", "rb").read()))
        assert got == [
            (1, 5, 50),
            (2, 9, 90),
            (2, 0, t.TOMBSTONE_FILE_SIZE),
        ]


class TestSyntheticVolume:
    def test_large_tier_roundtrip(self, tmp_path):
        # big enough for 2 large rows + small tail (tiny block sizes)
        base = str(tmp_path / "synth")
        rng = np.random.default_rng(0)
        data = rng.integers(0, 256, 2 * 10 * LARGE + 12345, dtype=np.uint8).tobytes()
        with open(base + ".dat", "wb") as f:
            f.write(data)
        rs = new_encoder()
        ec_files.write_ec_files(
            base, rs=rs, buffer_size=2500, large_block_size=LARGE, small_block_size=SMALL
        )
        # spot-check random spans through the striping
        pyrng = random.Random(0)
        for _ in range(50):
            off = pyrng.randrange(len(data))
            size = pyrng.randrange(1, min(3 * SMALL, len(data) - off) + 1)
            got = ec_files.read_shard_intervals(base, off, size, len(data), LARGE, SMALL)
            assert got == data[off : off + size]


class TestStreamDrivers:
    """Pipelined ec_stream drivers must be byte-identical to the
    classic synchronous loops. Kernel stages are injected as numpy
    functions so the pipeline (tiling, in-flight ordering, writes)
    is exercised on CPU hosts; kernel correctness is pinned in
    test_ec_codec.py."""

    def _cpu_stages(self):
        from seaweedfs_tpu.ec.codec import ReedSolomon

        rs = ReedSolomon(backend="cpu")

        def parity_fn(tile):
            return rs._apply(rs.parity_rows, tile)

        def rebuild_fn(survivors, targets, tile):
            from seaweedfs_tpu.ec import gf256

            rows = gf256.decode_rows(rs.matrix, survivors, targets)
            return rs._apply(rows, tile)

        return parity_fn, rebuild_fn, (lambda h: h)

    def test_stream_write_matches_classic(self, tmp_path):
        import numpy as np

        from seaweedfs_tpu.ec import ec_files, ec_stream

        rng = np.random.default_rng(17)
        payload = rng.integers(0, 256, 987_654, dtype=np.uint8).tobytes()
        LARGE, SMALL = 40_000, 4_000

        classic = tmp_path / "classic"
        stream = tmp_path / "stream"
        for d in (classic, stream):
            d.mkdir()
            (d / "1.dat").write_bytes(payload)

        ec_files.write_ec_files(
            str(classic / "1"),
            buffer_size=2_000,
            large_block_size=LARGE,
            small_block_size=SMALL,
        )
        parity_fn, _, fetch = self._cpu_stages()
        ec_stream.stream_write_ec_files(
            str(stream / "1"),
            tile_bytes=16_000,
            large_block_size=LARGE,
            small_block_size=SMALL,
            parity_fn=parity_fn,
            fetch_fn=fetch,
        )
        for i in range(14):
            ext = ec_files.to_ext(i)
            assert (stream / f"1{ext}").read_bytes() == (
                classic / f"1{ext}"
            ).read_bytes(), ext

    def test_stream_rebuild_matches_original(self, tmp_path):
        import os

        import numpy as np

        from seaweedfs_tpu.ec import ec_files, ec_stream

        rng = np.random.default_rng(18)
        payload = rng.integers(0, 256, 500_000, dtype=np.uint8).tobytes()
        LARGE, SMALL = 40_000, 4_000
        base = str(tmp_path / "1")
        (tmp_path / "1.dat").write_bytes(payload)
        ec_files.write_ec_files(
            base, buffer_size=2_000, large_block_size=LARGE, small_block_size=SMALL
        )
        originals = {
            i: open(base + ec_files.to_ext(i), "rb").read() for i in range(14)
        }
        for sid in (1, 7, 10, 13):
            os.remove(base + ec_files.to_ext(sid))

        _, rebuild_fn, fetch = self._cpu_stages()
        rebuilt = ec_stream.stream_rebuild_ec_files(
            base, tile_bytes=12_000, rebuild_fn=rebuild_fn, fetch_fn=fetch
        )
        assert rebuilt == [1, 7, 10, 13]
        for i in range(14):
            assert (
                open(base + ec_files.to_ext(i), "rb").read() == originals[i]
            ), i


    def test_stream_write_stage_error_propagates(self, tmp_path):
        """A kernel-stage failure mid-stream must raise on the caller
        (not hang the reader/writer threads and not leave them alive)."""

        import numpy as np
        import pytest as _pytest

        from seaweedfs_tpu.ec import ec_stream

        rng = np.random.default_rng(19)
        (tmp_path / "1.dat").write_bytes(
            rng.integers(0, 256, 200_000, dtype=np.uint8).tobytes()
        )
        calls = {"n": 0}

        def parity_fn(tile):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("kernel died")
            return np.zeros((4, tile.shape[1]), dtype=np.uint8)

        from tests.faults import ec_stream_threads

        with _pytest.raises(RuntimeError, match="kernel died"):
            ec_stream.stream_write_ec_files(
                str(tmp_path / "1"),
                tile_bytes=16_000,
                large_block_size=40_000,
                small_block_size=4_000,
                parity_fn=parity_fn,
                fetch_fn=lambda h: h,
            )
        assert not ec_stream_threads()  # stage threads joined

    def test_stream_write_pool_identical_odd_sizes(self, tmp_path):
        """The pwritev writer POOL lands tiles in completion order —
        positioned writes must keep the bytes identical to the classic
        serial driver on awkward sizes (tail zero-padding, one-tile
        rows, sub-tile remainders)."""
        import numpy as np

        from seaweedfs_tpu.ec import ec_files, ec_stream

        LARGE, SMALL = 40_000, 4_000
        rng = np.random.default_rng(23)
        parity_fn, _, fetch = self._cpu_stages()
        for size in (1, 3_999, 123_457, 1_000_001):
            classic = tmp_path / f"c{size}"
            stream = tmp_path / f"s{size}"
            payload = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
            for d in (classic, stream):
                d.mkdir()
                (d / "1.dat").write_bytes(payload)
            ec_files.write_ec_files(
                str(classic / "1"),
                rs=new_encoder(backend="cpu"),
                buffer_size=2_000,
                large_block_size=LARGE,
                small_block_size=SMALL,
            )
            ec_stream.stream_write_ec_files(
                str(stream / "1"),
                tile_bytes=7_000,
                large_block_size=LARGE,
                small_block_size=SMALL,
                parity_fn=parity_fn,
                fetch_fn=fetch,
                writer_threads=3,
                reader_threads=2,
            )
            for i in range(14):
                ext = ec_files.to_ext(i)
                assert (stream / f"1{ext}").read_bytes() == (
                    classic / f"1{ext}"
                ).read_bytes(), (size, ext)

    def test_stream_write_enospc_abort_no_leaks(self, tmp_path, monkeypatch):
        """A short-write/ENOSPC surfacing in the writer POOL mid-stream
        must raise on the caller, join every pool thread, and leak no
        fd (the .dat readers and all 14 preallocated shard fds)."""
        import errno
        import os

        import numpy as np
        import pytest as _pytest

        from seaweedfs_tpu.ec import ec_stream

        rng = np.random.default_rng(29)
        (tmp_path / "1.dat").write_bytes(
            rng.integers(0, 256, 300_000, dtype=np.uint8).tobytes()
        )
        calls = {"n": 0}
        real_pwritev = ec_stream._pwritev_full

        def flaky_pwritev(fd, bufs, offset):
            calls["n"] += 1
            if calls["n"] == 20:
                raise OSError(errno.ENOSPC, "No space left on device")
            return real_pwritev(fd, bufs, offset)

        monkeypatch.setattr(ec_stream, "_pwritev_full", flaky_pwritev)
        # leaks are counted by the pipeline's thread names and by this
        # test's own directory: an xdist worker's other threads and
        # sockets come and go
        from tests.faults import ec_stream_threads, fds_under

        with _pytest.raises(OSError, match="No space left"):
            ec_stream.stream_write_ec_files(
                str(tmp_path / "1"),
                tile_bytes=4_000,
                large_block_size=40_000,
                small_block_size=4_000,
                parity_fn=lambda t: np.zeros((4, t.shape[1]), dtype=np.uint8),
                fetch_fn=lambda h: h,
                writer_threads=3,
                reader_threads=2,
            )
        assert not ec_stream_threads()
        assert not fds_under(tmp_path)
        # the trace span must record the failure: an aborted encode
        # that looks clean in /debug/traces would hide exactly the
        # repair-path behavior the tracing plane exists to attribute
        from seaweedfs_tpu import trace

        encode_spans = [
            s
            for s in trace.debug_payload(n=64)["recent"]
            if s["name"] == "ec_stream.encode"
        ]
        assert encode_spans, "no ec_stream.encode span recorded"
        assert "No space left" in encode_spans[0].get("error", "")
        # no half-written shard files survive the abort: shard_presence
        # would otherwise count the garbage as a complete valid set
        from seaweedfs_tpu.ec import ec_files

        for i in range(14):
            assert not os.path.exists(
                str(tmp_path / "1") + ec_files.to_ext(i)
            ), i

    def test_stream_rebuild_enospc_abort_no_leaks(self, tmp_path, monkeypatch):
        import errno
        import os

        import numpy as np
        import pytest as _pytest

        from seaweedfs_tpu.ec import ec_files, ec_stream

        rng = np.random.default_rng(31)
        (tmp_path / "1.dat").write_bytes(
            rng.integers(0, 256, 300_000, dtype=np.uint8).tobytes()
        )
        base = str(tmp_path / "1")
        ec_files.write_ec_files(
            base,
            rs=new_encoder(backend="cpu"),
            buffer_size=2_000,
            large_block_size=40_000,
            small_block_size=4_000,
        )
        os.remove(base + ec_files.to_ext(2))
        _, rebuild_fn, fetch = self._cpu_stages()

        def broken_pwrite(fd, buf, offset):
            raise OSError(errno.ENOSPC, "No space left on device")

        monkeypatch.setattr(ec_stream, "_pwrite_full", broken_pwrite)
        from tests.faults import ec_stream_threads, fds_under

        with _pytest.raises(OSError, match="No space left"):
            ec_stream.stream_rebuild_ec_files(
                base,
                tile_bytes=3_000,
                rebuild_fn=rebuild_fn,
                fetch_fn=fetch,
                writer_threads=2,
                reader_threads=2,
            )
        assert not ec_stream_threads()
        assert not fds_under(tmp_path)
        # the half-written target was removed (a retry must see it as
        # still missing), the survivors untouched
        assert not os.path.exists(base + ec_files.to_ext(2))
        assert os.path.exists(base + ec_files.to_ext(3))

    def test_stream_rebuild_remote_readers_identical(self, tmp_path):
        """The rack-gather path: survivors held only by OTHER nodes
        arrive through injected remote readers; shards readable
        remotely are treated as present (not rebuilt) and the rebuilt
        bytes match the originals exactly."""
        import os

        import numpy as np

        from seaweedfs_tpu.ec import ec_files, ec_stream

        rng = np.random.default_rng(37)
        (tmp_path / "1.dat").write_bytes(
            rng.integers(0, 256, 500_000, dtype=np.uint8).tobytes()
        )
        base = str(tmp_path / "1")
        ec_files.write_ec_files(
            base, buffer_size=2_000, large_block_size=40_000, small_block_size=4_000
        )
        originals = {
            i: open(base + ec_files.to_ext(i), "rb").read() for i in range(14)
        }
        # shards 4..9 live only on the "remote holder" (moved away);
        # shards 2 and 12 are lost cluster-wide
        remote_dir = tmp_path / "remote"
        remote_dir.mkdir()
        remote_held = (4, 5, 6, 7, 8, 9)
        for sid in remote_held:
            os.rename(
                base + ec_files.to_ext(sid),
                str(remote_dir / f"1{ec_files.to_ext(sid)}"),
            )
        for sid in (2, 12):
            os.remove(base + ec_files.to_ext(sid))

        def make_reader(sid):
            path = str(remote_dir / f"1{ec_files.to_ext(sid)}")

            def read_into(offset, dest):
                with open(path, "rb") as f:
                    f.seek(offset)
                    return f.readinto(dest)

            return read_into

        _, rebuild_fn, fetch = self._cpu_stages()
        rebuilt = ec_stream.stream_rebuild_ec_files(
            base,
            tile_bytes=12_000,
            rebuild_fn=rebuild_fn,
            fetch_fn=fetch,
            remote_readers={sid: make_reader(sid) for sid in remote_held},
            writer_threads=2,
            reader_threads=2,
        )
        assert rebuilt == [2, 12]
        for sid in (2, 12):
            assert (
                open(base + ec_files.to_ext(sid), "rb").read()
                == originals[sid]
            ), sid
        # remote-held shards were NOT recreated locally
        for sid in remote_held:
            assert not os.path.exists(base + ec_files.to_ext(sid)), sid

    def test_stream_rebuild_read_error_propagates(self, tmp_path):
        """A truncated survivor detected by the reader THREAD must
        surface as the caller's exception."""
        import os

        import numpy as np
        import pytest as _pytest

        from seaweedfs_tpu.ec import ec_files, ec_stream

        rng = np.random.default_rng(20)
        (tmp_path / "1.dat").write_bytes(
            rng.integers(0, 256, 300_000, dtype=np.uint8).tobytes()
        )
        base = str(tmp_path / "1")
        ec_files.write_ec_files(
            base, buffer_size=2_000, large_block_size=40_000, small_block_size=4_000
        )
        os.remove(base + ec_files.to_ext(12))
        # truncate a survivor below one tile so the reader's pread fails
        surv = base + ec_files.to_ext(3)
        with open(surv, "r+b") as f:
            f.truncate(1_000)

        _, rebuild_fn, fetch = self._cpu_stages()
        with _pytest.raises(ValueError, match="truncated"):
            ec_stream.stream_rebuild_ec_files(
                base, tile_bytes=12_000, rebuild_fn=rebuild_fn, fetch_fn=fetch
            )


class TestLocateProperty:
    """Randomized cross-check of the striping math against the actual
    encoder: encode random .dat sizes with tiny block sizes, then for
    random spans gather bytes via locate_data +
    to_shard_id_and_offset from the shard FILES and compare with the
    .dat bytes. Covers multi-row large-tier layouts the fixture tests
    (production block sizes, tiny volumes) never reach."""

    @pytest.mark.parametrize("seed", range(6))
    def test_random_spans_roundtrip(self, seed, tmp_path):
        import random as _r

        rng = _r.Random(seed)
        large, small = 1000, 100  # tiny two-tier layout
        large_row = large * locate.DATA_SHARDS
        # avoid the documented exact-large-row-multiple reference quirk
        while True:
            dat_size = rng.randint(1, 4 * large_row)
            if dat_size % large_row:
                break
        base = str(tmp_path / f"p{seed}")
        data = bytes(rng.randbytes(dat_size))
        with open(base + ".dat", "wb") as f:
            f.write(data)
        ec_files.write_ec_files(
            base,
            rs=new_encoder(backend="cpu"),
            buffer_size=small,
            large_block_size=large,
            small_block_size=small,
        )
        shards = [
            open(base + ec_files.to_ext(i), "rb").read()
            for i in range(locate.DATA_SHARDS)
        ]
        for _ in range(25):
            off = rng.randint(0, dat_size - 1)
            size = rng.randint(1, min(dat_size - off, 3 * large))
            got = bytearray()
            for iv in locate.locate_data(large, small, dat_size, off, size):
                sid, soff = iv.to_shard_id_and_offset(large, small)
                got += shards[sid][soff : soff + iv.size]
            assert bytes(got) == data[off : off + size], (
                f"dat_size={dat_size} span=({off},{size})"
            )
