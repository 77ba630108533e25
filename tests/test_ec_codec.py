"""EC codec tests: field math, matrix construction, encode/reconstruct
properties, CPU↔TPU-backend equivalence.

Models the reference's ec_test.go strategy: encode, drop random shard
subsets, verify reconstruction equals the original bytes.
"""

import itertools

import numpy as np
import pytest

from seaweedfs_tpu.ec import gf256
from seaweedfs_tpu.ec.codec import ReedSolomon, cpu_apply_matrix, new_encoder


class TestGf256:
    def test_exp_table_basics(self):
        # generator 2, poly 0x11D: 2^0=1, 2^1=2, ..., 2^8 = 0x1d
        assert gf256.EXP_TABLE[0] == 1
        assert gf256.EXP_TABLE[1] == 2
        assert gf256.EXP_TABLE[7] == 0x80
        assert gf256.EXP_TABLE[8] == 0x1D

    def test_mul_matches_carryless_reference(self):
        def slow_mul(a, b):
            r = 0
            while b:
                if b & 1:
                    r ^= a
                a <<= 1
                if a & 0x100:
                    a ^= 0x11D
                b >>= 1
            return r

        rng = np.random.default_rng(0)
        for _ in range(500):
            a, b = int(rng.integers(256)), int(rng.integers(256))
            assert gf256.gf_mul(a, b) == slow_mul(a, b)

    def test_mul_table_symmetry_and_identity(self):
        assert np.array_equal(gf256.MUL_TABLE, gf256.MUL_TABLE.T)
        assert np.array_equal(gf256.MUL_TABLE[1], np.arange(256, dtype=np.uint8))
        assert np.all(gf256.MUL_TABLE[0] == 0)

    def test_div_inverts_mul(self):
        rng = np.random.default_rng(1)
        for _ in range(200):
            a, b = int(rng.integers(256)), int(rng.integers(1, 256))
            assert gf256.gf_div(gf256.gf_mul(a, b), b) == a

    def test_gf_exp_matches_reference_semantics(self):
        # galExp: n==0 → 1 even for a==0; a==0 → 0 otherwise
        assert gf256.gf_exp(0, 0) == 1
        assert gf256.gf_exp(0, 5) == 0
        assert gf256.gf_exp(3, 1) == 3
        v = 1
        for _ in range(7):
            v = gf256.gf_mul(v, 5)
        assert gf256.gf_exp(5, 7) == v

    def test_mat_inv(self):
        rng = np.random.default_rng(2)
        for n in [1, 2, 5, 10, 14]:
            # random invertible matrix: retry until non-singular
            while True:
                m = rng.integers(0, 256, (n, n)).astype(np.uint8)
                try:
                    inv = gf256.mat_inv(m)
                    break
                except np.linalg.LinAlgError:
                    continue
            assert np.array_equal(gf256.mat_mul(m, inv), gf256.identity(n))
            assert np.array_equal(gf256.mat_mul(inv, m), gf256.identity(n))

    def test_singular_raises(self):
        m = np.array([[1, 2], [1, 2]], dtype=np.uint8)
        with pytest.raises(np.linalg.LinAlgError):
            gf256.mat_inv(m)

    def test_code_matrix_systematic(self):
        a = gf256.build_code_matrix(10, 14)
        assert a.shape == (14, 10)
        assert np.array_equal(a[:10], gf256.identity(10))
        # parity rows must have no zero coefficients (MDS property side
        # effect of the Vandermonde construction)
        assert np.all(a[10:] != 0)

    def test_code_matrix_mds_property(self):
        # every k-row submatrix must be invertible (this is what makes
        # any-10-of-14 reconstruction work)
        a = gf256.build_code_matrix(4, 6)
        for rows in itertools.combinations(range(6), 4):
            inv = gf256.mat_inv(a[np.array(rows)])  # must not raise
            assert inv.shape == (4, 4)


def _random_shards(rng, k, n):
    return [rng.integers(0, 256, n).astype(np.uint8) for _ in range(k)]


class TestReedSolomonCpu:
    def setup_method(self):
        self.rs = new_encoder(10, 4, backend="cpu")
        self.rng = np.random.default_rng(42)

    def _encoded(self, n=1000):
        shards = _random_shards(self.rng, 10, n) + [None] * 4
        return self.rs.encode(shards)

    def test_encode_verify(self):
        shards = self._encoded()
        assert all(s is not None for s in shards)
        assert self.rs.verify(shards)

    def test_verify_detects_corruption(self):
        shards = self._encoded()
        shards[3] = shards[3].copy()
        shards[3][17] ^= 0xFF
        assert not self.rs.verify(shards)

    @pytest.mark.parametrize("n_missing", [1, 2, 3, 4])
    def test_reconstruct_any_missing(self, n_missing):
        original = self._encoded()
        for missing in itertools.islice(
            itertools.combinations(range(14), n_missing), 30
        ):
            shards = [s.copy() if i not in missing else None for i, s in enumerate(original)]
            self.rs.reconstruct(shards)
            for i in range(14):
                np.testing.assert_array_equal(shards[i], original[i], err_msg=f"shard {i}")

    def test_reconstruct_data_leaves_parity_missing(self):
        original = self._encoded()
        shards = [s.copy() for s in original]
        shards[2] = None
        shards[12] = None
        self.rs.reconstruct_data(shards)
        np.testing.assert_array_equal(shards[2], original[2])
        assert shards[12] is None

    def test_too_few_shards_raises(self):
        original = self._encoded()
        shards = [s.copy() for s in original]
        for i in [0, 1, 2, 3, 13]:
            shards[i] = None
        with pytest.raises(ValueError, match="too few"):
            self.rs.reconstruct(shards)

    def test_identity_passthrough(self):
        # encode must not modify data shards (systematic code)
        shards = self._encoded()
        data_copy = [s.copy() for s in shards[:10]]
        self.rs.encode(shards)
        for a, b in zip(shards[:10], data_copy):
            np.testing.assert_array_equal(a, b)

    def test_parity_linear_in_data(self):
        # RS is linear: parity(a ^ b) = parity(a) ^ parity(b)
        a = self._encoded(256)
        b = self._encoded(256)
        xored = [x ^ y for x, y in zip(a[:10], b[:10])] + [None] * 4
        self.rs.encode(xored)
        for i in range(10, 14):
            np.testing.assert_array_equal(xored[i], a[i] ^ b[i])


class TestTpuBackendEquivalence:
    """The TPU (bitsliced XOR-matmul) backend must be byte-identical to
    the CPU reference backend — the analogue of ec_test.go's
    read-vs-reconstruct cross-check."""

    def setup_method(self):
        self.rng = np.random.default_rng(7)

    def test_apply_matrix_equivalence(self):
        from seaweedfs_tpu.ec.codec_tpu import tpu_apply_matrix

        for r, c, n in [(4, 10, 512), (10, 10, 100), (1, 14, 63), (14, 14, 257)]:
            m = self.rng.integers(0, 256, (r, c)).astype(np.uint8)
            x = self.rng.integers(0, 256, (c, n)).astype(np.uint8)
            np.testing.assert_array_equal(
                tpu_apply_matrix(m, x), cpu_apply_matrix(m, x)
            )

    def test_encode_equivalence(self):
        cpu = new_encoder(10, 4, backend="cpu")
        tpu = new_encoder(10, 4, backend="tpu")
        data = _random_shards(self.rng, 10, 4096)
        s_cpu = cpu.encode([d.copy() for d in data] + [None] * 4)
        s_tpu = tpu.encode([d.copy() for d in data] + [None] * 4)
        for a, b in zip(s_cpu, s_tpu):
            np.testing.assert_array_equal(a, b)

    def test_reconstruct_equivalence(self):
        cpu = new_encoder(10, 4, backend="cpu")
        tpu = new_encoder(10, 4, backend="tpu")
        data = _random_shards(self.rng, 10, 1024)
        original = cpu.encode([d.copy() for d in data] + [None] * 4)
        for missing in [(0,), (0, 5, 10, 13), (10, 11, 12, 13), (6, 7, 8, 9)]:
            shards = [
                s.copy() if i not in missing else None for i, s in enumerate(original)
            ]
            tpu.reconstruct(shards)
            for i in range(14):
                np.testing.assert_array_equal(shards[i], original[i])

    def test_device_kernels(self):
        import jax.numpy as jnp

        from seaweedfs_tpu.ec.codec_tpu import TpuCodecKernels

        kern = TpuCodecKernels(10, 4)
        data = np.stack(_random_shards(self.rng, 10, 2048))
        parity = np.asarray(kern.encode(jnp.asarray(data)))
        cpu = new_encoder(10, 4, backend="cpu")
        expect = cpu.encode([d.copy() for d in data] + [None] * 4)
        for i in range(4):
            np.testing.assert_array_equal(parity[i], expect[10 + i])

        # degraded read: lose shards 2 and 11, rebuild from 10 survivors
        all_shards = np.concatenate([data, parity], axis=0)
        survivors = tuple(i for i in range(14) if i not in (2, 11))[:10]
        rebuilt = np.asarray(
            kern.reconstruct(survivors, (2, 11), jnp.asarray(all_shards[list(survivors)]))
        )
        np.testing.assert_array_equal(rebuilt[0], data[2])
        np.testing.assert_array_equal(rebuilt[1], expect[11])

    def test_batched_encode(self):
        import jax.numpy as jnp

        from seaweedfs_tpu.ec.codec_tpu import TpuCodecKernels

        kern = TpuCodecKernels(10, 4)
        batch = self.rng.integers(0, 256, (3, 10, 512)).astype(np.uint8)
        parity = np.asarray(kern.encode_batch(jnp.asarray(batch)))
        cpu = new_encoder(10, 4, backend="cpu")
        for b in range(3):
            expect = cpu.encode([batch[b, i].copy() for i in range(10)] + [None] * 4)
            for i in range(4):
                np.testing.assert_array_equal(parity[b, i], expect[10 + i])


class TestSmallConfigs:
    @pytest.mark.parametrize("k,p", [(1, 1), (2, 2), (4, 2), (10, 4), (17, 3)])
    def test_roundtrip(self, k, p):
        rng = np.random.default_rng(k * 31 + p)
        rs = ReedSolomon(k, p, backend="cpu")
        shards = [rng.integers(0, 256, 128).astype(np.uint8) for _ in range(k)] + [
            None
        ] * p
        rs.encode(shards)
        original = [s.copy() for s in shards]
        drop = list(range(min(p, k)))
        for i in drop:
            shards[i] = None
        rs.reconstruct(shards)
        for a, b in zip(shards, original):
            np.testing.assert_array_equal(a, b)


class TestSwarKernel:
    """The SWAR Horner Pallas kernel — the default serving path for
    streams >= 64 KiB on TPU hosts — via the Pallas interpreter, byte-
    compared against the CPU LUT backend (codec_tpu.py fast path)."""

    def test_encode_rows_interpret(self):
        from seaweedfs_tpu.ec.codec import cpu_apply_matrix
        from seaweedfs_tpu.ec.codec_tpu import swar_apply_matrix_host
        from seaweedfs_tpu.ec import gf256

        rng = np.random.default_rng(99)
        n = 128 * 1024  # above _SWAR_MIN_BYTES, multiple of 1024
        data = rng.integers(0, 256, (10, n), dtype=np.uint8)
        matrix = gf256.build_code_matrix(10, 14)
        parity_rows = matrix[10:]
        out = swar_apply_matrix_host(parity_rows, data, interpret=True)
        np.testing.assert_array_equal(out, cpu_apply_matrix(parity_rows, data))

    def test_decode_rows_interpret(self):
        import jax.numpy as jnp

        from seaweedfs_tpu.ec.codec import cpu_apply_matrix
        from seaweedfs_tpu.ec.codec_tpu import TpuCodecKernels, swar_apply_matrix_host

        rng = np.random.default_rng(100)
        n = 64 * 1024
        kern = TpuCodecKernels(10, 4)
        data = rng.integers(0, 256, (10, n), dtype=np.uint8)
        parity = cpu_apply_matrix(kern.matrix[10:], data)
        shards = np.concatenate([data, parity], axis=0)

        survivors = tuple(i for i in range(14) if i not in (0, 5, 12, 13))
        targets = (0, 5, 12, 13)
        rows = kern.decode_rows_for(survivors, targets)
        out = swar_apply_matrix_host(rows, shards[list(survivors)], interpret=True)
        np.testing.assert_array_equal(out[0], shards[0])
        np.testing.assert_array_equal(out[1], shards[5])
        np.testing.assert_array_equal(out[2], shards[12])
        np.testing.assert_array_equal(out[3], shards[13])


class TestNativeBackend:
    """The SIMD C shim (native/gf256.c) — the "native" codec backend
    serving plain hosts (the reference's klauspost/reedsolomon-AVX2
    role) — byte-compared against the numpy "cpu" backend. Skipped
    only when no system compiler exists."""

    @pytest.fixture(scope="class")
    def nat(self):
        try:
            from seaweedfs_tpu.native.gf import apply_matrix
        except ImportError:
            pytest.skip("native gf256 shim unavailable (no compiler)")
        return apply_matrix

    def test_apply_matrix_equivalence(self, nat):
        from seaweedfs_tpu.ec.codec import cpu_apply_matrix

        rng = np.random.default_rng(7)
        # sizes straddling the SIMD widths and the 256 KiB block size
        for n in (0, 1, 31, 32, 33, 63, 64, 65, 4096, 262144 + 17):
            matrix = rng.integers(0, 256, (4, 10), dtype=np.uint8)
            data = rng.integers(0, 256, (10, n), dtype=np.uint8)
            np.testing.assert_array_equal(
                nat(matrix, data), cpu_apply_matrix(matrix, data)
            )

    def test_zero_and_identity_coefficients(self, nat):
        from seaweedfs_tpu.ec.codec import cpu_apply_matrix

        rng = np.random.default_rng(8)
        data = rng.integers(0, 256, (3, 1000), dtype=np.uint8)
        matrix = np.array([[0, 1, 2], [1, 0, 0], [0, 0, 0]], dtype=np.uint8)
        np.testing.assert_array_equal(
            nat(matrix, data), cpu_apply_matrix(matrix, data)
        )

    def test_full_encoder_roundtrip(self, nat):
        from seaweedfs_tpu.ec.codec import new_encoder

        rng = np.random.default_rng(9)
        rs_nat = new_encoder(backend="native")
        rs_cpu = new_encoder(backend="cpu")
        data = [
            rng.integers(0, 256, 100_001, dtype=np.uint8) for _ in range(10)
        ]
        got = rs_nat.encode([d.copy() for d in data] + [None] * 4)
        want = rs_cpu.encode([d.copy() for d in data] + [None] * 4)
        for i in range(14):
            np.testing.assert_array_equal(got[i], want[i])

        # worst case: all four losses are data shards
        shards = [s.copy() for s in got]
        for i in (0, 3, 5, 9):
            shards[i] = None
        rs_nat.reconstruct(shards)
        for i in range(14):
            np.testing.assert_array_equal(shards[i], want[i])

    def test_default_backend_prefers_native_on_plain_hosts(
        self, nat, monkeypatch
    ):
        from seaweedfs_tpu.ec import codec

        # conftest pins WEED_EC_CODEC=cpu for determinism and forces a
        # cpu-only jax backend; with the pin lifted, auto-detect on
        # this no-accelerator host must land on the native shim
        monkeypatch.delenv("WEED_EC_CODEC", raising=False)
        monkeypatch.setattr(codec, "_default_backend", "")
        assert codec.default_backend() == "native"

    def test_thread_safety_parallel_calls(self, nat):
        """Server handler threads run EC ops concurrently; the shim's
        tables are read-only after dlopen and every call writes only
        its own output — N threads hammering apply_matrix must all get
        byte-identical results (ctypes releases the GIL, so the C code
        really runs in parallel)."""
        import threading

        from seaweedfs_tpu.ec.codec import cpu_apply_matrix

        rng = np.random.default_rng(11)
        matrix = rng.integers(0, 256, (4, 10), dtype=np.uint8)
        data = rng.integers(0, 256, (10, 1 << 18), dtype=np.uint8)
        want = cpu_apply_matrix(matrix, data)
        errors = []

        def worker():
            try:
                for _ in range(8):
                    np.testing.assert_array_equal(nat(matrix, data), want)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[:1]


class TestNoHiddenDeviceFallback:
    """A chip that cannot be initialised (another process holds it,
    say) must fail loudly — not read as "no chip" and demote the node
    to a host codec or the bit-matmul arm in silence."""

    @staticmethod
    def _chip_busy():
        raise RuntimeError("TPU is already in use by another process")

    def test_default_backend_propagates_device_errors(self, monkeypatch):
        import jax

        from seaweedfs_tpu.ec import codec

        monkeypatch.delenv("WEED_EC_CODEC", raising=False)
        monkeypatch.setattr(codec, "_default_backend", "")
        monkeypatch.setattr(jax, "devices", self._chip_busy)
        with pytest.raises(RuntimeError, match="already in use"):
            codec.default_backend()
        assert codec._default_backend == ""  # nothing cached either

    def test_on_tpu_propagates_device_errors(self, monkeypatch):
        import jax

        from seaweedfs_tpu.ec import codec_tpu

        codec_tpu.device_report.cache_clear()
        monkeypatch.setattr(jax, "devices", self._chip_busy)
        try:
            with pytest.raises(RuntimeError, match="already in use"):
                codec_tpu._on_tpu()
        finally:
            codec_tpu.device_report.cache_clear()

    def test_device_report_names_platform_and_arm(self):
        from seaweedfs_tpu.ec import codec_tpu

        report = codec_tpu.device_report()
        assert report["platform"] == "cpu" and report["arm"] == "bit-matmul"
        assert report["device_count"] >= 1 and report["device_kind"]
        assert codec_tpu._on_tpu() is False

    def test_compile_cache_placed_from_outside_or_fixed(self, monkeypatch):
        import jax

        from seaweedfs_tpu.ec import compile_cache

        # unset: one fixed path inside the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        keys = (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
        )
        was = [getattr(jax.config, k) for k in keys]
        try:
            assert compile_cache.place_compile_cache() == compile_cache.DEFAULT_DIR
            assert (
                jax.config.jax_compilation_cache_dir == compile_cache.DEFAULT_DIR
            )
        finally:
            for k, v in zip(keys, was):
                jax.config.update(k, v)
        assert compile_cache.DEFAULT_DIR.endswith("/.jax_cache")
        # set: JAX's own handling of it stands, the code sets nothing
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        monkeypatch.setattr(
            jax.config, "update", lambda *a: pytest.fail(f"set {a}")
        )
        assert compile_cache.place_compile_cache() == "/somewhere/else"
