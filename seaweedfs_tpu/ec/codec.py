"""Reed-Solomon codec: the `reedsolomon.Encoder` capability surface.

Mirrors the semantics the reference relies on (ec_encoder.go:173
`enc.Encode`, store_ec.go:364 `enc.ReconstructData`, rebuild loop
`enc.Reconstruct` at ec_encoder.go:227-281):

  encode(shards)            fill parity shards k..n-1 from data 0..k-1
  reconstruct(shards)       rebuild ALL missing shards (None entries)
  reconstruct_data(shards)  rebuild only missing DATA shards
  verify(shards)            recompute parity, compare

Shards are equal-length 1-D uint8 numpy arrays (missing = None). The
byte math runs through a pluggable backend:

  "cpu"     numpy LUT-gather XOR loops — bit-exact reference
  "native"  SIMD C shim (native/gf256.c, PSHUFB nibble tables) — the
            klauspost/reedsolomon-AVX2 role for plain hosts
  "tpu"     JAX SWAR/bitsliced kernels (codec_tpu.py)

All produce byte-identical output (tested against each other and
against the code-matrix algebra in gf256.py).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import numpy as np

from seaweedfs_tpu.ec import gf256

DATA_SHARDS = 10
PARITY_SHARDS = 4
TOTAL_SHARDS = DATA_SHARDS + PARITY_SHARDS

# backend name -> apply_matrix(matrix [R,C] u8, inputs [C,N] u8) -> [R,N] u8
_BACKENDS: dict[str, Callable[[np.ndarray, np.ndarray], np.ndarray]] = {}


def register_backend(
    name: str, fn: Callable[[np.ndarray, np.ndarray], np.ndarray]
) -> None:
    _BACKENDS[name] = fn


def cpu_apply_matrix(matrix: np.ndarray, inputs: np.ndarray) -> np.ndarray:
    """out[r] = XOR_c MUL[m[r,c]]·inputs[c] — vectorized LUT gathers."""
    r, c = matrix.shape
    assert inputs.shape[0] == c
    out = np.zeros((r, inputs.shape[1]), dtype=np.uint8)
    for ci in range(c):
        col = inputs[ci]
        for ri in range(r):
            coef = matrix[ri, ci]
            if coef == 0:
                continue
            if coef == 1:
                out[ri] ^= col
            else:
                out[ri] ^= gf256.MUL_TABLE[coef][col]
    return out


register_backend("cpu", cpu_apply_matrix)


# --- default-backend selection (the `ec.codec` config key) -----------------
#
# An explicit backend= argument always wins (servers thread their
# -ec.codec flag down through Store → DiskLocation → EcVolume). When no
# backend is given, the WEED_EC_CODEC env var (viper idiom for
# `ec.codec`) decides; otherwise auto-detect: tpu when an accelerator
# device is actually attached, else the native SIMD shim when it
# builds, else numpy (which beats XLA-on-CPU for this workload). All
# backends are byte-identical; selection is purely a performance
# choice, so a process-wide cached default is safe.
#
# Auto-detect INITIALISES the accelerator (jax.devices()), and a chip
# belongs to one process at a time: only the daemon that runs EC math
# (the volume server) may resolve the default. Admin-side processes
# (shell, upload, benchmark, filer, s3) name host_backend() explicitly.

_default_backend = ""  # "" = undecided; resolved lazily
_LAZY_BACKENDS = ("tpu", "native")  # registered on first resolve


def host_backend() -> str:
    """The best codec that never touches an accelerator: the native
    SIMD shim when it builds, else numpy."""
    try:
        from seaweedfs_tpu.ec import codec_native  # noqa: F401

        return "native"
    except ImportError:
        return "cpu"


def default_backend() -> str:
    global _default_backend
    import os

    env = os.environ.get("WEED_EC_CODEC", "").strip().lower()
    if env:
        if env not in _LAZY_BACKENDS and env not in _BACKENDS:
            raise ValueError(
                f"WEED_EC_CODEC={env!r} is not a known EC backend "
                f"(expected one of: cpu, native, tpu)"
            )
        return env
    if not _default_backend:
        try:
            import jax
        except ImportError:
            jax = None  # no JAX installed: the one thing that means "no accelerator"
        # anything jax.devices() raises (the chip could not be
        # initialised, e.g. another process holds it) propagates: a
        # silent host codec on a TPU host would hide the device
        if jax is not None and any(d.platform != "cpu" for d in jax.devices()):
            _default_backend = "tpu"
        else:
            _default_backend = host_backend()
    return _default_backend


class ReedSolomon:
    """Systematic RS(k, p) codec over GF(2^8), reference-field-compatible."""

    def __init__(
        self,
        data_shards: int = DATA_SHARDS,
        parity_shards: int = PARITY_SHARDS,
        backend: str | None = None,
    ):
        backend = backend or default_backend()
        if data_shards <= 0 or parity_shards <= 0:
            raise ValueError("shard counts must be positive")
        if data_shards + parity_shards > 256:
            raise ValueError("too many shards for GF(2^8)")
        self.data_shards = data_shards
        self.parity_shards = parity_shards
        self.total_shards = data_shards + parity_shards
        self.matrix = gf256.build_code_matrix(data_shards, self.total_shards)
        self.parity_rows = self.matrix[data_shards:].copy()
        self._backend_name = backend
        self._apply = self._resolve_backend(backend)
        # schedule optimization (ec/schedule.py): the numpy backend's
        # naive per-entry LUT chain is replaced by a precompiled
        # coefficient-grouped + pair-CSE'd XOR/mul program, compiled
        # here per (k,m) and reused by encode, rebuild and degraded
        # decode (they all route through self._apply). Byte-identical;
        # WEED_EC_SCHEDULE=0 is the kill switch restoring the naive
        # chain. The native/tpu backends keep their own realizations
        # (the SWAR kernel builder runs the same CSE pass device-side).
        from seaweedfs_tpu.ec import schedule as _schedule

        self.scheduled = backend == "cpu" and _schedule.schedule_enabled()
        if self.scheduled:
            self._apply = _schedule.scheduled_apply_matrix
            _schedule.compile_schedule(self.parity_rows)
        # cache: survivor-row tuple -> decode matrix (invert is host-side
        # 14x14 work; reuse across blocks of a streaming rebuild)
        self._decode_cache: dict[tuple[int, ...], np.ndarray] = {}
        # cache: (survivors, targets) -> decode ROWS — the per-target
        # slice every caller of gf256.decode_rows wants; one home so
        # the degraded read path and the stream rebuild driver don't
        # each grow their own (GIL-atomic dict ops; a racing recompute
        # is benign and identical)
        self._decode_rows_cache: dict[tuple, np.ndarray] = {}

    @staticmethod
    def _resolve_backend(name: str) -> Callable[[np.ndarray, np.ndarray], np.ndarray]:
        if name == "tpu":
            # lazy import so CPU-only users never touch jax
            from seaweedfs_tpu.ec import codec_tpu

            # initialise the backend now and say (once per process)
            # which platform and kernel arm "tpu" means here
            codec_tpu.device_report()
        if name == "native" and "native" not in _BACKENDS:
            from seaweedfs_tpu.ec import codec_native  # noqa: F401
        try:
            return _BACKENDS[name]
        except KeyError:
            raise ValueError(
                f"unknown EC backend {name!r}; registered: {sorted(_BACKENDS)}"
            ) from None

    # --- helpers ---
    def _check_shards(
        self, shards: Sequence[Optional[np.ndarray]], allow_missing: bool
    ) -> int:
        if len(shards) != self.total_shards:
            raise ValueError(
                f"expected {self.total_shards} shards, got {len(shards)}"
            )
        size = None
        present = 0
        for s in shards:
            if s is None:
                if not allow_missing:
                    raise ValueError("missing shard")
                continue
            present += 1
            if s.dtype != np.uint8 or s.ndim != 1:
                raise ValueError("shards must be 1-D uint8 arrays")
            if size is None:
                size = s.shape[0]
            elif s.shape[0] != size:
                raise ValueError("shards must all be the same length")
        if size is None or size == 0:
            raise ValueError("no shard data")
        return present

    # --- Encoder surface ---
    def encode(self, shards: list[Optional[np.ndarray]]) -> list[np.ndarray]:
        """Fill shards[k..n-1] with parity computed from shards[0..k-1]."""
        k = self.data_shards
        if len(shards) != self.total_shards:
            raise ValueError(f"expected {self.total_shards} shards")
        data = [s for s in shards[:k]]
        if any(s is None for s in data):
            raise ValueError("all data shards required for encode")
        stacked = np.stack(data)  # [k, N]
        parity = self._apply(self.parity_rows, stacked)
        for i in range(self.parity_shards):
            shards[k + i] = parity[i]
        return shards  # type: ignore[return-value]

    def parity_with_crc(
        self, stacked: np.ndarray
    ) -> tuple[np.ndarray, list[int]]:
        """([p, N] parity, [k+p] CRC-32C per shard row) for one [k, N]
        data tile — the HOST side of the fused-CRC stage contract the
        streaming pipeline's device kernels implement on-chip
        (ec/crc_kernel.py): every stage pair hands the writer pool
        (shard bytes, crc) pairs so nothing downstream re-reads the
        bytes to checksum them. Byte- and CRC-identical to the device
        pairs (enforced by tests)."""
        from seaweedfs_tpu.util.crc import crc32c

        parity = self._apply(self.parity_rows, stacked)
        crcs = [crc32c(stacked[i].tobytes()) for i in range(self.data_shards)]
        crcs += [crc32c(parity[i].tobytes()) for i in range(self.parity_shards)]
        return parity, crcs

    def verify(self, shards: Sequence[np.ndarray]) -> bool:
        self._check_shards(shards, allow_missing=False)
        k = self.data_shards
        stacked = np.stack(shards[:k])
        parity = self._apply(self.parity_rows, stacked)
        for i in range(self.parity_shards):
            if not np.array_equal(parity[i], shards[k + i]):
                return False
        return True

    def _decode_matrix(self, survivors: tuple[int, ...]) -> np.ndarray:
        m = self._decode_cache.get(survivors)
        if m is None:
            sub = gf256.sub_matrix_for_survivors(self.matrix, list(survivors))
            m = gf256.mat_inv(sub)
            self._decode_cache[survivors] = m
        return m

    def decode_rows(
        self, survivors: tuple[int, ...], targets: tuple[int, ...]
    ) -> np.ndarray:
        """Cached [len(targets), k] matrix rebuilding `targets` (data or
        parity) from `survivors` — apply it to the stacked survivor
        tile with `self._apply`."""
        key = (tuple(survivors), tuple(targets))
        rows = self._decode_rows_cache.get(key)
        if rows is None:
            rows = gf256.decode_rows(self.matrix, key[0], key[1])
            if len(self._decode_rows_cache) > 512:
                self._decode_rows_cache.clear()  # bound, rarely hit
            self._decode_rows_cache[key] = rows
        return rows

    def reconstruct(
        self, shards: list[Optional[np.ndarray]], data_only: bool = False
    ) -> list[np.ndarray]:
        """Rebuild missing (None) shards in place.

        Matches the reference library: needs ≥ k present shards; with
        data_only, parity shards are left as None if missing.
        """
        k = self.data_shards
        present = self._check_shards(shards, allow_missing=True)
        missing = [i for i, s in enumerate(shards) if s is None]
        if not missing:
            return shards  # type: ignore[return-value]
        if present < k:
            raise ValueError(
                f"too few shards to reconstruct: {present} of {k} required"
            )

        survivors = tuple(i for i, s in enumerate(shards) if s is not None)[:k]
        stacked = np.stack([shards[i] for i in survivors])  # [k, N]

        missing_data = [i for i in missing if i < k]
        if missing_data:
            decode = self._decode_matrix(survivors)
            rows = decode[np.array(missing_data, dtype=np.intp)]
            rebuilt = self._apply(rows, stacked)
            for j, i in enumerate(missing_data):
                shards[i] = rebuilt[j]

        if not data_only:
            missing_parity = [i for i in missing if i >= k]
            if missing_parity:
                data_stacked = np.stack(shards[:k])  # all data now present
                rows = self.matrix[np.array(missing_parity, dtype=np.intp)]
                rebuilt = self._apply(rows, data_stacked)
                for j, i in enumerate(missing_parity):
                    shards[i] = rebuilt[j]
        return shards  # type: ignore[return-value]

    def reconstruct_data(
        self, shards: list[Optional[np.ndarray]]
    ) -> list[Optional[np.ndarray]]:
        return self.reconstruct(shards, data_only=True)


def new_encoder(
    data_shards: int = DATA_SHARDS,
    parity_shards: int = PARITY_SHARDS,
    backend: str | None = None,
) -> ReedSolomon:
    """Factory mirroring reedsolomon.New(10, 4) (ec_encoder.go:193).

    backend=None picks the process default (`ec.codec` config): tpu on
    hosts with a JAX device, cpu otherwise."""
    return ReedSolomon(data_shards, parity_shards, backend)
