"""Device-side CRC32-C over u32-lane shard streams.

The streaming encode pipeline produces parity tiles ON DEVICE; the
host used to fetch the bytes and run the table CRC over them again
before the writer pool could stamp checksums — a second full pass over
every parity byte. This module folds the Castagnoli accumulation into
the same jitted program as the codec kernel, so a dispatch returns
(parity, per-row CRC) and the host never re-touches the bytes.

The trick is the same GF(2)-linearity the bitsliced codec kernels
lean on: with the init/final-xor constants stripped, a CRC register is
a linear function of the message bits, so the raw CRC of a row of n
uint32 lanes is ``XOR_i Z_{4(n-1-i)}(lane(x_i))`` — `lane` the [32,32]
bit-matrix of one 4-byte message, Z_k the k-zero-byte register transit
(util/crc) — and ANY grouping of that sum is valid. The fold groups it
by blocks:

  * the row is viewed as [K, L] (L = min(128, n) lanes, a reshape of a
    contiguous row) and the raw CRC of every block is ONE int8 matmul:
    the block's bits, [32, K, L], against a host-built [32, L, 32]
    operator whose slice for lane l is `lane` composed with the shift
    past the L-1-l lanes behind it (_block_bitmat). The contraction runs
    over (bit, lane) with the lane minor, so each bit plane is an
    elementwise shift of the [K, 128] tiles the row already lies in and
    nothing is transposed;
  * the K block CRCs combine by CONTIGUOUS halves,
    `Z_{h*span}(c[:h]) ^ c[h:]` for h = K/2 .. 1 (`Z_a(u) ^ v` joins any
    two partial sums whose spans lie a bytes apart): log2(K) rounds over
    at most a few thousand words;
  * the init/final-xor constants re-enter as a single per-length XOR.

No slice has a stride, and none may: halving the LANE axis with
`c[..., 0::2]` / `c[..., 1::2]` (eighteen rounds for a 1 MiB row) is
the same algebra, but a stride along the minor axis is a gather on the
TPU — 72 of them in the lowering at [14, 262144], each a `kCustom`
fusion that re-lays the tile out — and those gathers were the device's
whole burst: 4.14 ms a 14 MiB tile against 0.233 ms for this form,
where the SWAR kernel beside it takes 0.03 (PERF.md section 6, PR 31).

Everything is ordinary XLA (int8 matmul + bit packing, the
apply_matrix_bits idiom) — no Pallas, so it lowers on CPU and TPU with
bit-identical results to util/crc.crc32c, which the tests enforce.

Shape contract: lane counts must be a power of two (every stream tile
the drivers dispatch is; odd tails fall back to the host table CRC in
the driver). Leading axes pass through.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from seaweedfs_tpu.ec.compile_cache import place_compile_cache
from seaweedfs_tpu.util import crc as _crc

place_compile_cache()


def crc_supported(nbytes: int) -> bool:
    """True when the device kernel serves a row of `nbytes` stream
    bytes: whole u32 lanes, power-of-two lane count."""
    if nbytes <= 0 or nbytes % 4:
        return False
    n32 = nbytes // 4
    return n32 & (n32 - 1) == 0


def _raw_transit(data: bytes, reg: int) -> int:
    """CRC register after processing `data` starting from `reg` (the
    init/final-xor constants of crc32c stripped off)."""
    return _crc.crc32c(data, reg ^ 0xFFFFFFFF) ^ 0xFFFFFFFF


@functools.lru_cache(maxsize=1)
def _lane_cols() -> tuple[int, ...]:
    """Columns of the lane operator: raw CRC of the 4-byte
    little-endian message holding lane bit b (the numpy
    ``.view(np.uint32)`` packing the SWAR kernels use)."""
    return tuple(
        _raw_transit((1 << b).to_bytes(4, "little"), 0) for b in range(32)
    )


def _bitmat(cols) -> np.ndarray:
    """32-column operator -> [32(in), 32(out)] int8 bit-matrix for the
    device-side matmul apply."""
    m = np.zeros((32, 32), dtype=np.int8)
    for b, c in enumerate(cols):
        for j in range(32):
            m[b, j] = (c >> j) & 1
    return m


@functools.lru_cache(maxsize=128)
def _shift_bitmat(nbytes: int) -> np.ndarray:
    """Bit-matrix of Z_nbytes (advance a raw CRC past nbytes zero
    bytes), host-built by operator squaring."""
    return _bitmat(_crc._zero_shift_cols(nbytes))


@functools.lru_cache(maxsize=128)
def _final_const(nbytes: int) -> int:
    """crc32c(M) = crc_raw0(M) ^ _final_const(len(M)): the init state
    pushed through the message length, plus the final xor."""
    return _crc._gf2_apply(
        _crc._zero_shift_cols(nbytes), 0xFFFFFFFF
    ) ^ 0xFFFFFFFF if nbytes else 0


# lanes folded by the block matmul: one (8, 128) tile row of uint32
_BLOCK_LANES = 128


@functools.lru_cache(maxsize=8)
def _block_bitmat(lanes: int) -> np.ndarray:
    """[32(bit), lanes, 32(out)] int8 operator taking the bits of a block
    of `lanes` consecutive uint32 lanes to the block's raw CRC: slice l
    is the lane matrix followed by Z past the lanes-1-l lanes behind."""
    z4 = _shift_bitmat(4).astype(np.int32)
    op = _bitmat(_lane_cols()).astype(np.int32)
    out = np.empty((32, lanes, 32), dtype=np.int8)
    for lane in range(lanes - 1, -1, -1):
        out[:, lane, :] = op
        op = (op @ z4) & 1
    return out


_BIT_IDX = np.arange(32, dtype=np.uint32)


def _pack_bits(acc: jnp.ndarray) -> jnp.ndarray:
    """[..., 32] int32 matmul sums -> [...] uint32 of their parities."""
    return jnp.sum(
        (acc & 1).astype(jnp.uint32) << jnp.asarray(_BIT_IDX), axis=-1
    )


def _apply_bits(x: jnp.ndarray, m_bits: jnp.ndarray) -> jnp.ndarray:
    """Apply a [32,32] bit-matrix operator to every uint32 in x
    (elementwise over leading dims): unpack, int8 matmul, repack."""
    shifts = jnp.asarray(_BIT_IDX)
    bits = ((x[..., None] >> shifts) & jnp.uint32(1)).astype(jnp.int8)
    acc = jax.lax.dot_general(
        bits,
        m_bits,
        (((bits.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return _pack_bits(acc)


def _block_crcs(x_u32: jnp.ndarray, lanes: int) -> jnp.ndarray:
    """[..., n32] uint32 -> [..., n32 // lanes] raw CRC of each block of
    `lanes` consecutive lanes: bit planes [..., 32, K, lanes] against
    _block_bitmat, contracted over (bit, lane) in one dot_general."""
    blocks = x_u32.reshape(x_u32.shape[:-1] + (-1, lanes))
    shifts = jnp.asarray(_BIT_IDX)[:, None, None]
    bits = ((blocks[..., None, :, :] >> shifts) & jnp.uint32(1)).astype(jnp.int8)
    acc = jax.lax.dot_general(
        bits,
        jnp.asarray(_block_bitmat(lanes)),
        (((bits.ndim - 3, bits.ndim - 1), (0, 1)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return _pack_bits(acc)


def _fold_halves(c: jnp.ndarray, span: int) -> jnp.ndarray:
    """[..., K] raw CRCs of K consecutive segments of `span` bytes ->
    [...] raw CRC of the whole: K halves with Z over the half behind."""
    while c.shape[-1] > 1:
        h = c.shape[-1] // 2
        z = jnp.asarray(_shift_bitmat(h * span))
        c = _apply_bits(c[..., :h], z) ^ c[..., h:]
    return c[..., 0]


def crc_lin_rows(x_u32: jnp.ndarray) -> jnp.ndarray:
    """[..., n32] uint32 lanes -> [...] uint32 RAW (zero-init, no final
    xor) CRC of each row's 4*n32 bytes. The linear form — what crosses
    mesh devices, because raw CRCs of stream segments compose with the
    Z shift alone (mesh_codec's stripe-axis fold)."""
    n32 = x_u32.shape[-1]
    if n32 & (n32 - 1):
        raise ValueError(f"lane count {n32} is not a power of two")
    lanes = min(_BLOCK_LANES, n32)
    return _fold_halves(_block_crcs(x_u32, lanes), 4 * lanes)


def finalize_rows(lin: jnp.ndarray, nbytes: int) -> jnp.ndarray:
    """Raw row CRCs -> standard crc32c values for rows of `nbytes`."""
    return lin ^ jnp.uint32(_final_const(nbytes))


def crc32c_rows(x_u32: jnp.ndarray) -> jnp.ndarray:
    """[..., n32] uint32 lanes -> [...] uint32 standard CRC-32C of each
    row's bytes — bit-identical to util/crc.crc32c on the same bytes."""
    return finalize_rows(crc_lin_rows(x_u32), x_u32.shape[-1] * 4)
