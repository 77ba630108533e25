"""Batched RS(k,p) over a device mesh via shard_map.

Per-device work on TPU meshes is the SWAR Horner Pallas kernel on
u32 lanes (the same ~100 GB/s/chip fast path the single-chip tier
runs — encode_batch_u32 / reconstruct_batch_u32); CPU meshes and the
byte-layout APIs use the portable bitsliced XOR-matmul kernel
(codec_tpu.apply_matrix_bits — lowers everywhere; on a real TPU slice
XLA maps the int8 dot onto the MXU per chip). Both are byte-identical.
Shardings:

  volumes  [B, k, N]  P("vol", None, "stripe")
  parity   [B, p, N]  P("vol", None, "stripe")
  residual [B]        P("vol")  (after psum over "stripe")

Batched-encode role: the spread/encode fan-out of the reference's
shell command_ec_encode.go:153 + ec_encoder.go:173, lifted from
goroutine-per-volume to one SPMD program. Degraded-read fan-in role:
store_ec.go:344-373 (goroutine-per-shard gather + ReconstructData),
lifted to "reconstruct in one pmap" (SURVEY §2.6.5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from seaweedfs_tpu.ec.codec_tpu import (
    SCOPE_CRC_FOLD,
    SCOPE_CRC_GATHER,
    SCOPE_LAYOUT,
    SCOPE_SWAR,
    TpuCodecKernels,
    apply_matrix_bits_batch,
    apply_matrix_bits_u32_batch,
    counted_jit,
    gf_matrix_to_bits,
    swar_apply_matrix_u32_batch,
    swar_verify_matrix_u32_batch,
)

VOL_AXIS = "vol"
STRIPE_AXIS = "stripe"


def make_mesh(
    devices: list | None = None, stripe: int | None = None
) -> Mesh:
    """Build a (vol × stripe) mesh over `devices` (default: all).

    stripe=None picks 2 when the device count is even, else 1 — volume
    parallelism first (independent work), stripe parallelism to split
    streams too long for one device's HBM."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if stripe is None:
        stripe = 2 if n % 2 == 0 else 1
    if n % stripe:
        raise ValueError(f"{n} devices do not split into stripe={stripe}")
    return Mesh(
        np.array(devices).reshape(n // stripe, stripe), (VOL_AXIS, STRIPE_AXIS)
    )


class MeshCodec:
    """RS(k,p) batched encode / rebuild / verify over a Mesh.

    The jitted programs live ON the instance (cached properties, and
    _sharded_u32_cache per coefficient matrix), each counting its traces
    (codec_tpu.counted_jit): a caller that wants a program traced once
    keeps its MeshCodec. The batch drivers' own
    (ec_stream._default_mesh_codec) lives as long as the process, and
    with it _sharded_u32_cache and _decode_bits_dev: one entry per
    distinct (survivors, targets) set batch-rebuilt on this mesh, at
    most 1,470 for RS(10,4) with every survivor local (the missing set
    fixes the survivors), each one compiled program or a 2.6 KB
    bit-matrix on the device. Use after construction takes no lock: two
    threads that miss a cache at once both fill it with the same thing."""

    def __init__(self, mesh: Mesh, data_shards: int = 10, parity_shards: int = 4):
        self.mesh = mesh
        self.data_shards = data_shards
        self.parity_shards = parity_shards
        self.total_shards = data_shards + parity_shards
        # single-chip kernels own the code matrix and the decode-row
        # bit-matrix construction; MeshCodec lifts them over the mesh
        # and keeps its own device-array cache (jnp.asarray per call
        # would re-upload the bit-matrix host->device every rebuild)
        self._kern = TpuCodecKernels(data_shards, parity_shards)
        self.matrix = self._kern.matrix
        self._parity_bits = self._kern.encode_bits
        self._decode_bits_dev: dict[tuple[int, ...], jnp.ndarray] = {}
        self.block_sharding = NamedSharding(mesh, P(VOL_AXIS, None, STRIPE_AXIS))
        self.vol_sharding = NamedSharding(mesh, P(VOL_AXIS))
        # fast path per device: the SWAR Horner Pallas kernel lowers
        # only via Mosaic-TPU, so it serves TPU meshes; CPU meshes
        # (tests, the driver's virtual-device dryrun) fall back to the
        # byte-identical bit-matmul. _swar_interpret=True forces the
        # SWAR kernel through the Pallas interpreter on CPU meshes —
        # minutes-slow at real sizes, for equality tests only.
        self._tpu_mesh = all(
            getattr(d, "platform", "cpu") == "tpu"
            for d in np.asarray(mesh.devices).flat
        )
        self._swar_interpret = False
        self._sharded_u32_cache: dict[bytes, object] = {}

    # --- sharding helpers ---
    def shard_volumes(self, host_volumes: np.ndarray) -> jnp.ndarray:
        """[B, C, N] host → device array sharded P(vol, None, stripe).
        B must divide by the vol axis, N by the stripe axis."""
        return jax.device_put(host_volumes, self.block_sharding)

    def devices_holding(self, sharded: jnp.ndarray) -> int:
        """How many devices hold a non-empty part of `sharded`."""
        return len(
            {s.device for s in sharded.addressable_shards if s.data.size}
        )

    def report(self) -> dict:
        """The mesh's shape, its devices and the u32 kernel arm they
        select (see _swar_tier), for the batch drivers' stats."""
        first = np.asarray(self.mesh.devices).flat[0]
        return {
            "vol": self.mesh.shape[VOL_AXIS],
            "stripe": self.mesh.shape[STRIPE_AXIS],
            "platform": first.platform,
            "device_kind": first.device_kind,
            "arm": "swar" if self._swar_tier()[0] else "bit-matmul",
        }

    # --- batched encode ---
    @functools.cached_property
    def _encode_sharded(self):
        def per_device(bits, vols):  # vols [Bb, k, Nb]
            return apply_matrix_bits_batch(bits, vols)

        fn = shard_map(
            per_device,
            mesh=self.mesh,
            in_specs=(P(), P(VOL_AXIS, None, STRIPE_AXIS)),
            out_specs=P(VOL_AXIS, None, STRIPE_AXIS),
        )
        return counted_jit(fn)

    def _swar_ok(self, n_bytes: int) -> bool:
        """True when the byte-layout APIs route through the SWAR u32
        kernel — interpret mode only (byte-identity tests). On REAL TPU
        meshes the byte APIs keep the bit-matmul tier: materializing a
        device-side u8↔u32 view around a pallas call costs a relayout
        copy whose (8,128)-tiled padding measured 12.8× the array size
        on v5e (a 2.5 GB block tried to allocate 34 GB) — byte views
        are free on the HOST (np.view), so production TPU callers use
        the *_u32 APIs end-to-end (ec_files.py serving batch path,
        verify_batch_u32) and the byte layout stays a host-edge/test
        convenience."""
        stripe = self.mesh.shape[STRIPE_AXIS]
        if n_bytes % stripe:
            return False
        per_dev = n_bytes // stripe
        return (
            self._swar_interpret
            and not self._tpu_mesh  # never device-side byte views on TPU
            and per_dev % 4 == 0
            and (per_dev // 4) % 256 == 0
        )

    def _swar_bytes_per_device(self, rows: np.ndarray):
        """One device's byte-tile apply: u8 [Bb, C, Nb] → u8 [Bb, R, Nb]
        through the SWAR u32 kernel, bitcast views at the edges. The
        single home of the byte↔u32 packing contract — encode,
        reconstruct, and verify all ride this."""
        interpret = not self._tpu_mesh

        def per_device(vols_u8):  # [Bb, C, Nb]
            b, c, nb = vols_u8.shape
            u32 = jax.lax.bitcast_convert_type(
                vols_u8.reshape(b, c, nb // 4, 4), jnp.uint32
            )
            out32 = swar_apply_matrix_u32_batch(rows, u32, interpret)
            out8 = jax.lax.bitcast_convert_type(out32, jnp.uint8)
            return out8.reshape(b, out32.shape[1], nb)

        return per_device

    def _apply_sharded_bytes(self, rows: np.ndarray):
        """Sharded byte-layout [B, C, N] u8 → [B, R, N] u8 program that
        runs the SWAR u32 kernel per device with bitcast views at the
        edges — interpret-mode only (byte-identity tests; see _swar_ok
        for why real TPU meshes keep the bit-matmul on byte layouts and
        do their fast-tier work through the *_u32 APIs)."""
        rows = np.asarray(rows, dtype=np.uint8)
        key = b"u8" + rows.tobytes() + bytes(rows.shape)
        fn = self._sharded_u32_cache.get(key)
        if fn is not None:
            return fn
        per_device = self._swar_bytes_per_device(rows)
        fn = counted_jit(
            shard_map(
                per_device,
                mesh=self.mesh,
                in_specs=P(VOL_AXIS, None, STRIPE_AXIS),
                out_specs=P(VOL_AXIS, None, STRIPE_AXIS),
                check_vma=False,
            )
        )
        self._sharded_u32_cache[key] = fn
        return fn

    def encode_batch(self, volumes: jnp.ndarray) -> jnp.ndarray:
        """volumes [B, k, N] (sharded) → parity [B, p, N] (sharded).

        Positionwise GF math: no collectives; each device encodes its
        (volume-block × stripe-block) tile independently. Production
        TPU callers use encode_batch_u32 (u32 lanes are the native
        device layout — _swar_ok); this byte-layout API runs the
        bit-matmul tier on device meshes, SWAR under interpret mode."""
        if self._swar_ok(volumes.shape[-1]):
            return self._apply_sharded_bytes(self.matrix[self.data_shards :])(
                volumes
            )
        return self._encode_sharded(self._parity_bits, volumes)

    # --- u32-lane fast path (SWAR per device on TPU meshes) ---
    def _swar_tier(self) -> tuple[bool, bool]:
        """(use_swar, interpret): the ONE u32 tier-dispatch predicate —
        SWAR Pallas kernels on TPU meshes (interpreted under the test
        flag), bit-matmul otherwise. _per_device_u32_apply (encode /
        reconstruct) and _verify_sharded_u32 (the fused verify kernel)
        both dispatch through this."""
        return (self._tpu_mesh or self._swar_interpret, not self._tpu_mesh)

    def _per_device_u32_apply(self, rows: np.ndarray):
        """u32 apply for encode/reconstruct on the _swar_tier dispatch.
        Verify does NOT build on this on the SWAR tier — it uses the
        fused recompute-compare-count kernel (_verify_sharded_u32)
        instead of recompute-then-compare."""
        rows = np.asarray(rows, dtype=np.uint8)
        use_swar, interpret = self._swar_tier()
        if use_swar:

            def per_device(vols_u32):
                with jax.named_scope(SCOPE_SWAR):
                    return swar_apply_matrix_u32_batch(rows, vols_u32, interpret)

        else:
            bits = gf_matrix_to_bits(rows)

            def per_device(vols_u32):
                with jax.named_scope(SCOPE_SWAR):
                    return apply_matrix_bits_u32_batch(
                        jnp.asarray(bits), vols_u32
                    )

        return per_device

    def _apply_sharded_u32(self, rows: np.ndarray):
        """Sharded [B, k, N32] u32 → [B, R, N32] u32 program for one
        GF coefficient matrix, cached per matrix. Per-device kernel is
        the SWAR Pallas kernel on TPU meshes (the ~4× fast path the
        single-chip tier runs), the bit-matmul elsewhere."""
        rows = np.asarray(rows, dtype=np.uint8)
        key = rows.tobytes() + bytes(rows.shape)
        fn = self._sharded_u32_cache.get(key)
        if fn is not None:
            return fn
        per_device = self._per_device_u32_apply(rows)
        fn = counted_jit(
            shard_map(
                per_device,
                mesh=self.mesh,
                in_specs=P(VOL_AXIS, None, STRIPE_AXIS),
                out_specs=P(VOL_AXIS, None, STRIPE_AXIS),
                # pallas_call's out_shape carries no varying-mesh-axes
                # annotation; the program is collective-free (positionwise
                # GF math), so the vma check adds nothing here
                check_vma=False,
            )
        )
        self._sharded_u32_cache[key] = fn
        return fn

    def encode_batch_u32(self, volumes_u32: jnp.ndarray) -> jnp.ndarray:
        """volumes [B, k, N32] uint32 (the byte stream viewed 4 bytes
        per lane, sharded P(vol, None, stripe)) → parity [B, p, N32]
        uint32 (same packing, sharded). Per-device N32 must divide the
        stripe axis and stay a multiple of 256 lanes."""
        return self._apply_sharded_u32(self.matrix[self.data_shards :])(volumes_u32)

    # --- fused encode + CRC (the streaming pipeline's batch stage) ---
    def crc_supported(self, n_bytes: int) -> bool:
        """True when the fused Castagnoli pass serves streams of
        n_bytes: whole u32 lanes per device, power-of-two lane count
        (ec/crc_kernel.py's block fold and ladder of halves)."""
        from seaweedfs_tpu.ec import crc_kernel

        stripe = self.mesh.shape[STRIPE_AXIS]
        if n_bytes % stripe:
            return False
        return crc_kernel.crc_supported(n_bytes // stripe)

    def batch_layout(self, batch: int, n_bytes: int) -> dict:
        """Per-device work split for a [batch, k, n_bytes] encode —
        the numbers the MULTICHIP dryrun asserts: volumes per device
        along 'vol', stream bytes per device along 'stripe'."""
        vol = self.mesh.shape[VOL_AXIS]
        stripe = self.mesh.shape[STRIPE_AXIS]
        if batch % vol:
            raise ValueError(f"batch {batch} does not shard {vol}-way")
        if n_bytes % stripe:
            raise ValueError(f"stream {n_bytes} does not stripe {stripe}-way")
        return {
            "vol": vol,
            "stripe": stripe,
            "devices": vol * stripe,
            "per_device_volumes": batch // vol,
            "per_device_bytes": n_bytes // stripe,
        }

    @functools.cached_property
    def _encode_crc_sharded(self):
        """Sharded fused encode+CRC program: parity per device plus the
        standard CRC-32C of every shard ROW of the full global stream.
        Per device: encode its tile, run the crc_kernel bit-matmul
        accumulation over the tile while it is VMEM/HBM-resident, then
        COMPOSE the per-device raw CRCs across the stripe axis (an
        all_gather + Z-shift fold — CRCs of stream segments combine
        linearly, util/crc) so the host receives whole-row CRCs and
        never re-touches the bytes. Data rows are checksummed too —
        they are already device-resident."""
        from seaweedfs_tpu.ec import crc_kernel

        rows = np.asarray(self.matrix[self.data_shards :], dtype=np.uint8)
        per_device_apply = self._per_device_u32_apply(rows)
        stripe = self.mesh.shape[STRIPE_AXIS]

        def per_device(vols_u32):  # [Bb, k, Nb]
            parity = per_device_apply(vols_u32)  # under SCOPE_SWAR
            with jax.named_scope(SCOPE_LAYOUT):
                full = jnp.concatenate([vols_u32, parity], axis=1)
            with jax.named_scope(SCOPE_CRC_FOLD):
                lin = crc_kernel.crc_lin_rows(full)  # [Bb, k+p] raw CRCs
            seg_bytes = full.shape[-1] * 4
            if stripe > 1:
                with jax.named_scope(SCOPE_CRC_GATHER):
                    segs = jax.lax.all_gather(lin, STRIPE_AXIS)  # [S, Bb, R]
                with jax.named_scope(SCOPE_CRC_FOLD):
                    zbits = jnp.asarray(crc_kernel._shift_bitmat(seg_bytes))
                    acc = segs[0]
                    for s in range(1, stripe):
                        acc = crc_kernel._apply_bits(acc, zbits) ^ segs[s]
                    lin = acc
            with jax.named_scope(SCOPE_CRC_FOLD):
                crcs = crc_kernel.finalize_rows(lin, seg_bytes * stripe)
            return parity, crcs

        return counted_jit(
            shard_map(
                per_device,
                mesh=self.mesh,
                in_specs=P(VOL_AXIS, None, STRIPE_AXIS),
                out_specs=(
                    P(VOL_AXIS, None, STRIPE_AXIS),
                    # the stripe fold replicates the CRCs along the
                    # stripe axis; one copy per vol block comes home
                    P(VOL_AXIS, None),
                ),
                check_vma=False,
            )
        )

    def encode_batch_u32_crc(
        self, volumes_u32: jnp.ndarray
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Fused batch encode + Castagnoli pass: [B, k, N32] uint32 →
        (parity [B, p, N32] sharded, crcs [B, k+p] uint32 — standard
        CRC-32C of every shard row's full N32*4-byte stream,
        bit-identical to util/crc.crc32c). Requires
        crc_supported(N32 * 4)."""
        if not self.crc_supported(volumes_u32.shape[-1] * 4):
            raise ValueError(
                f"stream of {volumes_u32.shape[-1]} lanes unsupported by "
                f"the fused CRC pass (per-device lanes must be a power "
                f"of two)"
            )
        return self._encode_crc_sharded(volumes_u32)

    def reconstruct_batch_u32(
        self,
        survivors: tuple[int, ...],
        targets: tuple[int, ...],
        shard_data_u32: jnp.ndarray,
    ) -> jnp.ndarray:
        """u32-lane variant of reconstruct_batch: survivor blocks
        [B, k, N32] uint32 (in `survivors` order) → rebuilt targets
        [B, len(targets), N32] uint32."""
        return self._apply_sharded_u32(
            self._kern.decode_rows_for(survivors, targets)
        )(shard_data_u32)

    # --- batched degraded rebuild ---
    def _decode_bits(
        self, survivors: tuple[int, ...], targets: tuple[int, ...]
    ) -> jnp.ndarray:
        key = survivors + (256,) + targets
        bits = self._decode_bits_dev.get(key)
        if bits is None:
            bits = jnp.asarray(self._kern.decode_bits_for(survivors, targets))
            self._decode_bits_dev[key] = bits
        return bits

    def reconstruct_batch(
        self,
        survivors: tuple[int, ...],
        targets: tuple[int, ...],
        shard_data: jnp.ndarray,
    ) -> jnp.ndarray:
        """shard_data [B, k, N] survivor blocks (in `survivors` order,
        sharded) → [B, len(targets), N] rebuilt blocks (sharded).

        The gather of surviving shards into `shard_data` rides DCN
        (gRPC shard reads); the decode is one SPMD program — the
        store_ec.go:364 ReconstructData hot path, batched."""
        if self._swar_ok(shard_data.shape[-1]):
            return self._apply_sharded_bytes(
                self._kern.decode_rows_for(survivors, targets)
            )(shard_data)
        return self._encode_sharded(self._decode_bits(survivors, targets), shard_data)

    # --- verify with a stripe-axis collective ---
    @functools.cached_property
    def _verify_sharded(self):
        def per_device(bits, vols, parity):
            # [Bb, p, Nb] recomputed on this device's tile; residual =
            # COUNT of mismatched bytes (a byte-value sum would overflow
            # int32 on the multi-MiB blocks the SWAR tier serves)
            recomputed = apply_matrix_bits_batch(bits, vols)
            local = jnp.sum(
                (recomputed != parity).astype(jnp.int32), axis=(1, 2)
            )  # [Bb]
            return jax.lax.psum(local, STRIPE_AXIS)

        fn = shard_map(
            per_device,
            mesh=self.mesh,
            in_specs=(
                P(),
                P(VOL_AXIS, None, STRIPE_AXIS),
                P(VOL_AXIS, None, STRIPE_AXIS),
            ),
            out_specs=P(VOL_AXIS),
        )
        return counted_jit(fn)

    @functools.cached_property
    def _verify_sharded_swar(self):
        recompute = self._swar_bytes_per_device(
            np.asarray(self.matrix[self.data_shards :], dtype=np.uint8)
        )

        def per_device(vols_u8, parity):
            recomputed = recompute(vols_u8)
            local = jnp.sum(
                (recomputed != parity).astype(jnp.int32), axis=(1, 2)
            )  # [Bb] — mismatched-byte count, identical to the matmul tier
            return jax.lax.psum(local, STRIPE_AXIS)

        return counted_jit(
            shard_map(
                per_device,
                mesh=self.mesh,
                in_specs=(
                    P(VOL_AXIS, None, STRIPE_AXIS),
                    P(VOL_AXIS, None, STRIPE_AXIS),
                ),
                out_specs=P(VOL_AXIS),
                check_vma=False,
            )
        )

    @functools.cached_property
    def _verify_sharded_u32(self):
        """Tier dispatch mirrors _per_device_u32_apply: on TPU meshes
        (and under the interpret test flag) the FUSED SWAR verify
        kernel — recompute, compare, and count in one pallas call, no
        HBM round-trip for the recomputed parity, which is what held
        the unfused chain to a third of the encode rate — and the
        bit-matmul recompute + XLA compare on CPU meshes."""
        rows = np.asarray(self.matrix[self.data_shards :], dtype=np.uint8)
        use_swar, interpret = self._swar_tier()
        if use_swar:

            def per_device(vols_u32, parity_u32):
                local = swar_verify_matrix_u32_batch(
                    rows, vols_u32, parity_u32, interpret
                )  # [Bb] — mismatched-LANE count (u32 lanes; 0 = verified)
                return jax.lax.psum(local, STRIPE_AXIS)

        else:
            recompute = self._per_device_u32_apply(rows)

            def per_device(vols_u32, parity_u32):
                local = jnp.sum(
                    (recompute(vols_u32) != parity_u32).astype(jnp.int32),
                    axis=(1, 2),
                )  # [Bb]
                return jax.lax.psum(local, STRIPE_AXIS)

        return counted_jit(
            shard_map(
                per_device,
                mesh=self.mesh,
                in_specs=(
                    P(VOL_AXIS, None, STRIPE_AXIS),
                    P(VOL_AXIS, None, STRIPE_AXIS),
                ),
                out_specs=P(VOL_AXIS),
                check_vma=False,
            )
        )

    def verify_batch_u32(
        self, volumes_u32: jnp.ndarray, parity_u32: jnp.ndarray
    ) -> jnp.ndarray:
        """u32-lane verify at the SWAR encode rate (pre-growth
        kernel-only record r05, ROADMAP's table: 98.7 GB/s against
        93.9 encode on one v5e chip): the fused pallas kernel recomputes
        each parity tile in VMEM, compares in register, and accumulates the
        mismatched-lane count; the psum over the stripe axis reduces
        the per-device counts. [B] int32, 0 = verified. This is the
        TPU production tier — the u32 packing is the native device
        layout (see _swar_ok). Shape contract matches encode_batch_u32:
        per-device N32 must divide the stripe axis and stay a multiple
        of 256 lanes."""
        return self._verify_sharded_u32(volumes_u32, parity_u32)

    def verify_batch(
        self, volumes: jnp.ndarray, parity: jnp.ndarray
    ) -> jnp.ndarray:
        """Per-volume mismatched-byte count between recomputed and
        given parity: [B] int32, 0 = verified. The stripe-axis psum is
        the mesh collective of the degraded-read fan-in story (§2.6.5).
        The SWAR-rate tier is verify_batch_u32; this byte-layout API
        recomputes via the bit-matmul on device meshes (_swar_ok)."""
        if self._swar_ok(volumes.shape[-1]):
            return self._verify_sharded_swar(volumes, parity)
        return self._verify_sharded(self._parity_bits, volumes, parity)
