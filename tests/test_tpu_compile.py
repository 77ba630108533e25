"""The main path's device programs compile for the real chip.

Interpret mode and the CPU bit-matmul arm cannot see what the TPU's
compiler refuses (tiling, VMEM, partitioning), and a chip run costs
budget. The compiler is installed here and compiles for a chip that is
described, not attached — so each program the stream and mesh drivers
dispatch is lowered and compiled for "TPU v5 lite" at the width it
really runs at (DEFAULT_TILE_BYTES = 1 MiB per shard row = 262144 u32
lanes). Nothing executes: a compile that passes is not a chip run
(chip_smoke.py is).

The topology is described inside a module-scoped fixture — never at
import, in a skipif or in a parametrize argument — so every xdist
worker collects the same tests and only the worker handed this file
loads the TPU library; everything compiles in this process.
"""

import numpy as np
import pytest

from seaweedfs_tpu.ec import ec_stream

TILE_LANES = 262144  # ec_stream.DEFAULT_TILE_BYTES // 4


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it off around these
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — whatever the plugin raises
            jax.config.update("jax_enable_compilation_cache", cache_was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer code that asks _on_tpu() — it sees this process's CPU
    backend — onto the branch it takes on the chip."""
    from seaweedfs_tpu.ec import codec_tpu

    monkeypatch.setattr(codec_tpu, "_on_tpu", lambda: True)


def _compiled_text(fn, *shapes) -> str:
    import jax

    return jax.jit(fn).lower(*shapes).compile().as_text()


def _u32(shape, sharding):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def _parity_rows():
    from seaweedfs_tpu.ec import gf256

    return gf256.build_code_matrix(10, 14)[10:]


# --- the single-chip programs ------------------------------------------------


@pytest.mark.parametrize("lanes", [TILE_LANES, 16384])
def test_swar_apply(one_chip, lanes):
    """[10, 16384] is the 64 KiB floor where tpu_apply_matrix switches
    from the bit-matmul to the SWAR kernel (_SWAR_MIN_BYTES)."""
    from seaweedfs_tpu.ec import codec_tpu

    rows = _parity_rows()
    text = _compiled_text(
        lambda x: codec_tpu.swar_apply_matrix_u32(rows, x),
        _u32((10, lanes), one_chip),
    )
    assert "tpu_custom_call" in text


def test_encode_u32_crc(one_chip, on_tpu):
    """The stream encode driver's fused stage (ec_stream._tpu_encode_fns)."""
    from seaweedfs_tpu.ec.codec_tpu import TpuCodecKernels

    kern = TpuCodecKernels()
    text = _compiled_text(kern.encode_u32_crc, _u32((10, TILE_LANES), one_chip))
    assert "tpu_custom_call" in text


def test_reconstruct_u32_one_target(one_chip):
    from seaweedfs_tpu.ec.codec_tpu import TpuCodecKernels

    kern = TpuCodecKernels()
    survivors = tuple(range(1, 11))
    text = _compiled_text(
        lambda x: kern.reconstruct_u32(survivors, (0,), x),
        _u32((10, TILE_LANES), one_chip),
    )
    assert "tpu_custom_call" in text


def test_reconstruct_u32_crc_four_data_targets(one_chip, on_tpu):
    """The stream rebuild driver's fused stage at its worst case: all
    four losses are data shards (the inverted-matrix decode)."""
    from seaweedfs_tpu.ec.codec_tpu import TpuCodecKernels

    kern = TpuCodecKernels()
    survivors = tuple(range(4, 14))
    text = _compiled_text(
        lambda x: kern.reconstruct_u32_crc(survivors, (0, 1, 2, 3), x),
        _u32((10, TILE_LANES), one_chip),
    )
    assert "tpu_custom_call" in text


def test_swar_apply_batch(one_chip):
    from seaweedfs_tpu.ec import codec_tpu

    rows = _parity_rows()
    text = _compiled_text(
        lambda x: codec_tpu.swar_apply_matrix_u32_batch(rows, x),
        _u32((4, 10, TILE_LANES), one_chip),
    )
    assert "tpu_custom_call" in text


def test_swar_verify_batch(one_chip):
    from seaweedfs_tpu.ec import codec_tpu

    rows = _parity_rows()
    text = _compiled_text(
        lambda x, p: codec_tpu.swar_verify_matrix_u32_batch(rows, x, p),
        _u32((4, 10, TILE_LANES), one_chip),
        _u32((4, 4, TILE_LANES), one_chip),
    )
    assert "tpu_custom_call" in text


def test_apply_matrix_bits(one_chip):
    """The bit-matmul arm small degraded-read intervals take: plain
    XLA, no Pallas kernel."""
    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ec import codec_tpu

    bits = codec_tpu.gf_matrix_to_bits(_parity_rows())
    text = _compiled_text(
        lambda x: codec_tpu.apply_matrix_bits(bits, x),
        jax.ShapeDtypeStruct((10, 1 << 20), jnp.uint8, sharding=one_chip),
    )
    assert "tpu_custom_call" not in text


# --- the mesh programs of the batch drivers ----------------------------------


def _mesh_codec(topo, vol: int, stripe: int):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from seaweedfs_tpu.parallel import MeshCodec
    from seaweedfs_tpu.parallel.mesh_codec import STRIPE_AXIS, VOL_AXIS

    mesh = Mesh(
        np.array(topo.devices[: vol * stripe]).reshape(vol, stripe),
        (VOL_AXIS, STRIPE_AXIS),
    )
    codec = MeshCodec(mesh)
    assert codec.report()["arm"] == "swar"  # described devices are TPUs
    return codec, NamedSharding(mesh, P(VOL_AXIS, None, STRIPE_AXIS))


@pytest.mark.parametrize("vol,stripe", [(2, 2), (4, 1)])
def test_mesh_encode_batch_u32_crc(topo, vol, stripe):
    """One program per tile round of ec.batch; with a stripe axis the
    per-device CRCs fold through an all_gather."""
    codec, sharding = _mesh_codec(topo, vol, stripe)
    text = _compiled_text(
        codec.encode_batch_u32_crc, _u32((4, 10, TILE_LANES), sharding)
    )
    assert "tpu_custom_call" in text
    assert ("all-gather" in text) == (stripe > 1)


@pytest.mark.parametrize("vol,stripe", [(2, 2), (4, 1)])
def test_mesh_verify_batch_u32(topo, vol, stripe):
    """The fused verify kernel; the stripe-axis psum is an all-reduce."""
    codec, sharding = _mesh_codec(topo, vol, stripe)
    text = _compiled_text(
        codec.verify_batch_u32,
        _u32((4, 10, TILE_LANES), sharding),
        _u32((4, 4, TILE_LANES), sharding),
    )
    assert "tpu_custom_call" in text
    assert ("all-reduce" in text) == (stripe > 1)


# --- the names a trace reader finds them under --------------------------------

# benchmark/metrics/swar_kernel_roofline.json's own pattern: the Pallas
# kernel's event in a device trace is named by this instruction's text
SWAR_EVENT = r"%swar_apply[\w.]* = .*custom-call"


def _scoped(text: str, scope: str) -> bool:
    import re

    return re.search(rf'op_name="[^"]*/{re.escape(scope)}/', text) is not None


def test_encode_u32_crc_names(one_chip, on_tpu):
    """The compiled single-volume program: the kernel keeps the name the
    accepted roofline metric matches, and its three parts carry the
    scopes in their op_name metadata (the fusions' own names,
    `%fusion.1`, are the compiler's and move with any refactor)."""
    import re

    from seaweedfs_tpu.ec.codec_tpu import TpuCodecKernels

    text = _compiled_text(
        TpuCodecKernels().encode_u32_crc, _u32((10, TILE_LANES), one_chip)
    )
    assert re.search(SWAR_EVENT, text)
    for scope in ("ec.swar", "ec.layout", "ec.crc_fold"):
        assert _scoped(text, scope), scope


# lanes of a tile of the local rebuild's default (ISSUE 35) and of the two
# power-of-two spans its tail goes to the device as (ec_stream._pow2_spans)
REPAIR_LANES = [ec_stream.REBUILD_TILE_BYTES // (4 * k) for k in (1, 2, 4)]


@pytest.mark.parametrize("lanes", REPAIR_LANES)
def test_repair_cell_decode_program_names(one_chip, on_tpu, lanes):
    """The programs the cell `rebuild-1data` launches (ISSUE 32): the
    fused decode of shard 3 from survivors {0,1,2,4,...,10} at the local
    rebuild's default tile and at its tail's spans. The kernel keeps the
    name that benchmark/metrics/rebuild_swar_roofline.json matches (the
    encode metric's own pattern), with one output row."""
    import re

    from seaweedfs_tpu.ec.codec_tpu import TpuCodecKernels

    survivors = (0, 1, 2, 4, 5, 6, 7, 8, 9, 10)
    kern = TpuCodecKernels()
    text = _compiled_text(
        lambda x: kern.reconstruct_u32_crc(survivors, (3,), x),
        _u32((10, lanes), one_chip),
    )
    kernel = re.search(SWAR_EVENT, text)
    assert kernel and f"u32[1,{lanes}]" in kernel.group(0)
    for scope in ("ec.swar", "ec.crc_fold"):
        assert _scoped(text, scope), scope
    assert "kind=kCustom" not in text  # PR 31's fold: no gather fusion


def test_mesh_encode_batch_u32_crc_names(topo):
    import re

    codec, sharding = _mesh_codec(topo, 2, 2)
    text = _compiled_text(
        codec.encode_batch_u32_crc, _u32((4, 10, TILE_LANES), sharding)
    )
    assert re.search(SWAR_EVENT, text)
    for scope in ("ec.swar", "ec.layout", "ec.crc_fold", "ec.crc_gather"):
        assert _scoped(text, scope), scope
    gather = next(ln for ln in text.splitlines() if " all-gather(" in ln)
    assert _scoped(gather, "ec.crc_gather")


@pytest.mark.parametrize("vol,stripe", [(1, 1), (2, 2)])
def test_drive_loss_cell_decode_program_names(topo, vol, stripe):
    """The program the cell `batch-rebuild-2lost` launches a round
    (ISSUE 38): four volumes' [10, W] survivor tiles through
    reconstruct_batch_u32, shards 3 and 10 from survivors
    {0,1,2,4,...,9,11}, on the one-chip node's 1x1 mesh (and the 2x2 a
    four-chip node would provision), at the width the driver's own rule
    gives four volumes under the ring of the chip's host (ISSUE 39:
    thirteen cores, eight writers, twelve slots). The kernel keeps the
    name benchmark/metrics/rebuild_swar_roofline.json matches, with two
    output rows; a positionwise decode holds no collective."""
    import re

    lanes = ec_stream.batch_rebuild_tile_bytes(4, ec_stream._ring_slots(8)) // 4
    survivors, targets = (0, 1, 2, 4, 5, 6, 7, 8, 9, 11), (3, 10)
    codec, sharding = _mesh_codec(topo, vol, stripe)
    text = _compiled_text(
        lambda x: codec.reconstruct_batch_u32(survivors, targets, x),
        _u32((4, 10, lanes), sharding),
    )
    kernel = re.search(SWAR_EVENT, text)
    assert kernel and f"u32[{4 // vol},2,{lanes // stripe}]" in kernel.group(0)
    assert _scoped(text, "ec.swar")
    assert "all-gather" not in text and "all-reduce" not in text


def _relayouts(text: str) -> list[str]:
    """Compiled instructions that re-lay a tile out for the CRC fold: a
    gather, or the `kCustom` fusion the chip's compiler wraps one in
    (the Pallas kernels are `custom-call`s, not fusions)."""
    return [
        ln.strip()[:120]
        for ln in text.splitlines()
        if " gather(" in ln or "kind=kCustom" in ln
    ]


def test_fused_programs_hold_no_gather(topo, one_chip, on_tpu):
    """The fold by blocks, as the chip's compiler sees it: neither the
    single-volume program nor the mesh program (2x2, both axes) holds a
    gather. The strided halving it replaced compiled to 34 `kCustom`
    gather fusions a program, the device's whole burst (PERF.md, PR 31)."""
    from seaweedfs_tpu.ec.codec_tpu import TpuCodecKernels

    text = _compiled_text(
        TpuCodecKernels().encode_u32_crc, _u32((10, TILE_LANES), one_chip)
    )
    assert _relayouts(text) == []
    codec, sharding = _mesh_codec(topo, 2, 2)
    text = _compiled_text(
        codec.encode_batch_u32_crc, _u32((6, 10, TILE_LANES), sharding)
    )
    assert _relayouts(text) == []


# --- the kept programs themselves ----------------------------------------------


def test_kept_encode_program_compiles_and_keeps_its_names(
    one_chip, on_tpu, monkeypatch
):
    """The very jit object the stream encode driver launches
    (ec_stream._device_programs, taken under the chip's key): it lowers
    for the chip with the kernel and the scopes under the names the
    trace readers match, as a module named after the method — the
    counting wrapper (codec_tpu.counted_jit) leaves no name of its own."""
    import re

    from seaweedfs_tpu.ec import codec_tpu, ec_stream

    monkeypatch.setattr(ec_stream, "_KEPT", {})
    traces0 = codec_tpu.program_traces()
    program = ec_stream._device_programs().encode_u32_crc
    shape = _u32((10, TILE_LANES), one_chip)
    text = program.lower(shape).compile().as_text()
    assert codec_tpu.program_traces() - traces0 == 1
    assert text.startswith("HloModule jit_encode_u32_crc")
    assert re.search(SWAR_EVENT, text)
    for scope in ("ec.swar", "ec.layout", "ec.crc_fold"):
        assert _scoped(text, scope), scope
