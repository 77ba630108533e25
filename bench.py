"""RS(10,4) erasure-codec throughput on one TPU chip.

With no argument, runs the WHOLE BASELINE matrix (encode, rebuild,
batch, decode4, stream), printing one JSON line per config, e.g.:
  {"metric": "ec_encode_rs10_4", "value": <GB/s>, "unit": "GB/s",
   "vs_baseline": <value / 40.0>}
A single config name as argv[1] runs just that config.

value   = data bytes erasure-coded per second (the bytes of the sealed
          volume stream, i.e. the 10 data shards — same accounting as
          timing the reference's `ec.encode` hot loop, the
          klauspost/reedsolomon AVX2 Encode call at
          weed/storage/erasure_coding/ec_encoder.go:173).
baseline: the repo publishes no EC numbers (BASELINE.md), so the ratio
          is against the 40 GB/s/chip north-star target from
          BASELINE.json; vs_baseline >= 1.0 means target met.

Method: the TPU codec's SWAR Horner Pallas kernel
(seaweedfs_tpu/ec/codec_tpu.py) encodes a device-resident [10, n32]
uint32 volume-block stream (the byte stream viewed 4 bytes per vector
lane; a pure reinterpretation of the .dat bytes). Data is generated
on-device (no PCIe in the timed region); each timed iteration produces
the [4, n32] parity block. One fixed shape, compiled once.

Other configs (BASELINE.json):
  bench.py rebuild   single-shard rebuild kernel rate, scaled to the
                     <2 s / 30 GB volume target (config 2): rebuilding
                     shard 0 from the 10 survivors of a 30 GB volume
                     means streaming 10 x 3 GB through the decode
                     kernel; value = projected seconds, target 2 s.
  bench.py batch     config 3: batched encode over 256 sealed volumes.
                     The batched layout interleaves volumes along the
                     stream axis ([10, B*block] — the layout
                     parallel/mesh_codec.py shards P('vol',...,'stripe')
                     on a slice); one chip reports aggregate GB/s over
                     the whole batch.
  bench.py decode4   config 4: worst-case decode — all 4 missing
                     shards are data shards, so every output row needs
                     the full inverted-survivor-matrix path
                     (gf256.decode_rows over survivors 4..13).
  bench.py http      write/read req/s through the HTTP data plane via
                     the repo's own `weed benchmark` machinery — the
                     README's prose numbers, driver-tracked.
  bench.py stream    end-to-end `ec.encode` of a real on-disk volume
                     (.dat → 14 shard files) through write_ec_files
                     with the best HOST codec backend (the native
                     SIMD shim). On a TPU host the daemons route this
                     path through the ec_stream device driver instead;
                     this config does not measure that yet (ROADMAP
                     S1/S2 — chip_smoke.py proves it runs).
                     vs_baseline = speedup over the numpy "cpu"
                     backend end-to-end on the same machine (the
                     software-RS role the reference fills with
                     klauspost AVX2). The classic driver's phases
                     dict accounts for the whole wall
                     (read/encode/write/flush/loop; flush_s = kernel
                     dirty-page writeback at close, the dominant cost
                     on this host's disk — on tmpfs the same code
                     measures ~1.0 GB/s with loop_s ~7%, the serial
                     single-core framework floor). The pipelined
                     driver reports overlapped stages (read/stage/
                     device/writeback/compute/write + pipeline_depth,
                     docs/CODEC.md) whose sum can exceed wall, and
                     the serial phases head/dispatch_span/drain/
                     write_tail/flush, which sum to it
                     (docs/TRACING.md). The line also carries
                     serial_gb_s / vs_serial: the same encode through
                     the WEED_EC_PIPELINE=0 serial classic driver
                     (BENCH_r12 is the standing record).
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp


def _pipeline_disabled():
    """Context manager flipping WEED_EC_PIPELINE=0 for a serial-driver
    measurement leg, restoring the operator's prior value (incl. unset)
    on exit — the one home for the save/flip/restore dance the stream
    benches and the pipeline-identity check all need."""
    import contextlib

    @contextlib.contextmanager
    def _cm():
        prior = os.environ.get("WEED_EC_PIPELINE")
        os.environ["WEED_EC_PIPELINE"] = "0"
        try:
            yield
        finally:
            if prior is None:
                os.environ.pop("WEED_EC_PIPELINE", None)
            else:
                os.environ["WEED_EC_PIPELINE"] = prior

    return _cm()


def _chip():
    dev = jax.devices()[0]
    return dev, dev.platform != "cpu"


def _time_chain(step_body, init, iters, *consts):
    """Seconds for `iters` dependent iterations of step_body on device.

    The whole chain runs as one lax.fori_loop inside one jit: each
    iteration consumes the previous result, so no step can be elided
    or overlapped away, and a single dispatch keeps per-call launch
    latency out of the timed region. The final readback of one element
    is the completion barrier. Extra device-array operands ride as
    non-donated jit ARGUMENTS (`consts`) — closing over them would
    embed gigabytes as literals in the compiled program."""
    chain = jax.jit(
        lambda d, *cs: jax.lax.fori_loop(
            0, iters, lambda i, x: step_body(x, *cs), d
        ),
        donate_argnums=0,
    )
    copy = jax.jit(lambda a: a ^ jnp.zeros((), a.dtype))

    def trial():
        x = copy(init)
        int(jax.device_get(jnp.ravel(x)[0]))  # x materialized
        t0 = time.perf_counter()
        x = chain(x, *consts)
        int(jax.device_get(jnp.ravel(x)[0]))
        return time.perf_counter() - t0

    trial()  # compile + warm
    return min(trial() for _ in range(3))


def _gen_u32(seed: int, n32: int):
    """Device-resident [10, n32] uint32 random volume stream."""

    @jax.jit
    def gen(key):
        return jax.random.randint(
            key, (10, n32), 0, (1 << 31) - 1, dtype=jnp.int32
        ).astype(jnp.uint32)

    data = gen(jax.random.PRNGKey(seed))
    data.block_until_ready()
    return data


def _integrity_gate(kern, data, on_tpu, survivors=None, targets=None):
    """The timed kernel must match the CPU reference on a 1024-lane
    sample before its number means anything. survivors/targets=None
    checks encode parity; otherwise checks reconstruction of `targets`
    from shards `survivors` of the sample volume."""
    import numpy as np

    from seaweedfs_tpu.ec.codec import new_encoder

    sample_u32 = np.asarray(jax.device_get(data[:, :1024]))
    sample = sample_u32.view(np.uint8).reshape(10, 4096)
    rs = new_encoder(backend="cpu")
    full = rs.encode([sample[i].copy() for i in range(10)] + [None] * 4)
    if survivors is None:
        if on_tpu:
            got = np.asarray(
                jax.device_get(kern.encode_u32(jnp.asarray(sample_u32)))
            ).view(np.uint8)
        else:
            got = np.asarray(jax.device_get(kern.encode(jnp.asarray(sample))))
        want = [full[10 + i] for i in range(kern.parity_shards)]
    else:
        surv = np.stack([full[i] for i in survivors])
        if on_tpu:
            got = np.asarray(
                jax.device_get(
                    kern.reconstruct_u32(
                        survivors,
                        targets,
                        jnp.asarray(surv.view(np.uint32).reshape(10, 1024)),
                    )
                )
            ).view(np.uint8)
        else:
            got = np.asarray(
                jax.device_get(kern.reconstruct(survivors, targets, jnp.asarray(surv)))
            )
        want = [full[t] for t in targets]
    for g, w in zip(got, want):
        assert np.array_equal(g, w), (
            "bench kernel diverges from the CPU reference; refusing to "
            "publish a throughput number for wrong bytes"
        )


def _kernel_fn(kern, on_tpu, n32, survivors=None, targets=None):
    """The [10, n32] u32 → [R, n32] u32 apply for the timed step:
    the SWAR fast path on the real chip, the matmul path (same bytes)
    when falling back to CPU — Pallas interpret mode would be
    minutes-slow at any useful size."""
    shard_bytes = n32 * 4
    if survivors is None:
        if on_tpu:
            return kern.encode_u32

        def enc(d):
            u8 = jax.lax.bitcast_convert_type(d, jnp.uint8).reshape(10, shard_bytes)
            par = kern.encode(u8).reshape(kern.parity_shards, n32, 4)
            return jax.lax.bitcast_convert_type(par, jnp.uint32)

        return enc
    if on_tpu:
        return lambda d: kern.reconstruct_u32(survivors, targets, d)

    def rec(d):
        u8 = jax.lax.bitcast_convert_type(d, jnp.uint8).reshape(10, shard_bytes)
        out = kern.reconstruct(survivors, targets, u8).reshape(len(targets), n32, 4)
        return jax.lax.bitcast_convert_type(out, jnp.uint32)

    return rec


_DISK_CEILING: dict = {}


def _disk_ceiling(scratch_dir: str, mb: int = 192) -> dict:
    """Measured sequential write/read GB/s of `scratch_dir`'s
    filesystem, cached per st_dev — the hardware bar every
    `*_stream_e2e` line is judged against (an e2e GB/s number without
    it is unattributable: driver overhead and a slow disk read the
    same). Write: raw-fd 16 MiB positioned writes with the fdatasync
    INSIDE the timed region (the page cache must not impersonate the
    disk). Read: posix_fadvise(DONTNEED) drops the probe file from
    cache first; on tmpfs that is a no-op and the probe honestly
    reports memory bandwidth — which IS that filesystem's ceiling."""
    import numpy as np

    dev = os.stat(scratch_dir).st_dev
    cached = _DISK_CEILING.get(dev)
    if cached:
        return cached
    chunk = 16 * 1024 * 1024
    n = max(1, mb * 1024 * 1024 // chunk)
    buf = np.random.default_rng(3).integers(0, 256, chunk, dtype=np.uint8)
    path = os.path.join(scratch_dir, ".disk_probe")
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
    try:
        t0 = time.perf_counter()
        for i in range(n):
            os.pwritev(fd, [buf], i * chunk)
        os.fdatasync(fd)
        w_s = time.perf_counter() - t0
    finally:
        os.close(fd)
    fd = os.open(path, os.O_RDONLY)
    try:
        try:
            os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
        except OSError:
            pass
        out = np.empty(chunk, dtype=np.uint8)
        t0 = time.perf_counter()
        for i in range(n):
            os.preadv(fd, [out], i * chunk)
        r_s = time.perf_counter() - t0
    finally:
        os.close(fd)
        os.remove(path)
    res = {
        "disk_seq_write_gb_s": round(n * chunk / w_s / 1e9, 3),
        "disk_seq_read_gb_s": round(n * chunk / r_s / 1e9, 3),
    }
    _DISK_CEILING[dev] = res
    return res


def _report(
    metric: str, value: float, unit: str, vs_baseline: float, **extra
) -> None:
    out = {
        "metric": metric,
        "value": round(value, 4),
        "unit": unit,
        "vs_baseline": round(vs_baseline, 4),
    }
    out.update(extra)
    print(json.dumps(out))


def _run_chain(seed, n32, on_tpu, survivors=None, targets=None, iters_tpu=64):
    """Shared scaffolding for the four kernel configs: generate, gate,
    chain-time. Returns (elapsed_seconds, iters)."""
    from seaweedfs_tpu.ec.codec_tpu import TpuCodecKernels

    kern = TpuCodecKernels(10, 4)
    data = _gen_u32(seed, n32)
    _integrity_gate(kern, data, on_tpu, survivors, targets)
    apply_fn = _kernel_fn(kern, on_tpu, n32, survivors, targets)

    # fold one output row back into the data so each iteration depends
    # on the previous one (see _time_chain)
    def step(d):
        return d.at[0].set(d[0] ^ apply_fn(d)[0])

    iters = iters_tpu if on_tpu else 2
    return _time_chain(step, data, iters), iters


def bench_encode() -> None:
    dev, on_tpu = _chip()
    # 64 MiB per shard on the real chip (640 MiB data per step);
    # smaller when falling back to CPU so the bench stays quick.
    shard_len = (64 if on_tpu else 4) * 1024 * 1024
    elapsed, iters = _run_chain(0, shard_len // 4, on_tpu)
    gbps = 10 * shard_len * iters / elapsed / 1e9
    _report("ec_encode_rs10_4", gbps, "GB/s", gbps / 40.0)


def bench_rebuild() -> None:
    """BASELINE config 2: single-shard rebuild of a 30 GB volume.

    The kernel-side work is: 10 survivor shards x 3 GB streamed
    through the decode matrix. Measures the decode kernel on a
    64 MiB-per-shard working set and projects to the full volume
    (the streaming driver overlaps host IO; see ec/ec_stream.py).
    value = projected seconds for the 30 GB volume; target < 2 s.
    """
    dev, on_tpu = _chip()
    shard_len = (64 if on_tpu else 4) * 1024 * 1024
    survivors = tuple(range(1, 11))  # shard 0 missing, worst-ish case
    elapsed, iters = _run_chain(1, shard_len // 4, on_tpu, survivors, (0,))
    per_byte = elapsed / (iters * shard_len)  # seconds per rebuilt byte
    projected = per_byte * (30 * 1000**3 / 10)  # one shard of 30 GB
    _report("ec_rebuild_one_shard_30gb", projected, "s", 2.0 / projected)


def bench_batch() -> None:
    """BASELINE config 3: batched encode over 256 sealed volumes.

    Each volume contributes one HBM-resident block; the batch is laid
    out [10, B*block_n32] (volumes interleaved along the stream axis —
    byte position b of volume v lives at lane v*block_n32 + b/4).
    GF math is positionwise, so per-volume parity is the corresponding
    slice of the batched parity. This is exactly the layout
    parallel/mesh_codec.py shards over a Mesh ('vol' axis) on a v5e
    slice; a single chip measures the aggregate stream rate.
    """
    dev, on_tpu = _chip()
    n_volumes = 256
    # 1 MiB block per volume on the real chip (2.5 GiB batch, HBM-resident)
    block = (1024 if on_tpu else 16) * 1024
    total = n_volumes * block
    elapsed, iters = _run_chain(2, total // 4, on_tpu, iters_tpu=16)
    gbps = 10 * total * iters / elapsed / 1e9
    _report("ec_encode_batch256", gbps, "GB/s", gbps / 40.0)


def bench_decode4() -> None:
    """BASELINE config 4: worst-case decode with 4 missing shards.

    All four losses are data shards (0..3): survivors are shards 4..13
    (6 data + 4 parity) and every rebuilt row runs through the inverted
    survivor matrix — no cheap parity-only shortcut exists. Accounting
    matches bench_encode: value = volume data bytes processed per
    second (10 survivor shards in per step).
    """
    dev, on_tpu = _chip()
    shard_len = (64 if on_tpu else 4) * 1024 * 1024
    survivors = tuple(range(4, 14))
    targets = (0, 1, 2, 3)
    elapsed, iters = _run_chain(3, shard_len // 4, on_tpu, survivors, targets)
    gbps = 10 * shard_len * iters / elapsed / 1e9
    _report("ec_decode_4missing", gbps, "GB/s", gbps / 40.0)


def bench_shardmap() -> None:
    """shard_map(SWAR) through the mesh tier (parallel/mesh_codec.py)
    on one chip: the multi-chip program shape — a [B, 10, n32] volume
    batch laid out P('vol', None, 'stripe') on a 1×1 Mesh with the
    SWAR Pallas kernel per device — should cost ~nothing vs the plain
    single-chip kernel (compare with ec_encode_rs10_4 in the same
    run). On a real v5e slice the same program spreads the batch over
    the mesh; this pins the per-chip rate of that tier."""
    import numpy as np

    from seaweedfs_tpu.ec.codec import new_encoder
    from seaweedfs_tpu.parallel import MeshCodec, make_mesh

    dev, on_tpu = _chip()
    mesh = make_mesh([dev], stripe=1)
    codec = MeshCodec(mesh)
    b = 8
    shard_bytes = (8 if on_tpu else 1) * 1024 * 1024  # per volume in the batch
    n32 = shard_bytes // 4

    @jax.jit
    def gen(key):
        return jax.random.randint(
            key, (b, 10, n32), 0, (1 << 31) - 1, dtype=jnp.int32
        ).astype(jnp.uint32)

    data = gen(jax.random.PRNGKey(7))
    data.block_until_ready()

    # integrity gate: volume 0's first 4096 bytes vs the CPU reference
    sample_u32 = np.asarray(jax.device_get(data[:1, :, :1024]))
    sample = sample_u32.view(np.uint8).reshape(10, 4096)
    rs = new_encoder(backend="cpu")
    full = rs.encode([sample[i].copy() for i in range(10)] + [None] * 4)
    got = (
        np.asarray(jax.device_get(codec.encode_batch_u32(jnp.asarray(sample_u32))))
        .view(np.uint8)
        .reshape(4, 4096)
    )
    for i in range(4):
        assert np.array_equal(got[i], full[10 + i]), (
            "mesh-tier kernel diverges from the CPU reference; refusing "
            "to publish a throughput number for wrong bytes"
        )

    def step(d):
        return d.at[:, 0].set(d[:, 0] ^ codec.encode_batch_u32(d)[:, 0])

    iters = 64 if on_tpu else 2
    elapsed = _time_chain(step, data, iters)
    gbps = b * 10 * shard_bytes * iters / elapsed / 1e9
    _report("ec_encode_shardmap", gbps, "GB/s", gbps / 40.0)


def bench_shardmap_verify() -> None:
    """Mesh-tier verify (parallel/mesh_codec.verify_batch_u32) on one
    chip: recompute parity with the SWAR u32 kernel per device and psum
    the mismatched-lane count over the stripe axis — verify at the
    encode tier's rate (VERDICT r3 weak #3). u32 lanes are the TPU
    production layout: materializing byte views around a pallas call
    costs a 12.8× tiled-layout copy on v5e (mesh_codec._swar_ok).
    value = volume data bytes verified/s."""
    import numpy as np

    from seaweedfs_tpu.ec.codec import new_encoder
    from seaweedfs_tpu.parallel import MeshCodec, make_mesh

    dev, on_tpu = _chip()
    mesh = make_mesh([dev], stripe=1)
    codec = MeshCodec(mesh)
    b = 8
    shard_bytes = (8 if on_tpu else 1) * 1024 * 1024
    n32 = shard_bytes // 4

    @jax.jit
    def gen(key):
        return jax.random.randint(
            key, (b, 10, n32), 0, (1 << 31) - 1, dtype=jnp.int32
        ).astype(jnp.uint32)

    data = gen(jax.random.PRNGKey(9))
    data.block_until_ready()
    parity = codec.encode_batch_u32(data)
    parity.block_until_ready()

    # integrity gate: parity matches the CPU reference on a sample, the
    # residual is 0 on good parity and fires on corruption
    sample_u32 = np.asarray(jax.device_get(data[:1, :, :1024]))
    sample = sample_u32.view(np.uint8).reshape(10, 4096)
    rs = new_encoder(backend="cpu")
    full = rs.encode([sample[i].copy() for i in range(10)] + [None] * 4)
    got = np.asarray(jax.device_get(parity[0, :, :1024])).view(np.uint8).reshape(4, 4096)
    for i in range(4):
        assert np.array_equal(got[i], full[10 + i]), (
            "mesh verify bench: encode diverges from the CPU reference"
        )
    residual = np.asarray(jax.device_get(codec.verify_batch_u32(data, parity)))
    assert np.array_equal(residual, np.zeros(b, dtype=np.int32))

    def step(d, p):
        r = codec.verify_batch_u32(d, p)
        # fold the residual back in via a CONTIGUOUS row update: the
        # natural-looking d.at[:, 0, 0].set(...) is an 8-scalar scatter
        # that XLA implements as a full copy of the 640 MB carry each
        # iteration, and the measurement reads a third of the true rate
        return d.at[:, 0, :].set(d[:, 0, :] ^ r[:, None].astype(jnp.uint32))

    iters = 64 if on_tpu else 2
    elapsed = _time_chain(step, data, iters, parity)
    gbps = b * 10 * shard_bytes * iters / elapsed / 1e9
    _report("ec_verify_shardmap", gbps, "GB/s", gbps / 40.0)


def bench_stream() -> None:
    """End-to-end file encode: .dat → .ec00..13 via write_ec_files.

    Uses the best host backend (native SIMD if it builds, else numpy)
    — see the module docstring: the device driver is not measured
    here. Both sides report the steady-state (page-cache-warm,
    allocator-warm) best-of-N rate: cold first runs measure page
    faults, not the codec.
    """
    import os
    import tempfile

    import numpy as np

    from seaweedfs_tpu.ec import ec_files
    from seaweedfs_tpu.ec.codec import new_encoder

    def best_rate(base: str, rs, runs: int):
        size = os.path.getsize(base + ".dat")
        best, best_stats = float("inf"), {}
        for _ in range(runs):
            stats: dict = {}
            t0 = time.perf_counter()
            ec_files.write_ec_files(base, rs=rs, stats=stats)
            dt = time.perf_counter() - t0
            if dt < best:
                best, best_stats = dt, stats
        return size / best / 1e9, best_stats

    size = 256 * 1024 * 1024
    with tempfile.TemporaryDirectory() as d:
        base = os.path.join(d, "1")
        rng = np.random.default_rng(0)
        with open(base + ".dat", "wb") as f:
            for _ in range(size // (16 * 1024 * 1024)):
                f.write(
                    rng.integers(0, 256, 16 * 1024 * 1024, dtype=np.uint8).tobytes()
                )

        try:
            rs = new_encoder(backend="native")
        except (ImportError, ValueError):
            rs = new_encoder(backend="cpu")
        gbps, phases = best_rate(base, rs, runs=3)

        # the SERIAL driver on the same backend (WEED_EC_PIPELINE=0
        # kill switch — exactly what an operator flipping the knob
        # gets): the pipelined/serial ratio is the overlap win, the
        # per-stage phases above show where it comes from
        with _pipeline_disabled():
            serial_gbps, _ = best_rate(base, rs, runs=3)

        # numpy-backend baseline on a 32 MiB prefix (it is ~40x slower;
        # rate is size-independent at these scales), same warm protocol
        cpu_base = os.path.join(d, "2")
        with open(base + ".dat", "rb") as src, open(cpu_base + ".dat", "wb") as dst:
            dst.write(src.read(32 * 1024 * 1024))
        cpu_gbps, _ = best_rate(cpu_base, new_encoder(backend="cpu"), runs=2)
        ceiling = _disk_ceiling(d)

    _report(
        "ec_encode_stream_e2e",
        gbps,
        "GB/s",
        gbps / cpu_gbps,
        phases=phases,
        serial_gb_s=round(serial_gbps, 4),
        vs_serial=round(gbps / serial_gbps, 4),
        **ceiling,
    )


def bench_stream_rebuild() -> None:
    """End-to-end single-shard rebuild of a real on-disk EC volume:
    delete .ec00, rebuild it from the 10 survivors through the
    threaded stream_rebuild_ec_files driver with the best host codec
    backend (like bench_stream, not the device driver). value = volume data bytes (10 survivor shards in) per
    second; vs_baseline = speedup over the numpy "cpu" backend on the
    same machine — the software-RS role the reference fills with
    klauspost AVX2 in RebuildEcFiles (ec_encoder.go:227-281)."""
    import tempfile

    import numpy as np

    from seaweedfs_tpu.ec import ec_files, ec_stream
    from seaweedfs_tpu.ec.codec import new_encoder

    # the decode-rows-cached stage pair now lives in ec_stream (the
    # volume server's rack-gather rebuild verb uses the same one)
    make_rebuild_fns = ec_stream.local_rebuild_fns

    def best_rate(base: str, rs, runs: int):
        dat_bytes = os.path.getsize(base + ".dat")
        rebuild_fn, fetch = make_rebuild_fns(rs)
        best, best_stats = float("inf"), {}
        for _ in range(runs):
            os.remove(base + ec_files.to_ext(0))
            stats: dict = {}
            t0 = time.perf_counter()
            rebuilt = ec_stream.stream_rebuild_ec_files(
                base, rebuild_fn=rebuild_fn, fetch_fn=fetch, stats=stats
            )
            dt = time.perf_counter() - t0
            if dt < best:
                best, best_stats = dt, stats
            assert rebuilt == [0]
        return dat_bytes / best / 1e9, best_stats

    size = 256 * 1024 * 1024
    with tempfile.TemporaryDirectory() as d:
        base = os.path.join(d, "1")
        rng = np.random.default_rng(0)
        with open(base + ".dat", "wb") as f:
            for _ in range(size // (16 * 1024 * 1024)):
                f.write(
                    rng.integers(0, 256, 16 * 1024 * 1024, dtype=np.uint8).tobytes()
                )
        try:
            rs = new_encoder(backend="native")
        except (ImportError, ValueError):
            rs = new_encoder(backend="cpu")
        ec_files.write_ec_files(base, rs=rs)
        # integrity gate: the rebuilt shard must equal the original
        shard0 = base + ec_files.to_ext(0)
        want = open(shard0, "rb").read()
        rebuild_fn, fetch = make_rebuild_fns(rs)
        os.remove(shard0)
        ec_stream.stream_rebuild_ec_files(base, rebuild_fn=rebuild_fn, fetch_fn=fetch)
        assert open(shard0, "rb").read() == want, (
            "stream rebuild diverges from the encoded shard; refusing to "
            "publish a throughput number for wrong bytes"
        )
        gbps, phases = best_rate(base, rs, runs=3)

        # serial classic rebuild on the same backend (the
        # WEED_EC_PIPELINE=0 arm) for the overlap ratio
        def serial_rate(runs: int):
            dat_bytes = os.path.getsize(base + ".dat")
            best = float("inf")
            with _pipeline_disabled():
                for _ in range(runs):
                    os.remove(base + ec_files.to_ext(0))
                    t0 = time.perf_counter()
                    ec_files.rebuild_ec_files(base, rs=rs)
                    best = min(best, time.perf_counter() - t0)
            return dat_bytes / best / 1e9

        serial_gbps = serial_rate(runs=3)

        # numpy-backend baseline on a 32 MiB volume, same warm protocol
        cpu_base = os.path.join(d, "2")
        with open(base + ".dat", "rb") as src, open(cpu_base + ".dat", "wb") as dst:
            dst.write(src.read(32 * 1024 * 1024))
        cpu_rs = new_encoder(backend="cpu")
        ec_files.write_ec_files(cpu_base, rs=cpu_rs)
        cpu_gbps, _ = best_rate(cpu_base, cpu_rs, runs=2)
        ceiling = _disk_ceiling(d)

    # the rebuild streams 10 survivor-shard bytes in and 1 shard out
    # per volume byte: its disk bound is the sequential READ rate
    _report(
        "ec_rebuild_stream_e2e",
        gbps,
        "GB/s",
        gbps / cpu_gbps,
        phases=phases,
        serial_gb_s=round(serial_gbps, 4),
        vs_serial=round(gbps / serial_gbps, 4),
        # honesty line (VERDICT r4 weak #3): the headline
        # ec_rebuild_one_shard_30gb number is ON-CHIP KERNEL time; this
        # is what a 30 GB volume costs end-to-end through THIS HOST's
        # file driver at the rate just measured, judged against the
        # measured disk ceiling (utilization = fraction of the
        # sequential-read bar this driver reaches).
        file_path_30gb_s=round(30.0 / gbps, 2),
        utilization=round(gbps / ceiling["disk_seq_read_gb_s"], 3),
        **ceiling,
    )


def bench_rebuild_batch() -> None:
    """Batch-rebuild arm (docs/CODEC.md): >=4 concurrent small-volume
    rebuilds through ONE decode program for the whole group
    (ec_stream.stream_rebuild_ec_files_batch) vs the same volumes
    rebuilt one-at-a-time. Small volumes are exactly where the batch
    arm earns its keep: per-volume fixed costs (ring/thread spin-up,
    per-dispatch overhead on tiny tiles) dominate the serial loop, and
    the batch pays one set of them for the group. value = summed
    volume data bytes over batch wall time; vs_serial compares against
    the classic WEED_EC_PIPELINE=0 per-volume driver (the same serial
    baseline every other *_e2e line in BENCH_r12 uses) and is the
    acceptance ratio (BENCH_r13 bound: >= 1.3x); vs_pipelined_loop is
    the stricter secondary comparison against a per-volume loop of the
    pipelined single-volume driver."""
    import tempfile

    import numpy as np

    from seaweedfs_tpu.ec import ec_files, ec_stream
    from seaweedfs_tpu.ec.codec import new_encoder

    # 4 small volumes (the RepairScheduler's many-small-volumes case),
    # ragged tails so the last tile round is partial
    sizes = [1024 * 1024 + t for t in (0, 517, 4096, 1)]
    missing = [0, 13]  # same damage on every volume: one decode program
    runs = 5
    with tempfile.TemporaryDirectory() as d:
        try:
            rs = new_encoder(backend="native")
        except (ImportError, ValueError):
            rs = new_encoder(backend="cpu")
        rng = np.random.default_rng(5)
        bases = []
        for i, size in enumerate(sizes):
            base = os.path.join(d, str(i + 1))
            with open(base + ".dat", "wb") as f:
                f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
            ec_files.write_ec_files(base, rs=rs)
            bases.append(base)
        golden = {
            (base, sid): open(base + ec_files.to_ext(sid), "rb").read()
            for base in bases
            for sid in missing
        }
        dat_bytes = sum(os.path.getsize(b + ".dat") for b in bases)

        def damage():
            for base in bases:
                for sid in missing:
                    os.remove(base + ec_files.to_ext(sid))

        # integrity gate first: batch output must equal the encode
        damage()
        rebuilt = ec_stream.stream_rebuild_ec_files_batch(bases)
        assert rebuilt == [missing] * len(bases), rebuilt
        for (base, sid), want in golden.items():
            assert open(base + ec_files.to_ext(sid), "rb").read() == want, (
                f"batched rebuild diverges on {base}.ec{sid:02d}; refusing "
                "to publish a throughput number for wrong bytes"
            )

        best_batch, batch_stats = float("inf"), {}
        for _ in range(runs):
            damage()
            stats: dict = {}
            t0 = time.perf_counter()
            ec_stream.stream_rebuild_ec_files_batch(bases, stats=stats)
            dt = time.perf_counter() - t0
            if dt < best_batch:
                best_batch, batch_stats = dt, stats

        # serial arm: the volumes one-at-a-time through the classic
        # WEED_EC_PIPELINE=0 driver — the same serial baseline the
        # other *_e2e lines' vs_serial fields use
        best_serial = float("inf")
        for _ in range(runs):
            damage()
            t0 = time.perf_counter()
            with _pipeline_disabled():
                for base in bases:
                    ec_files.rebuild_ec_files(base, rs=rs)
            best_serial = min(best_serial, time.perf_counter() - t0)

        # secondary arm: per-volume loop of the pipelined driver (the
        # path a batch-unaware ec.rebuild loop takes today)
        rebuild_fn, fetch = ec_stream.local_rebuild_fns(rs)
        best_piped = float("inf")
        for _ in range(runs):
            damage()
            t0 = time.perf_counter()
            for base in bases:
                ec_stream.stream_rebuild_ec_files(
                    base, rebuild_fn=rebuild_fn, fetch_fn=fetch
                )
            best_piped = min(best_piped, time.perf_counter() - t0)
        ceiling = _disk_ceiling(d)

    gbps = dat_bytes / best_batch / 1e9
    serial_gbps = dat_bytes / best_serial / 1e9
    piped_gbps = dat_bytes / best_piped / 1e9
    _report(
        "ec_rebuild_batch_stream_e2e",
        gbps,
        "GB/s",
        gbps / serial_gbps,
        batch_volumes=len(bases),
        batch_groups=batch_stats.get("batch_groups"),
        mesh=batch_stats.get("mesh"),
        codec_arm=batch_stats.get("codec_arm"),
        host_inline=batch_stats.get("host_inline"),
        serial_gb_s=round(serial_gbps, 4),
        vs_serial=round(gbps / serial_gbps, 4),
        pipelined_loop_gb_s=round(piped_gbps, 4),
        vs_pipelined_loop=round(gbps / piped_gbps, 4),
        **ceiling,
    )


def bench_http_reqs() -> None:
    """Write/read req/s through the full HTTP data plane — the numbers
    README round 5 carried only as prose, now driver-tracked JSON
    (VERDICT round-5 ask). An in-process cluster (1 master + 1 volume
    server) takes the repo's own `weed benchmark` load
    (command/benchmark.run_benchmark: pooled keep-alive client
    transport, assign + upload per write, lookup + download per read —
    the exact workload the README prose was measured with).

    Emits two lines: http_write_req_s (vs the README's ~3,400/s
    round-5 prose figure) and http_read_req_s (vs ~11,000/s) — a
    data-plane regression now shows in the driver's record, not just
    in a stale paragraph. NOTE the README prose was measured across
    three PROCESSES; here master + volume + load generator share one
    GIL, so the absolute value is a conservative floor — the line
    exists for round-over-round regression tracking, vs_baseline for
    scale."""
    import tempfile

    from seaweedfs_tpu.command.benchmark import run_benchmark
    from seaweedfs_tpu.command.servers import _tune_gc
    from seaweedfs_tpu.util.availability import start_cluster

    _tune_gc()
    concurrency, num, size = 8, 2000, 1024
    with tempfile.TemporaryDirectory() as d:
        master, servers = start_cluster([tempfile.mkdtemp(dir=d)])
        try:
            results, _fids = run_benchmark(
                master=f"127.0.0.1:{master.port}",
                concurrency=concurrency,
                num=num,
                size=size,
            )
        finally:
            for vs in servers:
                vs.stop()
            master.stop()

    for (title, s), metric, baseline in zip(
        results, ("http_write_req_s", "http_read_req_s"), (3400.0, 11000.0)
    ):
        rate = s.completed / max(1e-9, (s.ended or time.perf_counter()) - s.start)
        _report(
            metric,
            rate,
            "req/s",
            rate / baseline,
            concurrency=concurrency,
            requests=s.completed,
            failed=s.failed,
        )


def bench_shard_hop() -> None:
    """`-shardWrites` loopback-hop cost, measured (VERDICT r5 "Next
    round" #3): the same write POSTed at a worker that OWNS the vid
    (local append) vs one that must hop it to the other writer over the
    loopback internal listener. One in-process master + sharded lead
    (writer 0 of 2) + write worker (writer 1 of 2), pooled keep-alive
    connection, median of N per arm — the same-process-pair A/B keeps
    scheduler noise common-mode.

    value = median added microseconds per hopped write;
    vs_baseline = owned/hopped latency ratio (1.0 = free hop). The
    W-core projection table in OPERATIONS.md §round 8 is built from
    this constant plus the measured per-write CPU split."""
    import json as _json
    import statistics
    import tempfile
    import urllib.request as _rq

    from seaweedfs_tpu.client.operation import _drop_conn, _pooled_conn
    from seaweedfs_tpu.command.servers import _tune_gc
    from seaweedfs_tpu.server.master_server import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.server.volume_workers import VolumeReadWorker
    from seaweedfs_tpu.util.availability import free_port

    _tune_gc()
    n = 400
    with tempfile.TemporaryDirectory() as vdir:
        mport = free_port()
        master = MasterServer(port=mport, volume_size_limit_mb=64)
        master.start()
        iport, winternal = free_port(), free_port()
        lead = VolumeServer(
            [vdir],
            port=free_port(),
            master=f"127.0.0.1:{mport}",
            heartbeat_interval=0.2,
            max_volume_counts=[100],
            internal_port=iport,
            shard_writes=True,
            n_writers=2,
        )
        lead._writer_internal_addr = lambda k: (
            f"127.0.0.1:{winternal}" if k == 1 else f"127.0.0.1:{iport}"
        )
        lead.start()
        deadline = time.time() + 30
        while time.time() < deadline and not master.topology.data_nodes():
            time.sleep(0.05)
        wport = free_port()
        worker = VolumeReadWorker(
            [vdir],
            host="127.0.0.1",
            port=free_port(),
            lead=f"127.0.0.1:{iport}",
            worker_port=wport,
            shard_writes=True,
            writer_index=1,
            n_writers=2,
            master=f"127.0.0.1:{mport}",
            internal_port=winternal,
        )
        worker.start()
        try:
            # one fid per parity; unique sub-keys via the ?count= delta
            # trick would complicate byte-accounting — instead rewrite
            # the same needle (overwrite path) ... no: overwrites take
            # the Python dedup path. Use fresh assigns per batch arm.
            def assign(parity):
                for _ in range(60):
                    with _rq.urlopen(
                        f"http://127.0.0.1:{mport}/dir/assign?count=500",
                        timeout=10,
                    ) as r:
                        a = _json.load(r)
                    if int(a["fid"].split(",")[0]) % 2 == parity:
                        return a
                raise RuntimeError(f"no parity-{parity} vid assigned")

            payload = b"\x00\x01hop-bench-payload\xff" * 50  # ~1 KB binary
            addr = f"127.0.0.1:{wport}"

            def arm(parity):
                """(wall latencies, cpu_us/write): master + lead +
                worker + client all share THIS process, so a
                process_time delta over the arm is the whole stack's
                CPU per write — the constant the W-core projection
                needs (wall on this throttled shared core is too noisy
                to subtract; the r5 A/B hit the same wall)."""
                a = assign(parity)
                base_fid = a["fid"]
                lat = []
                c, _ = _pooled_conn(addr, 30.0)
                try:
                    warm = n // 10
                    cpu0 = wall_cpu = None
                    for i in range(n):
                        if i == warm:
                            cpu0 = time.process_time()
                        fid = f"{base_fid}_{i}" if i else base_fid
                        t0 = time.perf_counter()
                        c.send_request(
                            "POST", f"/{fid}", payload,
                            {"Content-Type": "application/octet-stream"},
                        )
                        status, _h, _b, will_close = c.read_response("POST")
                        if i >= warm:
                            lat.append(time.perf_counter() - t0)
                        assert status == 201, f"write {fid} -> {status}"
                        if will_close:
                            _drop_conn(addr)
                            c, _ = _pooled_conn(addr, 30.0)
                    wall_cpu = time.process_time() - cpu0
                finally:
                    _drop_conn(addr)
                return lat, wall_cpu / (n - warm) * 1e6

            # interleave arms to keep host-throttle drift common-mode
            owned, hopped = [], []
            owned_cpu, hopped_cpu = [], []
            for _ in range(3):
                lat, cpu = arm(1)
                owned += lat
                owned_cpu.append(cpu)
                lat, cpu = arm(0)
                hopped += lat
                hopped_cpu.append(cpu)
            owned_us = statistics.median(owned) * 1e6
            hopped_us = statistics.median(hopped) * 1e6
            owned_cpu_us = statistics.median(owned_cpu)
            hopped_cpu_us = statistics.median(hopped_cpu)
        finally:
            worker.stop()
            lead.stop()
            master.stop()
    _report(
        "shard_writes_hop_us",
        hopped_cpu_us - owned_cpu_us,
        "us",
        owned_cpu_us / hopped_cpu_us if hopped_cpu_us > 0 else 1.0,
        owned_write_cpu_us=round(owned_cpu_us, 1),
        hopped_write_cpu_us=round(hopped_cpu_us, 1),
        owned_write_wall_us=round(owned_us, 1),
        hopped_write_wall_us=round(hopped_us, 1),
        requests_per_arm=len(owned),
    )


def bench_migration() -> None:
    """BASELINE config 5: live replication→EC warm-tier migration under
    concurrent reads — the availability claim, measured.

    An in-process cluster (1 master + 3 volume servers, native EC
    codec) holds a
    replicated keyset; one hammering reader loops every key through the
    master's GET /<fid> redirect while the full ec.encode pipeline
    (readonly → generate → spread → mount → confirm-registered →
    delete source, shell/commands.do_ec_encode matching
    volume_grpc_erasure_coding.go:25-36) runs underneath it.

    value = p99 read latency (ms) across the whole run including the
    transition; vs_baseline = 1.0 when ZERO reads failed (status,
    cookie, or body mismatch — the reference's no-unavailability
    property holds), 0.0 otherwise. max latency and read/failure counts
    ride as extra fields.
    """
    import io as _io
    import tempfile

    from seaweedfs_tpu.shell.command_env import CommandEnv
    from seaweedfs_tpu.shell.commands import do_ec_encode
    from seaweedfs_tpu.util.availability import (
        HammerReader,
        run_with_readers,
        start_cluster,
        write_keyset,
    )

    with tempfile.TemporaryDirectory() as d:
        master, servers = start_cluster(
            [tempfile.mkdtemp(dir=d) for _ in range(3)], ec_codec="native"
        )
        try:
            # ~50 KB payloads: enough bytes that the encode pipeline
            # has real work, small enough that the 1-vCPU rig's reader
            # keeps a tight loop
            vid, keys, _src = write_keyset(
                master.port,
                "bench",
                n=24,
                payload_fn=lambda i: (f"bench key {i} ".encode() * 4096)[
                    : 50_000 + 137 * i
                ],
            )
            env = CommandEnv([f"127.0.0.1:{master.port}"])
            reader = HammerReader(
                f"http://127.0.0.1:{master.port}", keys, "bench"
            )
            run_with_readers(
                [reader], lambda: do_ec_encode(env, vid, "bench", _io.StringIO())
            )
        finally:
            for vs in servers:
                vs.stop()
            master.stop()

    from seaweedfs_tpu.stats.quantile import percentile

    lat = reader.latencies
    _report(
        "ec_migration_read_availability",
        percentile(lat, 0.99) * 1000,
        "ms",
        1.0 if not reader.failures else 0.0,
        reads=reader.reads,
        failed_reads=len(reader.failures),
        p50_ms=round(percentile(lat, 0.5) * 1000, 3),
        max_ms=round(max(lat) * 1000, 3),
    )


def bench_migration_with_retry() -> None:
    """One retry for the migration config: it boots five servers on a
    host that throttles under the rest of the matrix; a transient
    startup hiccup must not leave a red line in the driver's record
    when a clean run is one attempt away."""
    try:
        bench_migration()
    except Exception:  # noqa: BLE001 - second attempt decides
        time.sleep(5)
        bench_migration()


def bench_scrub() -> None:
    """PR-2 config: the scrub plane's two operational numbers.

    Line 1 — `scrub_verify_gb_s`: how fast the background scrubber's
    EC parity re-verify core (scrub/verify.verify_parity_stream — the
    same code path the ScrubEngine and the rate-limited ec.verify run)
    moves shard bytes off THIS host's disk, unthrottled. Judged
    against the measured disk sequential-read ceiling (same honesty
    fields as the *_stream_e2e lines): utilization says how much of
    the hardware bar a full-speed sweep can use — and therefore what a
    production rate cap (-scrubRate) leaves for foreground reads.

    Line 2 — `scrub_interference_read_p99`: foreground read p99 with a
    CONTINUOUS rate-capped sweep running vs scrub off, one in-process
    master + volume server, same keyset. vs_baseline = p99_off/p99_on
    (1.0 = zero interference; >= 0.8 keeps the acceptance bound of
    p99-within-25%). The sweep runs at the production default 64 MB/s
    token bucket — the number the knob actually ships with.
    """
    import tempfile
    import urllib.request as _rq

    import numpy as np

    from seaweedfs_tpu.command.servers import _tune_gc
    from seaweedfs_tpu.ec.codec import new_encoder
    from seaweedfs_tpu.scrub.verify import verify_parity_stream

    _tune_gc()
    # --- line 1: verify core GB/s over real shard files ---
    shard_mb = 24
    with tempfile.TemporaryDirectory() as d:
        rs = new_encoder(backend="native")
        nbytes = shard_mb * 1024 * 1024
        rng = np.random.default_rng(11)
        tile = 4 * 1024 * 1024
        paths = [os.path.join(d, f"bench.ec{i:02d}") for i in range(14)]
        files = [open(p, "wb") for p in paths]
        try:
            for off in range(0, nbytes, tile):
                shards = [
                    rng.integers(0, 256, tile, dtype=np.uint8)
                    for _ in range(10)
                ] + [None] * 4
                rs.encode(shards)
                for f, s in zip(files, shards):
                    f.write(s.tobytes())
        finally:
            for f in files:
                f.close()
        fds = [os.open(p, os.O_RDONLY) for p in paths]
        try:
            for fd in fds:
                try:
                    os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
                except OSError:
                    pass
            readers = [
                (lambda off, size, _fd=fd: os.pread(_fd, size, off))
                for fd in fds
            ]
            t0 = time.perf_counter()
            res = verify_parity_stream(readers, rs=rs, tile_bytes=tile)
            elapsed = time.perf_counter() - t0
        finally:
            for fd in fds:
                os.close(fd)
        assert res.complete and not res.corrupt, res.mismatch
        total = res.bytes_per_shard * 14
        gbps = total / elapsed / 1e9

        # --- line 1b: same shards, `.ecc` sidecar fast pass ---
        # publish a sidecar attesting the shards just written, then
        # time scrub/verify.verify_ecc_stream over the same 14 files.
        # Two protocols: a cold pass (same fadvise protocol as line 1,
        # the operational number) and a warm best-of-2 pair of both
        # arms. The acceptance ratio (BENCH_r13: >= 3x parity) uses
        # the WARM pair: the sidecar's saving is the GF arithmetic it
        # removes (CRC instead of 4 parity rows per tile), and on an
        # IO-starved host both cold passes run at disk speed — the
        # saving shows up as freed scrub CPU, which the warm pair
        # isolates.
        from seaweedfs_tpu.ec import ecc_sidecar as _ecc
        from seaweedfs_tpu.scrub.verify import verify_ecc_stream
        from seaweedfs_tpu.util.crc import crc32c as _crc32c

        base = os.path.join(d, "bench")
        crcs = []
        for p in paths:
            c = 0
            with open(p, "rb") as f:
                while True:
                    chunk = f.read(tile)
                    if not chunk:
                        break
                    c = _crc32c(chunk, c)
            crcs.append(c)
        _ecc.write_sidecar(base, crcs, total_shards=len(paths))
        doc = _ecc.load_sidecar(base)
        shard_paths = {i: p for i, p in enumerate(paths)}
        for p in paths:
            fd = os.open(p, os.O_RDONLY)
            try:
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
            except OSError:
                pass
            finally:
                os.close(fd)
        t0 = time.perf_counter()
        eres = verify_ecc_stream(shard_paths, doc, tile_bytes=tile)
        ecc_elapsed = time.perf_counter() - t0
        assert eres.complete and not eres.corrupt, eres.bad_shards
        ecc_gbps = eres.bytes_scanned / ecc_elapsed / 1e9

        # warm pair: prime the cache (both passes above already read
        # every byte), then best-of-2 per arm on the page-cache-warm
        # files — the arithmetic-only comparison
        fds = [os.open(p, os.O_RDONLY) for p in paths]
        try:
            readers = [
                (lambda off, size, _fd=fd: os.pread(_fd, size, off))
                for fd in fds
            ]
            verify_parity_stream(readers, rs=rs, tile_bytes=tile)
            best_par = best_ecc = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                wres = verify_parity_stream(readers, rs=rs, tile_bytes=tile)
                best_par = min(best_par, time.perf_counter() - t0)
                assert wres.complete and not wres.corrupt, wres.mismatch
                t0 = time.perf_counter()
                weres = verify_ecc_stream(shard_paths, doc, tile_bytes=tile)
                best_ecc = min(best_ecc, time.perf_counter() - t0)
                assert weres.complete and not weres.corrupt, weres.bad_shards
        finally:
            for fd in fds:
                os.close(fd)
        total_warm = res.bytes_per_shard * 14
        warm_par_gbps = total_warm / best_par / 1e9
        warm_ecc_gbps = total_warm / best_ecc / 1e9
        ceiling = _disk_ceiling(d)
    _report(
        "scrub_verify_gb_s",
        gbps,
        "GB/s",
        gbps / ceiling["disk_seq_read_gb_s"],
        shard_bytes=res.bytes_per_shard,
        utilization=round(
            min(1.0, gbps / ceiling["disk_seq_read_gb_s"]), 3
        ),
        **ceiling,
    )
    _report(
        "scrub_ecc_verify_gb_s",
        warm_ecc_gbps,
        "GB/s",
        warm_ecc_gbps / warm_par_gbps,  # arithmetic-only: warm pair
        shard_bytes=res.bytes_per_shard,
        vs_parity=round(warm_ecc_gbps / warm_par_gbps, 4),
        parity_warm_gb_s=round(warm_par_gbps, 4),
        cold_gb_s=round(ecc_gbps, 4),
        vs_parity_cold=round(ecc_gbps / gbps, 4),
        utilization=round(
            min(1.0, ecc_gbps / ceiling["disk_seq_read_gb_s"]), 3
        ),
        **ceiling,
    )

    # --- line 2: foreground read p99, scrub off vs on ---
    import json as _json
    import threading as _threading

    from seaweedfs_tpu.util.availability import HammerReader, start_cluster

    hammer_seconds = 8.0
    with tempfile.TemporaryDirectory() as d:
        vol_dir = tempfile.mkdtemp(dir=d)
        # a ~256 MB sealed volume pre-seeded on disk: ONE rate-bound
        # sweep of it outlasts the whole hammer window, so the "on"
        # phase measures genuine continuous scrubbing (not a loop of
        # instant sweeps over a toy keyset)
        from seaweedfs_tpu.storage.needle import Needle as _Needle
        from seaweedfs_tpu.storage.volume import Volume as _Volume

        big = _Volume(vol_dir, 137)
        blob = bytes(
            np.random.default_rng(7).integers(0, 256, 1 << 20, dtype=np.uint8)
        )
        for k in range(1, 257):
            big.write_needle(_Needle(cookie=1, id=k, data=blob))
        big.close()
        master, servers = start_cluster(
            [vol_dir],
            ec_codec="native",
            scrub_interval=3600.0,  # engine exists; sweeps only when driven
            scrub_rate_mb_s=64.0,  # the production default cap
        )
        vs = servers[0]
        try:
            keys = {}
            for i in range(24):
                with _rq.urlopen(
                    f"http://127.0.0.1:{master.port}/dir/assign", timeout=10
                ) as r:
                    assign = _json.loads(r.read())
                payload = (f"scrub bench {i} ".encode() * 4096)[: 48_000 + i]
                _rq.urlopen(
                    _rq.Request(
                        f"http://{assign['url']}/{assign['fid']}",
                        data=payload,
                        method="POST",
                    ),
                    timeout=10,
                ).close()
                keys[assign["fid"]] = payload

            def p99_for(duration: float, pool: list | None = None) -> tuple[float, int]:
                reader = HammerReader(
                    f"http://{vs.host}:{vs.port}", keys, "scrub-bench"
                )
                reader.start()
                time.sleep(duration)
                reader.stop_event.set()
                reader.join(timeout=30)
                assert not reader.failures, reader.failures[:3]
                # drop the first keyset pass: connection setup and cold
                # page cache would smear both phases' tails
                kept = reader.latencies[len(keys):]
                if pool is not None:
                    pool.extend(kept)
                from seaweedfs_tpu.stats.quantile import percentile

                return percentile(kept, 0.99) * 1000, reader.reads

            # continuous sweeping: restart the (rate-capped) sweep in a
            # loop while the "on" phases run
            sweeping = _threading.Event()

            def sweep_loop():
                while sweeping.is_set():
                    vs.scrub.sweep_once()

            # adjacent OFF/ON pairs, median-of-ratios: this rig's
            # external throttle swings ±50% on the minute scale, so a
            # single back-to-back comparison routinely lies in either
            # direction on the SAME code. Each pair is seconds apart
            # (drift ~constant within it) and the median across pairs
            # discards an unlucky window.
            pairs = []
            reads_off = reads_on = 0
            phase = hammer_seconds / 2
            for _ in range(5):
                po, r = p99_for(phase)
                reads_off += r
                sweeping.set()
                t = _threading.Thread(target=sweep_loop, daemon=True)
                t.start()
                try:
                    pn, r = p99_for(phase)
                    reads_on += r
                finally:
                    sweeping.clear()
                    t.join(timeout=30)
                pairs.append((po, pn))
            pairs.sort(key=lambda pr: pr[0] / pr[1])
            p99_off, p99_on = pairs[len(pairs) // 2]
        finally:
            for s in servers:
                s.stop()
            master.stop()
    _report(
        "scrub_interference_read_p99",
        p99_on,
        "ms",
        (p99_off / p99_on) if p99_on > 0 else 1.0,
        p99_off_ms=round(p99_off, 3),
        p99_on_ms=round(p99_on, 3),
        reads_off=reads_off,
        reads_on=reads_on,
        scrub_rate_mb_s=64.0,
    )


def bench_trace() -> None:
    """Tracing plane A/B + stage attribution (docs/TRACING.md):

    Line 1 — `trace_write_overhead`: the volume write hot path with
    tracing on (full fidelity), sampled (-traceSample 16), and off,
    toggled in-process and interleaved PER WRITE so host-throttle
    drift is common-mode by construction — this rig's CPU clock ticks
    at 10 ms and its speed swings 2-4x on multi-second timescales
    (OPERATIONS.md round 10), which poisons every block-level process-
    CPU estimator; per-write WALL medians resolve sub-microsecond
    deltas (a planted no-op control measures +0.3 us). vs_baseline =
    off/on medians; overhead_us is the median-of-arm-medians delta.
    The acceptance bound (<= 2%, vs_baseline >= 0.98) is met by the
    sampled arm on this rig; full fidelity measures ~4% here, ~12 us
    of which is the span lifecycle itself (tight-loop) and the rest
    this rig's per-request cold-cache residue — see round 10 for the
    decomposition and the projection to the reference rig.

    Line 2 — `trace_stage_breakdown`: per-stage p50/p99 microseconds
    across the traced arm's volume.post spans — the stage attribution
    future perf PRs cite instead of end-to-end guesses.

    The `noscope` arm is the weedscope recorder A/B (ISSUE-20): tracing
    on but the blackbox flight recorder and histogram exemplars off —
    exactly what `WEED_SCOPE=0` boots into. The recorder must stay
    inside the trace plane's bound: vs_scope_off >= 0.98.
    """
    import json as _json
    import statistics
    import tempfile
    import urllib.request as _rq

    from seaweedfs_tpu import trace
    from seaweedfs_tpu.client.operation import _drop_conn, _pooled_conn
    from seaweedfs_tpu.command.servers import _tune_gc
    from seaweedfs_tpu.stats import metrics as metrics_mod
    from seaweedfs_tpu.trace import blackbox
    from seaweedfs_tpu.util.availability import start_cluster

    _tune_gc()
    n_writes, warmup, sample_n = 6000, 200, 16
    payload = b"\x00\x01trace-bench-payload\xff" * 50  # ~1 KB, not gzippable
    # arm per write, round-robin: off / on (full) / on (sampled
    # 1-in-sample_n) / noscope (tracing on, weedscope recorder off)
    arms = ("off", "on", "sampled", "noscope")
    with tempfile.TemporaryDirectory() as d:
        master, servers = start_cluster([tempfile.mkdtemp(dir=d)])
        m = f"127.0.0.1:{master.port}"
        addr = f"127.0.0.1:{servers[0].port}"
        lat: dict[str, list[float]] = {a: [] for a in arms}
        try:
            with _rq.urlopen(
                f"http://{m}/dir/assign?count={n_writes + 1}", timeout=10
            ) as r:
                base_fid = _json.load(r)["fid"]
            c, _ = _pooled_conn(addr, 30.0)
            try:
                for i in range(n_writes):
                    arm = arms[i % len(arms)]
                    trace.set_enabled(arm != "off")
                    trace.set_sample_every(
                        sample_n if arm == "sampled" else 1
                    )
                    blackbox.set_enabled(arm != "noscope")
                    metrics_mod.set_exemplars_enabled(arm != "noscope")
                    fid = f"{base_fid}_{i}" if i else base_fid
                    t0 = time.perf_counter()
                    c.send_request(
                        "POST", f"/{fid}", payload,
                        {"Content-Type": "application/octet-stream"},
                    )
                    status, _h, _b, will_close = c.read_response("POST")
                    dt = time.perf_counter() - t0
                    assert status == 201, f"write {fid} -> {status}"
                    if will_close:
                        _drop_conn(addr)
                        c, _ = _pooled_conn(addr, 30.0)
                    if i >= warmup:
                        lat[arm].append(dt)
            finally:
                _drop_conn(addr)
                trace.set_enabled(True)
                trace.set_sample_every(1)
                blackbox.set_enabled(True)
                metrics_mod.set_exemplars_enabled(True)
            # stage attribution: the in-process volume server shares
            # this process's ring, so read it directly
            stage_samples: dict[str, list[float]] = {}
            payload_spans = trace.debug_payload(4096)["recent"]
            for s in payload_spans:
                if s["name"] != "volume.post" or "stages_ms" not in s:
                    continue
                for k, v in s["stages_ms"].items():
                    stage_samples.setdefault(k, []).append(v * 1000.0)
        finally:
            for vs in servers:
                vs.stop()
            master.stop()
    med = {a: statistics.median(lat[a]) * 1e6 for a in arms}
    delta_us = med["on"] - med["off"]
    _report(
        "trace_write_overhead",
        delta_us,
        "us",
        med["off"] / med["on"] if med["on"] > 0 else 1.0,
        wall_off_us=round(med["off"], 1),
        wall_on_us=round(med["on"], 1),
        wall_sampled_us=round(med["sampled"], 1),
        vs_baseline_sampled=round(
            med["off"] / med["sampled"] if med["sampled"] > 0 else 1.0, 4
        ),
        wall_noscope_us=round(med["noscope"], 1),
        scope_overhead_us=round(med["on"] - med["noscope"], 2),
        vs_scope_off=round(
            med["noscope"] / med["on"] if med["on"] > 0 else 1.0, 4
        ),
        sample_every=sample_n,
        writes_per_arm=(n_writes - warmup) // len(arms),
    )

    from seaweedfs_tpu.stats.quantile import percentile as pct

    stages = {
        k: {"p50_us": round(pct(v, 0.5), 2), "p99_us": round(pct(v, 0.99), 2)}
        for k, v in sorted(stage_samples.items())
    }
    total_p99 = sum(v["p99_us"] for v in stages.values()) or 1.0
    _report(
        "trace_stage_breakdown",
        total_p99,
        "us",
        1.0,
        stages=stages,
        spans=len(next(iter(stage_samples.values()), [])),
    )


def bench_load() -> None:
    """Telemetry plane `load` config (docs/TELEMETRY.md, BENCH_r07).

    Lines 1+2 — `load_put` / `load_get`: weedload drives 4 worker
    PROCESSES (2 assign+PUT, 2 GET) against a REAL multi-process
    cluster (master + 2 volume servers as `python -m seaweedfs_tpu`
    subprocesses — every hop crosses a process boundary and a real
    socket, unlike the in-process `http` config whose tracker shares
    the servers' GIL, the BENCH_r06 caveat) and reports p50/p99/p99.9
    from log-bucketed latency histograms. vs_baseline = error-free
    fraction of ops (1.0 = every request succeeded); the latency value
    is the p99 in ms. This harness is the measurement substrate for
    the ROADMAP tail-latency plane (hedging on/off A/Bs).

    Line 3 — `load_profiler_overhead`: the volume write path with the
    continuous sampling profiler running vs paused, toggled in-process
    and interleaved PER WRITE (the bench_trace method: wall medians,
    host-throttle drift common-mode). Acceptance bound: <= 1% serving
    overhead (vs_baseline >= 0.99).
    """
    import statistics
    import subprocess
    import tempfile
    import urllib.request as _rq

    from seaweedfs_tpu.telemetry.weedload import run_load

    def _free_port():
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def _spawn(*args):
        env = dict(os.environ, JAX_PLATFORMS="cpu", WEED_EC_CODEC="cpu")
        return subprocess.Popen(
            [
                sys.executable,
                "-m",
                "seaweedfs_tpu",
                *args,
            ],
            env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT,
        )

    mport = _free_port()
    m = f"127.0.0.1:{mport}"
    procs = []
    with tempfile.TemporaryDirectory() as d:
        try:
            procs.append(
                _spawn("master", "-port", str(mport), "-mdir", d,
                       "-telemetryInterval", "2")
            )
            for i in range(2):
                vdir = os.path.join(d, f"v{i}")
                os.mkdir(vdir)
                procs.append(
                    _spawn(
                        "volume", "-port", str(_free_port()), "-dir", vdir,
                        "-mserver", m, "-max", "50", "-rack", f"rack{i}",
                        "-scrubInterval", "0",
                    )
                )
            deadline = time.time() + 60
            while time.time() < deadline:
                try:
                    with _rq.urlopen(f"http://{m}/dir/status", timeout=2) as r:
                        topo = json.load(r)["Topology"]
                    nodes = sum(
                        len(rk["DataNodes"])
                        for dc in topo.get("DataCenters", [])
                        for rk in dc.get("Racks", [])
                    )
                    if nodes >= 2:
                        break
                except OSError:
                    pass
                time.sleep(0.3)
            else:
                raise RuntimeError("multi-process cluster never became ready")
            report = run_load(
                m, duration_s=8.0, writers=2, readers=2,
                payload_bytes=1024, rate=0.0, seed_n=48,
            )
            # the cluster's own telemetry saw the load: health comes
            # along as evidence the collector aggregated real traffic
            try:
                with _rq.urlopen(f"http://{m}/cluster/health", timeout=5) as r:
                    health = json.load(r)
                scraped = sum(
                    1 for t in health.get("Targets", {}).values()
                    if t.get("Scrapes", 0) > 0
                )
            except (OSError, ValueError):
                scraped = 0
        finally:
            for p in procs:
                p.kill()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except Exception:  # noqa: BLE001
                    pass
    for mode in ("put", "get"):
        row = report.get(mode)
        if row is None:
            continue
        ok_frac = (
            (row["ops"] - row["errors"]) / row["ops"] if row["ops"] else 0.0
        )
        _report(
            f"load_{mode}",
            row["p99_ms"],
            "ms",
            round(ok_frac, 4),
            p50_ms=row["p50_ms"],
            p999_ms=row["p999_ms"],
            max_ms=row["max_ms"],
            req_per_sec=row["req_per_sec"],
            ops=row["ops"],
            errors=row["errors"],
            worker_processes=report["config"]["processes"],
            multi_process_cluster=len(procs),
            telemetry_targets_scraped=scraped,
            co_safe=report["config"]["coordinated_omission_safe"],
        )

    # --- line 3: profiler serving-path overhead A/B ---------------------
    from seaweedfs_tpu import trace
    from seaweedfs_tpu.client.operation import _drop_conn, _pooled_conn
    from seaweedfs_tpu.command.servers import _tune_gc
    from seaweedfs_tpu.telemetry import profiler
    from seaweedfs_tpu.util.availability import start_cluster

    if not profiler.ensure_started():
        _report("load_profiler_overhead", 0.0, "us", 1.0, skipped=True,
                reason="WEED_PROF=0")
        return
    _tune_gc()
    trace.set_enabled(False)  # measure the profiler alone, not trace+prof
    n_writes, warmup = 4200, 300
    payload = b"\x00\x01prof-bench-payload\xff" * 50
    arms = ("off", "on")
    with tempfile.TemporaryDirectory() as d:
        master, servers = start_cluster([tempfile.mkdtemp(dir=d)])
        mloc = f"127.0.0.1:{master.port}"
        addr = f"127.0.0.1:{servers[0].port}"
        lat: dict[str, list[float]] = {a: [] for a in arms}
        try:
            with _rq.urlopen(
                f"http://{mloc}/dir/assign?count={n_writes + 1}", timeout=10
            ) as r:
                base_fid = json.load(r)["fid"]
            c, _ = _pooled_conn(addr, 30.0)
            try:
                for i in range(n_writes):
                    arm = arms[i % len(arms)]
                    profiler.set_paused(arm == "off")
                    fid = f"{base_fid}_{i}" if i else base_fid
                    t0 = time.perf_counter()
                    c.send_request(
                        "POST", f"/{fid}", payload,
                        {"Content-Type": "application/octet-stream"},
                    )
                    status, _h, _b, will_close = c.read_response("POST")
                    dt = time.perf_counter() - t0
                    assert status == 201, f"write {fid} -> {status}"
                    if will_close:
                        _drop_conn(addr)
                        c, _ = _pooled_conn(addr, 30.0)
                    if i >= warmup:
                        lat[arm].append(dt)
            finally:
                _drop_conn(addr)
                profiler.set_paused(False)
                trace.set_enabled(True)
        finally:
            for vs in servers:
                vs.stop()
            master.stop()
    med = {a: statistics.median(lat[a]) * 1e6 for a in arms}
    _report(
        "load_profiler_overhead",
        med["on"] - med["off"],
        "us",
        round(med["off"] / med["on"], 4) if med["on"] > 0 else 1.0,
        wall_off_us=round(med["off"], 1),
        wall_on_us=round(med["on"], 1),
        sample_interval_ms=profiler.capture(0)["interval_ms"],
        writes_per_arm=(n_writes - warmup) // len(arms),
    )


def bench_serve() -> None:
    """Event-driven serving core A/B (docs/SERVING.md, BENCH_r08).

    Two identical single-volume clusters run as CLI subprocesses, one
    with the C epoll loop (default), one with WEED_NATIVE_SERVE=0 (the
    threaded mini-loop fallback) — the kill switch IS the A/B lever.
    weedload's GET fan drives 256 keep-alive connections (2 client
    processes x 128 selector-driven conns, real sockets, spawn start)
    through three mixes per arm:

      serve_get_*     hot-cache 1 KiB GETs, unpaced closed loop — the
                      max-throughput probe (req/s is the headline)
      serve_range_*   same keyset, every 3rd request a Range read
                      (suffix/interior/open-ended cycling; 200+206 mix)
      serve_paced_*   coordinated-omission-safe arm: every connection
                      paced at a fixed schedule chosen as ~60% of the
                      epoll arm's measured hot throughput, latency
                      charged from the SCHEDULED send — queueing delay
                      at equal offered load is where thread-per-
                      connection dies first

    vs_baseline on each epoll line = epoll/threaded ratio (req/s for
    the closed-loop mixes, threaded_p99/epoll_p99 for the paced arm).
    Acceptance (ISSUE 8): >=2x req/s or >=2x p99 at >=256 connections,
    0 errors."""
    import subprocess
    import tempfile
    import urllib.request as _rq

    from seaweedfs_tpu.telemetry.weedload import run_get_fan, seed_keys

    def _free_port():
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def _spawn(env_extra, *args):
        env = dict(
            os.environ, JAX_PLATFORMS="cpu", WEED_EC_CODEC="cpu", **env_extra
        )
        return subprocess.Popen(
            [
                sys.executable,
                "-m",
                "seaweedfs_tpu",
                *args,
            ],
            env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT,
        )

    RANGES = ["bytes=0-127", "bytes=-100", "bytes=256-", "bytes=100-611"]

    def _run_arm(
        native: bool, paced_rate: float, mixes: tuple = ("hot", "range")
    ) -> dict:
        env_extra = {} if native else {"WEED_NATIVE_SERVE": "0"}
        mport = _free_port()
        m = f"127.0.0.1:{mport}"
        procs = []
        with tempfile.TemporaryDirectory() as d:
            try:
                procs.append(
                    _spawn(env_extra, "master", "-port", str(mport),
                           "-mdir", d)
                )
                vdir = os.path.join(d, "v0")
                os.mkdir(vdir)
                procs.append(
                    _spawn(
                        env_extra, "volume", "-port", str(_free_port()),
                        "-dir", vdir, "-mserver", m, "-max", "20",
                        "-scrubInterval", "0",
                    )
                )
                deadline = time.time() + 60
                while time.time() < deadline:
                    try:
                        with _rq.urlopen(
                            f"http://{m}/dir/status", timeout=2
                        ) as r:
                            topo = json.load(r)["Topology"]
                        if any(
                            rk["DataNodes"]
                            for dc in topo.get("DataCenters", [])
                            for rk in dc.get("Racks", [])
                        ):
                            break
                    except OSError:
                        pass
                    time.sleep(0.3)
                else:
                    raise RuntimeError("serve-bench cluster never came up")
                payload = (b"weedload\x00\xff" * 103)[:1024]
                keys = seed_keys(m, 48, payload)
                common = dict(
                    master=m, duration_s=8.0, processes=2,
                    conns_per_proc=128, keys=keys,
                )
                out = {}
                if "hot" in mixes:
                    out["hot"] = run_get_fan(**common)
                if "range" in mixes:
                    out["range"] = run_get_fan(
                        **common, range_every=3, ranges=RANGES
                    )
                if paced_rate > 0:
                    out["paced"] = run_get_fan(**common, rate=paced_rate)
                return out
            finally:
                for p in procs:
                    p.kill()
                for p in procs:
                    try:
                        p.wait(timeout=10)
                    except Exception:  # noqa: BLE001
                        pass

    # throughput arms first; their hot req/s picks the paced schedule
    # (the second epoll pass runs ONLY the paced mix — the closed-loop
    # rows come from the first pass)
    epoll = _run_arm(True, 0.0)
    paced_rate = max(1.0, 0.6 * epoll["hot"]["req_per_sec"] / 256.0)
    epoll["paced"] = _run_arm(True, paced_rate, mixes=())["paced"]
    threaded = _run_arm(False, paced_rate)

    for mix in ("hot", "range"):
        e, t = epoll[mix], threaded[mix]
        ratio = e["req_per_sec"] / t["req_per_sec"] if t["req_per_sec"] else 0.0
        for arm_name, row, vs in (
            (f"serve_{mix}_epoll", e, ratio),
            (f"serve_{mix}_threaded", t, 1.0),
        ):
            _report(
                arm_name,
                row["req_per_sec"],
                "req/s",
                round(vs, 4),
                p50_ms=row["p50_ms"],
                p99_ms=row["p99_ms"],
                p999_ms=row["p999_ms"],
                ops=row["ops"],
                errors=row["errors"],
                connections=row["config"]["connections"],
                co_safe=row["config"]["coordinated_omission_safe"],
            )
    e, t = epoll["paced"], threaded["paced"]
    p99_ratio = t["p99_ms"] / e["p99_ms"] if e["p99_ms"] else 0.0
    for arm_name, row, vs in (
        ("serve_paced_epoll", e, round(p99_ratio, 4)),
        ("serve_paced_threaded", t, 1.0),
    ):
        _report(
            arm_name,
            row["p99_ms"],
            "ms",
            vs,
            p50_ms=row["p50_ms"],
            p999_ms=row["p999_ms"],
            req_per_sec=row["req_per_sec"],
            offered_per_conn=round(paced_rate, 2),
            ops=row["ops"],
            errors=row["errors"],
            connections=row["config"]["connections"],
            co_safe=row["config"]["coordinated_omission_safe"],
        )


def bench_serve_floor() -> None:
    """Syscall-floor serving edge (docs/SERVING.md, BENCH_r15).

    Four metric families for the PR-15 acceptance:

      serve_floor_hot / serve_floor_304 — syscalls per hot GET,
          measured EXTERNALLY: an LD_PRELOAD shim (native/syscount.c)
          counts every libc syscall wrapper in a quiet single-server
          process while one keep-alive connection runs a closed-loop
          window. The designed floor is 3 (epoll_wait + recv + one
          writev'd reply — sendmsg — with the plan served from the C
          fd/offset cache); the 304 window revalidates with
          If-None-Match and must hit the same floor.
      serve_cond_epoll/threaded — 50% conditional-GET mix through a
          CLI cluster: ratio_304 plus the C fast-path hit ratio
          scraped from /status ServeStats (>=90% required).
      serve_flagged_epoll/threaded — mime-flagged keyset (pre-rendered
          header path): same hit-ratio bar.
      serve_adm_shared — volume lead + 2 SO_REUSEPORT workers charging
          ONE mmap'd admission bucket: the measured global admitted
          rate must sit within +/-10% of -admissionRate no matter how
          the kernel spreads the connections.
    """
    import signal
    import socket as _socket
    import subprocess
    import tempfile
    import urllib.request as _rq

    from seaweedfs_tpu.telemetry.weedload import run_get_fan, seed_keys

    # ---------------- part A: syscalls per GET (LD_PRELOAD shim) ----
    native_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "seaweedfs_tpu", "native"
    )
    workdir = tempfile.mkdtemp(prefix="weedfloor")
    shim = os.path.join(workdir, "syscount.so")
    try:
        subprocess.run(
            ["cc", "-O2", "-Wall", "-Wextra", "-Werror", "-shared",
             "-fPIC", "-o", shim, os.path.join(native_dir, "syscount.c"),
             "-ldl"],
            check=True, capture_output=True,
        )
        srv_script = (
            "import json, tempfile, threading, time\n"
            "from seaweedfs_tpu.server.volume_server import VolumeServer\n"
            "from seaweedfs_tpu.storage.file_id import"
            " format_needle_id_cookie\n"
            "from seaweedfs_tpu.storage.needle import Needle\n"
            "from seaweedfs_tpu.util.httpd import WeedHTTPServer\n"
            "d = tempfile.mkdtemp()\n"
            "vs = VolumeServer([d], port=0, scrub_interval=0)\n"
            "vs.store.add_volume(1, '', '000', '')\n"
            "v = vs.store.find_volume(1)\n"
            "n = Needle(cookie=0x11, id=1,"
            " data=(b'weedload\\x00\\xff' * 103)[:1024])\n"
            "v.write_needle(n)\n"
            "srv = WeedHTTPServer(('127.0.0.1', 0),"
            " vs._http_handler_class())\n"
            "srv.trace_name = 'volume'\n"
            "srv.trace_node = 'floor'\n"
            "srv.fast_resolver = vs._make_fast_resolver()\n"
            "srv.native_serve = True\n"
            "threading.Thread(target=srv.serve_forever,"
            " daemon=True).start()\n"
            "print(json.dumps({'port': srv.server_address[1],"
            " 'fid': '1,' + format_needle_id_cookie(1, 0x11),"
            " 'etag': n.etag()}), flush=True)\n"
            "while True:\n"
            "    time.sleep(3600)\n"
        )
        out_path = os.path.join(workdir, "syscount.txt")
        env = dict(
            os.environ, JAX_PLATFORMS="cpu", LD_PRELOAD=shim,
            WEED_SYSCOUNT_OUT=out_path,
        )
        proc = subprocess.Popen(
            [sys.executable, "-c", srv_script],
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        try:
            info = json.loads(proc.stdout.readline())
            port, fid, etag = info["port"], info["fid"], info["etag"]

            def snapshot(prev_gen: int) -> tuple[int, dict]:
                os.kill(proc.pid, signal.SIGUSR2)
                deadline = time.time() + 5
                while time.time() < deadline:
                    try:
                        with open(out_path, encoding="ascii") as f:
                            lines = f.read().splitlines()
                        gen = int(lines[0].split()[1])
                        if gen > prev_gen:
                            return gen, {
                                k: int(v)
                                for k, v in (
                                    ln.split() for ln in lines[1:]
                                )
                            }
                    except (OSError, ValueError, IndexError):
                        pass
                    time.sleep(0.01)
                raise RuntimeError("syscount snapshot timed out")

            def window(req: bytes, n_reqs: int, gen: int):
                """Closed-loop: one keep-alive conn, n_reqs requests."""
                s = _socket.create_connection(("127.0.0.1", port), 10)
                s.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
                try:
                    def one():
                        s.sendall(req)
                        buf = b""
                        while b"\r\n\r\n" not in buf:
                            buf += s.recv(65536)
                        head, _, rest = buf.partition(b"\r\n\r\n")
                        cl = 0
                        for ln in head.split(b"\r\n")[1:]:
                            k, _, val = ln.partition(b":")
                            if k.strip().lower() == b"content-length":
                                cl = int(val.strip())
                        while len(rest) < cl:
                            rest += s.recv(65536)

                    for _ in range(50):
                        one()  # warm: plan cached, fd cached, conn up
                    gen, before = snapshot(gen)
                    for _ in range(n_reqs):
                        one()
                    gen, after = snapshot(gen)
                finally:
                    s.close()
                delta = {
                    k: after[k] - before.get(k, 0)
                    for k in after
                    if after[k] - before.get(k, 0) > 0
                }
                return gen, delta

            n_reqs = 1000
            gen, hot = window(
                f"GET /{fid} HTTP/1.1\r\n\r\n".encode(), n_reqs, 0
            )
            gen, cond = window(
                f"GET /{fid} HTTP/1.1\r\n"
                f'If-None-Match: "{etag}"\r\n\r\n'.encode(),
                n_reqs, gen,
            )
        finally:
            proc.kill()
            proc.wait(timeout=10)
        for name, delta in (("serve_floor_hot", hot),
                            ("serve_floor_304", cond)):
            per = sum(delta.values()) / n_reqs
            _report(
                name, per, "syscalls/req",
                round(3.0 / per, 4) if per else 0.0,
                breakdown={
                    k: round(v / n_reqs, 3)
                    for k, v in sorted(delta.items())
                },
                reqs=n_reqs,
                target="<=3",
            )
    finally:
        import shutil

        shutil.rmtree(workdir, ignore_errors=True)

    # ---------------- parts B+C: CLI clusters -----------------------
    def _free_port():
        with _socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def _spawn(env_extra, *args):
        env = dict(
            os.environ, JAX_PLATFORMS="cpu", WEED_EC_CODEC="cpu",
            **env_extra,
        )
        return subprocess.Popen(
            [
                sys.executable,
                "-m",
                "seaweedfs_tpu",
                *args,
            ],
            env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT,
        )

    def _cluster(env_extra, *vol_args):
        """master + one volume server; yields the master netloc."""
        mport = _free_port()
        m = f"127.0.0.1:{mport}"
        d = tempfile.mkdtemp(prefix="weedfloorcli")
        procs = [_spawn(env_extra, "master", "-port", str(mport),
                        "-mdir", d)]
        vdir = os.path.join(d, "v0")
        os.mkdir(vdir)
        procs.append(
            _spawn(env_extra, "volume", "-port", str(_free_port()),
                   "-dir", vdir, "-mserver", m, "-max", "20",
                   "-scrubInterval", "0", *vol_args)
        )
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                with _rq.urlopen(f"http://{m}/dir/status", timeout=2) as r:
                    topo = json.load(r)["Topology"]
                if any(
                    rk["DataNodes"]
                    for dc in topo.get("DataCenters", [])
                    for rk in dc.get("Racks", [])
                ):
                    return m, procs, d
            except OSError:
                pass
            time.sleep(0.3)
        for p in procs:
            p.kill()
        raise RuntimeError("serve-floor cluster never came up")

    def _teardown(procs, d):
        import shutil

        for p in procs:
            p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001
                pass
        shutil.rmtree(d, ignore_errors=True)

    payload = (b"weedload\x00\xff" * 103)[:1024]

    # conditional + flagged mixes, epoll vs threaded A/B
    arm_rows: dict = {}
    for native in (True, False):
        env_extra = {} if native else {"WEED_NATIVE_SERVE": "0"}
        m, procs, d = _cluster(env_extra)
        try:
            etags: dict = {}
            keys = seed_keys(m, 48, payload, etags=etags)
            # image/png stores a mime flag WITHOUT tripping the write
            # path's transparent gzip (text/* would be stored gzipped,
            # which the fast path declines by design)
            flagged = seed_keys(m, 48, payload, content_type="image/png")
            common = dict(
                master=m, duration_s=6.0, processes=2, conns_per_proc=64,
            )
            arm_rows[("cond", native)] = run_get_fan(
                **common, keys=keys, etags=etags, cond_every=2
            )
            arm_rows[("flagged", native)] = run_get_fan(
                **common, keys=flagged
            )
        finally:
            _teardown(procs, d)
    for mix in ("cond", "flagged"):
        e, t = arm_rows[(mix, True)], arm_rows[(mix, False)]
        ratio = (
            e["req_per_sec"] / t["req_per_sec"] if t["req_per_sec"] else 0.0
        )
        fp = e.get("fast_path") or {}
        for arm_name, row, vs in (
            (f"serve_{mix}_epoll", e, round(ratio, 4)),
            (f"serve_{mix}_threaded", t, 1.0),
        ):
            extra = dict(
                p50_ms=row["p50_ms"],
                p99_ms=row["p99_ms"],
                ops=row["ops"],
                errors=row["errors"],
                ratio_304=row["ratio_304"],
                connections=row["config"]["connections"],
            )
            if row is e and fp:
                extra["fast_path_hit_ratio"] = fp.get("hit_ratio", 0.0)
                extra["fast_path"] = fp
            _report(arm_name, row["req_per_sec"], "req/s", vs, **extra)

    # shared-bucket admission: lead + 2 workers, one mmap'd bucket.
    # The rate sits well below what 128 clients can offer even when
    # every shed reply parks them for the full 1 s retry floor —
    # otherwise tokens go unclaimed and the measurement undershoots.
    rate = 40.0
    m, procs, d = _cluster(
        {}, "-workers", "2", "-admissionRate", str(rate),
        "-admissionBurst", str(rate),
    )
    try:
        keys = seed_keys(m, 48, payload)
        row = run_get_fan(
            master=m, duration_s=15.0, processes=2, conns_per_proc=64,
            keys=keys,
        )
        wall = row["ops"] / row["req_per_sec"] if row["req_per_sec"] else 15.0
        # whatever burst survived the seed phase drains once at window
        # start and contributes at most burst/wall = rate/15 ~ 6.7% on
        # the high side — inside the +/-10% acceptance band, so the
        # plain windowed rate is the honest measurement
        measured = row["ops"] / wall
        _report(
            "serve_adm_shared", measured, "admitted/s",
            round(measured / rate, 4),
            configured_rate=rate,
            ops=row["ops"],
            shed=row["shed"],
            errors=row["errors"],
            connections=row["config"]["connections"],
            target="vs_baseline in [0.9, 1.1]",
        )
    finally:
        _teardown(procs, d)


def bench_qos() -> None:
    """QoS plane A/Bs (docs/QOS.md, BENCH_r09).

    qos_hedge_off / qos_hedge_on — a 2-replica CLI cluster (replication
    010) with one replica behind a SlowReplicaProxy delaying every
    response ~50x; weedload paced CO-safe GET workers rotate their
    primary across replicas. Arms differ ONLY in the hedge knob; each
    arm reports its median-of-3 pass (rig-throttle stalls would
    otherwise decide a max-op p99.9). vs_baseline on the `on` line =
    p99.9 speedup over the off arm (acceptance: >= 2, i.e. hedged
    p99.9 <= 0.5x unhedged, 0 errors). qos_hedge_on_threaded re-runs
    the hedged arm with WEED_NATIVE_SERVE=0 — the A/B holds on BOTH
    serving paths.

    qos_admission_off / qos_admission_on — closed-loop overload: 16
    connections against a threaded-path volume server that saturates
    around 8 (2x sustained overload by offered concurrency; both arms
    WEED_NATIVE_SERVE=0 since an admission-armed server routes through
    the mini loop anyway). Off arm: every request queues behind 16
    in-flight peers and p99 balloons. On arm: `-admissionInflight`
    caps the queue and `-admissionRate` caps the per-client rate, so
    the excess sheds as fast 503 + Retry-After and ACCEPTED requests
    see a short queue. vs_baseline on the `on` line = uncontended_p99
    / accepted_p99 (acceptance: >= 0.5, i.e. accepted-request p99
    within 2x uncontended). Latency here is service time (closed loop,
    no pacing): the queue under test is the SERVER's, and a shed
    request exits the system by design — CO pacing would charge
    client-side schedule debt to requests the server answered quickly.

    qos_group_commit — 64 concurrent writers through the commit seam:
    fsync-per-POST vs -commitWindowUs batching, byte-correct read-back
    enforced. vs_baseline = flushes-per-write reduction (acceptance:
    >= 4).
    """
    import subprocess
    import tempfile
    import threading
    import urllib.request as _rq

    from seaweedfs_tpu.telemetry.weedload import run_load, seed_keys_replicated
    from tests.faults import SlowReplicaProxy

    def _free_port():
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def _spawn(env_extra, *args):
        env = dict(os.environ, JAX_PLATFORMS="cpu", WEED_EC_CODEC="cpu",
                   **env_extra)
        return subprocess.Popen(
            [
                sys.executable,
                "-m",
                "seaweedfs_tpu",
                *args,
            ],
            env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT,
        )

    def _wait_nodes(m, n, deadline_s=60):
        deadline = time.time() + deadline_s
        while time.time() < deadline:
            try:
                with _rq.urlopen(f"http://{m}/dir/status", timeout=2) as r:
                    topo = json.load(r)["Topology"]
                nodes = sum(
                    len(rk["DataNodes"])
                    for dc in topo.get("DataCenters", [])
                    for rk in dc.get("Racks", [])
                )
                if nodes >= n:
                    return
            except OSError:
                pass
            time.sleep(0.3)
        raise RuntimeError("qos bench cluster never became ready")

    def _cluster(d, n_vols, env_extra=None, vol_args=()):
        mport = _free_port()
        m = f"127.0.0.1:{mport}"
        procs = [
            _spawn(env_extra or {}, "master", "-port", str(mport),
                   "-mdir", d, "-telemetryInterval", "0")
        ]
        vol_addrs = []
        for i in range(n_vols):
            vdir = os.path.join(d, f"v{i}")
            os.makedirs(vdir, exist_ok=True)
            vport = _free_port()
            vol_addrs.append(f"127.0.0.1:{vport}")
            procs.append(
                _spawn(
                    env_extra or {}, "volume", "-port", str(vport),
                    "-dir", vdir, "-mserver", m, "-max", "50",
                    "-rack", f"rack{i}", "-scrubInterval", "0", *vol_args,
                )
            )
        _wait_nodes(m, n_vols)
        return m, vol_addrs, procs

    def _kill(procs):
        for p in procs:
            p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001
                pass

    payload = (b"qos\x00\xff" * 205)[:1024]

    # --- leg 1: hedged reads vs an injected slow replica ---------------
    def _hedge_arm(m, keys, hedged):
        """Median-of-3 p99.9: this rig's container throttling injects
        occasional 300-700 ms CPU stalls that land on whichever arm
        happens to be running; with ~70 ops per pass the p99.9 IS the
        max op, so one stall would decide the A/B. Three passes, keep
        the median's full row."""
        env_key = "WEED_QOS_HEDGE"
        prev = os.environ.get(env_key)
        os.environ[env_key] = "1" if hedged else "0"
        try:
            rows = [
                run_load(
                    m, duration_s=8.0, writers=0, readers=2,
                    payload_bytes=1024, rate=3.0, keys=keys, hedge=hedged,
                )["get"]
                for _ in range(3)
            ]
        finally:
            if prev is None:
                os.environ.pop(env_key, None)
            else:
                os.environ[env_key] = prev
        rows.sort(key=lambda r: r["p999_ms"])
        row = rows[1]
        row["p999_runs_ms"] = [r["p999_ms"] for r in rows]
        return row

    def _hedge_pair(env_extra):
        with tempfile.TemporaryDirectory() as d:
            m, vols, procs = _cluster(d, 2, env_extra=env_extra)
            proxy = None
            try:
                keys = seed_keys_replicated(m, 24, payload, "010")
                victim = vols[1]
                # ~50x: loopback GETs run ~3-6 ms; the proxy holds every
                # response 250 ms
                proxy = SlowReplicaProxy(victim, delay_s=0.25)
                slowed = [
                    (fid, [proxy.addr if u == victim else u for u in urls])
                    for fid, urls in keys
                ]
                if not any(victim in urls for _, urls in keys):
                    raise RuntimeError("replication 010 left no replica "
                                       "on the victim server")
                # warmup: absorb the spawn-time CPU storm (client worker
                # processes importing jax starve the server processes on
                # a small rig) so neither measured arm eats it
                run_load(
                    m, duration_s=2.5, writers=0, readers=2,
                    payload_bytes=1024, rate=2.0, keys=slowed,
                )
                off = _hedge_arm(m, slowed, hedged=False)
                on = _hedge_arm(m, slowed, hedged=True)
                return off, on
            finally:
                if proxy is not None:
                    proxy.stop()
                _kill(procs)

    off, on = _hedge_pair(env_extra=None)
    _report(
        "qos_hedge_off", off["p999_ms"], "ms",
        1.0 if off["errors"] == 0 else 0.0,
        p50_ms=off["p50_ms"], p99_ms=off["p99_ms"], ops=off["ops"],
        errors=off["errors"], co_safe=True, slow_replica_delay_ms=250,
    )
    _report(
        "qos_hedge_on", on["p999_ms"], "ms",
        (off["p999_ms"] / on["p999_ms"]) if on["p999_ms"] > 0 else 0.0,
        p50_ms=on["p50_ms"], p99_ms=on["p99_ms"], ops=on["ops"],
        errors=on["errors"], co_safe=True,
        hedge_fired=on.get("hedge_fired", 0),
        hedge_won=on.get("hedge_won", 0),
        hedge_cancelled=on.get("hedge_cancelled", 0),
        p999_ratio_vs_unhedged=round(
            on["p999_ms"] / off["p999_ms"], 4
        ) if off["p999_ms"] > 0 else None,
    )
    _, on_thr = _hedge_pair(env_extra={"WEED_NATIVE_SERVE": "0"})
    _report(
        "qos_hedge_on_threaded", on_thr["p999_ms"], "ms",
        (off["p999_ms"] / on_thr["p999_ms"]) if on_thr["p999_ms"] > 0 else 0.0,
        ops=on_thr["ops"], errors=on_thr["errors"],
        hedge_fired=on_thr.get("hedge_fired", 0),
        hedge_won=on_thr.get("hedge_won", 0),
        serving_path="threaded (WEED_NATIVE_SERVE=0)",
    )

    # --- leg 2: admission control under 2x overload --------------------
    # Both arms run the threaded serving path (WEED_NATIVE_SERVE=0):
    # an admission-armed volume server routes every request through the
    # mini loop anyway (the zero-copy fast path stands down so the
    # token bucket sees every GET), so probing capacity on the C fast
    # path would compare different serving engines, not admission.
    from seaweedfs_tpu.telemetry.weedload import run_get_fan, seed_keys

    threaded = {"WEED_NATIVE_SERVE": "0"}
    # client shape: 2 selector-driven fan processes x 8 keep-alive conns
    # = 16 closed-loop connections — NOT 16 worker processes, whose
    # spawn-time jax imports would starve the servers and measure the
    # rig, not admission (the get_fan worker exists for exactly this).
    # 64 KiB bodies: admission creates headroom only when SERVICE costs
    # more than parse+reject — with tiny bodies a shed costs the same
    # as full service and refusing work frees nothing.
    big = (b"admission\x00\xff" * 5958)[: 64 << 10]
    with tempfile.TemporaryDirectory() as d:
        m, vols, procs = _cluster(d, 1, env_extra=threaded)
        try:
            keys = seed_keys(m, 24, big)
            probe = run_get_fan(
                m, duration_s=3.0, processes=1, conns_per_proc=4,
                payload_bytes=len(big), keys=keys,
            )
            capacity = max(probe["req_per_sec"], 20.0)
            base = run_get_fan(
                m, duration_s=4.0, processes=1, conns_per_proc=2,
                payload_bytes=len(big), keys=keys,
            )
            over_off = run_get_fan(
                m, duration_s=6.0, processes=2, conns_per_proc=8,
                payload_bytes=len(big), keys=keys,
            )
        finally:
            _kill(procs)
    with tempfile.TemporaryDirectory() as d:
        admit_rate = max(capacity * 0.6, 10.0)
        m, vols, procs = _cluster(
            d, 1,
            env_extra=threaded,
            vol_args=(
                "-admissionRate", str(admit_rate),
                "-admissionBurst", str(admit_rate),
                "-admissionInflight", "2",
            ),
        )
        try:
            keys = seed_keys(m, 24, big)
            over_on = run_get_fan(
                m, duration_s=6.0, processes=2, conns_per_proc=8,
                payload_bytes=len(big), keys=keys,
            )
        finally:
            _kill(procs)
    _report(
        "qos_admission_off", over_off["p99_ms"], "ms",
        (base["p99_ms"] / over_off["p99_ms"])
        if over_off["p99_ms"] > 0 else 0.0,
        uncontended_p99_ms=base["p99_ms"], capacity_req_s=round(capacity, 1),
        overload_connections=16, ops=over_off["ops"],
        errors=over_off["errors"],
    )
    _report(
        "qos_admission_on", over_on["p99_ms"], "ms",
        (base["p99_ms"] / over_on["p99_ms"])
        if over_on["p99_ms"] > 0 else 0.0,
        uncontended_p99_ms=base["p99_ms"],
        admission_rate_req_s=round(admit_rate, 1),
        admission_inflight_cap=2,
        overload_connections=16,
        shed=over_on.get("shed", 0),
        shed_p99_ms=over_on.get("shed_p99_ms"),
        accepted_ops=over_on["ops"], errors=over_on["errors"],
        accepted_req_s=over_on["req_per_sec"],
        p99_ratio_vs_uncontended=round(
            over_on["p99_ms"] / base["p99_ms"], 4
        ) if base["p99_ms"] > 0 else None,
    )

    # --- leg 3: group commit — flushes per POST at concurrency 64 ------
    from seaweedfs_tpu.qos.group_commit import GroupCommitter
    from seaweedfs_tpu.stats.metrics import COMMIT_FLUSHES
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    def _needle(i, tag):
        n = Needle(
            cookie=0xC0FFEE, id=10_000 + i,
            data=(b"%s-%03d\x00\xff" % (tag, i)) * 40,
        )
        n.set_has_last_modified_date()
        n.last_modified = 1700000000
        return n

    n_writers = 64

    def _commit_arm(d, name, window_us):
        os.mkdir(os.path.join(d, name))
        v = Volume(os.path.join(d, name), 1)
        gc = GroupCommitter(window_us=window_us, fsync=True)
        before = COMMIT_FLUSHES.value()
        barrier = threading.Barrier(n_writers)
        errs = []

        def w(i):
            try:
                barrier.wait(10)
                gc.write(v, _needle(i, name.encode()))
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=w, args=(i,)) for i in range(n_writers)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        wall = time.perf_counter() - t0
        if errs:
            raise RuntimeError(f"group-commit arm {name}: {errs[:2]}")
        flushes = COMMIT_FLUSHES.value() - before
        # byte-correctness: every needle reads back exactly
        for i in range(n_writers):
            got = bytes(v.read_needle(10_000 + i).data)
            want = (b"%s-%03d\x00\xff" % (name.encode(), i)) * 40
            assert got == want, f"needle {i} corrupted in arm {name}"
        v.close()
        return flushes, wall

    with tempfile.TemporaryDirectory() as d:
        flushes_off, wall_off = _commit_arm(d, "pp", 0)  # fsync-per-POST
        flushes_on, wall_on = _commit_arm(d, "gc", 2000)
    _report(
        "qos_group_commit", flushes_on / n_writers, "flushes/post",
        (flushes_off / max(flushes_on, 1)),
        flushes_per_post_off=round(flushes_off / n_writers, 4),
        flushes_per_post_on=round(flushes_on / n_writers, 4),
        concurrency=n_writers,
        wall_off_s=round(wall_off, 3), wall_on_s=round(wall_on, 3),
        byte_identical_readback=True,
    )


def bench_degraded() -> None:
    """Degraded-read fast path + repair-bandwidth-frugal rebuild A/B
    (docs/SCRUB.md degraded section, BENCH_r10).

    degraded_native / degraded_threaded — a 3-node CLI cluster per
    serving path (`WEED_NATIVE_SERVE=0` is the lever): seed one volume,
    ec.encode it, measure a paced CO-safe healthy GET pass against the
    shard-0 holder, kill shard 0 over the /ec/quarantine operator route
    (tests/faults.DeadShard), then measure two degraded passes — the
    first pays the k-shard gather + decode per tile (cold), the second
    serves every interval from the reconstructed-tile cache. weedload's
    degraded workers verify body LENGTH per GET, so errors:0 certifies
    reconstruction. Acceptance: warm degraded p99 <= 3x healthy p99 on
    BOTH paths, warm p50 <= 1.2x healthy p50, tile-cache hits observed
    on /metrics, 0 errors.

    degraded_rebuild — rebuild shard 0 ON the warm node (its cached
    degraded tiles seed the repair session), then read bytes-moved-
    per-rebuilt-byte off the weed_ec_repair_bytes_* counters.
    Acceptance: total moved <= 8x rebuilt (naive k-gather is 10x),
    donated bytes > 0 (piggyback engaged)."""
    import io
    import subprocess
    import tempfile
    import urllib.request as _rq

    from seaweedfs_tpu.pb import rpc, master_pb2
    from seaweedfs_tpu.telemetry.parse import parse_prometheus_text
    from seaweedfs_tpu.telemetry.weedload import run_load
    from tests.faults import DeadShard

    def _free_port():
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def _spawn(env_extra, *args):
        env = dict(os.environ, JAX_PLATFORMS="cpu", WEED_EC_CODEC="cpu",
                   **env_extra)
        return subprocess.Popen(
            [
                sys.executable,
                "-m",
                "seaweedfs_tpu",
                *args,
            ],
            env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT,
        )

    def _wait_nodes(m, n, deadline_s=60):
        deadline = time.time() + deadline_s
        while time.time() < deadline:
            try:
                with _rq.urlopen(f"http://{m}/dir/status", timeout=2) as r:
                    topo = json.load(r)["Topology"]
                nodes = sum(
                    len(rk["DataNodes"])
                    for dc in topo.get("DataCenters", [])
                    for rk in dc.get("Racks", [])
                )
                if nodes >= n:
                    return
            except OSError:
                pass
            time.sleep(0.3)
        raise RuntimeError("degraded bench cluster never became ready")

    def _kill(procs):
        for p in procs:
            p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001
                pass

    def _scrape(addr) -> dict:
        with _rq.urlopen(f"http://{addr}/metrics", timeout=10) as r:
            text = r.read().decode()
        out: dict = {}
        for name, labels, value in parse_prometheus_text(text):
            out[(name, labels)] = value
        return out

    def _counter(m, name, **labels):
        key = tuple(sorted(labels.items()))
        return m.get((name, key), 0.0)

    payload = (b"degraded\x00\xff" * 205)[:2048]

    def _arm(tag, env_extra):
        with tempfile.TemporaryDirectory() as d:
            mport = _free_port()
            m = f"127.0.0.1:{mport}"
            procs = [
                _spawn(env_extra, "master", "-port", str(mport),
                       "-mdir", d, "-telemetryInterval", "0")
            ]
            for i in range(3):
                vdir = os.path.join(d, f"v{i}")
                os.makedirs(vdir, exist_ok=True)
                procs.append(
                    _spawn(
                        env_extra, "volume", "-port", str(_free_port()),
                        "-dir", vdir, "-mserver", m, "-max", "50",
                        "-rack", f"rack{i}", "-scrubInterval", "0",
                    )
                )
            try:
                _wait_nodes(m, 3)
                # seed one keyset; assigns scatter across writable
                # volumes, so keep the most-loaded vid (same shape as
                # util.availability.write_keyset, minus its same-rack
                # replication demand — this cluster is one node/rack)
                by_vid: dict[int, dict] = {}
                for _ in range(40):
                    with _rq.urlopen(
                        f"http://{m}/dir/assign?collection=deg{tag}",
                        timeout=10,
                    ) as r:
                        a = json.load(r)
                    _rq.urlopen(
                        _rq.Request(
                            f"http://{a['url']}/{a['fid']}", data=payload,
                            method="POST",
                            headers={
                                "Content-Type": "application/octet-stream"
                            },
                        ),
                        timeout=10,
                    ).close()
                    fid_vid = int(a["fid"].partition(",")[0])
                    by_vid.setdefault(fid_vid, {})[a["fid"]] = payload
                vid = max(by_vid, key=lambda v: len(by_vid[v]))
                keys = by_vid[vid]
                from seaweedfs_tpu.shell.command_env import CommandEnv
                from seaweedfs_tpu.shell.commands import do_ec_encode

                env = CommandEnv([m])
                do_ec_encode(env, vid, f"deg{tag}", io.StringIO())
                # the shard-0 holder: all data of a <1MB .dat stripes
                # into block 0 = shard 0, so it serves healthy reads
                # locally and degraded reads after the kill
                with rpc.dial(f"127.0.0.1:{mport + 10000}") as ch:
                    resp = rpc.master_stub(ch).LookupEcVolume(
                        master_pb2.LookupEcVolumeRequest(volume_id=vid),
                        timeout=10,
                    )
                holder0 = next(
                    e.locations[0].url
                    for e in resp.shard_id_locations
                    if e.shard_id == 0 and e.locations
                )
                lkeys = [(fid, holder0) for fid in keys]

                def pass_(duration, rate):
                    return run_load(
                        m, duration_s=duration, writers=0, readers=2,
                        payload_bytes=len(payload), rate=rate, keys=lkeys,
                        verify_bytes=len(payload),
                    )["get"]

                pass_(2.5, 10.0)  # warmup: spawn-time jax import storm
                healthy = pass_(6.0, 20.0)
                m0 = _scrape(holder0)
                DeadShard(vid, sid=0, addr=holder0).kill()
                cold = pass_(6.0, 20.0)
                warm = pass_(6.0, 20.0)
                m1 = _scrape(holder0)
                hits = (
                    _counter(m1, "weed_ec_tile_cache_total", result="hit")
                    - _counter(m0, "weed_ec_tile_cache_total", result="hit")
                )
                misses = (
                    _counter(m1, "weed_ec_tile_cache_total", result="miss")
                    - _counter(m0, "weed_ec_tile_cache_total", result="miss")
                )
                degraded_total = (
                    _counter(m1, "weed_ec_degraded_read_total")
                    - _counter(m0, "weed_ec_degraded_read_total")
                )
                row = {
                    "healthy": healthy, "cold": cold, "warm": warm,
                    "tile_hits": hits, "tile_misses": misses,
                    "degraded_reads": degraded_total,
                }
                if tag != "native":
                    return row, None
                # rebuild leg (native arm only — the repair plane does
                # not touch the serving path): rebuild ON the warm
                # holder so its cached tiles piggyback into the session
                from seaweedfs_tpu.pb import volume_pb2

                r0 = _scrape(holder0)
                host, _, port = holder0.partition(":")
                with rpc.dial(f"{host}:{int(port) + 10000}") as ch:
                    rresp = rpc.volume_stub(ch).VolumeEcShardsRebuild(
                        volume_pb2.VolumeEcShardsRebuildRequest(
                            volume_id=vid, collection=f"deg{tag}"
                        ),
                        timeout=300,
                    )
                    rpc.volume_stub(ch).VolumeEcShardsMount(
                        volume_pb2.VolumeEcShardsMountRequest(
                            volume_id=vid, collection=f"deg{tag}",
                            shard_ids=list(rresp.rebuilt_shard_ids),
                        ),
                        timeout=30,
                    )
                r1 = _scrape(holder0)
                reb = {
                    "rebuilt_shards": list(rresp.rebuilt_shard_ids),
                    "read_local": _counter(
                        r1, "weed_ec_repair_bytes_read_total", source="local"
                    ) - _counter(
                        r0, "weed_ec_repair_bytes_read_total", source="local"
                    ),
                    "read_remote": _counter(
                        r1, "weed_ec_repair_bytes_read_total", source="remote"
                    ) - _counter(
                        r0, "weed_ec_repair_bytes_read_total", source="remote"
                    ),
                    "written": _counter(
                        r1, "weed_ec_repair_bytes_written_total"
                    ) - _counter(r0, "weed_ec_repair_bytes_written_total"),
                    "donated": _counter(
                        r1, "weed_ec_repair_donated_bytes_total"
                    ) - _counter(r0, "weed_ec_repair_donated_bytes_total"),
                }
                # post-rebuild: reads must still verify byte lengths
                reb["post_rebuild"] = pass_(3.0, 10.0)
                return row, reb
            finally:
                _kill(procs)

    for tag, env_extra in (
        ("native", {}),
        ("threaded", {"WEED_NATIVE_SERVE": "0"}),
    ):
        row, reb = _arm(tag, env_extra)
        healthy, cold, warm = row["healthy"], row["cold"], row["warm"]
        errors = healthy["errors"] + cold["errors"] + warm["errors"]
        _report(
            f"degraded_{tag}", warm["p99_ms"], "ms",
            (healthy["p99_ms"] * 3.0 / warm["p99_ms"])
            if warm["p99_ms"] > 0 else 0.0,  # >=1 == within the 3x bound
            healthy_p50_ms=healthy["p50_ms"], healthy_p99_ms=healthy["p99_ms"],
            cold_p99_ms=cold["p99_ms"], warm_p50_ms=warm["p50_ms"],
            warm_p50_vs_healthy_p50=round(
                warm["p50_ms"] / healthy["p50_ms"], 4
            ) if healthy["p50_ms"] > 0 else None,
            degraded_p99_vs_healthy_p99=round(
                warm["p99_ms"] / healthy["p99_ms"], 4
            ) if healthy["p99_ms"] > 0 else None,
            tile_cache_hits=row["tile_hits"],
            tile_cache_misses=row["tile_misses"],
            degraded_reads=row["degraded_reads"],
            ops=healthy["ops"] + cold["ops"] + warm["ops"],
            errors=errors, co_safe=True,
            serving_path=(
                "threaded (WEED_NATIVE_SERVE=0)" if tag == "threaded"
                else "native"
            ),
        )
        if reb is not None:
            moved = reb["read_local"] + reb["read_remote"]
            ratio = moved / reb["written"] if reb["written"] else 0.0
            _report(
                "degraded_rebuild", ratio, "bytes-moved/rebuilt-byte",
                (10.0 / ratio) if ratio > 0 else 0.0,  # vs naive k=10
                read_local_bytes=reb["read_local"],
                read_remote_bytes=reb["read_remote"],
                network_moved_per_rebuilt=round(
                    reb["read_remote"] / reb["written"], 4
                ) if reb["written"] else None,
                written_bytes=reb["written"],
                donated_bytes=reb["donated"],
                rebuilt_shards=reb["rebuilt_shards"],
                post_rebuild_errors=reb["post_rebuild"]["errors"],
            )


def bench_chaos_soak(minutes: float) -> None:
    """`bench.py chaos --soak <minutes>`: long-running background chaos
    (docs/CHAOS.md). One live cluster (master + healthy replica +
    proxied replica, replication=010) runs a continuous writer fan
    while the soak driver cycles fault regimes through the ChaosProxy
    pair — blackhole partition, 250 ms latency, 1 MB/s bandwidth cap,
    30% connection drop — healing between cycles and checking the
    invariants EVERY cycle: a sampled read-back of everything acked so
    far (no acked-write loss), retry amplification ≤ 1.15×, and a
    bounded time-to-recover probe after each heal. One JSON line per
    cycle; a cycle that breaks an invariant fails the run immediately
    (a soak that only reports at the end hides which fault did it).

    weedscope rides the soak as the standing SLO gate: the master runs
    a telemetry collector with seconds-scale burn windows, the run ends
    with the `chaos_soak_slo_scorecard` line (availability, accepted
    p99.9, retry amplification, MTTR, per-objective burn verdicts), and
    a deterministically FORCED breach (synthetic slow observations into
    the shared in-process registry every cycle) must fire the burn-rate
    alert and produce an alert-triggered capsule on >= 2 distinct
    nodes — the cross-node incident-capsule acceptance check."""
    import tempfile
    import threading as _threading

    # read at capsule-module import (inside the MasterServer ctor below):
    # a short cooldown lets the end-of-soak re-drive capture evidence
    # even if the alert's one firing edge landed mid-fault
    os.environ.setdefault("WEED_CAPSULE_COOLDOWN_S", "5")

    from seaweedfs_tpu.analysis.chaos import ProxyPair
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.client import retry as retry_mod
    from seaweedfs_tpu.server.master_server import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.stats.metrics import HTTP_REQUEST_HISTOGRAM
    from seaweedfs_tpu.telemetry import capsule as capsule_mod
    from seaweedfs_tpu.telemetry import slo as slo_mod
    from seaweedfs_tpu.util import deadline as dl_mod
    from seaweedfs_tpu.util.availability import free_port

    deadline_wall = time.time() + minutes * 60.0
    with tempfile.TemporaryDirectory() as d:
        capsule_mod.set_dir(tempfile.mkdtemp(dir=d))
        master = MasterServer(
            port=free_port(), volume_size_limit_mb=64, vacuum_interval=0,
            telemetry_interval=1.0,
            telemetry_kwargs={
                "slo_fast_s": 10.0,
                "slo_slow_s": 30.0,
                "slo_objectives": list(slo_mod.DEFAULT_OBJECTIVES) + [
                    slo_mod.SLOObjective(
                        "soak-forced-breach", "latency", 0.999,
                        family="weed_http_request_seconds",
                        threshold_s=0.5,
                    )
                ],
            },
        )
        master.start()
        maddr = f"127.0.0.1:{master.port}"
        vs_a = VolumeServer(
            [tempfile.mkdtemp(dir=d)], port=free_port(), master=maddr,
            heartbeat_interval=0.2, max_volume_counts=[200], rack="r0",
        )
        vs_a.start()
        b_port = free_port()
        pair = ProxyPair(f"127.0.0.1:{b_port}")
        vs_b = VolumeServer(
            [tempfile.mkdtemp(dir=d)], port=b_port, master=maddr,
            heartbeat_interval=0.2, max_volume_counts=[200], rack="r1",
            announce=pair.addr,
        )
        vs_b.start()
        stop = _threading.Event()
        acked: dict[str, bytes] = {}
        counters = {"ok": 0, "failed": 0}
        lock = _threading.Lock()
        policy = retry_mod.RetryPolicy(
            attempts=3, backoff_ms=50, backoff_max_ms=400,
            retry_on=(RuntimeError, OSError), label="bench-chaos-soak",
            cost=2.0,
        )

        def writer(w: int) -> None:
            i = 0
            while not stop.is_set():
                payload = (f"soak w{w} i{i} ".encode() * 30)[:512]
                i += 1
                try:
                    def one(_attempt):
                        with dl_mod.scope(dl_mod.Deadline.after(2.0)):
                            ar, _ = op.with_master_failover(
                                [maddr],
                                lambda m: op.assign(m, replication="010"),
                            )
                            ur = op.upload(
                                f"{ar.url}/{ar.fid}", payload, jwt=ar.auth
                            )
                        if ur.error:
                            raise RuntimeError(ur.error)
                        return ar.fid
                    fid = policy.run(one)
                except Exception:  # noqa: BLE001 — counted, audited
                    with lock:
                        counters["failed"] += 1
                    continue
                with lock:
                    acked[fid] = payload
                    counters["ok"] += 1
                time.sleep(0.02)

        try:
            t0 = time.time()
            while time.time() - t0 < 30 and len(master.topology.data_nodes()) < 2:
                time.sleep(0.05)
            writers = [
                _threading.Thread(target=writer, args=(w,), daemon=True)
                for w in range(3)
            ]
            for t in writers:
                t.start()

            def fault_partition():
                pair.partition()

            def fault_latency():
                pair.http.response.latency_s = 0.25
                pair.grpc.response.latency_s = 0.25

            def fault_bandwidth():
                pair.http.response.bandwidth_bps = 1 << 20
                pair.grpc.response.bandwidth_bps = 1 << 20

            def fault_drop():
                pair.http.request.drop_conn_p = 0.30
                pair.grpc.request.drop_conn_p = 0.30

            regimes = [
                ("partition", fault_partition),
                ("latency_250ms", fault_latency),
                ("bandwidth_1mbs", fault_bandwidth),
                ("drop_conn_30pct", fault_drop),
            ]
            cycle = 0
            while time.time() < deadline_wall:
                name, arm = regimes[cycle % len(regimes)]
                spent0 = retry_mod.DEFAULT_BUDGET.spent
                with lock:
                    ok0 = counters["ok"]
                arm()
                time.sleep(min(10.0, max(2.0, deadline_wall - time.time())))
                pair.heal()
                # forced SLO breach (weedscope acceptance): synthetic
                # slow observations into the shared in-process registry
                # keep soak-forced-breach burning in every scrape window
                # without touching the real serving path
                for _ in range(5):
                    HTTP_REQUEST_HISTOGRAM.observe(8.0, "volume", "GET")
                # time-to-recover: first clean replicated write after heal
                t_heal = time.perf_counter()
                recovered = None
                while time.perf_counter() - t_heal < 30:
                    try:
                        with dl_mod.scope(dl_mod.Deadline.after(2.0)):
                            ar, _ = op.with_master_failover(
                                [maddr],
                                lambda m: op.assign(m, replication="010"),
                            )
                            ur = op.upload(
                                f"{ar.url}/{ar.fid}", b"soak probe",
                                jwt=ar.auth,
                            )
                        if not ur.error:
                            recovered = time.perf_counter() - t_heal
                            break
                    except Exception:  # noqa: BLE001 — not yet healed
                        pass
                    time.sleep(0.25)
                # invariant: sampled read-back of the acked set
                with lock:
                    sample = list(acked.items())
                sample = sample[:: max(1, len(sample) // 50)][:50]
                lost = []
                for fid, want in sample:
                    try:
                        url = op.lookup_file_id(maddr, fid)
                        got, _ = op.download(url, timeout=10)
                        if got != want:
                            lost.append(fid)
                    except Exception:  # noqa: BLE001 — classified lost
                        lost.append(fid)
                with lock:
                    ok1, failed = counters["ok"], counters["failed"]
                retried = retry_mod.DEFAULT_BUDGET.spent - spent0
                done = max(1, ok1 - ok0)
                amp = (done + retried) / done
                cycle += 1
                row = {
                    "metric": "chaos_soak_cycle",
                    "cycle": cycle,
                    "regime": name,
                    "acked_total": ok1,
                    "failed_total": failed,
                    "sampled": len(sample),
                    "lost": len(lost),
                    "amplification": round(amp, 3),
                    "time_to_recover_s": (
                        round(recovered, 2) if recovered is not None else None
                    ),
                    "pass": bool(
                        not lost and amp <= 1.15 and recovered is not None
                    ),
                }
                print(json.dumps(row), flush=True)
                if not row["pass"]:
                    raise SystemExit(
                        f"chaos soak cycle {cycle} ({name}) failed: {row}"
                    )
            # --- weedscope soak gate: scorecard + cross-node capsule ---
            tel = master.telemetry
            if cycle == 0:  # sub-cycle soak: still force the breach
                for _ in range(5):
                    HTTP_REQUEST_HISTOGRAM.observe(8.0, "volume", "GET")

            def _forced_row():
                return next(
                    (
                        a for a in tel.alerts.firing()
                        if a["Alert"] == "slo_burn_rate"
                        and a["Target"] == "soak-forced-breach"
                    ),
                    None,
                )

            t_wait = time.time() + 30.0
            while time.time() < t_wait and _forced_row() is None:
                time.sleep(0.5)  # collector scrapes every 1 s
            forced = _forced_row()

            def _alert_nodes() -> set:
                return {
                    c.get("Node", "")
                    for c in capsule_mod.list_capsules()
                    if c.get("Trigger") == "alert"
                }

            # the one pending->firing edge may have landed mid-fault
            # (remote captures through a blackholed proxy fail): with
            # everything healed, re-drive the coordinator on the still-
            # firing row once the capture cooldown has lapsed
            if forced is not None and len(_alert_nodes()) < 3 \
                    and tel.alerts.on_fire is not None:
                time.sleep(6.0)
                tel.alerts.on_fire(forced)
            t_caps = time.time() + 20.0
            nodes = _alert_nodes()
            while time.time() < t_caps and len(nodes) < 3:
                time.sleep(0.5)
                nodes = _alert_nodes()
            cross_node = len(nodes) >= 2
            slo = tel.slo_payload()
            card = slo.get("Scorecard") or {}
            print(json.dumps({
                "metric": "chaos_soak_slo_scorecard",
                "window_s": card.get("WindowSeconds"),
                "availability_pct": card.get("AvailabilityPct"),
                "accepted_p999_ms": card.get("AcceptedP999Ms"),
                "retry_amplification": card.get("RetryAmplification"),
                "mttr_s": card.get("MTTRSeconds"),
                "objectives": {
                    r["Objective"]: r["Verdict"]
                    for r in card.get("Objectives", [])
                },
                "breaching": slo.get("Breaching", []),
                "forced_breach_fired": forced is not None,
                "capsule_nodes": sorted(nodes),
                "cross_node_capsule": cross_node,
                "pass": bool(forced is not None and cross_node),
            }), flush=True)
            if forced is None or not cross_node:
                raise SystemExit(
                    "chaos soak: forced SLO breach did not fire or did "
                    f"not produce a cross-node capsule (nodes={sorted(nodes)})"
                )
            print(json.dumps({
                "metric": "chaos_soak",
                "minutes": minutes,
                "cycles": cycle,
                "acked_total": counters["ok"],
                "pass": True,
            }), flush=True)
        finally:
            stop.set()
            pair.stop()
            vs_b.stop()
            vs_a.stop()
            master.stop()
            capsule_mod.set_dir("")


def bench_chaos() -> None:
    """weedchaos robustness config (docs/CHAOS.md, BENCH_r11).

    Per serving path (`WEED_NATIVE_SERVE=0` is the lever): a master +
    2 volume servers with one replica reachable only through a
    ChaosProxy pair, replication=010 writers under the unified
    RetryPolicy with per-write deadlines. Three phases:

      baseline — healthy cluster, retries disabled: request volume +
        write p99 to compare amplification and recovery against;
      fault — the replica BLACKHOLED (full two-way partition): error
        rate, p99 during the fault, and the retry-amplification
        factor = total upstream requests / work attempted. Acceptance:
        amplification <= 1.15x the no-retry baseline volume (the
        process-wide retry budget's promise — a blackholed replica
        degrades latency/errors, it must not multiply load);
      heal — time-to-recover: seconds from heal() until a replicated
        write round-trips cleanly again, plus the after-heal p99.

    Emits one JSON line per path and writes BENCH_r11.json.

    `bench.py chaos --soak <minutes>` runs the long-background soak
    mode instead (bench_chaos_soak): cycling fault regimes with
    per-cycle invariant checks for hours, not minutes."""
    if "--soak" in sys.argv[1:]:
        idx = sys.argv.index("--soak")
        try:
            minutes = float(sys.argv[idx + 1])
        except (IndexError, ValueError):
            raise SystemExit("usage: bench.py chaos --soak <minutes>")
        return bench_chaos_soak(minutes)
    import tempfile
    import threading as _threading

    from seaweedfs_tpu.analysis.chaos import ProxyPair
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.client import retry as retry_mod
    from seaweedfs_tpu.server.master_server import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.util import deadline as dl_mod
    from seaweedfs_tpu.util.availability import free_port
    from seaweedfs_tpu.stats.quantile import percentile

    results = []

    def one_path(native: str) -> dict:
        os.environ["WEED_NATIVE_SERVE"] = native
        label = "native" if native == "1" else "threaded"
        with tempfile.TemporaryDirectory() as d:
            master = MasterServer(
                port=free_port(), volume_size_limit_mb=64, vacuum_interval=0
            )
            master.start()
            maddr = f"127.0.0.1:{master.port}"
            vs_a = VolumeServer(
                [tempfile.mkdtemp(dir=d)], port=free_port(), master=maddr,
                heartbeat_interval=0.2, max_volume_counts=[100], rack="r0",
            )
            vs_a.start()
            b_port = free_port()
            pair = ProxyPair(f"127.0.0.1:{b_port}")
            # a different rack: replication=010 places the replica in
            # another rack, which is what routes every write through
            # the (blackholable) announced pair
            vs_b = VolumeServer(
                [tempfile.mkdtemp(dir=d)], port=b_port, master=maddr,
                heartbeat_interval=0.2, max_volume_counts=[100], rack="r1",
                announce=pair.addr,
            )
            vs_b.start()
            try:
                deadline_t = time.time() + 45
                while (
                    time.time() < deadline_t
                    and len(master.topology.data_nodes()) < 2
                ):
                    time.sleep(0.05)

                no_retry = retry_mod.RetryPolicy(attempts=1, budget=None)

                def write_round(lat, budget_s=2.0):
                    """One write op = 2 upstream requests (assign +
                    upload), whole-op deadline per attempt."""
                    t0 = time.perf_counter()
                    try:
                        with dl_mod.scope(dl_mod.Deadline.after(budget_s)):
                            ar, _ = op.with_master_failover(
                                [maddr],
                                lambda m: op.assign(m, replication="010"),
                                policy=no_retry,
                            )
                            ur = op.upload(
                                f"{ar.url}/{ar.fid}", b"chaos bench " * 40,
                                jwt=ar.auth,
                            )
                    finally:
                        lat.append(time.perf_counter() - t0)
                    if ur.error:
                        raise RuntimeError(ur.error)

                def fan(n_writers, n_writes, op_policy, budget_s=2.0):
                    """Writer fan; each failed op is retried through
                    `op_policy` (None = no retries). Returns request-
                    volume accounting for the amplification audit."""
                    lat: list[float] = []
                    failed = [0]
                    lock = _threading.Lock()
                    spent0 = retry_mod.DEFAULT_BUDGET.spent

                    def one_op():
                        if op_policy is None:
                            return write_round(lat, budget_s)
                        return op_policy.run(
                            lambda a: write_round(lat, budget_s)
                        )

                    def writer():
                        for _ in range(n_writes):
                            try:
                                one_op()
                            except Exception:
                                with lock:
                                    failed[0] += 1

                    ts = [
                        _threading.Thread(target=writer, daemon=True)
                        for _ in range(n_writers)
                    ]
                    for t in ts:
                        t.start()
                    for t in ts:
                        t.join(timeout=180)
                    attempts = n_writers * n_writes
                    retried_ops = retry_mod.DEFAULT_BUDGET.spent - spent0
                    return {
                        "attempts": attempts,
                        "failed": failed[0],
                        # 2 requests per op, retried ops re-issue both
                        "requests": 2 * (attempts + retried_ops),
                        "retried_ops": retried_ops,
                        "p99_ms": round(
                            percentile(lat, 0.99) * 1000, 1
                        ) if lat else None,
                    }

                base = fan(8, 15, None)

                # the unified policy + the process-wide budget: what a
                # naive client-side retry loop becomes under weedchaos.
                # Enough offered load that the dry-bucket probe trickle
                # and the min_reserve are noise against the ratio term —
                # the regime the ≤1.15x bound is stated for.
                storm_policy = retry_mod.RetryPolicy(
                    attempts=3, backoff_ms=50, backoff_max_ms=300,
                    retry_on=(RuntimeError, OSError),
                    label="bench-chaos-write",
                    # one retried write op reissues assign+upload
                    cost=2.0,
                )
                pair.partition()
                fault = fan(8, 60, storm_policy, budget_s=0.3)
                amp = fault["requests"] / (2 * max(1, fault["attempts"]))

                pair.heal()
                t_heal = time.perf_counter()
                recovered = None
                probe_lat: list[float] = []
                while time.perf_counter() - t_heal < 60:
                    try:
                        write_round(probe_lat)
                        recovered = time.perf_counter() - t_heal
                        break
                    except Exception:
                        time.sleep(0.25)
                after = fan(3, 10, None)
                row = {
                    "metric": "chaos",
                    "serving_path": label,
                    "baseline_p99_ms": base["p99_ms"],
                    "baseline_requests": base["requests"],
                    "baseline_errors": base["failed"],
                    "fault_error_rate": round(
                        fault["failed"] / max(1, fault["attempts"]), 3
                    ),
                    "fault_p99_ms": fault["p99_ms"],
                    "retry_amplification": round(amp, 3),
                    "amplification_bound": 1.15,
                    "time_to_recover_s": (
                        round(recovered, 2) if recovered is not None else None
                    ),
                    "after_heal_p99_ms": after["p99_ms"],
                    "after_heal_errors": after["failed"],
                    "pass": bool(
                        base["failed"] == 0
                        and amp <= 1.15
                        and recovered is not None
                        and after["failed"] == 0
                    ),
                }
                print(json.dumps(row))
                return row
            finally:
                pair.stop()
                vs_b.stop()
                vs_a.stop()
                master.stop()

    prior_native = os.environ.get("WEED_NATIVE_SERVE")
    try:
        for native in ("1", "0"):
            results.append(one_path(native))
    finally:
        if prior_native is None:
            os.environ.pop("WEED_NATIVE_SERVE", None)
        else:
            os.environ["WEED_NATIVE_SERVE"] = prior_native
    with open(os.path.join(os.path.dirname(__file__), "BENCH_r11.json"), "w") as f:
        json.dump({"chaos": results}, f, indent=2)


def bench_tier() -> None:
    """Lifecycle-tiering round (docs/TIERING.md, BENCH_r14), three legs:

    - tier_out_e2e / tier_in_e2e: GB/s moving a sealed EC volume's 14
      shard files to/from the local-dir backend, judged against the
      measured disk ceiling (both directions are one full sequential
      copy; the recall also pays the .ecc CRC verify).
    - replication_lag: per-event latency through the partitioned
      logqueue + the runner's poll/commit loop, producer and consumer
      concurrent; p99 is the SLO number RULE_REPL_LAG guards.
    - arbiter_ab: rebuild time-to-repair alone vs sharing the
      bandwidth arbiter with a flat-out handoff replay. The weighted
      shares (rebuild .45 / handoff .20) bound the contended TTR at
      <= 1.5x uncontended — the acceptance ratio.

    Writes BENCH_r14.json.
    """
    import random
    import tempfile
    import threading

    from seaweedfs_tpu.ec import ec_files
    from seaweedfs_tpu.ec.codec import new_encoder
    from seaweedfs_tpu.ec.ecc_sidecar import write_sidecar
    from seaweedfs_tpu.notification.logqueue import PartitionedLogQueue
    from seaweedfs_tpu.pb import filer_pb2 as fpb
    from seaweedfs_tpu.replication.replicate_runner import _consume_logqueue
    from seaweedfs_tpu.scrub.arbiter import BandwidthArbiter
    from seaweedfs_tpu.storage import backend as bk
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store
    from seaweedfs_tpu.storage.volume import Volume
    from seaweedfs_tpu.tier.ec_tier import tier_in_ec, tier_out_ec
    from seaweedfs_tpu.util.crc import crc32c

    rows = []

    # -- leg 1: tier-out / tier-in GB/s vs the disk ceiling ------------
    with tempfile.TemporaryDirectory() as d:
        ceiling = _disk_ceiling(d)
        vol_dir = os.path.join(d, "vols")
        os.makedirs(vol_dir)
        v = Volume(vol_dir, 5)
        rng = random.Random(5)
        chunk = rng.randbytes(1024 * 1024)
        for k in range(1, 65):  # 64 MiB of needle data
            v.write_needle(Needle(cookie=0xBEEF, id=k, data=chunk))
        v.close()
        base = os.path.join(vol_dir, "5")
        ec_files.write_ec_files(base, rs=new_encoder(backend="cpu"))
        ec_files.write_sorted_file_from_idx(base)
        os.remove(base + ".dat")
        os.remove(base + ".idx")
        crcs = {}
        for sid in range(14):
            with open(base + ec_files.to_ext(sid), "rb") as f:
                crcs[sid] = crc32c(f.read())
        write_sidecar(base, crcs)
        bdir = os.path.join(d, "backend")
        os.makedirs(bdir)
        bk.ensure_builtin_factories()
        inst = f"bench{os.getpid()}"
        bk.load_backend_config(
            {"dir": {inst: {"enabled": True, "dir": bdir}}}
        )
        store = Store([vol_dir], ec_backend="cpu")
        t0 = time.perf_counter()
        res = tier_out_ec(store, 5, f"dir.{inst}")
        out_s = time.perf_counter() - t0
        moved = res["Bytes"]
        t0 = time.perf_counter()
        res_in = tier_in_ec(store, 5)
        in_s = time.perf_counter() - t0
        store.close()
        for name, gb_s, secs in (
            ("tier_out_e2e", moved / out_s / 1e9, out_s),
            ("tier_in_e2e", res_in["Bytes"] / in_s / 1e9, in_s),
        ):
            row = {
                "metric": name,
                "value": round(gb_s, 3),
                "unit": "GB/s",
                "bytes": moved,
                "seconds": round(secs, 3),
                **ceiling,
            }
            rows.append(row)
            print(json.dumps(row))

    # -- leg 2: replication lag p99 through logqueue + runner ----------
    with tempfile.TemporaryDirectory() as d:
        lq = PartitionedLogQueue(d, partitions=4)
        lags_ms: list = []
        n_events = 2000

        class _LagSink:
            @staticmethod
            def replicate(key, msg):
                lags_ms.append(
                    (time.perf_counter() - float(msg.new_entry.name)) * 1e3
                )

        def produce():
            for i in range(n_events):
                ev = fpb.EventNotification()
                ev.new_entry.name = repr(time.perf_counter())
                lq.send_message(f"/bench/k{i % 16}", ev)

        tp = threading.Thread(target=produce)
        tp.start()
        rc = _consume_logqueue(
            lq, _LagSink, poll_interval=0.01, stop_after_idle=1.0
        )
        tp.join()
        lq.close()
        lags_ms.sort()
        row = {
            "metric": "replication_lag",
            "value": round(lags_ms[int(0.99 * (len(lags_ms) - 1))], 3),
            "unit": "p99_ms",
            "p50_ms": round(lags_ms[len(lags_ms) // 2], 3),
            "events": len(lags_ms),
            "drain_rc": rc,
            "pass": rc == 0 and len(lags_ms) >= n_events,
        }
        rows.append(row)
        print(json.dumps(row))

    # -- leg 3: arbiter A/B rebuild TTR --------------------------------
    rebuild_bytes = 48_000_000
    take_chunk = 64_000

    def rebuild_ttr(contended: bool) -> float:
        arb = BandwidthArbiter(total_bytes_s=32_000_000.0, yield_window_s=0.0)
        stop = threading.Event()

        def replay_storm():
            while not stop.is_set():
                arb.take("handoff", take_chunk, stop=stop)

        storm = threading.Thread(target=replay_storm)
        if contended:
            storm.start()
            time.sleep(0.05)  # the replay registers as active first
        t0 = time.perf_counter()
        done = 0
        while done < rebuild_bytes:
            arb.take("rebuild", take_chunk)
            done += take_chunk
        elapsed = time.perf_counter() - t0
        stop.set()
        if contended:
            storm.join()
        return elapsed

    alone = rebuild_ttr(False)
    shared = rebuild_ttr(True)
    ratio = shared / alone
    row = {
        "metric": "arbiter_ab",
        "value": round(ratio, 3),
        "unit": "ttr_ratio",
        "ttr_uncontended_s": round(alone, 3),
        "ttr_contended_s": round(shared, 3),
        "bound": 1.5,
        "pass": ratio <= 1.5,
    }
    rows.append(row)
    print(json.dumps(row))

    with open(os.path.join(os.path.dirname(__file__), "BENCH_r14.json"), "w") as f:
        json.dump({"tier": rows}, f, indent=2)


CONFIGS = {
    "encode": bench_encode,
    "rebuild": bench_rebuild,
    "batch": bench_batch,
    "decode4": bench_decode4,
    "shardmap": bench_shardmap,
    "shardmap-verify": bench_shardmap_verify,
    "stream": bench_stream,
    "stream-rebuild": bench_stream_rebuild,
    "rebuild-batch": bench_rebuild_batch,
    "http": bench_http_reqs,
    "shard-hop": bench_shard_hop,
    "migration": bench_migration_with_retry,
    "scrub": bench_scrub,
    "trace": bench_trace,
    "load": bench_load,
    "serve": bench_serve,
    "serve-floor": bench_serve_floor,
    "qos": bench_qos,
    "degraded": bench_degraded,
    "chaos": bench_chaos,
    "tier": bench_tier,
}


def check_native_post() -> int:
    """`bench.py --check`: smoke the C write path — build the native
    extension, run ONE write through the C hot loop and one through the
    forced-Python fallback, and fail loudly unless the .dat/.idx bytes
    and replies are identical. Cheap enough for the tier-1 budget; the
    full matrix lives in tests/test_native_post.py."""
    import tempfile

    from seaweedfs_tpu.server import write_path
    from seaweedfs_tpu.storage.file_id import FileId
    from seaweedfs_tpu.storage.volume import Volume

    if write_path._needle_ext is None or not hasattr(
        write_path._needle_ext, "post"
    ):
        print(json.dumps({
            "metric": "native_post_check",
            "ok": False,
            "skipped": True,
            "reason": "no C toolchain: needle_ext unavailable",
        }))
        return 0  # absent toolchain is a skip, not a failure
    body = b"\x00\x07check-payload\xff" * 64
    q = {"ts": "1700000000"}
    fid = FileId(1, 9, 0xBEEF)

    def now_ns(self):
        # pure function of volume state: both volumes stamp the same
        # append_at_ns, so byte comparison is exact
        return self.last_append_at_ns + 1

    orig = Volume._now_ns
    Volume._now_ns = now_ns
    try:
        with tempfile.TemporaryDirectory() as d:
            os.mkdir(os.path.join(d, "c"))
            os.mkdir(os.path.join(d, "py"))
            vc = Volume(os.path.join(d, "c"), 1)
            vp = Volume(os.path.join(d, "py"), 1)
            reply_c = write_path.try_native_post(vc, fid, q, body, {}, "", False)
            n, fname, err = write_path.build_upload_needle(fid, q, body, {}, "")
            assert err is None, err
            _, size, _ = vp.write_needle(n)
            reply_py = b'{"name": %s, "size": %d, "eTag": "%s"}' % (
                json.dumps(fname).encode(), size, n.etag().encode())
            vc.close()
            vp.close()
            with open(vc.base_name + ".dat", "rb") as f:
                dat_c = f.read()
            with open(vp.base_name + ".dat", "rb") as f:
                dat_py = f.read()
            with open(vc.base_name + ".idx", "rb") as f:
                idx_c = f.read()
            with open(vp.base_name + ".idx", "rb") as f:
                idx_py = f.read()
        ok = (
            reply_c is not None
            and reply_c == reply_py
            and dat_c == dat_py
            and idx_c == idx_py
        )
        print(json.dumps({
            "metric": "native_post_check",
            "ok": ok,
            "engaged": reply_c is not None,
            "dat_bytes": len(dat_c),
        }))
        return 0 if ok else 1
    finally:
        Volume._now_ns = orig


def check_native_serve() -> int:
    """`bench.py --check` serve leg: plain, Range, conditional
    (If-None-Match → 304, including INM-beats-Range), and flagged-
    needle (writev'd pre-rendered header) GETs through the C epoll
    loop and through the threaded mini loop must produce identical
    bytes, with every one answered from the C fast path (the
    served/not_modified counters move; handoffs do not). The full
    matrix lives in tests/test_native_serve.py and
    tests/test_serve_syscall_floor.py; the fuzzer in
    analysis/fuzz_serve.py."""
    import tempfile

    from seaweedfs_tpu.analysis import fuzz_serve
    from seaweedfs_tpu.util import native_serve

    if not native_serve.available():
        print(json.dumps({
            "check": "native_serve",
            "skipped": "no C toolchain / non-Linux: threaded loop serves",
        }))
        return 0
    with tempfile.TemporaryDirectory(prefix="weedserve_check") as d:
        pair = fuzz_serve.ServePair(d)
        try:
            hits = []
            orig = pair.servers[0].fast_resolver

            def counting(path, rng, head_only):
                plan = orig(path, rng, head_only)
                hits.append(plan is not None)
                return plan

            pair.servers[0].fast_resolver = counting
            before = native_serve.serve_stats()
            reqs = (
                f"GET /{pair.fids['small']} HTTP/1.1\r\n\r\n",
                f"GET /{pair.fids['big']} HTTP/1.1\r\nRange: bytes=-100\r\n\r\n",
                # conditional: exact validator revalidates as a 304
                f"GET /{pair.fids['small']} HTTP/1.1\r\n"
                'If-None-Match: "067c9745"\r\n\r\n',
                # RFC 9110: If-None-Match beats Range — 304, not 206
                f"GET /{pair.fids['small']} HTTP/1.1\r\nRange: bytes=0-9\r\n"
                'If-None-Match: W/"067c9745"\r\n\r\n',
                # flag-bearing needle: pre-rendered CT/CD header + small
                # body collapse into one writev on the C arm
                f"GET /{pair.fids['named']} HTTP/1.1\r\n\r\n",
            )
            for req in reqs:
                case = {"fragments": [req.encode()]}
                c = fuzz_serve.drive(pair.c_port, case)
                py = fuzz_serve.drive(pair.py_port, case)
                if c != py:
                    print(json.dumps({
                        "check": "native_serve",
                        "ok": False,
                        "error": f"C/Python GET bytes diverge for {req!r}",
                    }))
                    return 1
            after = native_serve.serve_stats()
            # a repeated fid may be answered from the C plan cache
            # WITHOUT calling the Python resolver — those requests are
            # cache_hits, the rest must all have resolved successfully
            dcache = after["cache_hits"] - before["cache_hits"]
            if not all(hits) or len(hits) + dcache != len(reqs):
                print(json.dumps({
                    "check": "native_serve",
                    "ok": False,
                    "error": f"fast path declined eligible GETs: "
                             f"{hits} (+{dcache} cache hits)",
                }))
                return 1
            d304 = after["not_modified"] - before["not_modified"]
            dhand = after["handoffs"] - before["handoffs"]
            if d304 < 2 or dhand > 0:
                print(json.dumps({
                    "check": "native_serve",
                    "ok": False,
                    "error": f"C arm left the fast path: "
                             f"not_modified+{d304}, handoffs+{dhand}",
                }))
                return 1
        finally:
            pair.close()
    print(json.dumps({"check": "native_serve", "ok": True,
                      "fast_path_hits": len(reqs), "not_modified": d304}))
    return 0


def check_trace_smoke() -> int:
    """`bench.py --check` trace leg: one traced write through the HTTP
    data plane must yield a span tree with the expected shape — a
    client root, a volume.post child sharing its trace ID, and the five
    write-path stage names (identical for the C and Python paths)."""
    import tempfile

    from seaweedfs_tpu import trace
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.server import write_path
    from seaweedfs_tpu.util.availability import start_cluster

    trace.set_enabled(True)
    with tempfile.TemporaryDirectory() as d:
        master, servers = start_cluster([tempfile.mkdtemp(dir=d)])
        m = f"127.0.0.1:{master.port}"
        try:
            with trace.span("check.client") as root:
                ar = op.assign(m)
                ur = op.upload(
                    f"{ar.url}/{ar.fid}",
                    b"\x00\x07trace-check\xff" * 64,
                    jwt=ar.auth,
                )
                trace_id, root_span = root.trace_id, root.span_id
        finally:
            for vs in servers:
                vs.stop()
            master.stop()
    posts = [
        s
        for s in trace.debug_payload(512)["recent"]
        if s["trace"] == trace_id and s["name"] == "volume.post"
    ]
    ok = (
        not ur.error
        and len(posts) == 1
        and posts[0]["parent"] == root_span
        and posts[0]["status"] == 201
        and set(posts[0].get("stages_ms", ())) == set(write_path.WRITE_STAGES)
    )
    print(json.dumps({
        "metric": "trace_check",
        "ok": ok,
        "trace_id": trace_id,
        "stages": sorted(posts[0].get("stages_ms", ())) if posts else [],
    }))
    return 0 if ok else 1


def check_telemetry_smoke() -> int:
    """`bench.py --check` telemetry leg: scrape a live daemon into the
    ring TSDB, run one alert-evaluation cycle, and pull folded stacks
    from the continuous profiler — the whole collector→rings→alerts→
    profiler chain in one cheap pass."""
    import tempfile
    import urllib.request as _rq

    from seaweedfs_tpu.telemetry import ClusterCollector
    from seaweedfs_tpu.util.availability import start_cluster

    with tempfile.TemporaryDirectory() as d:
        master, servers = start_cluster([tempfile.mkdtemp(dir=d)])
        try:
            collector = ClusterCollector(master, interval=0.5)
            master.telemetry = collector
            collector.collect_once()
            collector.collect_once()  # two cycles so rings can rate()
            targets = list(collector.targets.values())
            rings_ok = bool(targets) and all(
                ts.scrapes >= 2 and ts.series_count() > 0 for ts in targets
            )
            alerts = collector.alerts.payload()
            alerts_ok = not alerts["Firing"]  # healthy cluster: quiet
            health = collector.health_payload()
            health_ok = all(
                row["Up"] for row in health["Targets"].values()
            )
            with _rq.urlopen(
                f"http://127.0.0.1:{servers[0].port}"
                "/debug/profile?seconds=0.4",
                timeout=10,
            ) as r:
                prof = json.loads(r.read())
            if not prof.get("enabled", True):
                prof_ok = True  # WEED_PROF=0 opt-out is not a failure
            else:
                prof_ok = prof["samples"] > 0 and any(
                    ";" in stack for stack in prof["stacks"]
                )
        finally:
            for vs in servers:
                vs.stop()
            master.stop()
    ok = rings_ok and alerts_ok and health_ok and prof_ok
    print(json.dumps({
        "metric": "telemetry_check",
        "ok": ok,
        "rings": rings_ok,
        "alerts_quiet": alerts_ok,
        "targets_up": health_ok,
        "profiler_folded_stacks": prof_ok,
        "targets": len(health["Targets"]),
    }))
    return 0 if ok else 1


def check_capsule_smoke() -> int:
    """`bench.py --check` capsule leg (weedscope): force the SLO
    burn-rate rule to fire on a live cluster and assert the alert-
    triggered incident capsule lands DURABLY on every implicated node —
    manifest published last, blackbox wide-events, folded stacks, the
    /metrics exposition, and the leader-only TSDB window + cluster
    verdict sections. The breach is forced deterministically: the
    in-process cluster shares this process's metric registry, so one
    synthetic 10 s observation between two scrape cycles burns both
    windows of a seconds-scale latency objective."""
    import tempfile
    import urllib.request as _rq

    from seaweedfs_tpu.stats.metrics import HTTP_REQUEST_HISTOGRAM
    from seaweedfs_tpu.telemetry import ClusterCollector
    from seaweedfs_tpu.telemetry import capsule as capsule_mod
    from seaweedfs_tpu.telemetry import slo as slo_mod
    from seaweedfs_tpu.util.availability import start_cluster

    with tempfile.TemporaryDirectory() as d:
        capsule_mod.set_dir(tempfile.mkdtemp(dir=d))
        master, servers = start_cluster([tempfile.mkdtemp(dir=d)])
        lead_node = f"{master.host}:{master.port}"
        try:
            forced = slo_mod.SLOObjective(
                "check-forced-breach", "latency", 0.999,
                family="weed_http_request_seconds", threshold_s=0.5,
            )
            collector = ClusterCollector(
                master, interval=0.5,
                slo_objectives=[forced], slo_fast_s=30.0, slo_slow_s=60.0,
            )
            master.telemetry = collector
            master._wire_capsules()
            # light real traffic so blackbox/trace sections have events
            with _rq.urlopen(
                f"http://127.0.0.1:{servers[0].port}/debug/traces?n=8",
                timeout=10,
            ) as r:
                r.read()
            # cycle 1's own /metrics GET births the request-histogram
            # series; cycle 2 rings their baseline; the synthetic slow
            # observation then shows as an increase in cycle 3 -> fires
            collector.collect_once()
            collector.collect_once()
            HTTP_REQUEST_HISTOGRAM.observe(10.0, "volume", "GET")
            collector.collect_once()
            fired_ok = any(
                a["Alert"] == "slo_burn_rate"
                and a["Target"] == "check-forced-breach"
                for a in collector.alerts.firing()
            )
            # the CaptureCoordinator runs off-thread: local capture on
            # the leader plus /capsule/capture on every up peer
            caps: list[dict] = []
            deadline = time.time() + 20.0
            while time.time() < deadline:
                caps = [
                    c for c in capsule_mod.list_capsules()
                    if c.get("Trigger") == "alert"
                ]
                if len({c.get("Node") for c in caps}) >= 2:
                    break
                time.sleep(0.25)
            nodes = sorted({c.get("Node", "") for c in caps})
            cross_node_ok = len(nodes) >= 2
            lead = next(
                (c for c in caps if c.get("Node") == lead_node), None
            )
            files_ok = spans_ok = metrics_ok = tsdb_ok = False
            if lead is not None:
                ok_names = {
                    f["Name"] for f in lead["Files"] if f.get("Ok")
                }
                files_ok = {
                    "blackbox.json", "traces.json", "profile.txt",
                    "metrics.txt", "tsdb.json", "cluster.json",
                } <= ok_names
                bb = json.loads(
                    capsule_mod.read_file(lead["Id"], "blackbox.json")
                    or b"{}"
                )
                spans_ok = bool(bb.get("tail") or bb.get("ok"))
                mtxt = (
                    capsule_mod.read_file(lead["Id"], "metrics.txt") or b""
                ).decode()
                metrics_ok = "weed_slo_burn_rate" in mtxt
                tsdb = json.loads(
                    capsule_mod.read_file(lead["Id"], "tsdb.json") or b"{}"
                )
                tsdb_ok = bool(tsdb.get("Targets"))
        finally:
            for vs in servers:
                vs.stop()
            master.stop()
            capsule_mod.set_dir("")
    ok = bool(
        fired_ok and cross_node_ok and lead is not None
        and files_ok and spans_ok and metrics_ok and tsdb_ok
    )
    print(json.dumps({
        "metric": "capsule_check",
        "ok": ok,
        "slo_alert_fired": fired_ok,
        "cross_node": cross_node_ok,
        "capsule_nodes": nodes,
        "leader_files_durable": files_ok,
        "blackbox_events": spans_ok,
        "metrics_window": metrics_ok,
        "tsdb_window": tsdb_ok,
    }))
    return 0 if ok else 1


def check_weedlint() -> int:
    """Static-analysis gate: `python -m seaweedfs_tpu.analysis` must
    exit 0 (no unsuppressed findings, no reasonless suppressions)."""
    import subprocess

    try:
        proc = subprocess.run(
            [sys.executable, "-m", "seaweedfs_tpu.analysis"],
            capture_output=True,
            text=True,
            timeout=600,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired:
        # a wedged lint run must still land as a failing metric line,
        # not a traceback the driver can't parse
        print(json.dumps({
            "metric": "weedlint_check",
            "ok": False,
            "tail": ["timeout after 600s"],
        }))
        return 1
    print(json.dumps({
        "metric": "weedlint_check",
        "ok": proc.returncode == 0,
        "tail": proc.stdout.strip().splitlines()[-1:]
        + ([proc.stderr.strip()[:200]] if proc.returncode else []),
    }))
    return proc.returncode


def check_contracts_smoke() -> int:
    """`bench.py --check` contracts+lifecycle leg: both new weedlint
    tiers must (a) run clean on the real tree (that is check_weedlint's
    full-CLI job; here we assert the tiers themselves loaded) and
    (b) still DETECT planted bugs — a checker that silently goes blind
    is worse than none, so the gate proves the positive controls every
    run, via a throwaway fixture tree."""
    import tempfile
    import textwrap

    from seaweedfs_tpu.analysis import contracts, lifecycle

    with tempfile.TemporaryDirectory() as td:
        root = os.path.join(td, "fixturepkg")
        os.makedirs(root)
        with open(os.path.join(root, "__init__.py"), "w") as f:
            f.write("")
        with open(os.path.join(root, "srv.py"), "w") as f:
            f.write(textwrap.dedent("""
                import os
                import urllib.request
                from seaweedfs_tpu.util.httpd import FastHandler

                class H(FastHandler):
                    def do_GET(self):
                        if self.path == "/served":
                            return

                def dial():
                    urllib.request.urlopen(
                        "http://127.0.0.1:9999/never-served", timeout=5
                    )

                def leak(p):
                    fd = os.open(p, os.O_RDONLY)
                    if os.fstat(fd).st_size == 0:
                        return None
                    os.close(fd)
                    return True
            """))
        cf, _idx, _reg = contracts.check(root=root)
        lf, _idx2 = lifecycle.check(root=root)
    route_hit = any(
        f.rule == "contract-route" and "/never-served" in f.message
        for f in cf
    )
    leak_hit = any(f.rule == "lifecycle-fd-leak" for f in lf)
    ok = route_hit and leak_hit
    print(json.dumps({
        "metric": "contracts_smoke",
        "ok": ok,
        "planted_route_detected": route_hit,
        "planted_fd_leak_detected": leak_hit,
    }))
    return 0 if ok else 1


def check_crash_smoke() -> int:
    """`bench.py --check` crash leg (docs/ANALYSIS.md v3): the
    durability lint must DETECT a planted missing-fsync publish (a
    checker that silently goes blind is worse than none), the dynamic
    enumerator must DETECT the planted unsynced tmp+rename bug, and
    one real enumerator pass over a tiny volume's group-commit trace
    must come back with zero recovery-invariant violations."""
    import tempfile
    import textwrap

    from seaweedfs_tpu.analysis import crash, crashlint

    with tempfile.TemporaryDirectory() as td:
        root = os.path.join(td, "fixturepkg")
        os.makedirs(root)
        with open(os.path.join(root, "__init__.py"), "w") as f:
            f.write("")
        with open(os.path.join(root, "pub.py"), "w") as f:
            f.write(textwrap.dedent("""
                import os

                def publish(path):
                    tmp = path + ".tmp"
                    with open(tmp, "w") as f:
                        f.write("x")
                    os.replace(tmp, path)
            """))
        lint_findings, _idx = crashlint.check(root=root)
    lint_hit = any(
        f.rule == "crash-rename-unsynced-src" for f in lint_findings
    ) and any(f.rule == "crash-rename-no-dirsync" for f in lint_findings)
    dynamic_hit = bool(crash.run_broken_publish(budget=48).violations)
    sweep_rep = crash.run_group_commit(budget=64)
    sweep_ok = (
        sweep_rep.violations == [] and sweep_rep.states_tested >= 24
    )
    # the EC shard writer-pool flush ordering (ISSUE 12): durable arm
    # clean, and the PRE-FIX ordering must still be DETECTED — a sweep
    # that can no longer see torn-shards-under-complete-.ecx states
    # proves nothing
    ec_rep = crash.run_ec_encode(budget=48)
    ec_regress = bool(
        crash.run_ec_encode(budget=48, durable=False).violations
    )
    ec_ok = ec_rep.violations == [] and ec_regress
    # the .ecc scrub-sidecar publish ordering: durable arm clean, and
    # the planted shards-unsynced-before-publish ordering must be
    # DETECTED (a confident sidecar over lost shard bytes). The
    # planted violation lives in the few crash points BETWEEN the
    # sidecar rename landing and the trace end, so a sampled sweep can
    # legitimately miss it — this leg pays for the full candidate set
    # (~1000 states, ~1.5 s) to make detection deterministic.
    ecc_rep = crash.run_ecc_publish(budget=1200)
    ecc_regress = bool(
        crash.run_ecc_publish(budget=1200, durable=False).violations
    )
    ecc_ok = ecc_rep.violations == [] and ecc_regress
    ok = lint_hit and dynamic_hit and sweep_ok and ec_ok and ecc_ok
    print(json.dumps({
        "metric": "crash_smoke",
        "ok": ok,
        "planted_lint_detected": lint_hit,
        "planted_dynamic_detected": dynamic_hit,
        "group_commit_states_tested": sweep_rep.states_tested,
        "group_commit_violations": sweep_rep.violations[:3],
        "ec_encode_violations": ec_rep.violations[:3],
        "ec_encode_pre_fix_detected": ec_regress,
        "ecc_publish_violations": ecc_rep.violations[:3],
        "ecc_publish_pre_fix_detected": ecc_regress,
    }))
    return 0 if ok else 1


def check_qos_smoke() -> int:
    """`bench.py --check` qos leg (docs/QOS.md): a hedged GET against a
    stalled replica must win via the hedge (correct bytes, fired+won
    counted), and one group-commit batch must land byte-identical to
    the same needles written serially."""
    import tempfile

    from seaweedfs_tpu.qos import hedge
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume
    from seaweedfs_tpu.util.availability import start_cluster
    from tests.faults import SlowReplicaProxy

    # --- hedge: stalled replica loses to the hedged attempt -------------
    import urllib.request as _rq

    os.environ["WEED_QOS_HEDGE_MS"] = "40"
    proxy = None
    try:
        with tempfile.TemporaryDirectory() as d:
            master, servers = start_cluster(
                [tempfile.mkdtemp(dir=d), tempfile.mkdtemp(dir=d)]
            )
            m = f"127.0.0.1:{master.port}"
            try:
                payload = b"qos-check\x00\xff" * 64
                with _rq.urlopen(
                    f"http://{m}/dir/assign?replication=010", timeout=10
                ) as r:
                    a = json.load(r)
                _rq.urlopen(
                    _rq.Request(
                        f"http://{a['url']}/{a['fid']}", data=payload,
                        method="POST",
                        headers={"Content-Type": "application/octet-stream"},
                    ),
                    timeout=10,
                ).close()
                vid = a["fid"].partition(",")[0]
                with _rq.urlopen(
                    f"http://{m}/dir/lookup?volumeId={vid}", timeout=10
                ) as r:
                    urls = [l["url"] for l in json.load(r)["locations"]]
                if len(urls) < 2:
                    raise RuntimeError(f"replication 010 gave {urls}")
                proxy = SlowReplicaProxy(urls[0], delay_s=0.5)
                stats: dict = {}
                data, _ = hedge.download(
                    [f"{proxy.addr}/{a['fid']}", f"{urls[1]}/{a['fid']}"],
                    key=vid, stats=stats,
                )
                hedge_ok = (
                    data == payload
                    and stats.get("fired", 0) >= 1
                    and stats.get("won", 0) >= 1
                )
            finally:
                if proxy is not None:
                    proxy.stop()
                for vs in servers:
                    vs.stop()
                master.stop()
    finally:
        os.environ.pop("WEED_QOS_HEDGE_MS", None)

    # --- group commit: one batch byte-identical to serial writes --------
    def now_ns(self):
        return self.last_append_at_ns + 1

    def mk(i):
        n = Needle(cookie=0xAB, id=500 + i, data=b"gc-check-%d\xff" % i * 30)
        n.set_has_last_modified_date()
        n.last_modified = 1700000000
        return n

    orig = Volume._now_ns
    Volume._now_ns = now_ns
    try:
        with tempfile.TemporaryDirectory() as d:
            os.mkdir(os.path.join(d, "s"))
            os.mkdir(os.path.join(d, "b"))
            vs_, vb = Volume(os.path.join(d, "s"), 1), Volume(os.path.join(d, "b"), 1)
            for i in range(6):
                vs_.write_needle(mk(i))
            vb.write_needles([(mk(i), None) for i in range(6)], durable=True)
            vs_.close()
            vb.close()
            with open(vs_.base_name + ".dat", "rb") as f:
                dat_s = f.read()
            with open(vb.base_name + ".dat", "rb") as f:
                dat_b = f.read()
            gc_ok = dat_s == dat_b and len(dat_s) > 0
    finally:
        Volume._now_ns = orig

    ok = hedge_ok and gc_ok
    print(json.dumps({
        "metric": "qos_check",
        "ok": ok,
        "hedge_won_with_stalled_replica": hedge_ok,
        "group_commit_byte_identical": gc_ok,
    }))
    return 0 if ok else 1


def check_degraded_smoke() -> int:
    """`bench.py --check` degraded leg (docs/SCRUB.md): kill one shard
    of a live EC volume — the GET must succeed byte-identical via
    reconstruction, the SECOND read must be a tile-cache hit (no fresh
    decode), and the planted-regression guard asserts the old serial
    per-interval gather (per-call ThreadPoolExecutor) is gone from the
    hot path."""
    import inspect
    import random
    import tempfile

    from seaweedfs_tpu.ec import ec_files, ec_volume
    from seaweedfs_tpu.ec.codec import new_encoder
    from seaweedfs_tpu.stats.metrics import EC_TILE_CACHE
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store
    from seaweedfs_tpu.storage.volume import Volume

    serial_gone = (
        "ThreadPoolExecutor" not in inspect.getsource(ec_volume)
        and "as_completed" not in inspect.getsource(ec_volume)
    )
    with tempfile.TemporaryDirectory() as d:
        v = Volume(d, 9)
        rng = random.Random(7)
        payload = {}
        for k in range(1, 17):
            data = bytes(rng.randbytes(1500 + 31 * k))
            payload[k] = data
            v.write_needle(Needle(cookie=0xD00D, id=k, data=data))
        v.close()
        base = os.path.join(d, "9")
        ec_files.write_ec_files(base, rs=new_encoder(backend="cpu"))
        ec_files.write_sorted_file_from_idx(base)
        os.remove(base + ".dat")
        os.remove(base + ".idx")
        store = Store([d], ec_backend="cpu")
        ev = store.find_ec_volume(9)
        killed = ev.quarantine_shard(0, "check: degraded smoke")
        first_ok = all(
            bytes(ev.read_needle(k).data) == data
            for k, data in payload.items()
        )
        h0 = EC_TILE_CACHE.value("hit")
        m0 = EC_TILE_CACHE.value("miss")
        second_ok = all(
            bytes(ev.read_needle(k).data) == data
            for k, data in payload.items()
        )
        cache_hit = (
            EC_TILE_CACHE.value("hit") > h0
            and EC_TILE_CACHE.value("miss") == m0
        )
        store.close()
    ok = serial_gone and killed and first_ok and second_ok and cache_hit
    print(json.dumps({
        "metric": "degraded_smoke",
        "ok": ok,
        "shard_killed": killed,
        "degraded_read_byte_identical": first_ok and second_ok,
        "second_read_tile_cache_hit": cache_hit,
        "serial_fallback_gone": serial_gone,
    }))
    return 0 if ok else 1


def check_tier_smoke() -> int:
    """`bench.py --check` tiering leg (docs/TIERING.md): tier a sealed
    EC volume out to a local-dir backend (local shard files deleted),
    serve a degraded read from the backend byte-identical, then tier
    it back in — the recalled shards must pass the .ecc CRC gate and
    reads must match the originals."""
    import random
    import tempfile

    from seaweedfs_tpu.ec import ec_files
    from seaweedfs_tpu.ec.codec import new_encoder
    from seaweedfs_tpu.ec.ecc_sidecar import write_sidecar
    from seaweedfs_tpu.storage import backend as bkend
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store
    from seaweedfs_tpu.storage.volume import Volume
    from seaweedfs_tpu.tier.ec_tier import tier_in_ec, tier_out_ec
    from seaweedfs_tpu.util.crc import crc32c

    with tempfile.TemporaryDirectory() as d:
        vol_dir = os.path.join(d, "vols")
        os.makedirs(vol_dir)
        v = Volume(vol_dir, 11)
        rng = random.Random(23)
        payload = {}
        for k in range(1, 13):
            data = bytes(rng.randbytes(2000 + 97 * k))
            payload[k] = data
            v.write_needle(Needle(cookie=0xCAFE, id=k, data=data))
        v.close()
        base = os.path.join(vol_dir, "11")
        ec_files.write_ec_files(base, rs=new_encoder(backend="cpu"))
        ec_files.write_sorted_file_from_idx(base)
        os.remove(base + ".dat")
        os.remove(base + ".idx")
        crcs = {}
        for sid in range(14):
            with open(base + ec_files.to_ext(sid), "rb") as f:
                crcs[sid] = crc32c(f.read())
        write_sidecar(base, crcs)
        bdir = os.path.join(d, "backend")
        os.makedirs(bdir)
        bkend.ensure_builtin_factories()
        inst = f"chk{os.getpid()}"
        bkend.load_backend_config(
            {"dir": {inst: {"enabled": True, "dir": bdir}}}
        )
        store = Store([vol_dir], ec_backend="cpu")
        tier_out_ec(store, 11, f"dir.{inst}")
        ev = store.find_ec_volume(11)
        local_gone = not ev.shards and not any(
            os.path.exists(base + ec_files.to_ext(s)) for s in range(14)
        )
        degraded_ok = all(
            bytes(ev.read_needle(k).data) == data
            for k, data in payload.items()
        )
        tier_in_ec(store, 11)
        recalled = ev.remote is None and len(ev.shards) == 14
        recall_ok = all(
            bytes(ev.read_needle(k).data) == data
            for k, data in payload.items()
        )
        store.close()
    ok = local_gone and degraded_ok and recalled and recall_ok
    print(json.dumps({
        "metric": "tier_smoke",
        "ok": ok,
        "local_shards_released": local_gone,
        "degraded_read_byte_identical": degraded_ok,
        "recalled_fully_local": recalled,
        "recall_byte_identical": recall_ok,
    }))
    return 0 if ok else 1


def check_pipeline_identity() -> int:
    """`bench.py --check` streaming-pipeline leg (docs/CODEC.md): on
    the CPU backend, the pipelined single-volume driver, the pipelined
    MESH batch driver, and the WEED_EC_PIPELINE=0 serial classic
    driver must produce byte-identical shard files — and every fused
    shard CRC must equal needle/crc's host CRC32-C of the bytes on
    disk. Runs every --check, so a divergence in the device-resident
    path can never hide behind 'the TPU wasn't attached'."""
    import tempfile

    import numpy as np

    from seaweedfs_tpu.ec import ec_files, ec_stream
    from seaweedfs_tpu.ec.codec import new_encoder
    from seaweedfs_tpu.util.crc import crc32c

    small = 64 * 1024  # small-tier block: keeps the smoke sub-second
    large = 1 << 30
    rs = new_encoder(backend="cpu")
    rng = np.random.default_rng(3)
    problems: list[str] = []
    with tempfile.TemporaryDirectory() as d:
        data = rng.integers(0, 256, 10 * small * 2 + 777, dtype=np.uint8)
        for name in ("serial", "piped", "mesh"):
            with open(os.path.join(d, name + ".dat"), "wb") as f:
                f.write(data.tobytes())
        serial, piped, mesh = (os.path.join(d, n) for n in ("serial", "piped", "mesh"))

        sstats: dict = {}
        with _pipeline_disabled():
            ec_files.write_ec_files(
                serial, rs=rs, large_block_size=large, small_block_size=small,
                stats=sstats, want_crcs=True,
            )

        pstats: dict = {}
        parity_fn, fetch_fn = ec_stream.local_encode_fns(rs, want_crcs=True)
        ec_stream.stream_write_ec_files(
            piped, large_block_size=large, small_block_size=small,
            parity_fn=parity_fn, fetch_fn=fetch_fn, stats=pstats,
            want_crcs=True,
        )

        mstats: dict = {}
        ec_stream.stream_write_ec_files_batch(
            [mesh], large_block_size=large, small_block_size=small,
            stats=mstats, want_crcs=True,
        )

        for i in range(ec_files.TOTAL_SHARDS):
            sb = open(serial + ec_files.to_ext(i), "rb").read()
            pb = open(piped + ec_files.to_ext(i), "rb").read()
            mb = open(mesh + ec_files.to_ext(i), "rb").read()
            if not (sb == pb == mb):
                problems.append(f"shard {i} bytes diverge across drivers")
                continue
            want = crc32c(sb)
            for tag, st in (("serial", sstats), ("piped", pstats), ("mesh", mstats)):
                got = st.get("shard_crcs")
                got_i = got[i] if tag != "mesh" else got[0][i]
                if got_i != want:
                    problems.append(
                        f"{tag} shard {i} crc {got_i:#x} != host {want:#x}"
                    )

        # rebuild identity: pipelined vs serial, CRCs vs host
        os.remove(piped + ec_files.to_ext(0))
        rstats: dict = {}
        rebuild_fn, rfetch = ec_stream.local_rebuild_fns(rs, want_crcs=True)
        ec_stream.stream_rebuild_ec_files(
            piped, rebuild_fn=rebuild_fn, fetch_fn=rfetch, stats=rstats,
            want_crcs=True,
        )
        rb = open(piped + ec_files.to_ext(0), "rb").read()
        sb = open(serial + ec_files.to_ext(0), "rb").read()
        if rb != sb:
            problems.append("pipelined rebuild bytes diverge")
        if rstats.get("shard_crcs", {}).get(0) != crc32c(rb):
            problems.append("pipelined rebuild fused CRC != host CRC32-C")

        # batched-rebuild identity: the mesh batch driver over two
        # volumes (same damage -> one decode program) must reproduce
        # the serial arm's bytes, and its folded per-shard CRCs must
        # equal the host CRC32-C of what landed on disk
        for vol in (piped, mesh):
            for sid in (0, 13):
                try:
                    os.remove(vol + ec_files.to_ext(sid))
                except FileNotFoundError:
                    pass
        bstats: dict = {}
        ec_stream.stream_rebuild_ec_files_batch(
            [piped, mesh], stats=bstats, want_crcs=True
        )
        bcrcs = bstats.get("shard_crcs") or [{}, {}]
        for vi, vol in enumerate((piped, mesh)):
            for sid in (0, 13):
                vb = open(vol + ec_files.to_ext(sid), "rb").read()
                if vb != open(serial + ec_files.to_ext(sid), "rb").read():
                    problems.append(f"batched rebuild bytes diverge (shard {sid})")
                elif bcrcs[vi].get(sid) != crc32c(vb):
                    problems.append(
                        f"batched rebuild folded CRC != host (shard {sid})"
                    )

        # schedule identity (ec/schedule.py): the compiled XOR program
        # must be byte-identical to the naive LUT chain — both at the
        # matrix level and through a WEED_EC_SCHEDULE=0 encoder
        from seaweedfs_tpu.ec import codec as _codec
        from seaweedfs_tpu.ec import schedule as _sched

        mat = np.asarray(rs.parity_rows, dtype=np.uint8)
        inp = rng.integers(0, 256, (mat.shape[1], 8192), dtype=np.uint8)
        if not np.array_equal(
            _sched.scheduled_apply_matrix(mat, inp),
            _codec.cpu_apply_matrix(mat, inp),
        ):
            problems.append("scheduled parity rows != naive chain")
        dmat = rng.integers(0, 256, (4, 10), dtype=np.uint8)  # decode-shaped
        if not np.array_equal(
            _sched.scheduled_apply_matrix(dmat, inp),
            _codec.cpu_apply_matrix(dmat, inp),
        ):
            problems.append("scheduled random matrix != naive chain")
        prior = os.environ.get("WEED_EC_SCHEDULE")
        os.environ["WEED_EC_SCHEDULE"] = "0"
        try:
            naive_rs = new_encoder(backend="cpu")
            naive = os.path.join(d, "naive")
            with open(naive + ".dat", "wb") as f:
                f.write(data.tobytes())
            with _pipeline_disabled():
                ec_files.write_ec_files(
                    naive, rs=naive_rs,
                    large_block_size=large, small_block_size=small,
                )
            for i in range(ec_files.TOTAL_SHARDS):
                nb = open(naive + ec_files.to_ext(i), "rb").read()
                if nb != open(serial + ec_files.to_ext(i), "rb").read():
                    problems.append(
                        f"WEED_EC_SCHEDULE=0 shard {i} diverges from scheduled"
                    )
                    break
        finally:
            if prior is None:
                os.environ.pop("WEED_EC_SCHEDULE", None)
            else:
                os.environ["WEED_EC_SCHEDULE"] = prior

    ok = not problems
    print(json.dumps({
        "metric": "pipeline_identity",
        "ok": ok,
        "problems": problems[:4],
        "pipeline_depth": pstats.get("pipeline_depth"),
        "mesh": mstats.get("mesh"),
        "batch_rebuild_volumes": bstats.get("batch_volumes"),
        "schedule_terms": getattr(
            _sched.compile_schedule(mat), "n_terms", None
        ),
        "schedule_terms_naive": getattr(
            _sched.compile_schedule(mat), "n_terms_naive", None
        ),
    }))
    return 0 if ok else 1


def check_chaos_smoke() -> int:
    """`bench.py --check` weedchaos leg (docs/CHAOS.md): a planted
    partition must be DETECTED (a deadlined call through it fails
    fast, never parks) AND HEALED (the same call succeeds after
    heal()), and a planted EIO on an EC shard must QUARANTINE the
    shard — reads stay byte-identical, the server never crashes."""
    import tempfile

    from seaweedfs_tpu.analysis.chaos import ChaosProxy, DiskChaos, DiskFault
    from seaweedfs_tpu.client import operation as _cop
    from seaweedfs_tpu.server.master_server import MasterServer
    from seaweedfs_tpu.util import deadline as _cdl
    from seaweedfs_tpu.util.availability import free_port as _fp

    # --- partition: detected fast (deadline), healed cleanly ------------
    master = MasterServer(port=_fp(), volume_size_limit_mb=64,
                          vacuum_interval=0)
    master.start()
    proxy = ChaosProxy(f"127.0.0.1:{master.port}")
    detected = healed = False
    try:
        status, _, _ = _cop.http_call(
            "GET", f"{proxy.addr}/dir/status", timeout=5
        )
        pre_ok = status == 200
        proxy.partition()
        t0 = time.perf_counter()
        try:
            _cop.http_call(
                "GET", f"{proxy.addr}/dir/status", timeout=5,
                deadline=_cdl.Deadline.after(0.5),
            )
        except (TimeoutError, OSError):
            # the budget — not a parked socket — ended the call
            detected = time.perf_counter() - t0 < 3.0
        proxy.heal()
        status, _, _ = _cop.http_call(
            "GET", f"{proxy.addr}/dir/status", timeout=5
        )
        healed = pre_ok and status == 200
    finally:
        proxy.stop()
        master.stop()

    # --- EIO: quarantined, reads byte-identical, no crash ---------------
    import random as _random

    from seaweedfs_tpu.ec import ec_files as _ecf
    from seaweedfs_tpu.ec.codec import new_encoder as _enc
    from seaweedfs_tpu.storage.needle import Needle as _Needle
    from seaweedfs_tpu.storage.store import Store as _Store
    from seaweedfs_tpu.storage.volume import Volume as _Volume

    eio_ok = quarantined = False
    with tempfile.TemporaryDirectory() as d:
        vid = 7
        victim = os.path.join(d, f"{vid}.ec00")
        with DiskChaos([DiskFault("eio", victim)]):
            v = _Volume(d, vid)
            rng = _random.Random(11)
            payload = {}
            for k in range(1, 31):
                data = bytes(rng.randbytes(rng.randint(400, 3000)))
                payload[k] = data
                v.write_needle(_Needle(cookie=0x1234, id=k, data=data))
            v.close()
            base = os.path.join(d, str(vid))
            _ecf.write_ec_files(base, rs=_enc(backend="cpu"))
            _ecf.write_sorted_file_from_idx(base)
            os.remove(base + ".dat")
            os.remove(base + ".idx")
            store = _Store([d], ec_backend="cpu")
            try:
                ev = store.find_ec_volume(vid)
                ok_reads = 0
                for _pass in range(2):
                    for k, data in payload.items():
                        nd = store.read_needle(vid, k)
                        ok_reads += bytes(nd.data) == data
                eio_ok = ok_reads == 2 * len(payload)
                quarantined = 0 in ev.quarantined
            finally:
                store.close()

    ok = detected and healed and eio_ok and quarantined
    print(json.dumps({
        "metric": "chaos_smoke",
        "ok": ok,
        "partition_detected_fast": detected,
        "partition_healed": healed,
        "eio_reads_byte_identical": eio_ok,
        "eio_shard_quarantined": quarantined,
    }))
    return 0 if ok else 1


# suites each sanitizer mode must keep green: asan covers the byte
# parsers (heap corruption); tsan adds the epoll serving loop and the
# syscall-floor matrix, where the threads and the shm GCRA bucket live
_SAN_SUITES = {
    "asan": ("tests/test_native_post.py", "tests/test_fuzz_corpus.py"),
    "tsan": (
        "tests/test_native_post.py", "tests/test_fuzz_corpus.py",
        "tests/test_native_serve.py", "tests/test_serve_syscall_floor.py",
    ),
}


def check_sanitizer_smoke() -> int:
    """Sanitizer gate: the ASan build of the whole shim tier must pass
    the native-post identity matrix and the fuzz-corpus sweep, and the
    TSan build (weedrace v4) must additionally keep the serving loop
    and the syscall-floor matrix green. Each mode skips (ok) when no
    toolchain or no matching runtime exists on the host."""
    import subprocess

    from seaweedfs_tpu.native import _build

    rc = 0
    for mode, suites in _SAN_SUITES.items():
        env_extra = _build.san_preload_env(mode)
        if env_extra is None:
            print(json.dumps({
                "metric": "sanitizer_smoke",
                "ok": True,
                "mode": mode,
                "skipped": True,
                "reason": f"no {mode} runtime discoverable via the compiler",
            }))
            continue
        env = dict(os.environ, WEED_NATIVE_SAN=mode,
                   JAX_PLATFORMS="cpu", WEED_BENCH_CHECK_INNER="1",
                   **env_extra)
        try:
            proc = subprocess.run(
                [
                    sys.executable, "-m", "pytest",
                    *suites,
                    "-q", "-p", "no:cacheprovider",
                    # the smoke test that shells back into `bench.py
                    # --check` must not recurse under the sanitizer gate
                    "--deselect",
                    "tests/test_native_post.py::TestBenchCheckSmoke",
                ],
                capture_output=True,
                text=True,
                timeout=900,
                env=env,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
        except subprocess.TimeoutExpired:
            print(json.dumps({
                "metric": "sanitizer_smoke",
                "ok": False,
                "mode": mode,
                "tail": ["timeout after 900s"],
            }))
            rc = rc or 1
            continue
        tail = proc.stdout.strip().splitlines()[-1:] if proc.stdout else []
        print(json.dumps({
            "metric": "sanitizer_smoke",
            "ok": proc.returncode == 0,
            "mode": mode,
            "tail": tail
            + ([proc.stderr.strip()[-300:]] if proc.returncode else []),
        }))
        rc = rc or proc.returncode
    return rc


def check_race_smoke() -> int:
    """`bench.py --check` race leg (docs/ANALYSIS.md v4): every
    weedrace instrument must DETECT its planted bug on every run — a
    race tool that silently goes blind is worse than none, because it
    certifies orderings it never explored. Four positive controls plus
    the clean-tree negatives:

      * static `race` rule: an escaped check-then-act fixture is
        flagged; the same shape confined to the constructor is not;
      * dynamic enumerator: the PR-9 pre-fix admission ordering
        (check under one hold, count under a later one) breaches the
        cap under a schedule the explorer must find, while the real
        AdmissionController stays violation-free;
      * ctier shm-atomics: a plain-store mutant of weed_shm_admit's
        CAS is flagged; the shipped serve.c is clean;
      * GCRA model check: the blind-store protocol double-spends; the
        real CAS protocol survives every 2-worker interleaving
        including the SIGKILL arms, exhaustively (not truncated)."""
    import tempfile
    import textwrap

    from seaweedfs_tpu.analysis import ctier, race, racelint

    with tempfile.TemporaryDirectory() as td:
        root = os.path.join(td, "fixturepkg")
        os.makedirs(root)
        with open(os.path.join(root, "__init__.py"), "w") as f:
            f.write("")
        with open(os.path.join(root, "work.py"), "w") as f:
            f.write(textwrap.dedent("""
                import threading

                class Pump:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._primed = False
                        # same check-then-act shape, but confined to
                        # the ctor: must stay silent
                        if not self._primed:
                            self._primed = True

                    def prime(self):
                        if not self._primed:
                            self._primed = True

                def spin(p: "Pump"):
                    threading.Thread(target=p.prime).start()
            """))
        static_findings, _idx = racelint.check(root=root)
    static_hit = any(
        f.rule == "race-check-then-act" and "prime" in f.message
        for f in static_findings
    )
    static_quiet = not any(
        f.line < 12 for f in static_findings  # nothing inside __init__
    )

    planted = race.run_admission(budget=64, seed=0, pre_fix=True)
    fixed = race.run_admission(budget=32, seed=0)
    dyn_hit = bool(planted.violations)
    dyn_quiet = not fixed.violations

    serve_src = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "seaweedfs_tpu", "native", "serve.c",
    )
    c_hit = c_quiet = True  # hosts without serve.c have no C tier to prove
    if os.path.exists(serve_src):
        with open(serve_src, "r", encoding="utf-8") as f:
            src = f.read()
        mutant = src.replace(
            "if (__atomic_compare_exchange_n(slot, &tat, base + T, 0,",
            "if ((*slot = base + T) && (0,", 1,
        )
        c_hit = mutant != src and bool(
            ctier.check_shm_atomics(source=mutant)
        )
        c_quiet = not ctier.check_shm_atomics(source=src)

    blind = race.model_check_gcra(
        workers=2, attempts_per_worker=2, blind_store=True, kill_arm=False
    )
    model = race.model_check_gcra(
        workers=2, attempts_per_worker=2, budget=20000
    )
    gcra_hit = any("double-spend" in v for v in blind.violations)
    gcra_quiet = not model.violations and not model.truncated

    ok = (static_hit and static_quiet and dyn_hit and dyn_quiet
          and c_hit and c_quiet and gcra_hit and gcra_quiet)
    print(json.dumps({
        "metric": "race_smoke",
        "ok": ok,
        "planted_static_detected": static_hit,
        "ctor_negative_silent": static_quiet,
        "planted_admission_race_detected": dyn_hit,
        "fixed_admission_clean": dyn_quiet,
        "planted_c_data_race_detected": c_hit,
        "serve_c_shm_atomics_clean": c_quiet,
        "planted_blind_store_double_spend": gcra_hit,
        "gcra_cas_protocol_proved": gcra_quiet,
        "gcra_interleavings": model.interleavings,
    }))
    return 0 if ok else 1


def main() -> None:
    if "--check" in sys.argv[1:]:
        # one command gates perf identity (C-vs-Python write), static
        # analysis (weedlint), and memory safety (ASan matrix+corpus);
        # the inner marker keeps subprocess layers from recursing
        rc = check_native_post()
        rc = rc or check_native_serve()
        rc = rc or check_trace_smoke()
        rc = rc or check_telemetry_smoke()
        rc = rc or check_capsule_smoke()
        rc = rc or check_qos_smoke()
        rc = rc or check_degraded_smoke()
        rc = rc or check_tier_smoke()
        rc = rc or check_pipeline_identity()
        rc = rc or check_chaos_smoke()
        if os.environ.get("WEED_BENCH_CHECK_INNER") != "1":
            rc = rc or check_weedlint()
            rc = rc or check_contracts_smoke()
            rc = rc or check_crash_smoke()
            rc = rc or check_race_smoke()
            rc = rc or check_sanitizer_smoke()
        raise SystemExit(rc)
    config = sys.argv[1] if len(sys.argv) > 1 else "all"
    if config == "all":
        # The driver records whatever this prints: run the whole
        # BASELINE matrix, one JSON line per config. A config that
        # fails must not silence the rest.
        failures = []
        for name, fn in CONFIGS.items():
            try:
                fn()
            except Exception as e:  # noqa: BLE001 - report and continue
                failures.append(name)
                print(json.dumps({"metric": name, "error": str(e)[:200]}))
        if failures:
            raise SystemExit(f"bench configs failed: {failures}")
    elif config in CONFIGS:
        CONFIGS[config]()
    else:
        raise SystemExit(
            f"unknown bench config {config!r} (all|{'|'.join(CONFIGS)})"
        )


if __name__ == "__main__":
    sys.exit(main())
