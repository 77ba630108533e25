"""Test harness: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; all sharding/collective
tests run against 8 XLA host devices. Must run before jax is imported
anywhere.
"""

import os

# JAX_PLATFORMS=cpu in the environment is all it takes, here and in
# every subprocess a test spawns; it is set in code too so that a bare
# `pytest` from a shell that exports something else still runs on the
# virtual CPU mesh and never reaches for an attached chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

# Hermetic compiles: the program keeps a persistent compilation cache
# inside the checkout (ec/compile_cache.py), which six xdist workers
# would all write at once and later runs would read back.
jax.config.update("jax_enable_compilation_cache", False)

# Dynamic lock-order witness (analysis/witness.py): wraps Lock/RLock
# allocation for locks created in repo files and fails the run on any
# runtime acquisition-order inversion — the `-race`-style complement
# to the static weedlint pass, ON by default in tier-1. Installed here,
# before any seaweedfs_tpu module import can allocate its locks.
# WEED_LOCK_WITNESS=0 disables (e.g. when bisecting a perf number).
_WITNESS_ON = os.environ.get("WEED_LOCK_WITNESS", "1") != "0"
if _WITNESS_ON:
    from seaweedfs_tpu.analysis import witness as _witness

    _witness.install()

# Unit tests default to the cpu codec (fast, no per-shape jit compiles);
# the TPU serving path is covered explicitly by tests that pass
# ec_codec="tpu" / backend="tpu" (e.g. test_ec_tpu_serving.py), which
# overrides this env default.
os.environ.setdefault("WEED_EC_CODEC", "cpu")

import pathlib

import pytest

REFERENCE_ROOT = pathlib.Path("/root/reference")


def pytest_configure(config):
    # tier-1 deselects with `-m 'not slow'`; registering the marker
    # keeps the run warning-clean (unknown-mark warnings drown real
    # ones in the tail summary)
    config.addinivalue_line(
        "markers", "slow: long-running; excluded from the tier-1 sweep"
    )


@pytest.fixture(scope="session")
def reference_root() -> pathlib.Path:
    """Path to the read-only reference checkout; tests that golden-check
    against its binary fixtures skip when it is absent (e.g. on the
    bench host)."""
    if not REFERENCE_ROOT.exists():
        pytest.skip("reference checkout not available")
    return REFERENCE_ROOT


@pytest.fixture(autouse=_WITNESS_ON)
def _lock_order_witness():
    """Fails the test during which a lock-order inversion completed.
    The order graph is cumulative across the whole session (an
    inversion needs one test to establish A→B and possibly a later one
    to demonstrate B→A), so the failing test is the one that CLOSED
    the cycle — its stack is in the report."""
    from seaweedfs_tpu.analysis import witness as _w

    before = len(_w.inversions())
    yield
    found = _w.inversions()[before:]
    if found:
        pytest.fail(
            "dynamic lock-order witness detected inversion(s):\n"
            + _w.format_inversions(found),
            pytrace=False,
        )


@pytest.fixture(scope="session")
def native_post_toolchain():
    """C-path guard: tests that exercise the native write hot loop
    (native/post.c via needle_ext.post) SKIP — never error — on hosts
    without a working C toolchain, where the loader returns None and
    production falls back to the pure-Python path those same tests
    compare against."""
    from seaweedfs_tpu.server import write_path

    if write_path._needle_ext is None or not hasattr(
        write_path._needle_ext, "post"
    ):
        pytest.skip("no C toolchain: native needle_ext.post unavailable")


@pytest.fixture
def one_bench_rehearsal_at_a_time():
    """A `benchmark/run.py --rehearse` child keeps its run's files in
    ONE directory per checkout, which it empties first, and picks its
    node's ports from the bottom of one fixed range: two at once (two
    cells' test files on two xdist workers) take each other's files and
    ports. The rehearsals of every cell's tests hold this while they run."""
    import fcntl
    import tempfile

    path = os.path.join(tempfile.gettempdir(), "tpu-weed-bench-rehearsal.lock")
    with open(path, "w") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        yield
