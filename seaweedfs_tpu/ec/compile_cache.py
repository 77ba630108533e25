"""Where JAX keeps compiled programs between process starts.

Every node start otherwise recompiles one kernel per distinct
coefficient matrix (parity rows, one per survivor set) and per
bucketed shape. The directory is part of the cache key, so it must not
move between runs: JAX_COMPILATION_CACHE_DIR where the operator sets
it (JAX's own handling stands, nothing is set here), else one fixed
directory inside the checkout — never a tempfile, pid or time-derived
path.
"""

from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return it. Called by every module that imports JAX for program
    code, before anything compiles."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # the SWAR kernels compile in well under JAX's default 1 s floor,
    # and there is one per survivor set: keep them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_DIR
