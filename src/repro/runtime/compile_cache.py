"""Where JAX keeps its persistent compilation cache.

Every entry point (`chip_smoke.py`, the `python -m repro.*` CLIs,
`benchmarks/perf_gate.py`, `examples/`) calls `use_persistent_cache()`
before its first compile. A cache only hits when its directory is the
same from one process to the next, so the path is fixed: either the
directory the caller's environment names in `JAX_COMPILATION_CACHE_DIR`
(JAX reads that variable itself, and it stays in charge), or
`<checkout>/.jax_cache`, which `.gitignore` lists.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/src/repro/runtime/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    With `JAX_COMPILATION_CACHE_DIR` set, JAX already uses that directory
    and nothing is changed. Otherwise `jax_compilation_cache_dir` is set
    to `DEFAULT_DIR`, which is created here (JAX writes entries into an
    existing directory only)."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    os.makedirs(DEFAULT_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
