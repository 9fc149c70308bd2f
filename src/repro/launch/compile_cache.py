"""Where compiled programs persist between processes.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``benchmarks/run.py``) calls ``use_compile_cache()`` once, before its
first compile. A process that finds the cache then skips compiling what
an earlier process on the same checkout already compiled — a 36-layer
model's serving cells, for instance.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone. Otherwise the cache goes to the fixed ``<repo>/.jax_cache``
    (listed in ``.gitignore``): never a temporary name, a PID or a time,
    so a later process finds what an earlier one wrote."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
