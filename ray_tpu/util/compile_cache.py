"""JAX's persistent compilation cache, placed once for every process.

Every process that compiles calls ``enable_compile_cache()`` before its
first compile: engine construction, the train-step build, the bench
scripts and ``chip_smoke.py``'s children. Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX already reads it and nothing is set here, so the cache can be
placed from outside. Where it is not, the cache goes to ONE fixed
directory inside the checkout (git-ignored): the path is part of the
cache key, so a temp name, pid or timestamp would never hit. Workers
reach the same answer on their own — they inherit the environment and
share the checkout.

A process pinned to the CPU backend (``JAX_PLATFORMS=cpu``: the tests,
the workers that hold no chip) gets no default directory: XLA:CPU caches
ahead-of-time results whose every load logs a machine-feature mismatch,
and the CPU backend is the test backend, not what compile time is paid on.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_compile_cache")


def enable_compile_cache() -> str:
    """Switch the persistent cache on; return the directory in use ("" = off)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return ""
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
