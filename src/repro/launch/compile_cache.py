"""Where the entry points keep JAX's persistent compilation cache.

Called by the programs a user runs (``chip_smoke.py``, ``repro.launch.train``,
``examples/train_federated_lm.py``, ``benchmarks/run.py``), never on import
of a library module.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here changes it.  Otherwise the cache goes to ``.jax_cache``
at the root of the checkout: a fixed path, because the path is part of each
entry's key and a cache that moves is never hit.
"""

from __future__ import annotations

import os

import jax

__all__ = ["REPO_CACHE_DIR", "use_compile_cache"]

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
