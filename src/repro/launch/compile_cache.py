"""JAX's persistent compilation cache, for the processes that own a chip.

``enable_compile_cache`` is called by the entry points (``chip_smoke.py``
and the ``repro.launch`` launchers) before their first compile, never
on import and never by tests.  Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and nothing here names another directory.
Otherwise the cache lives in ``.jax_cache/`` at the root of this
checkout (git ignores it): a fixed path, because entries written under
a directory that changes from run to run are never found again."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory.  Every program is cached, however fast it compiled: the
    served path is many small programs (one per micro-batch bucket)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
