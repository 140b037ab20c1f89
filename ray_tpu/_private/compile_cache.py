"""Where compiled XLA programs are kept between processes and runs.

The one place that decides it.  ``JAX_COMPILATION_CACHE_DIR`` wins when
the caller (or the machine) set it; otherwise the cache is one fixed,
git-ignored directory in the checkout.  The path is part of the cache
key, so it is never a temporary name, a pid or a time.  The decision is
written into ``os.environ`` at ``import ray_tpu`` — before jax reads its
flags — so the driver, benchmark/run.py, chip_smoke.py and every worker the
node spawns (they inherit the environment) share one cache.
"""

from __future__ import annotations

import os
import sys

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> str:
    """Settle the cache directory for this process tree; returns it."""
    path = os.environ.setdefault(ENV, DEFAULT_DIR)
    jax = sys.modules.get("jax")
    if jax is not None:  # jax read its flags before us: tell it directly
        jax.config.update("jax_compilation_cache_dir", path)
    return path
