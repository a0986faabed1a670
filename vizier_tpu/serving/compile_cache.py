"""The one place that points JAX's persistent compilation cache somewhere.

Precedence, highest first:

1. ``JAX_COMPILATION_CACHE_DIR`` in the environment: JAX reads it itself,
   so nothing is set in code and that directory is reported.
2. The repo's own setting (``ServingConfig.compilation_cache_dir`` /
   ``VIZIER_COMPILE_CACHE_DIR``), when given.
3. For process entry points that run on the chip (``entry_point=True``):
   the fixed ``<checkout>/.jax_cache``. The path is part of what makes a
   cache entry findable again, so it is never built from a temporary name,
   a pid or a time.
4. Otherwise nothing: library import and a bare ``ServingRuntime()`` (the
   test suite's xdist workers) leave the cache off.
"""

from __future__ import annotations

import os
from typing import Optional

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure(
    cache_dir: Optional[str] = None, *, entry_point: bool = False
) -> Optional[str]:
    """Applies the precedence above; returns the directory in force or None."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    chosen = cache_dir or (CHECKOUT_CACHE_DIR if entry_point else None)
    if chosen is None:
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", chosen)
    # The small per-bucket GP programs compile in under JAX's 1 s floor on
    # some backends; cache them too.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return chosen


def configure_entry_point() -> Optional[str]:
    """``configure`` for a process entry point that runs on the chip: the
    repo's own setting from the environment, else the checkout directory."""
    from vizier_tpu.serving import config as config_lib

    return configure(
        config_lib.ServingConfig.from_env().compilation_cache_dir, entry_point=True
    )
