"""Serving runtime knobs.

Everything defaults ON; each knob can be forced off per-process via the
environment (useful for A/B runs and for restoring the reference's
cold-train-per-request behavior without code changes):

- ``VIZIER_SERVING_CACHE=0``      — no designer-state cache (stateless
  ``DesignerPolicy`` per request, the reference shape);
- ``VIZIER_SERVING_WARM_START=0`` — cache designers but cold-train ARD on
  every suggest (full restart budget from random inits);
- ``VIZIER_SERVING_COALESCING=0`` — every Pythia suggest computes its own
  designer run;
- ``VIZIER_BATCHING=0``           — no cross-study batch executor: every
  study's computation dispatches alone (today's per-study path,
  bit-identical suggestions);
- ``VIZIER_BATCHING_PREWARM=1``   — AOT-compile the batched programs over
  the padding-bucket grid when the first study of a shape arrives
  (default off: prewarm is explicit via ``ServingRuntime.prewarm_batching``).
- ``VIZIER_COMPILE_CACHE_DIR=/path`` — persist XLA compilations across
  process restarts (``jax_compilation_cache_dir``); JAX's own
  ``JAX_COMPILATION_CACHE_DIR`` outranks it (``serving.compile_cache``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

# All VIZIER_* switches are declared in (and read through) the central
# registry; an undeclared name raises instead of silently reading an
# always-unset variable. Enforced by the env_registry analysis pass.
from vizier_tpu.analysis import registry as _registry


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Knobs for the stateful serving runtime."""

    # Keep live designers + trained ARD params per study.
    designer_cache: bool = True
    # Inject the previous suggest's trained params as restart seed 0 and
    # shrink the restart budget to ``warm_ard_restarts``.
    warm_start: bool = True
    # Collapse concurrent identical Pythia suggest computations.
    coalescing: bool = True
    # Cache sizing: LRU beyond max_entries, TTL on idle entries.
    cache_max_entries: int = 64
    cache_ttl_seconds: float = 3600.0
    # Restart budget for a warm-started ARD train (cold trains keep the
    # designer's full ``ard_restarts``). Regret parity of 1 restart is held
    # by tests/serving/test_warm_start_parity.py; every benchmark cell serves
    # warm trains (``cache_warm_share.*``, ``train_wait_ms.*``).
    warm_ard_restarts: int = 1

    # -- cross-study batching (vizier_tpu.parallel.batch_executor) ----------
    # Collect concurrent designer computations from different studies into
    # shape-bucket queues and run each bucket as ONE vmapped device program.
    # Measured on the chip by the cell ``default20d.tenants16``
    # (``suggestions_per_s``, ``batched_share``); parity with the sequential
    # path: tests/parallel/test_batch_executor.py.
    batching: bool = True
    # Flush a bucket at this many studies ("full") ...
    batch_max_size: int = 8
    # ... or when its oldest request has waited this long ("timeout"), so
    # single-study latency is bounded by the micro-batch window.
    batch_max_wait_ms: float = 4.0
    # Pad partial batches to batch_max_size with masked copies of slot 0:
    # one compiled program shape per bucket regardless of occupancy.
    batch_pad_partial: bool = True
    # AOT-compile the batched programs over the padding-bucket grid when
    # the first study of a shape arrives (background thread). Explicit
    # prewarm via ServingRuntime.prewarm_batching works either way.
    batching_prewarm: bool = False
    # The padding-grid ceiling the prewarm walks (study sizes 1..N).
    batching_prewarm_max_trials: int = 32

    # JAX persistent compilation cache directory (applied at runtime init
    # by ``serving.compile_cache.configure``, which yields to
    # JAX_COMPILATION_CACHE_DIR); None leaves jax's default alone.
    compilation_cache_dir: Optional[str] = None

    @classmethod
    def from_env(cls) -> "ServingConfig":
        """The default config with per-knob environment overrides applied."""
        return cls(
            designer_cache=_registry.env_on("VIZIER_SERVING_CACHE"),
            warm_start=_registry.env_on("VIZIER_SERVING_WARM_START"),
            coalescing=_registry.env_on("VIZIER_SERVING_COALESCING"),
            batching=_registry.env_on("VIZIER_BATCHING"),
            batch_max_size=_registry.env_int("VIZIER_BATCH_MAX_SIZE", 8),
            batch_max_wait_ms=_registry.env_float("VIZIER_BATCH_MAX_WAIT_MS", 4.0),
            batching_prewarm=_registry.env_on("VIZIER_BATCHING_PREWARM"),
            compilation_cache_dir=(
                _registry.env_str("VIZIER_COMPILE_CACHE_DIR") or None
            ),
        )

    @classmethod
    def disabled(cls) -> "ServingConfig":
        """Reference behavior: stateless, cold, uncoalesced, unbatched."""
        return cls(
            designer_cache=False,
            warm_start=False,
            coalescing=False,
            batching=False,
        )
