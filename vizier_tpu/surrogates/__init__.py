"""Scalable surrogate tier: sparse-GP posteriors behind the designer seam.

The exact GP's O(n³) Cholesky makes large, long-lived studies expensive.
This package provides the sparse inducing-point alternative —
O(n·m²) training, O(m²) posterior — plus the :class:`SurrogateConfig`
auto-switch that moves a study from the exact to the sparse path at a
trial-count threshold (with hysteresis), serving-tier-wide via
``ServingRuntime.surrogates``.

Modules:

- ``config``        — :class:`SurrogateConfig` + ``VIZIER_SPARSE*`` env reads
  (importable without jax; the analysis CLI and config plumbing need that);
- ``sparse_gp``     — SGPR/Nyström collapsed-bound model, k-center inducing
  selection, mask-safe like the exact GP (``models.gp``);
- ``sparse_bandit`` — the jitted train/sweep/flush programs the GP-bandit
  designer and the cross-study batch executor consume.

Evidence: the cells ``default20d-sparse.lone25`` / ``.tenants16`` of
``BENCHMARK.json`` (the served sparse tier on the chip, ``correct`` against
a float64 SGPR reference); rank-sum regret parity vs exact:
``tests/surrogates/test_regret_parity.py``.
"""

from vizier_tpu.surrogates.config import SurrogateConfig  # noqa: F401

__all__ = ["SurrogateConfig"]
