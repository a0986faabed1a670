"""SurrogateConfig: the exact↔sparse auto-switch policy.

Stdlib-only (the serving runtime and the analysis CLI import this without
jax). The decision is made per suggest from the study's completed-trial
count:

- below ``sparse_threshold_trials`` the study runs the exact GP — the
  bit-identical seed path;
- at or above it the study switches to the sparse inducing-point surrogate
  (``surrogates.sparse_gp``);
- once sparse, a study only switches back when its trial count drops below
  ``sparse_threshold_trials - hysteresis_trials``, so a study sitting at
  the boundary (e.g. trials being deleted/re-added, or a rebuilt designer
  replaying a truncated study) cannot flap between compiled program
  families on alternate suggests.

Every knob has a ``VIZIER_SPARSE*`` environment override (declared in
``vizier_tpu/analysis/registry.py``, documented in
``docs/guides/performance.md``). ``VIZIER_SPARSE=0`` disables the switch
entirely: every study runs the exact path, bit-identical to the seed.
"""

from __future__ import annotations

import dataclasses
import logging

_logger = logging.getLogger(__name__)

# All VIZIER_* switches are declared in (and read through) the central
# registry; an undeclared name raises instead of silently reading an
# always-unset variable. Enforced by the env_registry analysis pass.
from vizier_tpu.analysis import registry as _registry

MODE_EXACT = "exact"
MODE_SPARSE = "sparse"

# -- crossover invalidation hook ---------------------------------------------
# A crossover drops the designer's warm seed and cached posterior; anything
# the serving tier derived from pre-crossover state (today: the speculative
# pre-computed suggestion batch) is equally stale. Listeners are installed
# as a plain designer attribute — the config object itself stays a frozen
# hashable value (it feeds jit statics) — and fired best-effort from inside
# the designer's mode switch, so invalidation happens the moment the flip
# occurs rather than after the compute returns.

_CROSSOVER_ATTR = "_surrogate_crossover_listener"


def install_crossover_listener(designer, listener) -> None:
    """Attaches ``listener(old_mode, new_mode)`` to ``designer`` (replacing
    any previous listener; idempotent re-installs are the common case)."""
    setattr(designer, _CROSSOVER_ATTR, listener)


def fire_crossover_hook(designer, old_mode: str, new_mode: str) -> None:
    """Invokes the installed crossover listener, swallowing its errors —
    a broken observer must never fail the designer's own compute."""
    listener = getattr(designer, _CROSSOVER_ATTR, None)
    if listener is None:
        return
    try:
        listener(old_mode, new_mode)
    except Exception:
        _logger.warning(
            "Surrogate crossover listener failed (%s -> %s).",
            old_mode,
            new_mode,
            exc_info=True,
        )


@dataclasses.dataclass(frozen=True)
class SurrogateConfig:
    """Knobs for the sparse-surrogate auto-switch."""

    # Master switch: False = exact GP always (the seed path, bit-identical).
    sparse: bool = True
    # Completed trials at which a study crosses exact -> sparse. The default
    # sits where the exact path's O(n³) train starts to dominate suggest
    # latency on every backend (docs/guides/performance.md has the cost
    # model); studies below it keep the seed-exact behavior.
    sparse_threshold_trials: int = 512
    # A sparse study only returns to exact below threshold - hysteresis, so
    # the boundary cannot flap between compiled program families.
    hysteresis_trials: int = 64
    # Inducing-point budget m. The designer pads it up the same bucket grid
    # as trial counts (``padding.trial_bucket_grid``) so every (n-bucket,
    # m-bucket) pair is one compiled program.
    num_inducing: int = 128
    # Extend the auto-switch to the GP-UCB-PE designer (the service
    # DEFAULT): above the threshold its greedy batch conditions on pending
    # picks through the inducing-point posterior (Nyström-augmented)
    # instead of the exact GP's O(n³) per-pick re-factorization. False
    # pins UCB-PE studies exact regardless of size (the pre-PR-9
    # behavior); single-objective independent-GP studies only either way.
    sparse_ucb_pe: bool = True

    def __post_init__(self):
        if self.sparse_threshold_trials < 1:
            raise ValueError(
                f"sparse_threshold_trials must be >= 1, got "
                f"{self.sparse_threshold_trials}."
            )
        if self.hysteresis_trials < 0:
            raise ValueError(
                f"hysteresis_trials must be >= 0, got {self.hysteresis_trials}."
            )
        if self.num_inducing < 1:
            raise ValueError(
                f"num_inducing must be >= 1, got {self.num_inducing}."
            )

    @classmethod
    def from_env(cls) -> "SurrogateConfig":
        """The default config with per-knob environment overrides applied."""
        return cls(
            sparse=_registry.env_on("VIZIER_SPARSE"),
            sparse_threshold_trials=_registry.env_int(
                "VIZIER_SPARSE_THRESHOLD", 512
            ),
            hysteresis_trials=_registry.env_int("VIZIER_SPARSE_HYSTERESIS", 64),
            num_inducing=_registry.env_int("VIZIER_SPARSE_INDUCING", 128),
            sparse_ucb_pe=_registry.env_on("VIZIER_SPARSE_UCB_PE"),
        )

    @classmethod
    def disabled(cls) -> "SurrogateConfig":
        """Exact GP always — the seed path."""
        return cls(sparse=False)

    def mode_for(self, num_trials: int, current: str = MODE_EXACT) -> str:
        """The surrogate mode for a study with ``num_trials`` completed
        trials, given its ``current`` mode (hysteresis needs history)."""
        if not self.sparse:
            return MODE_EXACT
        if current == MODE_SPARSE:
            floor = self.sparse_threshold_trials - self.hysteresis_trials
            return MODE_SPARSE if num_trials >= floor else MODE_EXACT
        return (
            MODE_SPARSE
            if num_trials >= self.sparse_threshold_trials
            else MODE_EXACT
        )

    def as_dict(self) -> dict:
        """JSON-stampable form (tools artifacts)."""
        return {
            "sparse": self.sparse,
            "sparse_threshold_trials": self.sparse_threshold_trials,
            "hysteresis_trials": self.hysteresis_trials,
            "num_inducing": self.num_inducing,
            "sparse_ucb_pe": self.sparse_ucb_pe,
        }
