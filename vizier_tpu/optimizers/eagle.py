"""Vectorized Eagle (firefly) strategy — the default acquisition maximizer.

Parity with the reference ``VectorizedEagleStrategy``
(``/root/reference/vizier/_src/algorithms/optimizers/eagle_strategy.py:411,500``):
a pool of fireflies moves through scaled feature space under pairwise
attraction toward better-scoring flies and repulsion from worse ones, plus a
decaying random perturbation; exhausted flies are re-seeded. The whole state
is a flax struct and every step is pure jax — it runs inside the vectorized
optimizer's ``fori_loop`` entirely on device, and the pool axis shards over
the mesh for multi-chip sweeps.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp

from vizier_tpu.models import kernels

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class EagleStrategyConfig:
    """Knobs (defaults follow the reference ``EagleStrategyConfig``)."""

    pool_size: int = 50
    visibility: float = 0.45
    gravity: float = 1.5
    negative_gravity: float = 0.008
    perturbation: float = 0.16
    perturbation_lower_bound: float = 7e-5
    penalize_factor: float = 0.7
    mutate_normalization_type: str = "mean"
    categorical_perturbation_factor: float = 25.0
    prob_same_category_without_perturbation: float = 0.98


@flax.struct.dataclass
class EagleState:
    features: Array  # [P, Dc] in [0, 1]
    categorical: Array  # [P, Ds] int32
    rewards: Array  # [P] best score seen by each fly (-inf = unevaluated)
    perturbations: Array  # [P] current perturbation scale


@dataclasses.dataclass(frozen=True)
class VectorizedEagleStrategy:
    """Firefly ask/tell over mixed feature space."""

    num_continuous: int
    category_sizes: Tuple[int, ...]
    config: EagleStrategyConfig = EagleStrategyConfig()

    @property
    def num_categorical(self) -> int:
        return len(self.category_sizes)

    @property
    def batch_size(self) -> int:
        return self.config.pool_size

    # -- init --------------------------------------------------------------

    def _draw_keys(self, rng: Array) -> Tuple[Array, Optional[Array]]:
        """(the continuous draw's key, the categorical draws' key). A key is
        split only where there are two draws to make: without categoricals
        ``rng`` is the continuous draw's own, and the sweep's loop launches
        no key derivation (PERF.md, PR 38)."""
        return tuple(jax.random.split(rng)) if self.num_categorical else (rng, None)

    def _random_features(self, rng: Array, n: int) -> Tuple[Array, Array]:
        c_rng, s_rng = self._draw_keys(rng)
        cont = jax.random.uniform(c_rng, (n, self.num_continuous), dtype=jnp.float32)
        if self.num_categorical:
            sizes = jnp.asarray(self.category_sizes, dtype=jnp.int32)
            u = jax.random.uniform(s_rng, (n, self.num_categorical))
            cat = jnp.minimum((u * sizes[None, :]).astype(jnp.int32), sizes[None, :] - 1)
        else:
            cat = jnp.zeros((n, 0), dtype=jnp.int32)
        return cont, cat

    def init_state(
        self, rng: Array, *, prior_features: Optional[kernels.MixedFeatures] = None
    ) -> EagleState:
        p = self.config.pool_size
        cont, cat = self._random_features(rng, p)
        if prior_features is not None and prior_features.continuous.shape[0] > 0:
            # Seed the head of the pool with prior (e.g. best observed) points.
            k = min(prior_features.continuous.shape[0], p)
            cont = cont.at[:k].set(prior_features.continuous[:k].astype(jnp.float32))
            if self.num_categorical:
                cat = cat.at[:k].set(prior_features.categorical[:k].astype(jnp.int32))
        return EagleState(
            features=cont,
            categorical=cat,
            rewards=jnp.full((p,), -jnp.inf, dtype=jnp.float32),
            perturbations=jnp.full((p,), self.config.perturbation, dtype=jnp.float32),
        )

    # -- ask ---------------------------------------------------------------

    def suggest(self, state: EagleState, rng: Array) -> kernels.MixedFeatures:
        cfg = self.config
        x = state.features  # [P, Dc]
        r = state.rewards

        # Pairwise pulls: toward better flies, away from worse ones.
        diff = x[None, :, :] - x[:, None, :]  # [P, P, Dc]: j - i
        sq_dist = jnp.sum(diff * diff, axis=-1)  # [P, P]
        better = (r[None, :] > r[:, None]).astype(jnp.float32)
        worse = 1.0 - better
        both_seen = (jnp.isfinite(r[None, :]) & jnp.isfinite(r[:, None])).astype(
            jnp.float32
        )
        scale = jnp.exp(-sq_dist / (2.0 * cfg.visibility**2 + 1e-12))
        force = both_seen * scale * (cfg.gravity * better - cfg.negative_gravity * worse)
        pull = jnp.einsum("ij,ijd->id", force, diff) / max(
            cfg.pool_size - 1, 1
        )

        p_rng, c_rng = self._draw_keys(rng)
        noise = jax.random.normal(p_rng, x.shape, dtype=x.dtype)
        new_x = x + pull + state.perturbations[:, None] * noise
        new_x = jnp.clip(new_x, 0.0, 1.0)

        # Categorical proposal: keep own category w.h.p., else copy from the
        # best-rewarded fly or mutate randomly (scaled by perturbation).
        if self.num_categorical:
            sizes = jnp.asarray(self.category_sizes, dtype=jnp.int32)
            k1, k2, k3 = jax.random.split(c_rng, 3)
            best_idx = jnp.argmax(r)
            best_cat = state.categorical[best_idx][None, :]  # [1, Ds]
            mutate_prob = jnp.minimum(
                state.perturbations[:, None] * cfg.categorical_perturbation_factor, 1.0
            )  # [P, 1]
            u = jax.random.uniform(k1, state.categorical.shape)
            rand_u = jax.random.uniform(k2, state.categorical.shape)
            rand_cat = jnp.minimum(
                (rand_u * sizes[None, :]).astype(jnp.int32), sizes[None, :] - 1
            )
            copy_best = jax.random.uniform(k3, state.categorical.shape) < 0.5
            proposal = jnp.where(copy_best, best_cat, rand_cat)
            new_cat = jnp.where(u < mutate_prob, proposal, state.categorical)
        else:
            new_cat = state.categorical
        return kernels.MixedFeatures(new_x, new_cat)

    # -- tell --------------------------------------------------------------

    def update(
        self,
        state: EagleState,
        rng: Array,
        candidates: kernels.MixedFeatures,
        scores: Array,
    ) -> EagleState:
        cfg = self.config
        improved = scores > state.rewards
        features = jnp.where(improved[:, None], candidates.continuous, state.features)
        categorical = jnp.where(
            improved[:, None], candidates.categorical, state.categorical
        ) if self.num_categorical else state.categorical
        rewards = jnp.where(improved, scores, state.rewards)
        # Flies that failed to improve get their perturbation penalized.
        perturbations = jnp.where(
            improved,
            jnp.asarray(cfg.perturbation, jnp.float32),
            state.perturbations * cfg.penalize_factor,
        )

        # Re-seed exhausted flies (perturbation collapsed) — but never the
        # current best fly.
        exhausted = perturbations < cfg.perturbation_lower_bound
        best_idx = jnp.argmax(rewards)
        exhausted = exhausted & (jnp.arange(cfg.pool_size) != best_idx)
        fresh_cont, fresh_cat = self._random_features(rng, cfg.pool_size)
        features = jnp.where(exhausted[:, None], fresh_cont, features)
        if self.num_categorical:
            categorical = jnp.where(exhausted[:, None], fresh_cat, categorical)
        rewards = jnp.where(exhausted, -jnp.inf, rewards)
        perturbations = jnp.where(
            exhausted, jnp.asarray(cfg.perturbation, jnp.float32), perturbations
        )
        return EagleState(
            features=features,
            categorical=categorical,
            rewards=rewards,
            perturbations=perturbations,
        )
