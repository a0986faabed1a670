"""Acquisition functions and the trust region.

Parity with
``/root/reference/vizier/_src/algorithms/designers/gp/acquisitions.py``
(UCB/LCB/EI/PI/Sample at ``:177-300``, q-variants ``:496-569``, TrustRegion
``:691``), rebuilt as stateless jax functions over posterior (mean, stddev)
so they fuse into the vectorized optimizer's scoring graph on device.
All-MAXIMIZE convention (labels are pre-flipped by the converters).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Protocol, Tuple

import flax.struct
import jax
import jax.numpy as jnp

from vizier_tpu.models import gp as gp_lib
from vizier_tpu.models import kernels

Array = jax.Array

_NORM_CONST = 0.3989422804014327  # 1/sqrt(2*pi)


def _norm_pdf(z: Array) -> Array:
    return _NORM_CONST * jnp.exp(-0.5 * z * z)


def _norm_cdf(z: Array) -> Array:
    return 0.5 * (1.0 + jax.scipy.special.erf(z / jnp.sqrt(2.0)))


def get_best_labels(labels: Array, mask: Array) -> Array:
    """Per-metric maxima over valid rows; labels ``[..., N]``, mask ``[N]``."""
    return jnp.max(jnp.where(mask, labels, -jnp.inf), axis=-1)


def get_worst_labels(labels: Array, mask: Array) -> Array:
    """Per-metric minima over valid rows; labels ``[..., N]``, mask ``[N]``."""
    return jnp.min(jnp.where(mask, labels, jnp.inf), axis=-1)


def get_reference_point(labels: Array, mask: Array, scale: float = 0.1) -> Array:
    """Hypervolume reference point: nadir − scale·range.

    [Ishibuchi2011] find 0.1 a robust scaling of the nadir offset (reference
    ``acquisitions.py:132``). With no valid rows the point falls back to 0
    so downstream scalarizations stay finite.
    """
    best = get_best_labels(labels, mask)
    worst = get_worst_labels(labels, mask)
    # Floor the span at 1.0 (warped labels are ~N(0,1) scale): with all-equal
    # labels a ref point AT the nadir would clamp every hypervolume
    # scalarization to a flat 0, leaving the acquisition optimizer nothing
    # to discriminate on.
    span = jnp.maximum(best - worst, 1.0)
    ref = worst - scale * span
    return jnp.where(jnp.isfinite(ref), ref, 0.0)


class Acquisition(Protocol):
    def __call__(self, mean: Array, stddev: Array, best_label: Array) -> Array:
        ...


@flax.struct.dataclass
class UCB:
    """Upper confidence bound: mean + c·stddev."""

    coefficient: float = flax.struct.field(pytree_node=False, default=1.8)

    def __call__(self, mean: Array, stddev: Array, best_label: Array) -> Array:
        del best_label
        return mean + self.coefficient * stddev


@flax.struct.dataclass
class LCB:
    coefficient: float = flax.struct.field(pytree_node=False, default=1.8)

    def __call__(self, mean: Array, stddev: Array, best_label: Array) -> Array:
        del best_label
        return mean - self.coefficient * stddev


@flax.struct.dataclass
class EI:
    """Expected improvement over the best observed label."""

    def __call__(self, mean: Array, stddev: Array, best_label: Array) -> Array:
        z = (mean - best_label) / stddev
        return stddev * (z * _norm_cdf(z) + _norm_pdf(z))


@flax.struct.dataclass
class LogEI:
    """Numerically-robust log(EI); same argmax as EI, better gradients."""

    def __call__(self, mean: Array, stddev: Array, best_label: Array) -> Array:
        z = (mean - best_label) / stddev
        # log(s * h(z)), h(z) = z Φ(z) + φ(z), in three regimes. Direct
        # evaluation cancels catastrophically in f32 once z ≲ -2 (both terms
        # shrink to ~φ(z) while h ~ φ(z)/z²), so the mid range uses
        # h = φ(z)·(1 + z Φ(z)/φ(z)) via log1p — the cancellation then
        # happens on an O(1) ratio instead of two tiny near-equal terms —
        # and the deep tail (where Φ, φ underflow f32) uses the asymptotic
        # h ≈ φ(z)(z²-3)/z⁴. Each branch is computed on a clipped copy of z
        # so the unused branches stay finite under jnp.where gradients.
        c = 0.5 * jnp.log(2.0 * jnp.pi)
        log_s = jnp.log(stddev)

        zd = jnp.maximum(z, -1.5)  # direct: z > -1
        direct = jnp.log(zd * _norm_cdf(zd) + _norm_pdf(zd))

        # mills: -10 < z <= -1. The ratio z·Φ(z)/φ(z) ∈ (-1, 0) is formed in
        # log space (log_ndtr stays accurate where f32 Φ saturates to 0).
        zm = jnp.clip(z, -12.0, -0.5)
        log_phi_m = -0.5 * zm * zm - c
        t = jnp.log(-zm) + jax.scipy.special.log_ndtr(zm) - log_phi_m
        ratio = -jnp.exp(jnp.minimum(t, 0.0))
        mills = log_phi_m + jnp.log1p(jnp.maximum(ratio, -0.9999999))

        zt = jnp.minimum(z, -4.0)  # tail: z <= -10
        tail = -0.5 * zt * zt - c + jnp.log(zt * zt - 3.0) - 2.0 * jnp.log(zt * zt)

        return jnp.where(z > -1.0, direct, jnp.where(z > -10.0, mills, tail)) + log_s


@flax.struct.dataclass
class PI:
    """Probability of improvement."""

    def __call__(self, mean: Array, stddev: Array, best_label: Array) -> Array:
        return _norm_cdf((mean - best_label) / stddev)


@flax.struct.dataclass
class PE:
    """Pure exploration: maximize posterior stddev (GP-UCB-PE batches)."""

    def __call__(self, mean: Array, stddev: Array, best_label: Array) -> Array:
        del mean, best_label
        return stddev


@flax.struct.dataclass
class Sample:
    """Thompson sampling via one marginal posterior sample."""

    seed: Array

    def __call__(self, mean: Array, stddev: Array, best_label: Array) -> Array:
        del best_label
        eps = jax.random.normal(self.seed, mean.shape, dtype=mean.dtype)
        return mean + stddev * eps


def q_acquisition(
    per_member_means: Array,  # [E, M]
    per_member_stddevs: Array,  # [E, M]
    rng: Array,
    *,
    best_label: Array,
    num_samples: int = 32,
    kind: str = "qei",
) -> Array:
    """Monte-Carlo q-style score per point: E[max(improvement, 0)] etc.

    Used for parallel-batch (q) acquisitions: samples fantasize over member
    × posterior draws (parity with QEI/QUCB, ``acquisitions.py:496-569``).
    """
    e, m = per_member_means.shape
    eps = jax.random.normal(rng, (num_samples, e, m), dtype=per_member_means.dtype)
    draws = per_member_means[None] + per_member_stddevs[None] * eps  # [S, E, M]
    draws = draws.reshape(-1, m)
    if kind == "qei":
        return jnp.mean(jnp.maximum(draws - best_label, 0.0), axis=0)
    if kind == "qpi":
        return jnp.mean((draws > best_label).astype(draws.dtype), axis=0)
    if kind == "qucb":
        mean = jnp.mean(draws, axis=0)
        return mean + 1.8 * jnp.std(draws, axis=0)
    raise ValueError(f"Unknown q-acquisition {kind!r}.")


@flax.struct.dataclass
class TrustRegion:
    """L∞ trust region around observed points.

    Parity with the reference ``TrustRegion`` (``acquisitions.py:691``):
    candidates farther than the trust radius from every observed point are
    penalized linearly, pushing the acquisition argmax back toward explored
    space until enough trials justify global moves. The radius grows with
    the number of observed trials.
    """

    observed_continuous: Array  # [N, Dc] scaled features
    observed_cat: Array  # [N, Ds]
    row_mask: Array  # [N]
    min_radius: float = flax.struct.field(pytree_node=False, default=0.2)
    penalty_weight: float = flax.struct.field(pytree_node=False, default=30.0)

    @classmethod
    def from_data(cls, data: gp_lib.GPData, **kwargs) -> "TrustRegion":
        return cls(
            observed_continuous=data.continuous,
            observed_cat=data.categorical,
            row_mask=data.row_mask,
            **kwargs,
        )

    def trust_radius(self) -> Array:
        n = jnp.sum(self.row_mask.astype(jnp.float32))
        dim = self.observed_continuous.shape[-1] + self.observed_cat.shape[-1]
        # 0.2 → 1.0 as observations accumulate relative to dimension.
        grow = 0.1 * n / jnp.maximum(jnp.sqrt(jnp.asarray(dim, jnp.float32)), 1.0)
        return jnp.minimum(self.min_radius + grow * 0.05, 1.0)

    def linf_distance(self, query: kernels.MixedFeatures) -> Array:
        """[M] distance to the nearest valid observed point (L∞).

        CONTINUOUS dims only: the reference's ``min_linf_distance``
        (``acquisitions.py:758``) deliberately excludes categorical
        features from the trust-region distance — a mismatch would put
        every unobserved category at L∞ = 1 > radius, and the penalty
        would forbid exploring new categorical combinations outright (on a
        pure-categorical space the argmax then collapses onto observed
        cells).
        """
        qc = query.continuous
        if qc.shape[-1] == 0:
            return jnp.zeros(qc.shape[0], jnp.float32)
        dc = jnp.abs(qc[:, None, :] - self.observed_continuous[None, :, :])  # [M,N,Dc]
        linf = jnp.max(dc, axis=-1)  # [M, N]
        linf = jnp.where(self.row_mask[None, :], linf, jnp.inf)
        dist = jnp.min(linf, axis=-1)
        # No observations at all -> everything is trusted.
        return jnp.where(jnp.isfinite(dist), dist, 0.0)

    def penalty(
        self, query: kernels.MixedFeatures, radius: Optional[Array] = None
    ) -> Array:
        """``radius`` is ``trust_radius()``, from a caller that scores many
        queries against one region and computed it once (a sweep's loop)."""
        radius = self.trust_radius() if radius is None else radius
        excess = jnp.maximum(self.linf_distance(query) - radius, 0.0)
        return self.penalty_weight * excess


@flax.struct.dataclass
class ScoringFunction:
    """Predictive + acquisition + optional trust region, as one callable.

    This is the function the vectorized optimizer maximizes on device; it is
    a pytree, so it can be donated/captured by jitted loops.
    """

    predictive: gp_lib.EnsemblePredictive
    acquisition: UCB  # any Acquisition pytree
    best_label: Array
    trust_region: Optional[TrustRegion] = None

    def score(self, query: kernels.MixedFeatures) -> Array:
        mean, stddev = self.predictive.predict(query)
        values = self.acquisition(mean, stddev, self.best_label)
        if self.trust_region is not None:
            values = values - self.trust_region.penalty(query)
        return values


@flax.struct.dataclass
class HVScalarizedScoring:
    """Multi-objective scoring: random-direction HV scalarization of UCB.

    Parity with the reference's multi-objective GP bandit path
    (``gp_bandit.py:213-242`` + ``create_hv_scalarization``,
    ``acquisitions.py:571``): per-metric UCB vectors are scalarized along K
    random positive directions and averaged — maximizing the expected
    hypervolume improvement direction-by-direction.
    """

    metric_states: gp_lib.GPState  # leading axis M (one GP per objective)
    directions: Array  # [K, M] positive unit vectors
    reference_point: Array  # [M]
    ucb_coefficient: float = flax.struct.field(pytree_node=False, default=1.8)
    trust_region: Optional[TrustRegion] = None

    def score(self, query: kernels.MixedFeatures) -> Array:
        means, stddevs = jax.vmap(lambda s: s.predict(query))(self.metric_states)
        ucb = means + self.ucb_coefficient * stddevs  # [M, Q]
        m = ucb.shape[0]
        shifted = jnp.maximum(ucb - self.reference_point[:, None], 0.0)  # [M, Q]
        # ratios[k, m, q] then min over m, ^M, mean over k.
        ratios = shifted[None, :, :] / jnp.maximum(self.directions[:, :, None], 1e-12)
        values = jnp.mean(jnp.min(ratios, axis=1) ** m, axis=0)  # [Q]
        if self.trust_region is not None:
            values = values - self.trust_region.penalty(query)
        return values


@flax.struct.dataclass
class MaxValueEntropySearch:
    """Max-value entropy search (MES) via Gumbel-sampled optimum values.

    Parity with the reference ``MaxValueEntropySearch``: approximates the
    mutual information between a candidate's observation and the (unknown)
    optimum value y*, with y* samples drawn from a Gumbel approximation to
    the max-posterior distribution.
    """

    y_star_samples: Array  # [S] sampled optimum values

    @classmethod
    def from_predictive(
        cls,
        predictive,
        observed: kernels.MixedFeatures,
        rng: Array,
        *,
        num_samples: int = 16,
    ) -> "MaxValueEntropySearch":
        mean, stddev = predictive.predict(observed)
        # Gumbel approximation: fit location/scale from the max of the
        # posterior marginals at observed points.
        upper = jnp.max(mean + 3.0 * stddev)
        lower = jnp.max(mean)
        scale = jnp.maximum((upper - lower) / 3.0, 1e-3)
        u = jax.random.uniform(
            rng, (num_samples,), minval=jnp.finfo(jnp.float32).tiny, maxval=1.0
        )
        gumbel = -jnp.log(-jnp.log(u))
        return cls(y_star_samples=lower + scale * gumbel)

    def __call__(self, mean: Array, stddev: Array, best_label: Array) -> Array:
        del best_label
        z = (self.y_star_samples[:, None] - mean[None, :]) / stddev[None, :]  # [S, Q]
        pdf = _norm_pdf(z)
        cdf = jnp.clip(_norm_cdf(z), 1e-9, 1.0 - 1e-9)
        # MI ≈ E_y*[ z φ(z) / (2 Φ(z)) − log Φ(z) ].
        return jnp.mean(z * pdf / (2.0 * cdf) - jnp.log(cdf), axis=0)
