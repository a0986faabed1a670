"""ARD Matern-5/2 kernel over mixed continuous + categorical features.

TPU-first replacement for the reference's TFP kernel stack
(``FeatureScaledWithCategorical`` over Matern-5/2,
``/root/reference/vizier/_src/jax/models/tuned_gp_models.py:132-220``):
pure jax.numpy, batched [N, D] x [M, D] → [N, M]. The squared distance
uses the exact-difference form for typical dims (D ≤ 64) — XLA fuses the
broadcast-subtract-square-reduce into one pass, and f32 stays accurate
enough for the downstream Cholesky — and switches to the MXU
||a||² - 2a·b + ||b||² matmul expansion only for wide feature spaces.

Categorical features are integer category indices; the ARD distance adds
(mismatch / lengthscale²) per categorical dimension (the exact-match kernel
the reference builds from one-hot + feature scaling, but without
materializing one-hots).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

Array = jax.Array

_SQRT5 = 2.2360679774997896


def matern52(sq_dist: Array) -> Array:
    """Matern-5/2 of a *squared* scaled distance."""
    d = jnp.sqrt(jnp.maximum(sq_dist, 1e-20))
    return (1.0 + _SQRT5 * d + (5.0 / 3.0) * sq_dist) * jnp.exp(-_SQRT5 * d)


_DIRECT_DIST_MAX_DIM = 64


def sq_distance_of_scaled(a: Array, b: Array) -> Array:
    """[N, D], [M, D] -> [N, M] sum_d (a-b)^2 of rows already over their
    length scales (``x * ScaledRows.inverse_scales``).

    For D <= 64 (the typical Vizier regime) uses exact elementwise diffs —
    the ||a||²-2a·b+||b||² MXU expansion suffers f32 cancellation (~1e-3
    absolute on near-duplicate points), which poisons the Cholesky diagonal.
    Wide feature spaces fall back to the matmul expansion with clamping.
    """
    if a.shape[-1] <= _DIRECT_DIST_MAX_DIM:
        diff = a[:, None, :] - b[None, :, :]
        return jnp.sum(diff * diff, axis=-1)
    a2 = jnp.sum(a * a, axis=-1, keepdims=True)  # [N, 1]
    b2 = jnp.sum(b * b, axis=-1, keepdims=True).T  # [1, M]
    cross = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST
    )
    return jnp.maximum(a2 + b2 - 2.0 * cross, 0.0)


def categorical_sq_distance(
    z1: Array, z2: Array, length_scales: Array, *, dim_mask: Optional[Array] = None
) -> Array:
    """[N, S] int, [M, S] int -> [N, M] sum_s mismatch/l_s^2."""
    if z1.shape[-1] == 0:
        return jnp.zeros((z1.shape[0], z2.shape[0]), dtype=jnp.float32)
    inv_sq = 1.0 / (length_scales * length_scales)
    if dim_mask is not None:
        inv_sq = jnp.where(dim_mask, inv_sq, 0.0)
    mismatch = (z1[:, None, :] != z2[None, :, :]).astype(jnp.float32)  # [N, M, S]
    return jnp.einsum("nms,s->nm", mismatch, inv_sq)


class MixedFeatures(NamedTuple):
    """Plain-array view of model inputs (already scaled/indexed)."""

    continuous: Array  # [N, Dc] float
    categorical: Array  # [N, Ds] int


class ScaledRows(NamedTuple):
    """The kernel's second argument as the kernel reads it. None of it
    depends on the first argument, so a caller that holds the rows fixed
    over many first arguments — an acquisition sweep's data — makes it once
    (:func:`scaled_rows`)."""

    continuous: Array  # [M, Dc] over the length scales, masked dimensions 0
    categorical: Array  # [M, Ds] int
    inverse_scales: Array  # [Dc] what scaled them, and scales the other side


def scaled_rows(
    f2: MixedFeatures,
    continuous_length_scales: Array,
    continuous_dim_mask: Optional[Array] = None,
) -> ScaledRows:
    inv = 1.0 / continuous_length_scales
    if continuous_dim_mask is not None:
        inv = jnp.where(continuous_dim_mask, inv, 0.0)
    return ScaledRows(f2.continuous * inv, f2.categorical, inv)


def matern52_ard(
    f1: MixedFeatures,
    f2: MixedFeatures,
    *,
    amplitude: Array,
    continuous_length_scales: Array,
    categorical_length_scales: Array,
    continuous_dim_mask: Optional[Array] = None,
    categorical_dim_mask: Optional[Array] = None,
) -> Array:
    """Full mixed-feature ARD Matern-5/2 kernel matrix [N, M].

    XLA fuses the exact-difference distance (broadcast-subtract-square-
    reduce over D) into a single pass — no [N, M, D] intermediate reaches
    HBM. A hand-written Pallas kernel for this op was measured at
    0.4-0.93x the XLA-fused path on TPU v5e across 512..16k point counts
    (round 2) and removed: the op is bandwidth/dispatch-bound and the
    compiler already schedules it optimally.
    """
    return matern52_ard_to_rows(
        f1,
        scaled_rows(f2, continuous_length_scales, continuous_dim_mask),
        amplitude=amplitude,
        categorical_length_scales=categorical_length_scales,
        categorical_dim_mask=categorical_dim_mask,
    )


def matern52_ard_to_rows(
    f1: MixedFeatures,
    rows: ScaledRows,
    *,
    amplitude: Array,
    categorical_length_scales: Array,
    categorical_dim_mask: Optional[Array] = None,
) -> Array:
    """:func:`matern52_ard` against rows already scaled: the same arithmetic
    in the same order, so the same bits."""
    sq = sq_distance_of_scaled(f1.continuous * rows.inverse_scales, rows.continuous)
    sq = sq + categorical_sq_distance(
        f1.categorical, rows.categorical, categorical_length_scales,
        dim_mask=categorical_dim_mask,
    )
    return (amplitude * amplitude) * matern52(sq)
