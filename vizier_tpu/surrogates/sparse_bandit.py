"""The sparse-GP bandit programs: jitted train / sweep / batched flush.

These mirror the exact-GP programs in ``designers.gp_bandit``
(``_train_gp`` / ``_sweep_one`` / ``_gp_bandit_flush_program``) one-for-one
so the sparse path inherits every serving discipline for free:

- the SAME multi-restart L-BFGS ARD program shape (the collapsed bound
  needs no variational loop), with the SAME warm-seed-as-extra-restart-row
  semantics — a trained sparse optimum seeds the next sparse train exactly
  like the exact path's (PARITY.md "Warm-start ARD seeding");
- the SAME acquisition machinery (ScoringFunction / TrustRegion / eagle
  sweep) over the :class:`~vizier_tpu.surrogates.sparse_gp.SparseEnsemblePredictive`;
- ONE fused flush program per (trial-bucket, inducing-bucket) pair for the
  cross-study batch executor, vmapped over a leading study axis — sparse
  studies batch, prewarm, fail-isolate and trace exactly like exact ones.

Layering: this module sits BELOW the designers (``designers.gp_bandit``
imports it), so it depends only on models/optimizers/acquisitions.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from vizier_tpu import types
from vizier_tpu.designers.gp import acquisitions
from vizier_tpu.models import gp as gp_lib
from vizier_tpu.models import kernels
from vizier_tpu.optimizers import lbfgs as lbfgs_lib
from vizier_tpu.optimizers import vectorized as vectorized_lib
from vizier_tpu.surrogates import sparse_gp

Array = jax.Array


def _heuristic_init(coll, data: gp_lib.GPData) -> gp_lib.Params:
    """A deterministic restart seed inside the collapsed bound's sound
    basin: the model's three scales read off the data's own.

    The Titsias trace term 1/(2σ²)·tr(Knn − Qnn) is stiff wherever the
    inducing rows explain little of the other rows: its gradient drives the
    amplitude to its lower clip before the length scales can grow, and a
    row that starts there ends in the noise-only corner (amplitude at its
    floor, noise = the labels' stddev, length scales back on the priors'
    centre). EVERY random restart starts there — they are drawn around the
    priors' centre, length scale 0.3 — measured on a 60×3 study (8/8) and
    at 600 × 20-D (PERF.md section 6, PR 41), where a start at unit scales
    (length scale 1, amplitude 1) collapsed too: the rows of a 20-D unit
    cube lie ~1.8 apart and their warped labels spread by ~0.16. So this
    row starts where m rows DO explain the others: every continuous length
    scale at the data's width (:func:`_data_width`: one length scale spans
    the study), the amplitude at the labels' stddev and the noise at half
    of it; both the exact and the sparse fit of such a study end at length
    scales of that order. Parameters with no such scale start at 1.
    """
    valid = data.row_mask.astype(jnp.float32)
    count = jnp.maximum(jnp.sum(valid), 1.0)
    mean = jnp.sum(valid * data.labels) / count
    spread = jnp.sqrt(jnp.sum(valid * (data.labels - mean) ** 2) / count)
    specs = {spec.name: spec for spec in coll.specs}
    constrained = {
        name: jnp.full(spec.shape, 1.0, jnp.float32) for name, spec in specs.items()
    }
    for name, scale in (
        ("amplitude", spread),
        ("noise_stddev", 0.5 * spread),
        ("continuous_length_scales", _data_width(data)),
    ):
        spec = specs.get(name)
        if spec is not None:  # (clipped: a study of equal labels, or of one row)
            constrained[name] = jnp.full(
                spec.shape, jnp.clip(scale, spec.init_low, spec.bijector.high), jnp.float32
            )
    return coll.unconstrain(constrained)


def _data_width(data: gp_lib.GPData) -> Array:
    """The diagonal of the valid rows' bounding box over the live continuous
    dimensions."""
    rows = data.row_mask[:, None]
    high = jnp.max(jnp.where(rows, data.continuous, -jnp.inf), axis=0)
    low = jnp.min(jnp.where(rows, data.continuous, jnp.inf), axis=0)
    span = jnp.where(data.cont_dim_mask & jnp.any(data.row_mask), high - low, 0.0)
    return jnp.sqrt(jnp.sum(span * span))


@functools.partial(
    jax.jit, static_argnames=("model", "optimizer", "num_restarts", "ensemble_size")
)
def _train_sparse_gp(
    model: sparse_gp.SparseGaussianProcess,
    optimizer: lbfgs_lib.LbfgsOptimizer,
    data: gp_lib.GPData,
    rng: Array,
    num_restarts: int,
    ensemble_size: int,
    warm_start: Optional[gp_lib.Params] = None,
) -> Tuple[sparse_gp.SparseGPState, Array]:
    """Sparse ARD: k-center inducing selection → restarts → L-BFGS → top-k,
    and the optimizer's own count of its work (``OptimizeResult.work``, as
    ``gp_bandit._train_gp`` hands it out).

    The inducing set is selected INSIDE the program (deterministic given
    the data) and shared by every restart; ``warm_start`` is prepended as
    an extra restart row, identical to ``gp_bandit._train_gp``, after the
    deterministic :func:`_heuristic_init` row that starts inside the
    collapsed bound's sound basin.

    The rows' end points are ranked by the BOUND each reaches, not by the
    regularised loss each was optimised under. Just past the switch the two
    disagree: at 600 × 20-D the noise-only corner pays nothing to the
    length scales' log-normal priors (it sits on their centre) while a fit
    that explains the labels pays ~80 nats for twenty length scales of 3-20,
    which is about what 128 inducing rows let the bound gain there — so the
    loss prefers, by a few nats, the row that explains nothing, on about one
    study in three (PERF.md section 6, PR 41). The priors keep each row's
    path well-posed; which basin serves the study is the evidence's to say.
    """
    sdata = sparse_gp.select_inducing_kcenter(data, model.num_inducing)
    coll = model.param_collection()
    inits = coll.batch_random_init_unconstrained(rng, num_restarts)
    rows = [_heuristic_init(coll, data)]
    if warm_start is not None:
        rows.insert(0, warm_start)
    inits = jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate([x[None] for x in xs[:-1]] + [xs[-1]], axis=0),
        *rows,
        inits,
    )
    loss_fn = lambda p: model.neg_log_likelihood(p, sdata)
    num_rows = num_restarts + len(rows)
    result = optimizer(loss_fn, inits, best_n=num_rows)  # every row, best loss first
    pull = jax.vmap(lambda p: coll.regularization(coll.constrain(p)))(result.params)
    _, keep = jax.lax.top_k(pull - jnp.sort(result.losses), ensemble_size)  # the best bounds
    params = jax.tree_util.tree_map(lambda a: a[keep], result.params)
    states = jax.vmap(lambda p: model.precompute(p, sdata))(params)
    return states, result.work()


@functools.partial(jax.jit, static_argnames=("vec_opt", "count"))
def _maximize_sparse_acquisition(
    vec_opt: vectorized_lib.VectorizedOptimizer,
    scoring: acquisitions.ScoringFunction,
    rng: Array,
    count: int,
    prior_features: kernels.MixedFeatures,
) -> vectorized_lib.VectorizedOptimizerResult:
    return vec_opt(scoring.score, rng, count=count, prior_features=prior_features)


def _prior_features_from_data(data: gp_lib.GPData) -> kernels.MixedFeatures:
    """Top observed points (by warped label) to seed the eagle pool —
    trace-identical to the exact path's helper (k derives from the padded
    row count, so shapes are stable within a padding bucket)."""
    labels = jnp.where(data.row_mask, data.labels, -jnp.inf)
    k = min(10, data.num_rows)
    _, idx = jax.lax.top_k(labels, k)
    num_valid = jnp.sum(data.row_mask)
    idx = jnp.where(jnp.arange(k) < num_valid, idx, idx[0])
    return kernels.MixedFeatures(data.continuous[idx], data.categorical[idx])


def _sweep_one(vec_opt, acquisition, s, d, k, count, use_trust_region):
    """Per-study scoring + eagle sweep over the SPARSE posterior (the
    sequential suggest and the batched flush share this trace)."""
    best_label = jnp.max(jnp.where(d.row_mask, d.labels, -jnp.inf))
    trust = acquisitions.TrustRegion.from_data(d) if use_trust_region else None
    scoring = acquisitions.ScoringFunction(
        predictive=sparse_gp.SparseEnsemblePredictive(s),
        acquisition=acquisition,
        best_label=best_label,
        trust_region=trust,
    )
    return _maximize_sparse_acquisition(
        vec_opt, scoring, k, count, _prior_features_from_data(d)
    )


def _warm_next_batched(
    model: sparse_gp.SparseGaussianProcess, states: sparse_gp.SparseGPState
) -> gp_lib.Params:
    """Per-slot warm seed for the NEXT sparse train: best member's params
    mapped back through the bijectors, vmapped over the study axis."""
    coll = model.param_collection()
    return jax.vmap(
        lambda p: coll.unconstrain(jax.tree_util.tree_map(lambda a: a[0], p))
    )(states.params)


@functools.partial(
    jax.jit,
    static_argnames=(
        "model", "optimizer", "vec_opt", "acquisition",
        "num_restarts", "ensemble_size", "count", "use_trust_region",
    ),
)
def _sparse_flush_program(
    model: sparse_gp.SparseGaussianProcess,
    optimizer: lbfgs_lib.LbfgsOptimizer,
    vec_opt: vectorized_lib.VectorizedOptimizer,
    acquisition,
    md: types.ModelData,  # stacked host ModelData, leading study axis
    rng_train: Array,  # [B]
    rng_acq: Array,  # [B]
    warm: gp_lib.Params,  # [B]
    num_restarts: int,
    ensemble_size: int,
    count: int,
    use_trust_region: bool,
):
    """ONE device program per sparse-bucket flush: encode → select inducing
    → train collapsed bound → sweep → warm seed. The sparse twin of
    ``gp_bandit._gp_bandit_flush_program`` (its fourth result too: the
    train's own work counts, ``[B, 2, rows]``); slot i matches study i run
    alone through the sequential sparse path.
    """
    data = jax.vmap(lambda m: gp_lib.GPData.from_model_data(m))(md)
    states, work = jax.vmap(
        lambda d, k, w: _train_sparse_gp(
            model, optimizer, d, k, num_restarts, ensemble_size, w
        )
    )(data, rng_train, warm)
    result = jax.vmap(
        lambda s, d, k: _sweep_one(
            vec_opt, acquisition, s, d, k, count, use_trust_region
        )
    )(states, data, rng_acq)
    return states, _warm_next_batched(model, states), result, work
