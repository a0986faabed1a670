"""VizierGPUCBPEBandit: the DEFAULT algorithm (GP-UCB with Pure Exploration).

Parity with ``/root/reference/vizier/_src/algorithms/designers/gp_ucb_pe.py``
(config ``:80``, score functions ``:282,384,510``, designer ``:609`` — the
service default, ``policy_factory.py:40-47``; algorithm from Contal et al.,
"Parallel Gaussian Process Optimization with UCB and Pure Exploration"):

- Two conditioned posteriors: ``completed`` (observed labels) and ``all``
  (completed + pending/active + already-picked batch points, labels ignored
  — only the stddev matters, and GP posterior stddev is label-free).
- **UCB score** = mean(completed) + c·stddev(all): pending points deflate
  the stddev so concurrent workers do not duplicate suggestions.
- **PE score** = stddev(all) + penalty·min(explore_ucb − threshold, 0) where
  the threshold is the completed-posterior *mean at the argmax-UCB point*
  over observed+pending features, and explore_ucb uses its own (smaller)
  coefficient — pure exploration restricted to the promising region.
- **UCB/PE choice** per pick: fresh completed trials → UCB except w.p.
  ``pe_overwrite_probability`` (raised in the high-noise regime detected by
  the signal-to-noise threshold); otherwise PE except w.p.
  ``ucb_overwrite_probability``. Within a batch, picks after the first see
  the earlier picks as pending, so they explore.
- **Multimetric**: per-metric independent GPs; UCB hypervolume-scalarized
  along random directions (clamped at the observed labels' scalarization);
  PE penalty scalarized by union/intersection/average across metrics.
- **Set acquisition** (optional): the PE batch is optimized *jointly* —
  log-det of the batch posterior covariance — instead of greedily.

TPU-first: the WHOLE batch loop — per-pick Cholesky re-conditioning on the
growing pending set, penalty, and the eagle acquisition sweep — is one
jitted ``fori_loop``; picks are written into spare padded rows (no reshapes
or retraces within a padding bucket), and ensemble members × metrics are
``vmap``-batched Cholesky factorizations that XLA maps onto the MXU.
"""

from __future__ import annotations

import abc
import dataclasses
import functools
from typing import Any, Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from vizier_tpu import types
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.compute import ir as compute_ir
from vizier_tpu.compute import registry as compute_registry
from vizier_tpu.designers import gp_bandit
from vizier_tpu.surrogates import config as surrogate_config_lib
from vizier_tpu.surrogates import sparse_bandit
from vizier_tpu.surrogates import sparse_gp
from vizier_tpu.designers.gp import acquisitions
from vizier_tpu.models import gp as gp_lib
from vizier_tpu.models import kernels
from vizier_tpu.models import multitask_gp as mtgp
from vizier_tpu.models import output_warpers
from vizier_tpu.observability import jax_timing
from vizier_tpu.observability import tracing as tracing_lib
from vizier_tpu.optimizers import eagle as eagle_lib
from vizier_tpu.optimizers import vectorized as vectorized_lib
from vizier_tpu.pyvizier import base_study_config
from vizier_tpu.pyvizier import trial as trial_
from vizier_tpu.utils import profiler

Array = jax.Array

# Re-export: `UCBPEConfig(multitask_type=MultiTaskType.SEPARABLE)` matches
# the reference's `UCBPEConfig.multitask_type` (gp_ucb_pe.py:130-134).
MultiTaskType = mtgp.MultiTaskType

_PE_NOISE_STDDEV = 1e-5  # noise floor for the all-predictive in high noise


@dataclasses.dataclass(frozen=True)
class UCBPEConfig:
    """UCB-PE config (reference ``UCBPEConfig``, ``gp_ucb_pe.py:80-132``).

    Frozen/hashable so it rides into jitted programs as a static argument.
    """

    ucb_coefficient: float = 1.8
    # A separate (smaller) coefficient defining the region worth exploring.
    explore_region_ucb_coefficient: float = 0.5
    # Slope of the linear penalty for violating UCB(x) >= threshold.
    cb_violation_penalty_coefficient: float = 10.0
    # P(UCB) when there are NO new completed trials.
    ucb_overwrite_probability: float = 0.25
    # P(PE) when there ARE new completed trials.
    pe_overwrite_probability: float = 0.1
    # Same, in the detected-high-noise regime.
    pe_overwrite_probability_in_high_noise: float = 0.7
    # signal/noise variance ratio below which noise is considered high
    # (0 disables the high-noise behaviors).
    signal_to_noise_threshold: float = 0.7
    # Optimize the exploration batch jointly (log-det set acquisition).
    optimize_set_acquisition_for_exploration: bool = False
    # Multimetric promising-region penalty: union | intersection | average.
    multimetric_promising_region_penalty_type: str = "average"
    # Random HV-scalarization directions for multimetric UCB.
    num_scalarizations: int = 1000
    # Multimetric GP structure (reference ``UCBPEConfig.multitask_type``,
    # ``gp_ucb_pe.py:130-134``): INDEPENDENT trains one GP per metric; the
    # SEPARABLE* variants train a single GP with a learned task-covariance B
    # over a B ⊗ Kx Kronecker Gram, sharing statistical strength across
    # metrics. SEPARABLE (= SEPARABLE_NORMAL) is a free signed Cholesky;
    # SEPARABLE_LKJ uses an LKJ-prior correlation factor; SEPARABLE_DIAG a
    # diagonal B (see ``models.multitask_gp``).
    multitask_type: mtgp.MultiTaskType = mtgp.MultiTaskType.INDEPENDENT

    def __post_init__(self):
        if self.multimetric_promising_region_penalty_type not in (
            "union",
            "intersection",
            "average",
        ):
            raise ValueError(
                "multimetric_promising_region_penalty_type must be one of "
                "'union' | 'intersection' | 'average', got "
                f"{self.multimetric_promising_region_penalty_type!r}."
            )
        if not isinstance(self.multitask_type, mtgp.MultiTaskType):
            raise ValueError(
                f"multitask_type must be a MultiTaskType, got "
                f"{self.multitask_type!r}."
            )


def _per_member(member: Callable, *states):
    """``member`` of one member's states, over the [M, E] leading axes of
    the ``states`` pytrees: every output gains them in front.

    One metric × one member — the shipped default — is evaluated unbatched
    and the two unit axes are put back on the outputs. Under
    ``vmap(vmap())`` they ride into the fused kernel pass as broadcast axes,
    and the TPU's compiler lays them second-minor: ``[1, 1, Q, N, D]`` tiled
    ``T(1,128)``, one sublane of a vector register's eight (PERF.md, PR 42).
    The choice is made from a static shape, and the arithmetic is the same
    in the same order; with more metrics or members the batched program is
    untouched.
    """
    if jax.tree_util.tree_leaves(states)[0].shape[:2] != (1, 1):
        return jax.vmap(jax.vmap(member))(*states)
    out = member(*jax.tree_util.tree_map(lambda a: a[0, 0], states))
    return jax.tree_util.tree_map(lambda a: a[None, None], out)


def _mixture_predict(
    states, query: kernels.MixedFeatures
) -> Tuple[Array, Array]:
    """Moment-matched mixture over the ensemble axis, per metric.

    ``states``: GPState pytree with leading axes [M, E]. Returns
    ([M, Q] mean, [M, Q] stddev).
    """
    return _moment_match(*_per_member(lambda s: s.predict(query), states))


def _moment_match(means: Array, stddevs: Array) -> Tuple[Array, Array]:
    """[M, E, Q] member means and stddevs -> the uniform mixture's [M, Q]."""
    mean = jnp.mean(means, axis=1)
    second = jnp.mean(stddevs**2 + means**2, axis=1)
    var = jnp.maximum(second - mean**2, 1e-12)
    return mean, jnp.sqrt(var)


def _exact_posterior_pair(
    states_completed: gp_lib.GPState,  # [M, E]
    states_all: gp_lib.GPState,  # [M, E], over the all-points rows
    rows_all: kernels.ScaledRows,  # [M, E] ``states_all.kernel_rows()``
    query: kernels.MixedFeatures,
) -> Tuple[Array, Array, Array]:
    """(mean, stddev) of the completed posterior and the all-points stddev
    at ``query``, each [M, Q], from ONE cross-covariance a member.

    ``_mixture_predict`` of each side built its own k(query, X): the sweep's
    dearest pass, twice an iteration. The all-points hyperparameters are the
    trained ones but for the noise (``_pe_conditioning``), which the kernel
    does not read, and the completed rows are the leading rows of the
    all-points data (``_all_points_model_data``), so k(query, X_completed)
    is the leading block of k(query, X_all); each posterior masks it with
    its own ``row_mask``.
    """
    n_completed = states_completed.alpha.shape[-1]

    def member(completed, everything, rows):
        k_all = everything.cross_covariance(query, rows)
        return (
            *completed.predict_from_cross(k_all[:, :n_completed]),
            *everything.predict_from_cross(k_all),
        )

    mean_c, std_c, mean_all, std_all = _per_member(
        member, states_completed, states_all, rows_all
    )
    return (*_moment_match(mean_c, std_c), _moment_match(mean_all, std_all)[1])


def _mt_mixture_predict(
    states: "mtgp.MultiTaskGPState", query: kernels.MixedFeatures
) -> Tuple[Array, Array]:
    """Moment-matched mixture over the ensemble axis for a multitask state.

    ``states``: MultiTaskGPState pytree with leading axis [E]; its
    ``predict`` is per-task already. Returns ([M, Q] mean, [M, Q] stddev) —
    the same contract as :func:`_mixture_predict`.
    """
    means, stddevs = jax.vmap(lambda s: s.predict(query))(states)  # [E, M, Q]
    mean = jnp.mean(means, axis=0)
    second = jnp.mean(stddevs**2 + means**2, axis=0)
    var = jnp.maximum(second - mean**2, 1e-12)
    return mean, jnp.sqrt(var)


def _pe_conditioning(
    states_completed,  # GPState [M, E] or MultiTaskGPState [E]
    all_data,  # GPData or MultiTaskData
    config: UCBPEConfig,
    *,
    mixture=None,
    base_data=None,
    snr=None,
) -> Tuple[dict, Array, Array]:
    """(pe_params, noise_is_high, threshold[M]): shared UCB-PE conditioning.

    - High-noise detection: all ensemble members' signal/noise variance
      ratios below the config threshold → the all-points predictive gets a
      near-zero noise floor so pending points fully deflate local stddev.
      ``snr`` overrides the default scalar amplitude²/noise² ratio (the
      multitask path scales signal by the learned task covariance diag).
    - Promising-region threshold: completed-posterior mean at the
      argmax-UCB point among observed + pending features, per metric.
    """
    mixture = mixture or _mixture_predict
    base = base_data(all_data) if base_data is not None else all_data
    params = states_completed.params  # constrained, [M, E] (or [E]) leaves
    if snr is None:
        snr = (params["amplitude"] / params["noise_stddev"]) ** 2
    noise_is_high = jnp.all(snr < config.signal_to_noise_threshold) & (
        config.signal_to_noise_threshold > 0.0
    )
    pe_params = dict(params)
    pe_params["noise_stddev"] = jnp.where(
        noise_is_high, _PE_NOISE_STDDEV, params["noise_stddev"]
    )
    all_pts = base.features()
    mean_at, std_at = mixture(states_completed, all_pts)  # [M, N2]
    ucb_at = jnp.where(
        base.row_mask[None, :],
        mean_at + config.ucb_coefficient * std_at,
        -jnp.inf,
    )
    threshold = jnp.take_along_axis(
        mean_at, jnp.argmax(ucb_at, axis=-1, keepdims=True), axis=-1
    )[:, 0]  # [M]
    return pe_params, noise_is_high, threshold


def _append_row(
    data: gp_lib.GPData, x: kernels.MixedFeatures
) -> gp_lib.GPData:
    """Writes x into the first free padded row (labels stay 0: stddev-only)."""
    idx = jnp.sum(data.row_mask.astype(jnp.int32))  # first free slot
    return gp_lib.GPData(
        continuous=data.continuous.at[idx].set(x.continuous[0]),
        categorical=data.categorical.at[idx].set(x.categorical[0]),
        labels=data.labels,
        row_mask=data.row_mask.at[idx].set(True),
        cont_dim_mask=data.cont_dim_mask,
        cat_dim_mask=data.cat_dim_mask,
    )


def _append_row_mt(
    data: "mtgp.MultiTaskData", x: kernels.MixedFeatures
) -> "mtgp.MultiTaskData":
    """Multitask pending-point append: every task observes the new row."""
    fd = data.features_data
    idx = jnp.sum(fd.row_mask.astype(jnp.int32))
    return mtgp.MultiTaskData(
        features_data=_append_row(fd, x),
        task_labels=data.task_labels,
        task_mask=data.task_mask.at[:, idx].set(True),
    )


# A pick whose Nyström residual k** − ‖L⁻¹k(Z,x)‖² exceeds this fraction of
# the prior variance is "not near an inducing row": the base inducing set
# carries (almost) no information at x, so conditioning through it would
# barely deflate the local stddev and the PE score would re-pick the same
# point for the rest of the batch. Such picks join the inducing set.
_NYSTROM_RESIDUAL_FRACTION = 0.1


def _append_row_sparse(
    sdata: "sparse_gp.SparseGPData",
    x: kernels.MixedFeatures,
    ref_state: "sparse_gp.SparseGPState",
) -> "sparse_gp.SparseGPData":
    """Sparse pending-pick conditioning: append + conditional Nyström augment.

    The pick always joins the all-points data rows (so ``A`` gains a
    column and the inducing posterior's stddev deflates near it, exactly
    like the exact path's pending rows). When the pick is NOT near an
    inducing row — measured by its Nyström residual under ``ref_state``,
    the trained completed-posterior's member-0 factorization — it is also
    written into the next spare (masked-off) inducing slot reserved by
    :func:`sparse_gp.with_pending_capacity`, restoring the variance
    deflation the inducing bottleneck would otherwise swallow. Traceable:
    fixed shapes, pure ``at[].set`` writes.
    """
    data = _append_row(sdata.data, x)
    # Residual vs the BASE inducing set (amp² − ‖L⁻¹k(Z,x)‖² at member 0).
    kz = ref_state.model.base._kernel(
        ref_state.params, x, ref_state.sdata.z_features(), ref_state.sdata.data
    )  # [1, m]
    kz = jnp.where(ref_state.sdata.inducing_mask[None, :], kz, 0.0)
    t1 = jnp.matmul(ref_state.linv, kz[0], precision=gp_lib.POSTERIOR_PRECISION)
    amp2 = ref_state.params["amplitude"] * ref_state.params["amplitude"]
    residual = amp2 - jnp.sum(t1 * t1)
    augment = residual > _NYSTROM_RESIDUAL_FRACTION * amp2
    # Masks stay a true-prefix (k-center fills a prefix; augments extend
    # it), so the next free slot is the current true count.
    idx = jnp.sum(sdata.inducing_mask.astype(jnp.int32))
    idx = jnp.minimum(idx, sdata.inducing_mask.shape[0] - 1)
    write = augment & ~sdata.inducing_mask[idx]
    z_cont = sdata.z_continuous.at[idx].set(
        jnp.where(write, x.continuous[0], sdata.z_continuous[idx])
    )
    z_cat = sdata.z_categorical.at[idx].set(
        jnp.where(write, x.categorical[0], sdata.z_categorical[idx])
    )
    mask = sdata.inducing_mask.at[idx].set(sdata.inducing_mask[idx] | write)
    return sparse_gp.SparseGPData(
        data=data,
        z_continuous=z_cont,
        z_categorical=z_cat,
        inducing_mask=mask,
        inducing_indices=sdata.inducing_indices,
    )


# The sequential path's crossings between its compiled programs. Each
# is ONE small program, compiled in set-up with the shapes it serves, where
# the eager form launched one program per operation and per leaf.
# ``_stack_fits``, ``_metric_zero``, ``_sparse_all_points`` and
# ``_append_first_pick`` only move and select values.
# ``_sweep_inputs`` computes (the reference point is a min, a max, a multiply
# and a subtract per metric; the prior features a ``top_k`` and a sum), so
# that its bits are the eager form's is measured, not given: suggestions and
# metadata equal the eager tree's on the CPU (tests/designers) and on a v5e
# (PERF.md section 6, PR 30: served and designer-level sequences, one and two
# metrics, exact, sparse and multitask).


@jax.jit
def _stack_fits(states_list):
    """Per-metric trained states -> (states [M, E, ...], each metric's best
    member's constrained params: what seeds its next train)."""
    states_me = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states_list)
    best = [
        jax.tree_util.tree_map(lambda a: a[0], states.params)
        for states in states_list
    ]
    return states_me, best


@jax.jit
def _metric_zero(states_me):
    """Metric 0 of a trained per-metric state ([M, E, ...] -> [E, ...]): what
    ``_last_predictive`` holds. Eager, this was a ``dynamic_slice`` a leaf
    (12 of an exact state, 16 of a sparse one), each with its start index
    put on the device first."""
    return jax.tree_util.tree_map(lambda a: a[0], states_me)


@jax.jit
def _sweep_inputs(datas: Tuple[gp_lib.GPData, ...]):
    """What the sweeps read of the completed data besides the fit: labels
    [M, N1], their row mask, the reference point (nadir - 0.1 * range,
    Ishibuchi2011) and the prior features. The formulas the fused programs
    fold into their trace (``_sweep_batched``)."""
    labels_mn = jnp.stack([d.labels for d in datas])
    labels_mask = datas[0].row_mask
    ref_point = acquisitions.get_reference_point(labels_mn, labels_mask)
    prior = gp_bandit._prior_features_from_data(datas[0])
    return labels_mn, labels_mask, ref_point, prior


@functools.partial(jax.jit, static_argnames=("count",))
def _sparse_all_points(
    states_me: sparse_gp.SparseGPState, all_data: gp_lib.GPData, count: int
) -> sparse_gp.SparseGPData:
    """The all-points rows over the trained inducing set (metric 0's member
    0: every member shares it) with ``count`` spare Nystrom slots. Eager,
    this was a slice a leaf and four concatenates between the train and the
    sweeps: ~43 ms of a sparse suggest on a v5e that no stage span named
    (PERF.md section 6, PR 41)."""
    sdata0 = jax.tree_util.tree_map(lambda a: a[0, 0], states_me.sdata)
    return sparse_gp.with_pending_capacity(sdata0, all_data, count)


@jax.jit
def _append_first_pick(all_data, features: kernels.MixedFeatures, states_me=None):
    """The all-points data with a sweep's first pick written into its first
    free row: what the next sweep conditions on. ``states_me`` is the sparse
    path's trained state (its member 0 decides the Nystrom augment)."""
    x = kernels.MixedFeatures(features.continuous[:1], features.categorical[:1])
    if isinstance(all_data, sparse_gp.SparseGPData):
        member0 = jax.tree_util.tree_map(lambda a: a[0, 0], states_me)
        return _append_row_sparse(all_data, x, member0)
    if isinstance(all_data, mtgp.MultiTaskData):
        return _append_row_mt(all_data, x)
    return _append_row(all_data, x)


def _hv_scalarized(
    values: Array,  # [M, Q] per-metric acquisition values
    weights: Array,  # [K, M] positive scalarization directions
    ref_point: Array,  # [M]
    labels: Array,  # [M, N] warped labels (completed)
    labels_mask: Array,  # [N]
) -> Array:
    """Random-direction hypervolume scalarization, clamped at the labels.

    Reference ``UCBScoreFunction.score_with_aux`` + ``create_hv_scalarization``
    (``acquisitions.py:571``, https://arxiv.org/abs/2006.04655): scalarize
    per direction as min_m((v_m - ref_m)/w_m)^M, floor each direction at the
    best scalarized observed label, then average over directions.
    """
    m = values.shape[0]
    inv_w = 1.0 / jnp.maximum(weights, 1e-6)  # [K, M]
    shifted = jnp.maximum(values - ref_point[:, None], 0.0)  # [M, Q]
    per_dir = jnp.min(inv_w[:, :, None] * shifted[None, :, :], axis=1) ** m  # [K, Q]
    lab_shifted = jnp.maximum(labels - ref_point[:, None], 0.0)  # [M, N]
    lab_per_dir = jnp.min(inv_w[:, :, None] * lab_shifted[None, :, :], axis=1) ** m
    lab_best = jnp.max(
        jnp.where(labels_mask[None, :], lab_per_dir, -jnp.inf), axis=-1
    )  # [K]
    return jnp.mean(jnp.maximum(per_dir, lab_best[:, None]), axis=0)  # [Q]


def _scalarize_penalty(penalty: Array, mode: str) -> Array:
    """[M, Q] per-metric promising-region penalties → [Q] (reference modes)."""
    if mode == "union":
        return jnp.max(penalty, axis=0)
    if mode == "intersection":
        return jnp.min(penalty, axis=0)
    return jnp.mean(penalty, axis=0)


class _ScoreOperands(NamedTuple):
    """Every array a pick's score reads (:func:`_suggest_batch`)."""

    states_completed: Any  # GPState [M, E], or MultiTaskGPState [E]
    states_all: Any  # the same, conditioned on the pending rows too
    rows_all: Any  # exact GP: ``states_all``'s ``kernel_rows()``; else None
    threshold: Array  # [M]
    use_ucb: Array  # scalar bool
    weights: Array  # [num_scalarizations, M]
    trust: Optional[acquisitions.TrustRegion]
    trust_radius: Array
    ref_point: Array  # [M]
    labels_mn: Array  # [M, N1]
    labels_mask: Array  # [N1]


@functools.partial(
    jax.jit,
    static_argnames=(
        "model", "vec_opt", "count", "config", "use_trust_region", "mesh",
        "prior_acquisition",
    ),
)
def _suggest_batch(
    model,  # VizierGaussianProcess or MultiTaskGaussianProcess (static)
    vec_opt: vectorized_lib.VectorizedOptimizer,
    states_completed,  # GPState [M, E], or MultiTaskGPState [E]
    all_data,  # GPData/MultiTaskData: completed+active rows valid; labels 0
    labels_mn: Array,  # [M, N1] warped labels of the completed data
    labels_mask: Array,  # [N1]
    ref_point: Array,  # [M]
    prior_features: kernels.MixedFeatures,
    rng: Array,
    first_has_new: Array,  # scalar bool: new completed since last active
    has_completed: Array,  # scalar bool
    count: int,
    config: UCBPEConfig,
    use_trust_region: bool = True,
    mesh=None,  # jax.sharding.Mesh: shard the per-pick sweep's eagle pools
    prior_acquisition=None,  # Callable[[MixedFeatures], [Q]-array] user prior
) -> Tuple[vectorized_lib.VectorizedOptimizerResult, dict]:
    """The greedy batch: per pick, UCB-or-PE with pending-point conditioning."""
    # Static dispatch: the multitask (SEPARABLE) and sparse (SGPR) paths
    # swap the posterior ops; every acquisition formula below is shared.
    is_mt = isinstance(model, mtgp.MultiTaskGaussianProcess)
    is_sparse = isinstance(model, sparse_gp.SparseGaussianProcess)
    is_exact = not (is_mt or is_sparse)
    if is_sparse:
        # Pending-pick conditioning through the inducing-point posterior:
        # ``all_data`` is a SparseGPData (completed+active rows + the
        # trained Z with spare augment slots); re-conditioning rebuilds the
        # O(n·m²) SGPR factorization on the grown pending set instead of
        # the exact path's O(n³) per-pick Cholesky. ``model`` is the
        # augmented-capacity SparseGaussianProcess (m + count slots).
        mixture = _mixture_predict  # SparseGPState duck-types .predict
        base_data = lambda d: d.data  # noqa: E731
        member0 = jax.tree_util.tree_map(lambda a: a[0, 0], states_completed)
        append = lambda d, x: _append_row_sparse(d, x, member0)  # noqa: E731
        recondition = lambda p, d: jax.vmap(  # noqa: E731
            jax.vmap(lambda q: model.precompute_constrained(q, d))
        )(p)
        mt_snr = None
    elif is_mt:
        mixture = _mt_mixture_predict
        base_data = lambda d: d.features_data  # noqa: E731
        append = _append_row_mt
        recondition = lambda p, d: jax.vmap(  # noqa: E731
            lambda q: model.precompute_constrained(q, d)
        )(p)
        # Per-task signal variance is amplitude² · B[m,m] (what the MT
        # posterior uses as prior variance), not amplitude² alone.
        mt_p = states_completed.params  # [E] leaves
        b_diag = jax.vmap(lambda q: jnp.diagonal(model._task_cov(q)))(mt_p)
        mt_snr = (
            (mt_p["amplitude"][:, None] ** 2)
            * b_diag
            / (mt_p["noise_stddev"][:, None] ** 2)
        )  # [E, M]
    else:
        if states_completed.alpha.shape[-1] > all_data.num_rows:
            raise ValueError(
                "The all-points data must hold the completed rows as its "
                f"leading rows: trained pad {states_completed.alpha.shape[-1]} "
                f"> all-points pad {all_data.num_rows}."
            )
        mixture = _mixture_predict
        base_data = lambda d: d  # noqa: E731
        append = _append_row
        recondition = lambda p, d: jax.vmap(  # noqa: E731
            jax.vmap(lambda q: model.precompute_constrained(q, d))
        )(p)
        mt_snr = None

    dc = base_data(all_data).continuous.shape[-1]
    ds = base_data(all_data).categorical.shape[-1]
    num_metrics = labels_mn.shape[0]

    trust = (
        acquisitions.TrustRegion.from_data(base_data(all_data))
        if use_trust_region
        else None
    )
    trust_radius = (
        trust.trust_radius() if trust is not None else jnp.asarray(jnp.inf)
    )

    def pick(b, carry):
        all_data, out_cont, out_cat, out_scores, aux, rng = carry
        rng, ucb_rng, w_rng, opt_rng = jax.random.split(rng, 4)

        # Shared conditioning, recomputed on the grown pending set.
        pe_params, noise_is_high, threshold = _pe_conditioning(
            states_completed, all_data, config,
            mixture=mixture, base_data=base_data, snr=mt_snr,
        )
        # Re-condition the all-points posterior on the grown pending set.
        states_all = recondition(pe_params, all_data)

        # Pick-level UCB/PE decision (reference `_suggest_one` logic).
        pe_p = jnp.where(
            noise_is_high,
            config.pe_overwrite_probability_in_high_noise,
            config.pe_overwrite_probability,
        )
        use_ucb = jnp.where(
            (b == 0) & first_has_new,
            ~jax.random.bernoulli(ucb_rng, pe_p),
            has_completed
            & jax.random.bernoulli(ucb_rng, config.ucb_overwrite_probability),
        )

        weights = jnp.abs(
            jax.random.normal(
                w_rng, (config.num_scalarizations, num_metrics), jnp.float32
            )
        )
        weights = weights / jnp.linalg.norm(weights, axis=-1, keepdims=True)

        # Every array a score reads, as one pytree ``o``: under a mesh the
        # sweep's manual map takes them as replicated operands (a closure's
        # values cannot enter it); one chip binds them below.
        operands = _ScoreOperands(
            states_completed=states_completed,
            states_all=states_all,
            # The data side of the candidates' cross-covariance, once a pick.
            rows_all=(
                jax.vmap(jax.vmap(lambda s: s.kernel_rows()))(states_all)
                if is_exact
                else None
            ),
            threshold=threshold,
            use_ucb=use_ucb,
            weights=weights,
            trust=trust,
            trust_radius=trust_radius,
            ref_point=ref_point,
            labels_mn=labels_mn,
            labels_mask=labels_mask,
        )

        def score(o: _ScoreOperands, query: kernels.MixedFeatures) -> Array:
            # (mean, stddev) of the completed posterior and the all-points
            # stddev, [M, Q] each.
            if is_exact:
                mean_c, std_c, std_all = _exact_posterior_pair(
                    o.states_completed, o.states_all, o.rows_all, query
                )
            else:
                mean_c, std_c = mixture(o.states_completed, query)
                std_all = mixture(o.states_all, query)[1]
            ucb_vals = mean_c + config.ucb_coefficient * std_all
            if num_metrics == 1:
                ucb_score = ucb_vals[0]
            else:
                ucb_score = _hv_scalarized(
                    ucb_vals, o.weights, o.ref_point, o.labels_mn, o.labels_mask
                )
            explore_ucb = mean_c + config.explore_region_ucb_coefficient * std_c
            penalty = config.cb_violation_penalty_coefficient * jnp.minimum(
                explore_ucb - o.threshold[:, None], 0.0
            )
            if num_metrics == 1:
                pe_score = std_all[0] + penalty[0]
            else:
                pe_score = jnp.mean(std_all, axis=0) + _scalarize_penalty(
                    penalty, config.multimetric_promising_region_penalty_type
                )
            value = jnp.where(o.use_ucb, ucb_score, pe_score)
            if prior_acquisition is not None:
                # Additive user prior over the space (reference adds it to
                # both the UCB and PE scores, `gp_ucb_pe.py:377,419`).
                value = value + prior_acquisition(query)
            if o.trust is not None:
                value = value - o.trust.penalty(query, o.trust_radius)
            return value

        if mesh is None:
            result = vec_opt(
                functools.partial(score, operands),
                opt_rng,
                count=1,
                prior_features=prior_features,
            )
        else:
            from vizier_tpu import parallel

            result = parallel.maximize_score_fn_sharded(
                vec_opt, score, operands, opt_rng, 1,
                len(mesh.devices.flat), mesh, prior_features,
            )
        x = kernels.MixedFeatures(
            result.features.continuous[:1], result.features.categorical[:1]
        )
        mean_x, std_x = mixture(states_completed, x)  # [M, 1]
        _, std_all_x = mixture(states_all, x)
        all_data = append(all_data, x)
        out_cont = out_cont.at[b].set(x.continuous[0])
        out_cat = out_cat.at[b].set(x.categorical[0])
        out_scores = out_scores.at[b].set(result.scores[0])
        aux = dict(
            mean=aux["mean"].at[b].set(mean_x[:, 0]),
            stddev=aux["stddev"].at[b].set(std_x[:, 0]),
            stddev_from_all=aux["stddev_from_all"].at[b].set(std_all_x[:, 0]),
            use_ucb=aux["use_ucb"].at[b].set(use_ucb),
        )
        return all_data, out_cont, out_cat, out_scores, aux, rng

    init_aux = dict(
        mean=jnp.zeros((count, num_metrics), jnp.float32),
        stddev=jnp.zeros((count, num_metrics), jnp.float32),
        stddev_from_all=jnp.zeros((count, num_metrics), jnp.float32),
        use_ucb=jnp.zeros((count,), bool),
    )
    init = (
        all_data,
        jnp.zeros((count, dc), base_data(all_data).continuous.dtype),
        jnp.zeros((count, ds), base_data(all_data).categorical.dtype),
        jnp.zeros((count,), jnp.float32),
        init_aux,
        rng,
    )
    grown, out_cont, out_cat, out_scores, aux, _ = jax.lax.fori_loop(
        0, count, pick, init
    )
    aux["trust_radius"] = trust_radius
    if is_sparse:
        # Picks of this sweep that joined the inducing set (a sparse
        # program's own result: the exact and multitask programs have none).
        aux["nystrom_augments"] = jnp.sum(
            grown.inducing_mask.astype(jnp.int32)
        ) - jnp.sum(all_data.inducing_mask.astype(jnp.int32))
    return (
        vectorized_lib.VectorizedOptimizerResult(
            kernels.MixedFeatures(out_cont, out_cat), out_scores
        ),
        aux,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "model", "vec_opt", "q", "config", "use_trust_region",
        "prior_acquisition",
    ),
)
def _suggest_set_pe(
    model: gp_lib.VizierGaussianProcess,
    vec_opt: vectorized_lib.VectorizedOptimizer,
    states_completed: gp_lib.GPState,  # [M=1, E]
    all_data: gp_lib.GPData,
    rng: Array,
    q: int,
    config: UCBPEConfig,
    use_trust_region: bool = True,
    prior_acquisition=None,  # Callable[[MixedFeatures], [Q]-array] user prior
) -> Tuple[vectorized_lib.VectorizedOptimizerResult, dict]:
    """Joint exploration batch: maximize log-det of the set's posterior cov.

    Reference ``SetPEScoreFunction`` (``gp_ucb_pe.py:510``, eq. (8) of
    Contal et al.): candidates are whole q-point sets, searched in the
    flattened (q·D)-space by the same eagle strategy; single-metric only.
    """
    dc = all_data.continuous.shape[-1]
    ds = all_data.categorical.shape[-1]

    pe_params, _, thresholds = _pe_conditioning(
        states_completed, all_data, config
    )
    threshold = thresholds[0]  # single metric
    states_all = jax.vmap(
        jax.vmap(lambda p: model.precompute_constrained(p, all_data))
    )(pe_params)
    # Flatten [M=1, E] -> [E] for the joint-covariance math.
    states_all_e = jax.tree_util.tree_map(lambda a: a[0], states_all)
    trust = (
        acquisitions.TrustRegion.from_data(all_data) if use_trust_region else None
    )

    def score_fn(flat: kernels.MixedFeatures) -> Array:
        bsz = flat.continuous.shape[0]
        pts_c = flat.continuous.reshape(bsz, q, dc)
        pts_s = flat.categorical.reshape(bsz, q, ds)

        def per_candidate(cont: Array, cat: Array) -> Array:
            query = kernels.MixedFeatures(cont, cat)
            means, covs = jax.vmap(lambda s: s.predict_joint(query))(
                states_all_e
            )  # [E, q], [E, q, q]
            mu = jnp.mean(means, axis=0)
            # Moment-matched mixture covariance over ensemble members.
            cov = (
                jnp.mean(covs + means[:, :, None] * means[:, None, :], axis=0)
                - mu[:, None] * mu[None, :]
            )
            chol = jnp.linalg.cholesky(
                cov + 1e-6 * jnp.eye(q, dtype=cov.dtype)
            )
            logdet = 2.0 * jnp.sum(jnp.log(gp_lib.cholesky_diagonal(chol)))
            logdet = jnp.where(jnp.isnan(logdet), -jnp.inf, logdet)
            mean_c, std_c = _mixture_predict(states_completed, query)  # [1, q]
            explore_ucb = (
                mean_c[0] + config.explore_region_ucb_coefficient * std_c[0]
            )
            value = logdet + config.cb_violation_penalty_coefficient * jnp.sum(
                jnp.minimum(explore_ucb - threshold, 0.0)
            )
            if prior_acquisition is not None:
                value = value + jnp.sum(prior_acquisition(query))
            if trust is not None:
                value = value - jnp.sum(trust.penalty(query))
            return value

        return jax.vmap(per_candidate)(pts_c, pts_s)

    result = vec_opt(score_fn, rng, count=1)
    # Unflatten the winning set into q suggestions.
    cont_rows = result.features.continuous[0].reshape(q, dc)
    cat_rows = result.features.categorical[0].reshape(q, ds)
    set_query = kernels.MixedFeatures(cont_rows, cat_rows)
    mean_x, std_x = _mixture_predict(states_completed, set_query)  # [1, q]
    _, std_all_x = _mixture_predict(states_all, set_query)
    aux = dict(
        mean=mean_x.T,  # [q, 1]
        stddev=std_x.T,
        stddev_from_all=std_all_x.T,
        use_ucb=jnp.zeros((q,), bool),
        trust_radius=(
            trust.trust_radius() if trust is not None else jnp.asarray(jnp.inf)
        ),
    )
    return (
        vectorized_lib.VectorizedOptimizerResult(
            set_query, jnp.full((q,), result.scores[0])
        ),
        aux,
    )


def _sweep_batched(
    model, vec_opt, states_me, all_data, data, rng,
    first_has_new, has_completed, count, config, use_trust_region,
):
    """The sweep of both flush programs: ONE traced vmap of the sequential
    :func:`_suggest_batch` (greedy per-pick UCB/PE with pending-point
    conditioning) over a leading study axis, so slot i matches study i
    executed alone. The label stack / reference point / prior features the
    sequential path computes before its sweep are folded into the trace
    (same formulas, zero host dispatches per study)."""

    def one(s, ad, d, r, f, h):
        labels_mn = d.labels[None]  # [M=1, N1]
        labels_mask = d.row_mask
        ref_point = acquisitions.get_reference_point(labels_mn, labels_mask)
        prior = gp_bandit._prior_features_from_data(d)
        return _suggest_batch(
            model, vec_opt, s, ad, labels_mn, labels_mask, ref_point, prior,
            r, f, h, count, config, use_trust_region, None, None,
        )

    return jax.vmap(one)(
        states_me, all_data, data, rng, first_has_new, has_completed
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "model", "optimizer", "vec_opt", "vec_opt_rest", "num_restarts",
        "ensemble_size", "count", "config", "use_trust_region", "two_phase",
    ),
)
def _ucb_pe_flush_program(
    model,
    optimizer,
    vec_opt,  # full-budget sweep (the two-phase first pick)
    vec_opt_rest,  # the budget policy's sweep for the (remaining) picks
    md,  # stacked host ModelData (completed trials), leading study axis
    all_md,  # stacked host ModelData (completed+active, spare pick rows)
    rng_train: Array,  # [B]
    rng_acq: Array,  # [B]
    rng_rest: Array,  # [B] (ignored unless two_phase)
    warm,  # per-study warm ARD seeds, leading axis [B]
    first_has_new: Array,  # [B] bool
    has_completed: Array,  # [B] bool
    num_restarts: int,
    ensemble_size: int,
    count: int,
    config: UCBPEConfig,
    use_trust_region: bool,
    two_phase: bool,
):
    """ONE device program per bucket flush: encode→ARD→UCB-PE batch→warm.

    The whole multi-study suggest — including the two-phase
    ``first_pick_full`` flow with its mid-flight pending-row append — is a
    single XLA dispatch, so a flush pays program-launch/host-sync overhead
    once instead of ~4·B times.
    """
    data = jax.vmap(lambda m: gp_lib.GPData.from_model_data(m))(md)
    all_data = jax.vmap(lambda m: gp_lib.GPData.from_model_data(m))(all_md)
    states, work = jax.vmap(
        lambda d, k, w: gp_bandit._train_gp(
            model, optimizer, d, k, num_restarts, ensemble_size, w
        )
    )(data, rng_train, warm)
    warm_next = gp_bandit._warm_next_batched(model, states)
    # [B, E] -> [B, M=1, E]: the UCB-PE programs are per-metric batched.
    states_me = jax.tree_util.tree_map(lambda a: a[:, None], states)
    if two_phase:
        first, aux1 = _sweep_batched(
            model, vec_opt, states_me, all_data, data, rng_acq,
            first_has_new, has_completed, 1, config, use_trust_region,
        )
        x = kernels.MixedFeatures(
            first.features.continuous[:, :1], first.features.categorical[:, :1]
        )
        all_data = jax.vmap(_append_row)(all_data, x)
        rest, aux2 = _sweep_batched(
            model, vec_opt_rest, states_me, all_data, data, rng_rest,
            jnp.zeros_like(first_has_new), has_completed, count - 1,
            config, use_trust_region,
        )
        segments = ((first, aux1), (rest, aux2))
    else:
        batch, aux = _sweep_batched(
            model, vec_opt_rest, states_me, all_data, data, rng_acq,
            first_has_new, has_completed, count, config, use_trust_region,
        )
        segments = ((batch, aux),)
    return states, warm_next, data, segments, work


@functools.partial(
    jax.jit,
    static_argnames=(
        "model", "aug_model", "optimizer", "vec_opt", "vec_opt_rest",
        "num_restarts", "ensemble_size", "count", "config",
        "use_trust_region", "two_phase",
    ),
)
def _sparse_ucb_pe_flush_program(
    model,  # SparseGaussianProcess over the trained m-bucket
    aug_model,  # SparseGaussianProcess with m + count augment slots
    optimizer,
    vec_opt,
    vec_opt_rest,
    md,  # stacked host ModelData (completed trials), leading study axis
    all_md,  # stacked host ModelData (completed+active, spare pick rows)
    rng_train: Array,  # [B]
    rng_acq: Array,  # [B]
    rng_rest: Array,  # [B] (ignored unless two_phase)
    warm,  # per-study warm ARD seeds, leading axis [B]
    first_has_new: Array,  # [B] bool
    has_completed: Array,  # [B] bool
    num_restarts: int,
    ensemble_size: int,
    count: int,
    config: UCBPEConfig,
    use_trust_region: bool,
    two_phase: bool,
):
    """The sparse twin of :func:`_ucb_pe_flush_program`: ONE device program
    per bucket flush — encode → k-center inducing selection → collapsed-
    bound ARD → the greedy UCB-PE batch with pending-pick conditioning
    through the inducing posterior (Nyström-augmented) → warm seed, and
    the train's own work counts last, as the exact twin hands them out. A
    slot traces what its study runs alone through the sequential sparse
    path: the same answers to the bit at a small size
    (tests/compute/test_program_parity.py) and, over a 50-iteration L-BFGS
    train, up to float32's order of summation under the ``vmap``
    (tests/chipbench/test_sparse_pool.py).
    """
    data = jax.vmap(lambda m: gp_lib.GPData.from_model_data(m))(md)
    all_gp = jax.vmap(lambda m: gp_lib.GPData.from_model_data(m))(all_md)
    states, work = jax.vmap(
        lambda d, k, w: sparse_bandit._train_sparse_gp(
            model, optimizer, d, k, num_restarts, ensemble_size, w
        )
    )(data, rng_train, warm)
    warm_next = sparse_bandit._warm_next_batched(model, states)
    # [B, E] -> [B, M=1, E]: the UCB-PE programs are per-metric batched.
    states_me = jax.tree_util.tree_map(lambda a: a[:, None], states)
    # Per-slot all-points data over the slot's trained inducing set (every
    # ensemble member shares it), with count spare Nyström slots.
    all_sdata = jax.vmap(
        lambda s, ag: sparse_gp.with_pending_capacity(
            jax.tree_util.tree_map(lambda a: a[0], s.sdata), ag, count
        )
    )(states, all_gp)
    if two_phase:
        first, aux1 = _sweep_batched(
            aug_model, vec_opt, states_me, all_sdata, data, rng_acq,
            first_has_new, has_completed, 1, config, use_trust_region,
        )
        x = kernels.MixedFeatures(
            first.features.continuous[:, :1], first.features.categorical[:, :1]
        )
        member0 = jax.tree_util.tree_map(lambda a: a[:, 0, 0], states_me)
        all_sdata = jax.vmap(_append_row_sparse)(all_sdata, x, member0)
        rest, aux2 = _sweep_batched(
            aug_model, vec_opt_rest, states_me, all_sdata, data, rng_rest,
            jnp.zeros_like(first_has_new), has_completed, count - 1,
            config, use_trust_region,
        )
        segments = ((first, aux1), (rest, aux2))
    else:
        batch, aux = _sweep_batched(
            aug_model, vec_opt_rest, states_me, all_sdata, data, rng_acq,
            first_has_new, has_completed, count, config, use_trust_region,
        )
        segments = ((batch, aux),)
    return states, warm_next, data, segments, work


def _train_mt_gp(
    model: mtgp.MultiTaskGaussianProcess,
    optimizer,
    data: mtgp.MultiTaskData,
    rng: Array,
    num_restarts: int,
    ensemble_size: int,
) -> mtgp.MultiTaskGPState:
    """Joint multitask ARD: restarts → L-BFGS → top-k posteriors ([E])."""
    coll = model.param_collection()
    inits = coll.batch_random_init_unconstrained(rng, num_restarts)
    loss_fn = lambda p: model.neg_log_likelihood(p, data)  # noqa: E731
    result = optimizer(loss_fn, inits, best_n=ensemble_size)
    return jax.vmap(lambda p: model.precompute(p, data))(result.params)


class _MetricZeroMTPredictive:
    """Duck-typed ``.predict`` over the FIRST metric of a multitask state.

    Mirrors what the independent path exposes via ``EnsemblePredictive``
    (metric 0 only) so ``predict``/``sample`` keep one contract.
    """

    def __init__(self, states: mtgp.MultiTaskGPState):
        self._states = states

    def predict(self, query: kernels.MixedFeatures) -> Tuple[Array, Array]:
        mean, std = _mt_mixture_predict(self._states, query)
        return mean[0], std[0]


_MIN_PICK_EVALUATIONS = 500  # ≥10 eagle generations at the default pool of 50


@dataclasses.dataclass
class VizierGPUCBPEBandit(gp_bandit.VizierGPBandit):
    """GP-UCB-PE batch designer (service DEFAULT)."""

    config: UCBPEConfig = UCBPEConfig()
    num_seed_trials: int = 1  # reference default: center point first
    # Acquisition evaluation budget semantics for batch suggests (measured
    # A/B in docs/guides/tpu_architecture.md):
    # - "first_pick_full" (default): the batch's FIRST pick — the
    #   exploitation (UCB) pick whose local optimization precision drives
    #   simple regret — runs the full ``max_acquisition_evaluations``;
    #   the remaining picks, which maximize the flatter pure-exploration
    #   stddev surface, split one further full budget between them. Total
    #   ≈ 2 sweeps per suggest() regardless of batch size.
    # - "per_batch": one full budget split across ALL picks (floored at
    #   _MIN_PICK_EVALUATIONS) — cheapest, measurably worse exploitation
    #   precision on 20-D (regret: budget_ab_r5.json; its time against the
    #   default's is not measured on the chip).
    # - "per_pick": every pick runs the full budget — the reference's
    #   effective behavior (its ``_suggest_one`` spends max_evaluations=75k
    #   per pick, ``gp_ucb_pe.py:693-697,1440-1446``, with a TODO
    #   acknowledging the budget should scale with count).
    acquisition_budget_policy: str = "first_pick_full"
    # Optional additive acquisition prior (reference `prior_acquisition`,
    # gp_ucb_pe.py:299): called with the candidate MixedFeatures batch,
    # returns a [Q] score added to both the UCB and PE acquisitions. Must be
    # a jax-traceable callable; it is baked into the jitted suggest program,
    # so use one stable callable per designer (a fresh lambda per call would
    # retrace).
    prior_acquisition: Optional[Callable[[kernels.MixedFeatures], Array]] = None

    def __post_init__(self):
        super().__post_init__()
        if self.acquisition_budget_policy not in (
            "first_pick_full",
            "per_batch",
            "per_pick",
        ):
            raise ValueError(
                "acquisition_budget_policy must be 'first_pick_full' | "
                "'per_batch' | 'per_pick', got "
                f"{self.acquisition_budget_policy!r}."
            )
        self._active_trials: List[trial_.Trial] = []
        self._metric_warpers: List[output_warpers.WarperPipeline] = []
        self._warpers_fitted = False
        # Trained per-metric states, reused until new data arrives (predict/
        # sample after a suggest must not pay a second ARD optimization).
        self._cached_states = None
        # What the last exact train's program(s) counted of their own work
        # (``gp_bandit._train_gp``), on the device until a suggest's timed
        # train phase reads it; a train made for ``sample`` is read by the
        # next suggest.
        self._unread_train_work: tuple = ()
        # (datas, _sweep_inputs(datas)) of the last fit's datas: made once
        # a fit, found again by the identity of the list.
        self._sweep_inputs_of: Optional[tuple] = None
        # Each metric's best constrained params of the last train, until
        # ``_seed_next_trains`` has mapped them back for the next one.
        self._unseeded_best: Optional[list] = None
        # Joint set-PE optimizers are built lazily per batch size.
        self._set_opt_cache: dict = {}
        # Per-pick sweep optimizers under the per_batch budget policy, keyed
        # by their per-pick evaluation budget.
        self._pick_opt_cache: dict = {}
        # Per-objective warm-start seeds for the independent-GP path,
        # random-initialized so the ARD program's pytree structure is
        # stable from the first suggest (same trick as the base class's
        # scalar `_warm_params`). The multitask (SEPARABLE) trainer has no
        # warm-start path and always counts as a cold train.
        coll = self._model.param_collection()
        n_obj = len(self._objective_indices())
        keys = jax.random.split(jax.random.PRNGKey(self.rng_seed + 2), max(n_obj, 1))
        self._warm_params_me = [
            coll.random_init_unconstrained(k) for k in keys[:n_obj]
        ]

    def _split_vec_opt(self, num_picks: int) -> vectorized_lib.VectorizedOptimizer:
        """One full budget split evenly across ``num_picks`` picks."""
        if num_picks <= 1:
            return self._vec_opt
        per_pick = max(
            self.max_acquisition_evaluations // num_picks,
            _MIN_PICK_EVALUATIONS,
        )
        opt = self._pick_opt_cache.get(per_pick)
        if opt is None:
            opt = vectorized_lib.VectorizedOptimizer(
                self._vec_opt.strategy, max_evaluations=per_pick
            )
            self._pick_opt_cache[per_pick] = opt
        return opt

    def _pick_vec_opt(self, count: int) -> vectorized_lib.VectorizedOptimizer:
        """The acquisition optimizer the batch loop's picks run with.

        "per_batch" splits ``max_acquisition_evaluations`` across all
        ``count`` picks; "first_pick_full" handles its full-budget first
        pick separately in ``suggest`` and splits across the remainder.
        """
        if self.acquisition_budget_policy == "per_pick" or count <= 1:
            return self._vec_opt
        if self.acquisition_budget_policy == "first_pick_full":
            return self._split_vec_opt(count - 1)
        return self._split_vec_opt(count)

    # -- Designer ----------------------------------------------------------

    def update(
        self,
        completed: core_lib.CompletedTrials,
        all_active: core_lib.ActiveTrials = core_lib.ActiveTrials(),
    ) -> None:
        if completed.trials:
            self._cached_states = None  # new labels invalidate the GP fit
        self._trials.extend(completed.trials)
        self._store.sync(self._trials)
        self._active_trials = list(all_active.trials)

    def _has_new_completed_trials(self) -> bool:
        """True iff a completed trial postdates every active trial's creation
        (reference ``_has_new_completed_trials``, ``gp_ucb_pe.py:142``)."""
        if not self._trials:
            return False
        if not self._active_trials:
            return True
        completion = [t.completion_time for t in self._trials if t.completion_time]
        creation = [t.creation_time for t in self._active_trials if t.creation_time]
        if not completion or not creation:
            return True
        return max(completion) > max(creation)

    def _objective_indices(self) -> List[int]:
        return [
            j
            for j, m in enumerate(self.problem.metric_information)
            if not m.is_safety_metric
        ]

    # -- scalable surrogate for the DEFAULT (vizier_tpu.surrogates) ---------

    def _sparse_ucb_pe_eligible(self) -> bool:
        """Whether the sparse surrogate may serve this designer's suggests.

        The single-objective independent-GP greedy path only: multitask,
        multi-objective, set-acquisition, transfer priors, custom
        acquisition priors, and mesh-sharded designers stay exact — the
        same carve-outs the base class documents for its sparse path.
        """
        cfg = self.surrogate
        return bool(
            cfg is not None
            and cfg.sparse
            and getattr(cfg, "sparse_ucb_pe", True)
            and self._mesh is None
            and len(self._objective_indices()) == 1
            and not self.config.optimize_set_acquisition_for_exploration
            and self.prior_acquisition is None
            and not getattr(self, "_priors", None)
        )

    def _refresh_ucb_pe_surrogate_mode(self) -> str:
        """The auto-switch, applied only where the sparse UCB-PE programs
        cover; ineligible designers never leave exact (bit-identical)."""
        if not self._sparse_ucb_pe_eligible():
            return self._surrogate_mode
        return self._refresh_surrogate_mode()

    def _refresh_surrogate_mode(self) -> str:
        before = self._surrogate_counts["crossovers"]
        mode = super()._refresh_surrogate_mode()
        if self._surrogate_counts["crossovers"] != before:
            # The base crossover dropped ITS warm/posterior state; the
            # UCB-PE designer's cross-surrogate state — per-metric warm
            # seeds and the cached fit — is equally stale. Fresh random
            # placeholders keep the train program's pytree stable.
            coll = self._model.param_collection()
            n = max(len(self._warm_params_me), 1)
            keys = jax.random.split(
                jax.random.PRNGKey(
                    self.rng_seed + 2 + self._surrogate_counts["crossovers"]
                ),
                n,
            )
            self._warm_params_me = [
                coll.random_init_unconstrained(k)
                for k in keys[: len(self._warm_params_me)]
            ]
            self._cached_states = None
        return mode

    def _sparse_all_model(self, count: int) -> sparse_gp.SparseGaussianProcess:
        """The re-conditioning model over the augmented inducing capacity:
        the trained posterior's m slots plus one spare Nyström slot per
        batch pick (a frozen value object — stable jit static)."""
        base = self._sparse_model()
        return sparse_gp.SparseGaussianProcess(
            base=self._model, num_inducing=base.num_inducing + count
        )

    @staticmethod
    def _warp_column(raw: np.ndarray) -> Tuple[np.ndarray, Any]:
        """One metric's raw labels warped by a warper fitted on them: a
        whole-study computation (half-rank and the infeasible shift depend
        on every label), so the store keeps its input and not its result."""
        warper = output_warpers.create_default_warper()
        return (warper(raw) if raw.shape[0] else raw), warper

    def _encode_datas(self) -> List[gp_lib.GPData]:
        """The host half of a train: the completed trials' rows from the
        store, warped and padded, one host GPData per objective metric."""
        cont, cat, raw = self._completed_rows()  # raw: [N, M_all], all-MAXIMIZE
        features, n_pad = self._padded_features(cont, cat)
        datas = []
        self._metric_warpers = []
        self._warpers_fitted = raw.shape[0] > 0
        for j in self._objective_indices():
            warped, warper = self._warp_column(raw[:, j])
            self._metric_warpers.append(warper)
            data = gp_lib.GPData.from_model_data(
                types.ModelData(features, self._padded_labels(warped, n_pad))
            )
            datas.append(data)
        return datas

    def _train_states_me(
        self, datas: Optional[List[gp_lib.GPData]] = None, seed_next: bool = True
    ) -> Tuple[gp_lib.GPState, List[gp_lib.GPData]]:
        """Per-metric GP training: GPState with leading [M, E] + the datas
        (``_encode_datas()``'s, handed in by a caller that already has them).

        Cached between calls until update() delivers new completed trials —
        predict()/sample() right after a suggest() reuse the same fit.
        ``seed_next=False`` leaves ``_seed_next_trains()`` to the caller
        (``suggest``, which has the sweeps to enqueue first).
        """
        if self._cached_states is not None:
            return self._cached_states
        if datas is None:
            datas = self._encode_datas()
        ensemble = max(self.ensemble_size, 1)
        if (
            len(datas) == 1
            and self._refresh_ucb_pe_surrogate_mode()
            == surrogate_config_lib.MODE_SPARSE
        ):
            # Sparse DEFAULT: the SGPR collapsed bound replaces the exact
            # O(n³) ARD — same multi-restart L-BFGS program shape, same
            # warm-seed-as-extra-restart-row semantics, k-center inducing
            # selection inside the jitted program.
            model = self._sparse_model()
            restarts = max(
                self._warm_restart_budget() or self.ard_restarts, ensemble
            )
            states, work = sparse_bandit._train_sparse_gp(
                model,
                self._ard,
                datas[0],
                self._next_rng(),
                restarts,
                ensemble,
                self._warm_params_me[0],
            )
            self._unread_train_work = (work,)
            self._record_train()
            states_me, self._unseeded_best = _stack_fits((states,))
            if seed_next:
                self._seed_next_trains()
            self._cached_states = (states_me, datas)
            return self._cached_states
        if self._use_multitask(len(datas)):
            # One joint GP: learned task covariance over a B ⊗ Kx Gram.
            mt_model = self._mt_model(len(datas))
            mt_data = mtgp.MultiTaskData.from_gp_datas(tuple(datas))
            if self._mesh is None:
                states = _train_mt_gp(
                    mt_model, self._ard, mt_data, self._next_rng(),
                    self.ard_restarts, ensemble,
                )
            else:
                # Same restart sharding as the independent path — the
                # sharded trainer is model-agnostic (duck-typed
                # param_collection / neg_log_likelihood / precompute).
                from vizier_tpu import parallel

                ndev = self._mesh_size()
                restarts = -(-self.ard_restarts // ndev) * ndev
                states, _ = parallel.train_gp_sharded(
                    mt_model, self._ard, mt_data, self._next_rng(),
                    restarts, ensemble, self._mesh,
                )
            self._ard_train_counts["cold"] += 1
            self._cached_states = (states, datas)
            return self._cached_states
        # Mesh-aware: restarts shard over devices when a mesh is present.
        # Each metric's train is seeded with ITS previous optimum (restart
        # 0); with a trained seed and a configured warm budget the restart
        # count drops to ``warm_ard_restarts`` — the steady-state serving
        # win (hyperparameters move little between suggests, so the seeded
        # restart early-exits the L-BFGS while random restarts burn the
        # full budget).
        warm_budget = self._warm_restart_budget()
        states_list, self._unread_train_work = zip(
            *(
                self._train(
                    data,
                    self._next_rng(),
                    ensemble,
                    warm_start=self._warm_params_me[j],
                    num_restarts=warm_budget,
                )
                for j, data in enumerate(datas)
            )
        )
        self._record_train()
        states_me, self._unseeded_best = _stack_fits(states_list)
        if seed_next:
            self._seed_next_trains()
        self._cached_states = (states_me, datas)
        return self._cached_states

    def _seed_next_trains(self) -> None:
        """Each metric's best member of the last train seeds its next one
        (constrained params mapped back through the bijectors), once the
        floor is met. Eager: ~8 one-operation programs a hyperparameter."""
        best, self._unseeded_best = self._unseeded_best, None
        if best is not None and self._warm_update_allowed():
            coll = self._model.param_collection()
            self._warm_params_me = [coll.unconstrain(p) for p in best]
            self._warm_is_trained = True

    # -- serving warm-start surface (vizier_tpu.serving) --------------------

    def warm_start_state(self) -> Optional[List]:
        """Per-objective trained unconstrained params (independent path)."""
        return list(self._warm_params_me) if self._warm_is_trained else None

    def set_warm_start_state(self, params: List) -> None:
        if len(params) != len(self._warm_params_me):
            raise ValueError(
                f"Expected {len(self._warm_params_me)} per-metric param "
                f"pytrees, got {len(params)}."
            )
        self._warm_params_me = list(params)
        self._warm_is_trained = True

    # -- read by the registered programs at the bottom of this module ------

    def _batch_ensemble(self) -> int:
        return max(self.ensemble_size, 1)

    def _batch_restarts(self) -> int:
        """Mirrors ``_train_states_me``'s budget: warm override or full,
        floored at the ensemble size."""
        return max(
            self._warm_restart_budget() or self.ard_restarts,
            self._batch_ensemble(),
        )

    def _use_multitask(self, num_metrics: int) -> bool:
        return (
            self.config.multitask_type is not mtgp.MultiTaskType.INDEPENDENT
            and num_metrics > 1
        )

    def _mt_model(self, num_metrics: int) -> mtgp.MultiTaskGaussianProcess:
        return mtgp.MultiTaskGaussianProcess(
            num_continuous=self._model.num_continuous,
            num_categorical=self._model.num_categorical,
            num_tasks=num_metrics,
            multitask_type=self.config.multitask_type,
        )

    def _all_points_model_data(self, count: int) -> types.ModelData:
        """Host (numpy) ModelData over completed+active rows with capacity
        for the picks: the store's rows and, encoded now (they are replaced
        on every update), the ACTIVE trials'. Every suggest path reads its
        all-points rows here once, so this is where the store's read is
        counted."""
        completed_cont, completed_cat, _ = self._completed_rows()
        cont, cat = completed_cont, completed_cat
        active = self._active_trials
        self._store.tally(also_encoded=len(active))
        if active:
            active_cont, active_cat = self._converter.encoder.encode(active)
            cont = np.concatenate([cont, active_cont.astype(np.float32)])
            cat = np.concatenate([cat, active_cat])
        num_rows = cont.shape[0]
        features, n_pad = self._padded_features(cont, cat, extra_rows=count)
        # The sweeps read k(query, completed rows) as the leading block of
        # k(query, all points) (``_exact_posterior_pair``): the completed
        # rows lead, as the train's data holds them, bit for bit.
        lead = np.s_[: len(completed_cont)]
        if not (
            np.array_equal(
                features.continuous.padded_array[lead, : completed_cont.shape[1]],
                completed_cont,
            )
            and np.array_equal(
                features.categorical.padded_array[lead, : completed_cat.shape[1]],
                completed_cat,
            )
        ):
            raise RuntimeError(
                "The all-points rows must begin with the completed rows."
            )
        spare = n_pad - num_rows
        if spare < count:  # capacity guard: _append_row must never no-op
            raise RuntimeError(
                f"Padded capacity {n_pad} leaves {spare} spare rows for a "
                f"batch of {count}; padding schedule must reserve the batch."
            )
        zero_labels = types.PaddedArray.from_array(
            np.zeros((num_rows, 1), np.float32), (n_pad, 1), fill_value=np.nan
        )
        return types.ModelData(features, zero_labels)

    def _all_points_data(self, count: int) -> gp_lib.GPData:
        """Host GPData over completed+active rows with capacity for the
        picks."""
        return gp_lib.GPData.from_model_data(self._all_points_model_data(count))

    def _sweep_inputs(self, datas: List[gp_lib.GPData]) -> tuple:
        """``_sweep_inputs`` of a fit's datas, made once a fit: a suggest
        on a cached fit finds them again and launches nothing for them."""
        held = self._sweep_inputs_of
        if held is None or held[0] is not datas:
            held = self._sweep_inputs_of = (datas, _sweep_inputs(tuple(datas)))
        return held[1]

    def _sweep_operands(self, count: int, datas: List[gp_lib.GPData]) -> tuple:
        """What the sweeps read beside the trained states, made on the host
        (NumPy, but for the fit's ``_sweep_inputs``): ``(all_data,
        labels_mn, labels_mask, ref_point, prior_feats, first_has_new,
        has_completed)``. No train reads any of it."""
        return (
            self._all_points_data(count),
            *self._sweep_inputs(datas),
            np.asarray(self._has_new_completed_trials()),
            np.asarray(bool(self._trials)),
        )

    def _dispatch_sweeps(self, count: int, states_me, operands: tuple) -> List[Tuple]:
        """Enqueues a suggest's sweep programs behind whatever computes
        ``states_me`` and waits for none of them: ``[(result, aux, rows)]``,
        the last entry's ``result.scores`` being the last thing the device
        finishes."""
        (
            all_data, labels_mn, labels_mask, ref_point, prior_feats,
            first_has_new, has_completed,
        ) = operands
        is_sparse = isinstance(states_me, sparse_gp.SparseGPState)
        if isinstance(states_me, mtgp.MultiTaskGPState):
            num_metrics = labels_mn.shape[0]
            model = self._mt_model(num_metrics)
            all_data = mtgp.MultiTaskData(
                features_data=all_data,
                task_labels=jnp.zeros(
                    (num_metrics,) + all_data.labels.shape, jnp.float32
                ),
                task_mask=jnp.tile(all_data.row_mask[None, :], (num_metrics, 1)),
            )
        elif is_sparse:
            # All-points twin of the trained posterior's inducing set, with
            # one spare Nyström slot per pick; the augmented-capacity model
            # re-conditions per pick in O(n·m²) instead of O(n³).
            model = self._sparse_all_model(count)
            all_data = _sparse_all_points(states_me, all_data, count)
        else:
            model = self._model
        if self.acquisition_budget_policy == "first_pick_full" and count > 1:
            # Full budget on the exploitation-critical first pick; one
            # further full budget split across the remaining picks.
            first, aux1 = _suggest_batch(
                model, self._vec_opt, states_me, all_data,
                labels_mn, labels_mask, ref_point, prior_feats,
                self._next_rng(), first_has_new, has_completed, 1,
                self.config, self.use_trust_region, self._mesh,
                self.prior_acquisition,
            )
            all_data = _append_first_pick(
                all_data, first.features, states_me if is_sparse else None
            )
            # _pick_vec_opt(count) is the ONE budget-dispatch point: under
            # first_pick_full it returns the (count-1)-way split sweep.
            rest, aux2 = _suggest_batch(
                model, self._pick_vec_opt(count), states_me,
                all_data, labels_mn, labels_mask, ref_point, prior_feats,
                self._next_rng(), np.asarray(False), has_completed,
                count - 1, self.config, self.use_trust_region,
                self._mesh, self.prior_acquisition,
            )
            return [(first, aux1, 1), (rest, aux2, count - 1)]
        batch, aux = _suggest_batch(
            model, self._pick_vec_opt(count), states_me, all_data,
            labels_mn, labels_mask, ref_point, prior_feats,
            self._next_rng(), first_has_new, has_completed, count,
            self.config, self.use_trust_region, self._mesh,
            self.prior_acquisition,
        )
        return [(batch, aux, count)]

    def suggest(self, count: Optional[int] = None) -> List[trial_.TrialSuggestion]:
        """Seed trials, then GP-UCB-PE: train (unless the fit is cached),
        sweep, decode.

        Every device program of the suggest is enqueued before the host
        waits for any of them. A suggest that trains makes the sweeps'
        host inputs and dispatches the sweeps right after the train's
        dispatch, INSIDE the train's ``device.wait`` phase, and only then
        blocks on the trained states: the launches (and their host→device
        copies) run under the train instead of between two programs with
        the chip idle. The train's span so ends when the train ends, and
        the acquire span that follows holds only the wait for the sweeps'
        end (``observability/jax_timing.py``). On a process's first such
        call the sweeps' trace and compile are therefore inside the
        train's span (``mode="compile"`` there already) and the first
        acquire span is the wait alone. A suggest on a cached fit has no
        train to hide behind and keeps the older order: inputs in
        ``designer.prepare``, sweeps dispatched under the acquire phase.
        """
        count = count or 1
        if len(self._trials) + len(self._active_trials) < self.num_seed_trials:
            return self._seed_suggestions(count)
        if getattr(self, "_priors", None):
            return self._suggest_with_priors(count)

        if (
            self.config.optimize_set_acquisition_for_exploration
            and len(self._objective_indices()) > 1
        ):
            raise ValueError(
                "optimize_set_acquisition_for_exploration supports exactly "
                "one objective metric."
            )
        self._mesh_suggests += self._mesh is not None
        tracer = tracing_lib.get_tracer()
        set_acquisition = (
            self.config.optimize_set_acquisition_for_exploration and count > 1
        )
        # The sweeps go out under the train when there is a train to go out
        # under (the set acquisition, which no served path runs, keeps its
        # sweeps after the block).
        trains = self._cached_states is None
        sweeps_under_train = trains and not set_acquisition
        # What the host does before the device can start: the completed
        # trials' encode (skipped when the fit is cached) and, when no train
        # will hide them, the sweeps' inputs.
        with tracer.span("designer.prepare"):
            # The surrogate auto-switch decides the device-phase family up
            # front (idempotent; ineligible designers always report exact).
            sparse_mode = (
                self._refresh_ucb_pe_surrogate_mode()
                == surrogate_config_lib.MODE_SPARSE
            )
            if trains:
                datas = self._encode_datas()
            else:
                self._ard_train_counts["cached"] += 1  # this suggest trains nothing
                datas = self._cached_states[1]
            operands = (
                None if sweeps_under_train else self._sweep_operands(count, datas)
            )
        results: Optional[List[Tuple]] = None  # [(result, aux, rows)]
        with profiler.timeit("train_gp"):
            # Device-attributed ARD timing (compile vs. steady-state): see
            # gp_bandit.suggest for the rationale; no-op + no device sync
            # when observability is off.
            with jax_timing.device_phase(
                "sparse_gp.ucb_pe_train_gp" if sparse_mode else "gp_ucb_pe.train_gp",
                stage="train",
                devices=self._mesh_size(),
            ) as phase:
                states_me, datas = self._train_states_me(
                    datas, seed_next=not sweeps_under_train
                )
                phase.ahead(self._unread_train_work)
                if sweeps_under_train:
                    operands = self._sweep_operands(count, datas)
                    results = self._dispatch_sweeps(count, states_me, operands)
                    # Polled, not waited for: were the sweeps enqueued while
                    # the train still ran?
                    ahead = not jax.tree_util.tree_leaves(states_me)[0].is_ready()
                    self._ard_train_counts["sequential_trains"] += 1
                    self._ard_train_counts["sweeps_ahead"] += ahead
                    phase.set_attributes(sweeps_ahead=int(ahead))
                phase.block(states_me)
                if phase.enabled:
                    works, self._unread_train_work = self._unread_train_work, ()
                    self._record_train_work(
                        gp_bandit.read_train_work(phase, works)
                    )
        is_sparse = isinstance(states_me, sparse_gp.SparseGPState)
        if set_acquisition:
            self._remember_fit(states_me)
            all_data, labels_mn, labels_mask, ref_point, _, first_has_new, has_completed = (
                operands
            )
            return self._suggest_with_set_acquisition(
                count, states_me, all_data, labels_mn, labels_mask, ref_point,
                first_has_new, has_completed, datas,
            )

        # Device-attributed sweep timing: from here (the train's end, when
        # the sweeps went out under it) to the sweeps' end.
        with profiler.timeit("acquisition_optimizer"), jax_timing.device_phase(
            "sparse_gp.ucb_pe_acquisition"
            if is_sparse
            else "gp_ucb_pe.acquisition",
            stage="acquire",
            devices=self._mesh_size(),
        ):
            if results is None:
                results = self._dispatch_sweeps(count, states_me, operands)
            # The next train's seeds go out BEHIND the sweeps. Between the
            # train and the sweeps their ~30 one-operation programs filled
            # the device's queue of programs in flight (32 on a TPU v5e),
            # and the host's next launch, the second sweep's, waited for
            # the train's end (PERF.md section 6, PR 45).
            self._seed_next_trains()
            jax.block_until_ready(results[-1][0].scores)
        if is_sparse:
            self._surrogate_counts["sparse_suggests"] += 1
        with profiler.timeit("best_candidates_to_trials"), tracer.span(
            "designer.decode"
        ):
            self._remember_fit(states_me)
            return self._decode_ucb_pe(results)

    def _remember_fit(self, states_me) -> None:
        """Keeps the trained per-metric state; metric 0's predictive is made
        of it when somebody reads ``_last_predictive`` (a device program,
        which no suggest needs)."""
        self._predictive = None
        self._unread_fit = states_me

    def _predictive_of(self, states_me):
        """Metric 0's trained posterior (``_last_predictive``)."""
        if isinstance(states_me, mtgp.MultiTaskGPState):
            return _MetricZeroMTPredictive(states_me)
        member_states = _metric_zero(states_me)
        if isinstance(states_me, sparse_gp.SparseGPState):
            self._last_sparse_state = member_states
            return sparse_gp.SparseEnsemblePredictive(member_states)
        return gp_lib.EnsemblePredictive(member_states)

    def sparse_inducing_state(self) -> Optional[sparse_gp.SparseGPState]:
        if isinstance(self._unread_fit, sparse_gp.SparseGPState):
            _ = self._last_predictive  # slices the unread fit, this state with it
        return self._last_sparse_state

    def _suggest_with_set_acquisition(
        self, count, states_me, all_data, labels_mn, labels_mask, ref_point,
        first_has_new, has_completed, datas,
    ) -> List[trial_.TrialSuggestion]:
        """Reference flow: one UCB pick if fresh data, then a joint PE set."""
        suggestions: List[trial_.TrialSuggestion] = []
        if bool(first_has_new):
            with profiler.timeit("acquisition_optimizer"):
                first, aux1 = _suggest_batch(
                    self._model, self._vec_opt, states_me, all_data,
                    labels_mn, labels_mask, ref_point,
                    self._sweep_inputs(datas)[3], self._next_rng(),
                    first_has_new, has_completed, 1, self.config,
                    self.use_trust_region, self._mesh, self.prior_acquisition,
                )
                jax.block_until_ready(first.scores)
            suggestions.extend(self._decode_ucb_pe([(first, aux1, 1)]))
            all_data = _append_first_pick(all_data, first.features)
        q = count - len(suggestions)
        set_opt = self._set_opt_cache.get(q)
        if set_opt is None:
            enc = self._converter.encoder
            cat_sizes = tuple(enc.category_sizes) + (1,) * (
                self._cat_width - enc.num_categorical
            )
            strategy = eagle_lib.VectorizedEagleStrategy(
                num_continuous=self._cont_width * q,
                category_sizes=cat_sizes * q,
            )
            set_opt = vectorized_lib.VectorizedOptimizer(
                strategy, max_evaluations=self.max_acquisition_evaluations
            )
            self._set_opt_cache[q] = set_opt
        with profiler.timeit("set_acquisition_optimizer"):
            result, aux = _suggest_set_pe(
                self._model,
                set_opt,
                states_me,
                all_data,
                self._next_rng(),
                q,
                self.config,
                self.use_trust_region,
                self.prior_acquisition,
            )
            jax.block_until_ready(result.scores)
        with profiler.timeit("best_candidates_to_trials"):
            suggestions.extend(self._decode_ucb_pe([(result, aux, q)]))
        return suggestions

    def _decode_ucb_pe(
        self, segments: Sequence[Tuple[Any, dict, int]]
    ) -> List[trial_.TrialSuggestion]:
        """The suggestions of a suggest's sweeps, ``(result, aux, rows)``
        each in pick order: ONE device->host fetch for everything (each
        separate np.asarray on a device array is a blocking round trip) and
        ONE decode of the whole batch's rows."""
        conv = self._converter
        fetched = jax.device_get(
            [
                (
                    result.features.continuous,
                    result.features.categorical,
                    result.scores,
                    aux["mean"],
                    aux["stddev"],
                    aux["stddev_from_all"],
                    aux["use_ucb"],
                    aux["trust_radius"],
                    aux.get("nystrom_augments"),  # a sparse sweep's; else None
                )
                for result, aux, _ in segments
            ]
        )
        # Each sweep's first ``rows`` picks, one after another; a sweep's one
        # trust radius is every one of its picks'.
        columns: List[List[np.ndarray]] = [[] for _ in range(8)]
        for (*per_pick, radius, augments), (_, _, rows) in zip(fetched, segments):
            for column, values in zip(columns, per_pick):
                column.append(np.asarray(values)[:rows])
            columns[7].append(np.full(rows, radius))
            self._surrogate_counts["nystrom_augments"] += int(augments or 0)
        cont, cat, scores, mean, stddev, stddev_all, use_ucb, trust_radius = (
            np.concatenate(column) for column in columns
        )
        parameters = conv.to_parameters(
            cont[:, : conv.encoder.num_continuous],
            cat[:, : conv.encoder.num_categorical],
        )
        suggestions = []
        for i, params in enumerate(parameters):
            s = trial_.TrialSuggestion(parameters=params)
            ns = s.metadata.ns("gp_ucb_pe")
            ns["acquisition"] = float(scores[i])
            ns["use_ucb"] = str(bool(use_ucb[i]))
            ns["trust_radius"] = float(trust_radius[i])
            pred = ns.ns("prediction_in_warped_y_space")
            pred["mean"] = np.array2string(mean[i], separator=",")
            pred["stddev"] = np.array2string(stddev[i], separator=",")
            pred["stddev_from_all"] = np.array2string(
                stddev_all[i], separator=","
            )
            suggestions.append(s)
        return suggestions

    # -- Predictor (unwarped; reference `sample`/`predict`) -----------------

    def sample(
        self,
        suggestions: Sequence[trial_.TrialSuggestion],
        rng=None,
        num_samples: int = 1000,
    ) -> np.ndarray:
        """Unwarped posterior samples: [S, T] (single) or [S, T, M] (multi).

        ``rng`` may be a jax PRNGKey or a numpy Generator (Predictor base
        contract)."""
        rng = gp_bandit._as_prng_key(rng)
        if not suggestions:
            return np.zeros((num_samples, 0))
        states_me, _ = self._train_states_me()
        feats = self._encode_suggestions(suggestions)
        if isinstance(states_me, mtgp.MultiTaskGPState):
            mean, stddev = _mt_mixture_predict(states_me, feats)  # [M, T]
        else:
            mean, stddev = _mixture_predict(states_me, feats)  # [M, T]
        eps = jax.random.normal(rng, (num_samples,) + mean.shape, mean.dtype)
        warped = np.asarray(mean[None] + stddev[None] * eps)  # [S, M, T]
        if not self._warpers_fitted:
            # No completed labels to fit a warper on: the warped space IS the
            # native space (prior samples on a fresh study).
            out = warped
            out = np.moveaxis(out, 1, 2)
            return out[:, :, 0] if out.shape[-1] == 1 else out
        out = np.empty_like(warped)
        metrics_enc = self._converter.metrics
        for m, (warper, idx) in enumerate(
            zip(self._metric_warpers, self._objective_indices())
        ):
            flat = warped[:, m, :].reshape(-1, 1)
            unwarped = warper.unwarp(flat).reshape(warped.shape[0], -1)
            # The converter owns the all-MAXIMIZE flip rule; route back
            # through it so samples land in the user's metric scale.
            out[:, m, :] = metrics_enc.decode_column(unwarped, idx)
        out = np.moveaxis(out, 1, 2)  # [S, T, M]
        return out[:, :, 0] if out.shape[-1] == 1 else out

    def predict(
        self,
        suggestions: Sequence[trial_.TrialSuggestion],
        rng=None,
        num_samples: Optional[int] = 1000,
    ) -> core_lib.Prediction:
        """Empirical mean/stddev of unwarped posterior samples."""
        samples = self.sample(suggestions, rng, num_samples or 1000)
        return core_lib.Prediction(
            mean=np.mean(samples, axis=0), stddev=np.std(samples, axis=0)
        )


def default_factory(
    problem: base_study_config.ProblemStatement, seed: Optional[int] = None, **kwargs
) -> VizierGPUCBPEBandit:
    return VizierGPUCBPEBandit(problem, rng_seed=seed or 0, **kwargs)


# -- compute-IR programs (vizier_tpu.compute) --------------------------------
#
# The batched designer-compute contract for the service DEFAULT: one
# program per compiled-flush family (exact | sparse UCB-PE) over one shared
# body (``_UCBPEFlush``). The sparse family swaps only the train and the
# per-pick re-conditioning — SGPR train + pending-pick conditioning through
# the inducing-point posterior — so 1000+-trial studies on the service
# DEFAULT scale like the sparse GP-bandit path.


def _ucb_pe_unbatchable(designer: "VizierGPUCBPEBandit", count: int) -> bool:
    """Paths the batched UCB-PE flush programs do not cover.

    Batchable: the single-objective independent-GP greedy path with no
    cached fit (a cached fit means the sequential suggest would skip
    training — re-training it in a batch would deviate). Multitask,
    set-acquisition, priors, custom acquisition priors, mesh sharding, and
    the seeding stage run sequentially.
    """
    return bool(
        designer._mesh is not None
        or len(designer._trials) + len(designer._active_trials)
        < designer.num_seed_trials
        or getattr(designer, "_priors", None)
        or len(designer._objective_indices()) != 1
        or designer.config.optimize_set_acquisition_for_exploration
        or designer.prior_acquisition is not None
        or designer._cached_states is not None
    )


def _ucb_pe_two_phase(designer: "VizierGPUCBPEBandit", count: int) -> bool:
    """Whether the budget policy makes two sweeps of this suggest (the
    first pick alone, then the rest), exactly like the sequential flow."""
    return designer.acquisition_budget_policy == "first_pick_full" and count > 1


def _ucb_pe_demux(items, states, warm_next, data, segments, rows, train_work):
    """ONE device->host fetch for everything the demux needs; per-slot
    slices below are then free numpy views. The per-flush half of the fused
    path's ``designer.decode`` stage (``finalize`` is the per-slot half).
    ``train_work`` (the flush's one train program:
    ``gp_bandit.read_train_work``) goes to the first member alone, so that
    it is counted once a flush."""
    from vizier_tpu.parallel import batch_executor

    with tracing_lib.get_tracer().span(
        "designer.decode", **tracing_lib.FUSED_FLUSH
    ):
        states, warm_next, data, segments = jax.device_get(
            (states, warm_next, data, segments)
        )
        return [
            dict(
                states=batch_executor.slice_pytree(states, i),
                warm_next=batch_executor.slice_pytree(warm_next, i),
                data=batch_executor.slice_pytree(data, i),
                segments=[
                    (
                        batch_executor.slice_pytree(result, i),
                        batch_executor.slice_pytree(aux, i),
                        n,
                    )
                    for (result, aux), n in zip(segments, rows)
                ],
                train_work=train_work if i == 0 else None,
            )
            for i in range(len(items))
        ]


class _UCBPEFlush(compute_ir.DesignerProgram):
    """The flush both UCB-PE families run: vmapped ARD train + vmapped
    greedy batch loop(s) (two sweep programs under ``first_pick_full`` with
    count > 1, exactly like the sequential flow).

    A subclass states its registry literals and the three things that
    differ: the surrogate mode it owns, its models and flush program, and
    what ``finalize`` keeps of the fit."""

    #: The ``surrogate_config_lib.MODE_*`` whose studies this program owns.
    surrogate_mode = ""

    @abc.abstractmethod
    def _models(self, designer: "VizierGPUCBPEBandit", count: int) -> tuple:
        """The model(s) the flush trains and re-conditions: the bucket
        statics after the all-points pad, and the first flush arguments."""

    @abc.abstractmethod
    def _flush(self, *args):
        """The jitted flush program of this family, looked up in its
        module when called (``tests/compute/test_tpu_compile.py`` swaps
        it there): its outputs, the train's count of its work last."""

    @abc.abstractmethod
    def _keep_fit(self, designer: "VizierGPUCBPEBandit", states) -> None:
        """The sequential suggest's bookkeeping of a trained fit."""

    def bucket_key(self, designer, count):
        if _ucb_pe_unbatchable(designer, count):
            return None
        if designer._refresh_ucb_pe_surrogate_mode() != self.surrogate_mode:
            return None  # the other family's program owns this study
        pad = designer._converter.padding
        n_all = len(designer._trials) + len(designer._active_trials)
        return compute_ir.BucketKey(
            kind=self.kind,
            pad_trials=pad.pad_trials(len(designer._trials)),
            cont_width=designer._cont_width,
            cat_width=designer._cat_width,
            metric_count=1,
            count=count,
            statics=(
                # all-points rows get their own padded size (spare rows for
                # the batch picks), so it is part of the shape identity.
                pad.pad_trials(n_all + count),
                *self._models(designer, count),
                designer._ard,
                designer._vec_opt,
                designer._pick_vec_opt(count),
                designer._batch_restarts(),
                designer._batch_ensemble(),
                designer.config,
                designer.use_trust_region,
                designer.acquisition_budget_policy,
            ),
        )

    def prepare(self, designer, count):
        """Host-side half of a batched UCB-PE suggest (single-objective).

        Pads + warps this study's rows from the store and draws RNG keys in
        exactly the sequential order: one train key, then one acquisition
        key per ``_suggest_batch`` call the budget policy would make.
        Host-only (numpy ModelData): GPData conversion, label stacking,
        reference point, and prior features all happen inside the batched
        device programs — prepare's only device work is the RNG splits.
        """
        cont, cat, raw = designer._completed_rows()
        features, n_pad = designer._padded_features(cont, cat)
        warped, warper = designer._warp_column(
            raw[:, designer._objective_indices()[0]]
        )
        designer._metric_warpers = [warper]
        designer._warpers_fitted = raw.shape[0] > 0
        md = types.ModelData(features, designer._padded_labels(warped, n_pad))
        rng_train = designer._next_rng()
        return dict(
            designer=designer,
            count=count,
            md=md,
            all_md=designer._all_points_model_data(count),
            first_has_new=np.asarray(designer._has_new_completed_trials()),
            has_completed=np.asarray(bool(designer._trials)),
            warm=designer._warm_params_me[0],
            restarts=designer._batch_restarts(),
            rng_train=rng_train,
            rng_acq=designer._next_rng(),
            rng_acq_rest=(
                designer._next_rng()
                if _ucb_pe_two_phase(designer, count)
                else None
            ),
        )

    def device_program(self, items, pad_to=None, placement=None):
        from vizier_tpu.parallel import batch_executor

        d0: "VizierGPUCBPEBandit" = items[0]["designer"]
        count = items[0]["count"]
        two_phase = _ucb_pe_two_phase(d0, count)
        names = (
            "md", "all_md", "rng_train", "rng_acq", "warm", "first_has_new",
            "has_completed",
        )
        stacked = batch_executor.stack_members(
            items,
            names + ("rng_acq_rest",) if two_phase else names,
            pad_to,
            placement,
        )
        with jax_timing.device_phase(
            self.device_phase, **tracing_lib.FUSED_FLUSH
        ) as phase:
            states, warm_next, data, segments, work = self._flush(
                *self._models(d0, count),
                d0._ard, d0._vec_opt, d0._pick_vec_opt(count),
                stacked["md"], stacked["all_md"],
                stacked["rng_train"], stacked["rng_acq"],
                stacked["rng_acq_rest" if two_phase else "rng_acq"],
                stacked["warm"], stacked["first_has_new"],
                stacked["has_completed"],
                items[0]["restarts"], d0._batch_ensemble(), count,
                d0.config, d0.use_trust_region, two_phase,
            )
            phase.block(segments)
            train_work = gp_bandit.read_train_work(phase, (work,))
        rows = [1, count - 1] if two_phase else [count]
        return _ucb_pe_demux(
            items, states, warm_next, data, segments, rows, train_work
        )

    def finalize(self, designer, item, output):
        """Host-side demux: warm writeback, fit caching for predict/sample,
        and per-segment decode — the sequential suggest's state
        transitions."""
        states = output["states"]  # [E] leaves (this study's ensemble)
        designer._record_train()
        designer._record_train_work(output["train_work"])
        if designer._warm_update_allowed():
            # The unconstrain already ran (vmapped) inside the flush program.
            designer._warm_params_me = [output["warm_next"]]
            designer._warm_is_trained = True
        states_me = jax.tree_util.tree_map(lambda a: a[None], states)  # [1, E]
        designer._cached_states = (states_me, [output["data"]])
        self._keep_fit(designer, states)
        return designer._decode_ucb_pe(output["segments"])

    def prewarm_factory(self, problem, **kwargs):
        return VizierGPUCBPEBandit(problem, **kwargs)


class UCBPEProgram(_UCBPEFlush):
    """Exact UCB-PE flush."""

    kind = "gp_ucb_pe"
    device_phase = "gp_ucb_pe.suggest_batched"
    surrogate_family = "exact"
    shardable_batch_axis = "study"
    algorithms = ("DEFAULT", "GP_UCB_PE", "ALGORITHM_UNSPECIFIED")
    surrogate_mode = surrogate_config_lib.MODE_EXACT

    def _models(self, designer, count):
        return (designer._model,)

    def _flush(self, *args):
        return _ucb_pe_flush_program(*args)

    def _keep_fit(self, designer, states):
        designer._last_predictive = gp_lib.EnsemblePredictive(states)


class UCBPESparseProgram(_UCBPEFlush):
    """Sparse UCB-PE flush: SGPR collapsed-bound train + the greedy batch
    with pending-pick conditioning through the inducing-point posterior."""

    kind = "gp_ucb_pe_sparse"
    device_phase = "sparse_gp.ucb_pe_suggest_batched"
    surrogate_family = "sparse"
    shardable_batch_axis = "study"
    algorithms = ("DEFAULT", "GP_UCB_PE", "ALGORITHM_UNSPECIFIED")
    surrogate_mode = surrogate_config_lib.MODE_SPARSE

    def _models(self, designer, count):
        # Both sparse models: the m-bucket (train) AND the augmented-
        # capacity model (re-conditioning), so equal keys ⇒ one compiled
        # program per (n, m, count).
        return (designer._sparse_model(), designer._sparse_all_model(count))

    def _flush(self, *args):
        return _sparse_ucb_pe_flush_program(*args)

    def _keep_fit(self, designer, states):
        designer._last_predictive = sparse_gp.SparseEnsemblePredictive(states)
        designer._last_sparse_state = states
        designer._surrogate_counts["sparse_suggests"] += 1


compute_registry.register(VizierGPUCBPEBandit, UCBPEProgram())
compute_registry.register(VizierGPUCBPEBandit, UCBPESparseProgram())
