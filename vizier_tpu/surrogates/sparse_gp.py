"""Sparse inducing-point GP: SGPR collapsed bound, mask-safe, TPU-first.

The exact GP (``models.gp``) pays O(n³) per ARD loss evaluation and O(n²)
per posterior query. This module is the inducing-point alternative
("Scalable Thompson Sampling using Sparse Gaussian Process Models",
arXiv:2006.05356; Titsias' SGPR collapsed bound): m ≪ n pseudo-inputs Z summarize the data, training costs O(n·m²)
and each posterior query O(m²) — and because the collapsed bound
marginalizes the inducing distribution in closed form, there is no
variational optimization loop: the SAME multi-restart L-BFGS program that
trains the exact GP trains this one (the hyperparameter pytree is
identical, so warm-started ARD restarts keep working across the seam).

Design mirrors ``models.gp`` deliberately:

- **mask-safe everywhere**: padded data rows AND padded inducing slots are
  decoupled (zero cross-covariance, unit diagonal, zero residual), so one
  compiled program serves every (trial-bucket, inducing-bucket) pair —
  fill values cannot leak into either Cholesky;
- **k-center inducing selection** (farthest-point traversal, seeded at the
  incumbent) is deterministic given the data and runs INSIDE the jitted
  program — O(n·m·d), negligible next to training, and vmappable over the
  cross-study batch axis;
- **matmul-only predictions**: like ``GPState.linv``, the two triangular
  inverses are formed once at precompute so the acquisition sweep's
  thousands of posterior queries ride the MXU instead of sequential
  triangular solves.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import flax.struct
import jax
import jax.numpy as jnp

from vizier_tpu.models import gp as gp_lib
from vizier_tpu.models import kernels
from vizier_tpu.models import params as params_lib

Array = jax.Array
Params = params_lib.Params

_LOG_2PI = 1.8378770664093453
# Noise-floor jitter matching the exact GP's Gram stabilizer.
_JITTER = 1e-5
# Kmm jitter: inducing Grams are denser (k-center picks spread points, but
# duplicate training rows can still select twice); a slightly larger
# diagonal keeps the m×m Cholesky conditioned without visibly biasing the
# posterior at SGPR scales.
_KMM_JITTER = 1e-4


@flax.struct.dataclass
class SparseGPData:
    """Training data + the selected (padded, masked) inducing set."""

    data: gp_lib.GPData
    z_continuous: Array  # [M, Dc] float32
    z_categorical: Array  # [M, Ds] int32
    inducing_mask: Array  # [M] bool, True = real inducing point
    inducing_indices: Array  # [M] int32 rows of ``data`` the points came from

    @property
    def num_inducing(self) -> int:
        return self.z_continuous.shape[0]

    def z_features(self) -> kernels.MixedFeatures:
        return kernels.MixedFeatures(self.z_continuous, self.z_categorical)


def select_inducing_kcenter(data: gp_lib.GPData, m: int) -> SparseGPData:
    """Greedy k-center (farthest-point) selection of ``m`` inducing points.

    Deterministic given the data: starts at the best-label valid row (the
    incumbent — the region Thompson/UCB exploitation cares most about),
    then repeatedly takes the valid row farthest from the chosen set under
    the unit-lengthscale mixed metric (squared euclidean on continuous +
    hamming on categorical, both dim-masked). Traceable: fixed [m] output
    shapes, ``fori_loop`` over picks, so it vmaps over the cross-study
    batch axis. When fewer than ``m`` valid rows exist the surplus slots
    repeat already-chosen rows and are masked out of every downstream
    computation by ``inducing_mask``.
    """
    cont, cat = data.continuous, data.categorical
    valid = data.row_mask
    num_valid = jnp.sum(valid.astype(jnp.int32))
    start = jnp.argmax(jnp.where(valid, data.labels, -jnp.inf)).astype(jnp.int32)

    cont_w = data.cont_dim_mask.astype(cont.dtype)
    cat_w = data.cat_dim_mask.astype(cont.dtype)

    def dist_to(idx: Array) -> Array:
        dc = cont - cont[idx][None, :]
        sq = jnp.sum(dc * dc * cont_w[None, :], axis=-1)
        mismatch = (cat != cat[idx][None, :]).astype(cont.dtype)
        return sq + jnp.sum(mismatch * cat_w[None, :], axis=-1)

    def body(i, carry):
        min_d, idxs = carry
        min_d = jnp.minimum(min_d, dist_to(idxs[i - 1]))
        nxt = jnp.argmax(jnp.where(valid, min_d, -jnp.inf)).astype(jnp.int32)
        return min_d, idxs.at[i].set(nxt)

    idxs = jnp.zeros((m,), jnp.int32).at[0].set(start)
    min_d = jnp.full((cont.shape[0],), jnp.inf, dtype=cont.dtype)
    if m > 1:
        _, idxs = jax.lax.fori_loop(1, m, body, (min_d, idxs))
    mask = jnp.arange(m) < jnp.minimum(num_valid, m)
    return SparseGPData(
        data=data,
        z_continuous=cont[idxs],
        z_categorical=cat[idxs],
        inducing_mask=mask,
        inducing_indices=idxs,
    )


def with_pending_capacity(
    sdata: SparseGPData, data: gp_lib.GPData, extra: int
) -> SparseGPData:
    """An all-points twin of a trained posterior's inducing set.

    Carries the SAME inducing rows Z over a different data block (the
    completed+active rows with spare slots for a batch's picks), plus
    ``extra`` masked-off spare inducing slots that per-pick conditioning
    may Nyström-fill (``gp_ucb_pe._append_row_sparse``) when a pick lands
    where Z has no support. Traceable fixed shapes: one compiled program
    per (n-bucket, m-bucket, extra) triple.
    """
    z_cont = jnp.concatenate(
        [
            sdata.z_continuous,
            jnp.zeros(
                (extra, sdata.z_continuous.shape[-1]), sdata.z_continuous.dtype
            ),
        ],
        axis=0,
    )
    z_cat = jnp.concatenate(
        [
            sdata.z_categorical,
            jnp.zeros(
                (extra, sdata.z_categorical.shape[-1]),
                sdata.z_categorical.dtype,
            ),
        ],
        axis=0,
    )
    mask = jnp.concatenate(
        [sdata.inducing_mask, jnp.zeros((extra,), bool)], axis=0
    )
    indices = jnp.concatenate(
        [sdata.inducing_indices, jnp.zeros((extra,), jnp.int32)], axis=0
    )
    return SparseGPData(
        data=data,
        z_continuous=z_cont,
        z_categorical=z_cat,
        inducing_mask=mask,
        inducing_indices=indices,
    )


@dataclasses.dataclass(frozen=True)
class SparseGaussianProcess:
    """Static sparse-model config + pure functions over (params, data).

    Wraps the exact model for its kernel and hyperparameter declaration —
    the parameter pytree is IDENTICAL to the exact GP's, which is what lets
    warm-started ARD restarts and the serving designer-state cache carry
    trained params across suggests without knowing which surrogate is
    active. ``num_inducing`` is the PADDED inducing-slot count (a jit
    static; the designer buckets it via the padding schedule).
    """

    base: gp_lib.VizierGaussianProcess
    num_inducing: int

    def param_collection(self) -> params_lib.ParameterCollection:
        return self.base.param_collection()

    # -- masked covariance blocks ------------------------------------------

    def _masked_kmm(self, p: Params, sdata: SparseGPData) -> Array:
        """K(Z, Z) + jitter·I on valid slots; identity on padded slots."""
        zf = sdata.z_features()
        k = self.base._kernel(p, zf, zf, sdata.data)
        m = sdata.inducing_mask
        pair = m[:, None] & m[None, :]
        k = jnp.where(pair, k, 0.0)
        amp2 = p["amplitude"] * p["amplitude"]
        diag = jnp.where(m, amp2 + _KMM_JITTER, 1.0)
        eye = jnp.eye(k.shape[0], dtype=bool)
        return jnp.where(eye, 0.0, k) + jnp.diag(diag)

    def _masked_knm(self, p: Params, sdata: SparseGPData) -> Array:
        """K(X, Z) zeroed on padded rows and padded inducing slots."""
        k = self.base._kernel(p, sdata.data.features(), sdata.z_features(), sdata.data)
        keep = sdata.data.row_mask[:, None] & sdata.inducing_mask[None, :]
        return jnp.where(keep, k, 0.0)

    def _factorize(self, p: Params, sdata: SparseGPData):
        """The shared SGPR factorization (GPflow notation).

        L  = chol(Kmm)                                  [M, M]
        A  = L⁻¹ Kmn / σ                                [M, N]
        B  = I + A Aᵀ,  LB = chol(B)                    [M, M]
        c  = LB⁻¹ A y / σ                               [M]

        Padded inducing slots have zero A rows ⇒ unit rows of B ⇒ unit LB
        diagonal and zero c entries; padded data rows have zero A columns
        and zero labels — both drop out of every term below.

        The two products over the data axis run at full f32 precision
        (``models.gp`` ``POSTERIOR_PRECISION``), like the predictive's own:
        B's entries reach 1e5 beside a smallest eigenvalue near 1, and at
        the TPU's default (one bf16 pass) the posterior's stddev came out
        0.05-1.0 label stddevs off float64 on a v5e where the CPU's f32
        is 2e-5 off (PERF.md section 6, PR 41).
        """
        kmm = self._masked_kmm(p, sdata)
        knm = self._masked_knm(p, sdata)
        chol = jnp.linalg.cholesky(kmm)
        sigma2 = p["noise_stddev"] * p["noise_stddev"] + _JITTER
        sigma = jnp.sqrt(sigma2)
        a = jax.scipy.linalg.solve_triangular(chol, knm.T, lower=True) / sigma
        precision = gp_lib.POSTERIOR_PRECISION
        b = jnp.eye(a.shape[0], dtype=a.dtype) + jnp.matmul(a, a.T, precision=precision)
        chol_b = jnp.linalg.cholesky(b)
        c = (
            jax.scipy.linalg.solve_triangular(
                chol_b, jnp.matmul(a, sdata.data.labels, precision=precision), lower=True
            )
            / sigma
        )
        return chol, chol_b, a, c, sigma2

    # -- collapsed bound (the ARD loss) ------------------------------------

    def neg_log_likelihood(self, unconstrained: Params, sdata: SparseGPData) -> Array:
        """Negated Titsias collapsed bound + the shared ARD regularizer.

        -ELBO = ½[n·log 2π + log|B| + n·log σ² + yᵀy/σ² − cᵀc]
                + ½/σ²·tr(Knn − Qnn)

        with every n-indexed term restricted to valid rows. Minimizing this
        is the drop-in replacement for the exact GP's NLL in the SAME
        multi-restart L-BFGS program.
        """
        coll = self.param_collection()
        bounded = coll.constrain(unconstrained)
        # (The noise as the base model builds it: with its nugget.)
        p = gp_lib._conditioned(bounded)
        chol, chol_b, a, c, sigma2 = self._factorize(p, sdata)
        del chol
        data = sdata.data
        y = data.labels
        n_valid = jnp.sum(data.row_mask.astype(y.dtype))
        log_det = n_valid * jnp.log(sigma2) + 2.0 * jnp.sum(
            jnp.where(
                sdata.inducing_mask,
                jnp.log(gp_lib.cholesky_diagonal(chol_b)),
                0.0,
            )
        )
        quad = jnp.dot(y, y) / sigma2 - jnp.dot(c, c)
        amp2 = p["amplitude"] * p["amplitude"]
        # tr(Knn − Qnn)/σ²: diag(Knn) = amplitude² on valid rows; ΣA² is
        # exactly tr(Qnn)/σ² (padded columns are zero).
        trace = n_valid * amp2 / sigma2 - jnp.sum(a * a)
        nll = 0.5 * (n_valid * _LOG_2PI + log_det + quad + trace)
        loss = nll + coll.regularization(bounded)
        # Guard non-finite (Cholesky blow-ups under extreme params) — the
        # same fail-soft the exact GP's loss applies.
        return jnp.where(jnp.isfinite(loss), loss, jnp.asarray(1e10, loss.dtype))

    # -- predictive --------------------------------------------------------

    def precompute(self, unconstrained: Params, sdata: SparseGPData) -> "SparseGPState":
        """Factorize once; posterior queries are then matmul-only O(m²)."""
        return self.precompute_constrained(self.base.constrain(unconstrained), sdata)

    def precompute_constrained(self, p: Params, sdata: SparseGPData) -> "SparseGPState":
        """Factorization from already-constrained params.

        The UCB-PE pending-pick re-conditioning path: per pick, the greedy
        batch loop overrides the constrained noise floor and rebuilds the
        posterior over the grown pending set — O(n·m²) per pick, the
        inducing-point replacement for the exact path's O(n³) per-pick
        Cholesky (duck-type parity with
        ``VizierGaussianProcess.precompute_constrained``).
        """
        chol, chol_b, _, c, _ = self._factorize(p, sdata)
        eye = jnp.eye(chol.shape[0], dtype=chol.dtype)
        linv = jax.scipy.linalg.solve_triangular(chol, eye, lower=True)
        lb_inv = jax.scipy.linalg.solve_triangular(chol_b, eye, lower=True)
        # mean(x*) = k*ᵀ L⁻ᵀ LB⁻ᵀ c — fold the two back-substitutions into
        # one [M] weight vector; var needs both inverses separately.
        # (At full f32 precision too: ``lb_linv`` is a product of two
        # triangular inverses whose entries the variance squares.)
        precision = gp_lib.POSTERIOR_PRECISION
        w = jnp.matmul(
            linv.T, jnp.matmul(lb_inv.T, c, precision=precision), precision=precision
        )
        return SparseGPState(
            model=self,
            params=p,
            sdata=sdata,
            w=w,
            linv=linv,
            lb_linv=jnp.matmul(lb_inv, linv, precision=precision),
        )


@flax.struct.dataclass
class SparseGPState:
    """Factorized SGPR posterior, ready for O(Q·M²) batched predictions."""

    model: SparseGaussianProcess = flax.struct.field(pytree_node=False)
    params: Params
    sdata: SparseGPData
    w: Array  # [M] predictive-mean weights
    linv: Array  # [M, M] = chol(Kmm)^-1
    lb_linv: Array  # [M, M] = chol(B)^-1 @ chol(Kmm)^-1

    @property
    def data(self) -> gp_lib.GPData:
        """The training data (duck-type parity with ``GPState.data``)."""
        return self.sdata.data

    def predict(
        self, query: kernels.MixedFeatures, *, include_noise: bool = False
    ) -> Tuple[Array, Array]:
        """Posterior mean and stddev at query points ([Q], [Q]).

        var(x*) = k** − ‖L⁻¹k*‖² + ‖LB⁻¹L⁻¹k*‖² — strictly the SGPR
        predictive (Qnn-corrected), not the DTC approximation.
        """
        model, p, sdata = self.model, self.params, self.sdata
        k_star = model.base._kernel(p, query, sdata.z_features(), sdata.data)
        k_star = jnp.where(sdata.inducing_mask[None, :], k_star, 0.0)  # [Q, M]
        # Full f32 precision, like the exact posterior (models.gp
        # POSTERIOR_PRECISION): the same difference of near-equal terms.
        precision = gp_lib.POSTERIOR_PRECISION
        mean = jnp.matmul(k_star, self.w, precision=precision)
        # [M, Q] — matmul-only hot loop
        t1 = jnp.matmul(self.linv, k_star.T, precision=precision)
        t2 = jnp.matmul(self.lb_linv, k_star.T, precision=precision)
        amp2 = p["amplitude"] * p["amplitude"]
        var = amp2 - jnp.sum(t1 * t1, axis=0) + jnp.sum(t2 * t2, axis=0)
        if include_noise:
            var = var + p["noise_stddev"] * p["noise_stddev"]
        return mean, jnp.sqrt(jnp.maximum(var, 1e-12))

    def sample(
        self, query: kernels.MixedFeatures, rng: Array, num_samples: int
    ) -> Array:
        """Marginal posterior samples [num_samples, Q] (diagonal cov)."""
        mean, stddev = self.predict(query)
        eps = jax.random.normal(rng, (num_samples,) + mean.shape, dtype=mean.dtype)
        return mean[None, :] + stddev[None, :] * eps


@flax.struct.dataclass
class SparseEnsemblePredictive:
    """Uniform mixture over a leading ensemble axis of SparseGPStates.

    Same moment-matched combination as ``gp.EnsemblePredictive`` — the
    acquisition layer consumes either interchangeably.
    """

    states: SparseGPState  # leading axis E

    @property
    def ensemble_size(self) -> int:
        return self.states.w.shape[0]

    def predict(self, query: kernels.MixedFeatures) -> Tuple[Array, Array]:
        means, stddevs = jax.vmap(lambda s: s.predict(query))(self.states)
        mean = jnp.mean(means, axis=0)
        second = jnp.mean(stddevs**2 + means**2, axis=0)
        var = jnp.maximum(second - mean**2, 1e-12)
        return mean, jnp.sqrt(var)

    def predict_per_member(self, query: kernels.MixedFeatures) -> Tuple[Array, Array]:
        return jax.vmap(lambda s: s.predict(query))(self.states)
