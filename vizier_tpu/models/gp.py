"""The Vizier Gaussian process: masked training, prediction, ensembles.

TPU-first rebuild of the reference GP stack
(``/root/reference/vizier/_src/jax/models/tuned_gp_models.py:78`` and
``stochastic_process_model.py:205,835,890``): an ARD Matern-5/2 GP over mixed
continuous/categorical features with

- hyperparameters as an unconstrained pytree (see ``models.params``) so ARD
  training is plain unconstrained optimization under jit/vmap;
- *mask-safe* likelihood/Cholesky: padded rows are decoupled (off-diagonal
  zeroed, unit diagonal, zero residual) so one compiled graph serves every
  trial count inside a padding bucket — fill values cannot leak into the
  factorization;
- f32 throughout with a noise floor + jitter instead of the reference's
  forced float64 (``pythia_service.py:50-57``) — TPU-native numerics;
- ensembles as a leading vmapped axis, ready to shard across devices over
  the ``ensemble`` mesh axis (see ``vizier_tpu.parallel``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from vizier_tpu import types
from vizier_tpu.models import kernels
from vizier_tpu.models import params as params_lib

Array = jax.Array
Params = params_lib.Params

_LOG_2PI = 1.8378770664093453
_JITTER = 1e-5
# The posterior's matmuls run at full f32 precision. At the TPU's default
# (one bf16 pass) the variance — a difference of near-equal terms through
# L⁻¹, whose entries grow with the Gram's condition number — came out
# NEGATIVE on a v5e at 400×20-D (PR 21's chip run): the clamp then
# zeroes the stddev and the UCB/PE terms with it. Whether a cheaper
# precision suffices per matmul is ROADMAP S2's measurement to make.
POSTERIOR_PRECISION = jax.lax.Precision.HIGHEST
# The nugget every Gram carries, as a share of the amplitude: the model is
# built with noise^2 + (NUGGET_TO_AMPLITUDE * amplitude)^2 where the noise
# parameter alone would stand, and reports that as its ``noise_stddev``. It
# bounds the Gram's condition number by n / NUGGET_TO_AMPLITUDE^2 (400 n:
# 1e5 at 250 trials), which is about what float32 carries through a
# Cholesky, its inverse and the variance's difference of near-equal terms.
# Beyond it (a noise-free objective fits the noise's lower bound: 1e6-1e7
# on 250 clustered trials) a v5e read the variance at a point the data
# already held -- exact: under the noise -- as large as the largest
# anywhere, so PE handed out a point another worker still held and the
# promising region's threshold landed on another trial; the mean drifted by
# a tenth of a label stddev; and an ARD train met Grams that float32 does
# not factor and walked off to a saturated bound (PERF.md, PR 29). A noisy
# objective (``default20d`` fits the noise at 6-8 % of the amplitude) moves
# its noise parameter down by the nugget and keeps its Gram.
NUGGET_TO_AMPLITUDE = 0.05


def cholesky_diagonal(chol: Array) -> Array:
    """diag(L) of a ``[..., n, n]`` factor, read as a masked row-sum.

    Not ``jnp.diagonal``: under a ``vmap`` (the ARD restarts, a flush's
    slots) that lowers on the TPU to a gather, and backward to a
    scatter-add, which want the factor batch-minor: every ``[rows, n, n]``
    factor is copied into a layout with ``rows`` padded to 128 lanes,
    forward and backward, in every L-BFGS evaluation (at 2 x 512 x 512,
    eight 134 MB copies: half of the warm train's loop; PERF.md, PR 34).
    This is one fused pass over the factor the forward already holds, and
    the same bits: a row's sum has one non-zero term, and the transpose of
    the select is the select the scatter-add wrote.
    """
    eye = jnp.eye(chol.shape[-1], dtype=bool)
    return jnp.sum(jnp.where(eye, chol, 0.0), axis=-1)


def _conditioned(p: Params) -> Params:
    """``p`` with the nugget (``NUGGET_TO_AMPLITUDE``) in its noise."""
    p = dict(p)
    nugget = NUGGET_TO_AMPLITUDE * p["amplitude"]
    p["noise_stddev"] = jnp.sqrt(p["noise_stddev"] * p["noise_stddev"] + nugget * nugget)
    return p


@flax.struct.dataclass
class GPData:
    """Plain-array training data with validity masks (all jit-traceable)."""

    continuous: Array  # [N, Dc] float32 in [0, 1]
    categorical: Array  # [N, Ds] int32
    labels: Array  # [N] float32 (warped; no NaNs among valid rows)
    row_mask: Array  # [N] bool, True = real data
    cont_dim_mask: Array  # [Dc] bool
    cat_dim_mask: Array  # [Ds] bool

    @classmethod
    def from_model_data(cls, data: types.ModelData, metric_index: int = 0) -> "GPData":
        """Host ``ModelData`` (every leaf NumPy) converts in NumPy, to NumPy
        leaves: masks and selections only, so the bits are those the traced
        conversion gives, and the host launches no device program for them.
        The compiled programs take either."""
        on_host = all(
            isinstance(leaf, np.ndarray) for leaf in jax.tree_util.tree_leaves(data)
        )
        xp = np if on_host else jnp
        cont = data.features.continuous
        cat = data.features.categorical
        labels = data.labels.padded_array[:, metric_index]
        row_mask = (
            cont.valid_mask(0)
            & data.labels.valid_mask(0)
            & ~xp.isnan(labels)
        )
        return cls(
            continuous=xp.asarray(cont.padded_array, xp.float32),
            categorical=xp.asarray(cat.padded_array, xp.int32),
            labels=xp.where(row_mask, xp.nan_to_num(labels), 0.0).astype(xp.float32),
            row_mask=row_mask,
            cont_dim_mask=cont.valid_mask(1),
            cat_dim_mask=cat.valid_mask(1),
        )

    @property
    def num_rows(self) -> int:
        return self.continuous.shape[0]

    def features(self) -> kernels.MixedFeatures:
        return kernels.MixedFeatures(self.continuous, self.categorical)


@dataclasses.dataclass(frozen=True)
class VizierGaussianProcess:
    """Static model config + pure functions over (params, data)."""

    num_continuous: int
    num_categorical: int
    use_linear_mean: bool = False
    # HEBO-style learnable Kumaraswamy input warping of the [0,1] continuous
    # features (parity with the reference's hebo_gp_model.py): u ->
    # 1-(1-u^a)^b with per-dimension a, b — lets the GP adapt to
    # non-stationary objectives (e.g. log-like sensitivity near a boundary).
    use_input_warping: bool = False

    # -- hyperparameter declaration ---------------------------------------

    def param_collection(self) -> params_lib.ParameterCollection:
        sc = params_lib.SoftClip
        specs = [
            params_lib.ParameterSpec(
                "amplitude", (), sc(0.01, 100.0), 0.1, 10.0, prior_mu=0.0, prior_sigma=1.0
            ),
            params_lib.ParameterSpec(
                "noise_stddev", (), sc(1e-3, 1.0), 5e-3, 0.3,
                prior_mu=float(np.log(1e-2)), prior_sigma=1.0,
            ),
        ]
        if self.num_continuous:
            specs.append(
                params_lib.ParameterSpec(
                    "continuous_length_scales",
                    (self.num_continuous,),
                    sc(0.005, 100.0),
                    0.05,
                    2.0,
                    prior_mu=float(np.log(0.3)),
                    prior_sigma=1.0,
                )
            )
        if self.num_categorical:
            # Weak prior centered at ls ~ 0.71, matching the reference's
            # categorical length_scale_squared regularizer
            # 0.01*log(ls^2/0.5)^2 over bounds ls in [0.1, 10]
            # (`tuned_gp_models.py:183-193`). A tight categorical prior is
            # destructive: at ls ~ 0.3 a single category mismatch puts
            # cells ~11 scaled units apart, zeroing all cross-cell
            # correlation — the GP then sees every unobserved cell as
            # prior-mean, the UCB-PE promising region collapses onto
            # observed cells, and batch exploration dies.
            specs.append(
                params_lib.ParameterSpec(
                    "categorical_length_scales",
                    (self.num_categorical,),
                    sc(0.05, 100.0),
                    0.1,
                    10.0,
                    prior_mu=float(np.log(np.sqrt(0.5))),
                    prior_sigma=3.5,
                )
            )
        if self.use_input_warping and self.num_continuous:
            for name in ("warp_a", "warp_b"):
                specs.append(
                    params_lib.ParameterSpec(
                        name,
                        (self.num_continuous,),
                        sc(0.25, 4.0),
                        0.8,
                        1.25,
                        prior_mu=0.0,  # log-normal centered at identity (a=b=1)
                        prior_sigma=0.5,
                    )
                )
        if self.use_linear_mean and self.num_continuous:
            # Linear mean coefficients are unconstrained; modelled via a wide
            # softclip to keep the single-pytree machinery uniform.
            specs.append(
                params_lib.ParameterSpec(
                    "mean_scale", (), sc(1e-3, 10.0), 0.1, 1.0, prior_mu=0.0
                )
            )
        return params_lib.ParameterCollection(tuple(specs))

    def constrain(self, unconstrained: Params) -> Params:
        """Constrained hyperparameters as the Gram is built with them: the
        collection's bounds, then the nugget in the noise
        (``NUGGET_TO_AMPLITUDE``)."""
        return _conditioned(self.param_collection().constrain(unconstrained))

    # -- kernel & mean -----------------------------------------------------

    def _warp_features(self, p: Params, f: kernels.MixedFeatures) -> kernels.MixedFeatures:
        if not (self.use_input_warping and self.num_continuous):
            return f
        u = jnp.clip(f.continuous, 1e-6, 1.0 - 1e-6)
        warped = 1.0 - (1.0 - u ** p["warp_a"]) ** p["warp_b"]
        return kernels.MixedFeatures(warped, f.categorical)

    def _kernel(
        self, p: Params, f1: kernels.MixedFeatures, f2: kernels.MixedFeatures, data: GPData
    ) -> Array:
        return self._kernel_to_rows(p, f1, self._kernel_rows(p, f2, data), data)

    def _kernel_rows(
        self, p: Params, f2: kernels.MixedFeatures, data: GPData
    ) -> kernels.ScaledRows:
        """The kernel's second argument as the kernel reads it (warped, over
        the length scales, masked dimensions zeroed): whoever holds ``f2``
        fixed over many ``f1`` prepares it once (``GPState.kernel_rows``)."""
        cont_ls = p.get("continuous_length_scales", jnp.ones((self.num_continuous,)))
        return kernels.scaled_rows(
            self._warp_features(p, f2), cont_ls, data.cont_dim_mask
        )

    def _kernel_to_rows(
        self, p: Params, f1: kernels.MixedFeatures, rows: kernels.ScaledRows, data: GPData
    ) -> Array:
        """``_kernel(p, f1, f2, data)`` for ``rows = _kernel_rows(p, f2, data)``."""
        cat_ls = p.get("categorical_length_scales", jnp.ones((self.num_categorical,)))
        return kernels.matern52_ard_to_rows(
            self._warp_features(p, f1),
            rows,
            amplitude=p["amplitude"],
            categorical_length_scales=cat_ls,
            categorical_dim_mask=data.cat_dim_mask,
        )

    # -- likelihood --------------------------------------------------------

    def _masked_gram(self, p: Params, data: GPData) -> Array:
        """K + (noise²+jitter)·I on valid rows; identity on padded rows."""
        k = self._kernel(p, data.features(), data.features(), data)
        m = data.row_mask
        pair = m[:, None] & m[None, :]
        k = jnp.where(pair, k, 0.0)  # also zeroes padded diagonal entries
        noise = p["noise_stddev"] * p["noise_stddev"] + _JITTER
        return k + jnp.diag(jnp.where(m, noise, 1.0))

    def neg_log_likelihood(self, unconstrained: Params, data: GPData) -> Array:
        """-log p(y | X, θ) + log-normal regularization (the ARD loss)."""
        coll = self.param_collection()
        bounded = coll.constrain(unconstrained)
        p = _conditioned(bounded)
        gram = self._masked_gram(p, data)
        chol = jnp.linalg.cholesky(gram)
        y = data.labels
        alpha = jax.scipy.linalg.cho_solve((chol, True), y)
        n_valid = jnp.sum(data.row_mask.astype(jnp.float32))
        # Padded rows: y = 0 and unit diag ⇒ zero contribution to each term.
        data_fit = 0.5 * jnp.dot(y, alpha)
        log_det = jnp.sum(
            jnp.where(data.row_mask, jnp.log(cholesky_diagonal(chol)), 0.0)
        )
        nll = data_fit + log_det + 0.5 * n_valid * _LOG_2PI
        # (The priors are on the parameters, not on the noise with its nugget.)
        loss = nll + coll.regularization(bounded)
        # Guard non-finite (Cholesky blow-ups under extreme params).
        return jnp.where(jnp.isfinite(loss), loss, jnp.asarray(1e10, loss.dtype))

    # -- predictive --------------------------------------------------------

    def precompute(self, unconstrained: Params, data: GPData) -> "GPState":
        return self.precompute_constrained(self.constrain(unconstrained), data)

    def precompute_constrained(self, p: Params, data: GPData) -> "GPState":
        """Precompute from already-constrained params (e.g. after a noise
        override for pure-exploration conditioning, gp_ucb_pe.py).

        Also forms L^-1 explicitly: the acquisition sweep calls predict()
        thousands of times per suggest, and a precomputed inverse turns each
        per-query triangular solve (sequential, slow on TPU) into a plain
        matmul that rides the MXU. One extra O(N^3) solve here is amortized
        over ~3000 sweep iterations.
        """
        gram = self._masked_gram(p, data)
        chol = jnp.linalg.cholesky(gram)
        alpha = jax.scipy.linalg.cho_solve((chol, True), data.labels)
        eye = jnp.eye(chol.shape[0], dtype=chol.dtype)
        linv = jax.scipy.linalg.solve_triangular(chol, eye, lower=True)
        return GPState(
            model=self, params=p, data=data, chol=chol, alpha=alpha, linv=linv
        )


@flax.struct.dataclass
class GPState:
    """Cholesky-precomputed posterior, ready for O(N·M) predictions."""

    model: VizierGaussianProcess = flax.struct.field(pytree_node=False)
    params: Params
    data: GPData
    chol: Array  # [N, N]
    alpha: Array  # [N]
    linv: Array  # [N, N] = chol^-1 (matmul-only predicts; MXU-friendly)

    def kernel_rows(self) -> kernels.ScaledRows:
        """The data side of :meth:`cross_covariance`, prepared once for many
        queries (a sweep's 1,500 candidate batches)."""
        return self.model._kernel_rows(self.params, self.data.features(), self.data)

    def cross_covariance(
        self,
        query: kernels.MixedFeatures,
        rows: Optional[kernels.ScaledRows] = None,
    ) -> Array:
        """k(query, X) over every padded row, [M, N], not yet masked.
        ``rows`` is :meth:`kernel_rows`, from a caller that kept it."""
        rows = self.kernel_rows() if rows is None else rows
        return self.model._kernel_to_rows(self.params, query, rows, self.data)

    def predict_from_cross(
        self, k_star: Array, *, include_noise: bool = False
    ) -> Tuple[Array, Array]:
        """Posterior mean and stddev ([M], [M]) from ``cross_covariance``.

        The kernel reads neither the noise nor the labels, so one
        cross-covariance serves every posterior over the same rows with the
        same kernel hyperparameters, and its leading columns a posterior over
        the leading rows (GP-UCB-PE's completed and all-points posteriors)."""
        p, data = self.params, self.data
        k_star = jnp.where(data.row_mask[None, :], k_star, 0.0)
        mean = jnp.matmul(k_star, self.alpha, precision=POSTERIOR_PRECISION)
        # [N, M] — pure matmul in the hot loop
        v = jnp.matmul(self.linv, k_star.T, precision=POSTERIOR_PRECISION)
        prior_var = p["amplitude"] * p["amplitude"]
        var = prior_var - jnp.sum(v * v, axis=0)
        if include_noise:
            var = var + p["noise_stddev"] * p["noise_stddev"]
        return mean, jnp.sqrt(jnp.maximum(var, 1e-12))

    def predict(
        self, query: kernels.MixedFeatures, *, include_noise: bool = False
    ) -> Tuple[Array, Array]:
        """Posterior mean and stddev at query points ([M], [M])."""
        return self.predict_from_cross(
            self.cross_covariance(query), include_noise=include_noise
        )

    def predict_joint(self, query: kernels.MixedFeatures) -> Tuple[Array, Array]:
        """Posterior mean [M] and full covariance [M, M] at query points.

        Needed by joint q-batch acquisitions: duplicated batch members are
        perfectly correlated, which marginal-only sampling cannot express.
        """
        model, p, data = self.model, self.params, self.data
        k_star = model._kernel(p, query, data.features(), data)  # [M, N]
        k_star = jnp.where(data.row_mask[None, :], k_star, 0.0)
        mean = jnp.matmul(k_star, self.alpha, precision=POSTERIOR_PRECISION)
        v = jnp.matmul(self.linv, k_star.T, precision=POSTERIOR_PRECISION)  # [N, M]
        k_qq = model._kernel(p, query, query, data)  # [M, M]
        cov = k_qq - jnp.matmul(v.T, v, precision=POSTERIOR_PRECISION)
        # Symmetrize + jitter for downstream Cholesky.
        cov = 0.5 * (cov + cov.T) + 1e-6 * jnp.eye(cov.shape[0], dtype=cov.dtype)
        return mean, cov

    def sample(
        self, query: kernels.MixedFeatures, rng: Array, num_samples: int
    ) -> Array:
        """Marginal posterior samples [num_samples, M] (diagonal covariance)."""
        mean, stddev = self.predict(query)
        eps = jax.random.normal(rng, (num_samples,) + mean.shape, dtype=mean.dtype)
        return mean[None, :] + stddev[None, :] * eps


@flax.struct.dataclass
class EnsemblePredictive:
    """Uniform mixture over a leading ensemble axis of GPStates.

    Parity with ``UniformEnsemblePredictive``
    (``stochastic_process_model.py:835``): predictions vmap over members and
    combine as a uniform Gaussian mixture (moment-matched).
    """

    states: GPState  # leading axis E on params/chol/alpha/data

    @property
    def ensemble_size(self) -> int:
        return self.states.alpha.shape[0]

    def predict(self, query: kernels.MixedFeatures) -> Tuple[Array, Array]:
        means, stddevs = jax.vmap(lambda s: s.predict(query))(self.states)
        mean = jnp.mean(means, axis=0)
        second = jnp.mean(stddevs**2 + means**2, axis=0)
        var = jnp.maximum(second - mean**2, 1e-12)
        return mean, jnp.sqrt(var)

    def predict_per_member(self, query: kernels.MixedFeatures) -> Tuple[Array, Array]:
        return jax.vmap(lambda s: s.predict(query))(self.states)
