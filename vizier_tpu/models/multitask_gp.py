"""Multi-task GP: a separable task kernel over correlated metrics.

Parity with
``/root/reference/vizier/_src/jax/models/multitask_tuned_gp_models.py``
(``MultiTaskType``: INDEPENDENT plus three SEPARABLE task-kernel priors,
``:41-60``): the covariance factorizes as
``K((x,i),(x',j)) = k_x(x,x') · B[i,j]`` and the joint Gram is the Kronecker
product ``B ⊗ K_x`` over flattened (task-major) observations, mask-safe the
same way as the single-task GP. INDEPENDENT multi-task is served by the
per-metric vmapped training in ``designers.gp_bandit``.

Task-covariance parameterizations (all SIGNED — off-diagonal Cholesky
entries can go negative, so anti-correlated objectives, the common case for
multi-objective trade-offs, are representable):

- ``SEPARABLE`` (= reference ``SEPARABLE_NORMAL_TASK_KERNEL_PRIOR``,
  ``:144-170``): free lower-triangular Cholesky; positive diagonal, signed
  off-diagonals with a Normal(0, 1) prior centered at the identity.
- ``SEPARABLE_LKJ`` (``:93-137``): correlation Cholesky via row
  normalization of signed entries (the ``CorrelationCholesky`` bijector's
  construction) scaled by a per-task sqrt-diagonal in (1e-6, 1); an
  LKJ(concentration=1) log-density on the correlation factor joins the
  regularizer.
- ``SEPARABLE_DIAG`` (``:77-92``): diagonal-only B (no cross-task
  coupling, but a learned per-task scale).
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from vizier_tpu.models import gp as gp_lib
from vizier_tpu.models import kernels
from vizier_tpu.models import params as params_lib

Array = jax.Array
_JITTER = 1e-5
_LOG_2PI = 1.8378770664093453


class MultiTaskType(enum.Enum):
    INDEPENDENT = "INDEPENDENT"
    # Normal-prior signed Cholesky (reference SEPARABLE_NORMAL_TASK_KERNEL_PRIOR).
    SEPARABLE = "SEPARABLE"
    SEPARABLE_NORMAL = "SEPARABLE"  # alias of SEPARABLE
    SEPARABLE_LKJ = "SEPARABLE_LKJ"
    SEPARABLE_DIAG = "SEPARABLE_DIAG"


def _corr_cholesky(vec: Array, m: int) -> Array:
    """Signed lower-tri entries → unit-diagonal correlation Cholesky.

    The ``CorrelationCholesky`` bijector's construction: fill the strict
    lower triangle, put 1 on the diagonal, L2-normalize each row. Rows of
    the result have unit norm, so ``LLᵀ`` is a correlation matrix.
    """
    l = jnp.eye(m, dtype=jnp.float32)
    if m > 1:
        rows, cols = jnp.tril_indices(m, k=-1)
        l = l.at[rows, cols].set(vec)
    return l / jnp.linalg.norm(l, axis=-1, keepdims=True)


@flax.struct.dataclass
class MultiTaskData:
    """Shared features, per-task labels [M, N] with a joint mask."""

    features_data: gp_lib.GPData  # labels field unused; masks/features shared
    task_labels: Array  # [M, N]
    task_mask: Array  # [M, N] bool (valid observation of task m at row n)

    @classmethod
    def from_gp_datas(cls, datas: Tuple[gp_lib.GPData, ...]) -> "MultiTaskData":
        labels = jnp.stack([d.labels for d in datas])
        masks = jnp.stack([d.row_mask for d in datas])
        return cls(features_data=datas[0], task_labels=labels, task_mask=masks)


@dataclasses.dataclass(frozen=True)
class MultiTaskGaussianProcess:
    """Separable multi-task GP over ``num_tasks`` correlated metrics."""

    num_continuous: int
    num_categorical: int
    num_tasks: int
    multitask_type: MultiTaskType = MultiTaskType.SEPARABLE

    def __post_init__(self):
        if self.multitask_type is MultiTaskType.INDEPENDENT:
            raise ValueError(
                "INDEPENDENT multi-task is the per-metric vmapped path in "
                "designers.gp_bandit; MultiTaskGaussianProcess models the "
                "SEPARABLE* variants."
            )

    def _base(self) -> gp_lib.VizierGaussianProcess:
        return gp_lib.VizierGaussianProcess(
            num_continuous=self.num_continuous, num_categorical=self.num_categorical
        )

    def param_collection(self) -> params_lib.ParameterCollection:
        specs = list(self._base().param_collection().specs)
        m = self.num_tasks
        ntril = m * (m - 1) // 2
        t = self.multitask_type
        if t is MultiTaskType.SEPARABLE_DIAG:
            # Diagonal-only B: per-task sqrt-scale in (1e-6, 1), uniform
            # init and a Uniform prior — zero penalty (reference
            # correlation_diag, Sigmoid-constrained, Uniform prior).
            specs.append(
                params_lib.ParameterSpec(
                    "task_sqrt_diag", (m,),
                    params_lib.SoftClip(1e-6, 1.0, log_space=False),
                    0.3, 0.95, linear=True, regularize=False,
                )
            )
        elif t is MultiTaskType.SEPARABLE_LKJ:
            # Correlation Cholesky from SIGNED entries (row-normalized) x a
            # per-task sqrt-diagonal. The ONLY prior on the correlation
            # entries is the LKJ density in _extra_regularization, and the
            # sqrt-diagonal's reference prior is Uniform (zero penalty) —
            # per-spec Gaussian penalties are disabled so task coupling is
            # not shrunk beyond the reference's priors
            # (multitask_tuned_gp_models.py:100-127).
            if m > 1:
                specs.append(
                    params_lib.ParameterSpec(
                        "task_corr_chol_vec", (ntril,),
                        params_lib.SoftClip(-5.0, 5.0, log_space=False),
                        -0.5, 0.5, linear=True, regularize=False,
                    )
                )
            specs.append(
                params_lib.ParameterSpec(
                    "task_sqrt_diag", (m,),
                    params_lib.SoftClip(1e-6, 1.0, log_space=False),
                    0.3, 0.95, linear=True, regularize=False,
                )
            )
        else:  # SEPARABLE (normal prior on Cholesky entries)
            # Positive diagonal with a log-normal prior at 1 (the reference
            # centers the Cholesky prior at the identity).
            specs.append(
                params_lib.ParameterSpec(
                    "task_chol_diag", (m,), params_lib.SoftClip(0.05, 5.0),
                    0.3, 2.0,
                )
            )
            if m > 1:
                # SIGNED off-diagonals with a Normal(0, 1) prior: negative
                # task correlations (anti-correlated objectives — the common
                # multi-objective trade-off case) are representable, matching
                # the reference's signed Normal prior
                # (multitask_tuned_gp_models.py:144-151).
                specs.append(
                    params_lib.ParameterSpec(
                        "task_chol_offdiag", (ntril,),
                        params_lib.SoftClip(-5.0, 5.0, log_space=False),
                        -0.5, 0.5, prior_mu=0.0, prior_sigma=1.0, linear=True,
                    )
                )
        return params_lib.ParameterCollection(tuple(specs))

    def _task_cholesky(self, p: params_lib.Params) -> Array:
        """Lower-triangular factor L with B = LLᵀ (+ jitter)."""
        m = self.num_tasks
        t = self.multitask_type
        if t is MultiTaskType.SEPARABLE_DIAG:
            return jnp.diag(p["task_sqrt_diag"])
        if t is MultiTaskType.SEPARABLE_LKJ:
            vec = p.get("task_corr_chol_vec", jnp.zeros((0,), jnp.float32))
            corr = _corr_cholesky(vec, m)
            return corr * p["task_sqrt_diag"][:, None]
        chol = jnp.diag(p["task_chol_diag"])
        if m > 1:
            rows, cols = jnp.tril_indices(m, k=-1)
            chol = chol.at[rows, cols].set(p["task_chol_offdiag"])
        return chol

    def _task_cov(self, p: params_lib.Params) -> Array:
        chol = self._task_cholesky(p)
        return chol @ chol.T + 1e-6 * jnp.eye(self.num_tasks)

    def _extra_regularization(self, p: params_lib.Params) -> Array:
        """Model-level prior terms beyond the per-spec regularizers.

        LKJ(concentration=1) Cholesky log-density on the correlation factor:
        -log p(L) = -Σ_i (m - i - 1)·log L_ii (0-indexed diagonal).
        """
        if self.multitask_type is MultiTaskType.SEPARABLE_LKJ and self.num_tasks > 1:
            vec = p.get("task_corr_chol_vec", jnp.zeros((0,), jnp.float32))
            corr = _corr_cholesky(vec, self.num_tasks)
            i = jnp.arange(self.num_tasks, dtype=jnp.float32)
            exponents = self.num_tasks - i - 1.0
            return -jnp.sum(
                exponents * jnp.log(jnp.diagonal(corr) + 1e-12)
            )
        return jnp.asarray(0.0, jnp.float32)

    def _joint_gram(self, p: params_lib.Params, data: MultiTaskData) -> Array:
        base = self._base()
        fd = data.features_data
        kx = base._kernel(p, fd.features(), fd.features(), fd)  # [N, N]
        b = self._task_cov(p)  # [M, M]
        gram = jnp.kron(b, kx)  # [MN, MN], task-major
        mask = data.task_mask.reshape(-1)  # [MN]
        pair = mask[:, None] & mask[None, :]
        gram = jnp.where(pair, gram, 0.0)
        noise = p["noise_stddev"] * p["noise_stddev"] + _JITTER
        return gram + jnp.diag(jnp.where(mask, noise, 1.0))

    def neg_log_likelihood(
        self, unconstrained: params_lib.Params, data: MultiTaskData
    ) -> Array:
        coll = self.param_collection()
        p = coll.constrain(unconstrained)
        gram = self._joint_gram(p, data)
        y = jnp.where(data.task_mask, data.task_labels, 0.0).reshape(-1)
        chol = jnp.linalg.cholesky(gram)
        alpha = jax.scipy.linalg.cho_solve((chol, True), y)
        mask = data.task_mask.reshape(-1)
        n_valid = jnp.sum(mask.astype(jnp.float32))
        nll = (
            0.5 * jnp.dot(y, alpha)
            + jnp.sum(
                jnp.where(mask, jnp.log(gp_lib.cholesky_diagonal(chol)), 0.0)
            )
            + 0.5 * n_valid * _LOG_2PI
        )
        loss = nll + coll.regularization(p) + self._extra_regularization(p)
        return jnp.where(jnp.isfinite(loss), loss, jnp.asarray(1e10, loss.dtype))

    def precompute(
        self, unconstrained: params_lib.Params, data: MultiTaskData
    ) -> "MultiTaskGPState":
        return self.precompute_constrained(
            self.param_collection().constrain(unconstrained), data
        )

    def precompute_constrained(
        self, p: params_lib.Params, data: MultiTaskData
    ) -> "MultiTaskGPState":
        gram = self._joint_gram(p, data)
        y = jnp.where(data.task_mask, data.task_labels, 0.0).reshape(-1)
        chol = jnp.linalg.cholesky(gram)
        alpha = jax.scipy.linalg.cho_solve((chol, True), y)
        return MultiTaskGPState(
            model=self, params=p, data=data, chol=chol, alpha=alpha
        )


@flax.struct.dataclass
class MultiTaskGPState:
    model: MultiTaskGaussianProcess = flax.struct.field(pytree_node=False)
    params: params_lib.Params
    data: MultiTaskData
    chol: Array  # [MN, MN]
    alpha: Array  # [MN]

    def predict(self, query: kernels.MixedFeatures) -> Tuple[Array, Array]:
        """Posterior per task: mean [M, Q], stddev [M, Q]."""
        model, p, data = self.model, self.params, self.data
        base = model._base()
        fd = data.features_data
        kx_star = base._kernel(p, query, fd.features(), fd)  # [Q, N]
        b = model._task_cov(p)  # [M, M]
        # Cross-covariance of task m at query q with all (task, row) obs:
        # kron(b[m], kx_star[q]) → build [M, Q, M*N].
        k_star = jnp.einsum("mt,qn->mqtn", b, kx_star).reshape(
            model.num_tasks, query.continuous.shape[0], -1
        )
        mask = data.task_mask.reshape(-1)
        k_star = jnp.where(mask[None, None, :], k_star, 0.0)
        mean = k_star @ self.alpha  # [M, Q]
        flat = k_star.reshape(-1, k_star.shape[-1])  # [MQ, MN]
        v = jax.scipy.linalg.solve_triangular(self.chol, flat.T, lower=True)
        prior_var = (
            p["amplitude"] * p["amplitude"] * jnp.diag(b)[:, None]
        )  # [M, 1]
        var = prior_var - jnp.sum(v * v, axis=0).reshape(mean.shape)
        return mean, jnp.sqrt(jnp.maximum(var, 1e-12))
