"""The NLLs read diag(L) as a masked row-sum and lose nothing by it.

``models.gp.cholesky_diagonal`` replaced ``jnp.diagonal`` in the three
likelihoods that take the log-determinant from a Cholesky factor, for the
layout the TPU gives the ``vmap``-ped gather (PERF.md, PR 34). The
mathematics and the precision are the same, so the loss and its gradient
over a batch of parameter rows — how every ARD train evaluates them — are
held here to the same likelihood with ``jnp.diagonal`` in the helper's
place: the program as it was.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vizier_tpu.models import gp as gp_lib
from vizier_tpu.models import multitask_gp
from vizier_tpu.surrogates import sparse_gp


def _data(n_valid: int, n_pad: int, d: int, seed: int) -> gp_lib.GPData:
    """``n_valid`` rows of a smooth function, padded with masked rows."""
    rng = np.random.default_rng(seed)
    cont = np.zeros((n_pad, d), np.float32)
    cont[:n_valid] = rng.uniform(size=(n_valid, d))
    labels = np.zeros(n_pad, np.float32)
    raw = np.sin(3.0 * cont[:n_valid, 0]) + cont[:n_valid, 1:].sum(axis=1)
    labels[:n_valid] = (raw - raw.mean()) / raw.std()
    return gp_lib.GPData(
        continuous=jnp.asarray(cont),
        categorical=jnp.zeros((n_pad, 0), jnp.int32),
        labels=jnp.asarray(labels),
        row_mask=jnp.arange(n_pad) < n_valid,
        cont_dim_mask=jnp.ones((d,), bool),
        cat_dim_mask=jnp.ones((0,), bool),
    )


def _exact():
    # The warm train of ``default20d.lone25``: 20-D, pad 512, 385 trials.
    model = gp_lib.VizierGaussianProcess(num_continuous=20, num_categorical=0)
    return model, _data(385, 512, 20, seed=1)


def _multitask():
    d = 3
    model = multitask_gp.MultiTaskGaussianProcess(
        num_continuous=d, num_categorical=0, num_tasks=2
    )
    first = _data(40, 64, d, seed=2)
    # The second task has seen fewer rows: the joint mask is ragged.
    second = first.replace(
        labels=jnp.where(jnp.arange(64) < 31, -first.labels, 0.0),
        row_mask=jnp.arange(64) < 31,
    )
    return model, multitask_gp.MultiTaskData.from_gp_datas((first, second))


def _sparse():
    d = 4
    base = gp_lib.VizierGaussianProcess(num_continuous=d, num_categorical=0)
    model = sparse_gp.SparseGaussianProcess(base=base, num_inducing=64)
    data = _data(200, 256, d, seed=3)
    # 48 real inducing points in 64 slots: padded slots are masked too.
    sdata = sparse_gp.select_inducing_kcenter(data, 64)
    return model, sdata.replace(inducing_mask=jnp.arange(64) < 48)


@pytest.mark.parametrize(
    "build, grad_tol",
    [(_exact, 0.0), (_multitask, 0.0), (_sparse, 1e-6)],
    ids=["exact", "multitask", "sparse"],
)
def test_masked_row_sum_reads_what_diagonal_read(monkeypatch, build, grad_tol):
    model, data = build()
    rows = model.param_collection().batch_random_init_unconstrained(
        jax.random.PRNGKey(7), 3
    )

    def evaluate():
        return jax.jit(
            jax.vmap(
                jax.value_and_grad(lambda p: model.neg_log_likelihood(p, data))
            )
        )(rows)

    value, grad = evaluate()
    monkeypatch.setattr(
        gp_lib,
        "cholesky_diagonal",
        lambda chol: jnp.diagonal(chol, axis1=-2, axis2=-1),
    )
    want_value, want_grad = evaluate()

    assert np.all(np.isfinite(value)) and np.all(np.asarray(value) < 1e9)
    np.testing.assert_array_equal(np.asarray(value), np.asarray(want_value))
    assert grad.keys() == want_grad.keys()
    for name, leaf in grad.items():
        assert np.any(np.asarray(leaf) != 0.0), name
        # Relative to the leaf's largest entry; 0 is bit for bit.
        scale = np.max(np.abs(np.asarray(want_grad[name])))
        np.testing.assert_allclose(
            np.asarray(leaf),
            np.asarray(want_grad[name]),
            rtol=0.0,
            atol=grad_tol * scale,
            err_msg=name,
        )
