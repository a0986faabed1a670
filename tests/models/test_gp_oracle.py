"""GP numerics vs an independent float64 numpy oracle.

Reference test strategy analog: ``stochastic_process_model_test.py`` checks
the GP stack against closed-form expectations. Here a from-scratch float64
numpy GP (same Matern-5/2 ARD + categorical index distance + noise/jitter
semantics) is the oracle; the f32 TPU-path implementation must agree to
f32 tolerance on mean, stddev, joint covariance, and the log-likelihood —
with and without padded rows, which must be exactly invisible.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vizier_tpu import types
from vizier_tpu.models import gp as gp_lib
from vizier_tpu.models import kernels

_SQRT5 = np.sqrt(5.0)
_JITTER = 1e-5
_LOG_2PI = float(np.log(2.0 * np.pi))


def _oracle_kernel(x1, z1, x2, z2, amp, cont_ls, cat_ls):
    """float64 ARD Matern-5/2 over mixed features (index mismatch distance)."""
    sq = np.zeros((x1.shape[0], x2.shape[0]))
    if x1.shape[1]:
        diff = (x1[:, None, :] - x2[None, :, :]) / cont_ls[None, None, :]
        sq = sq + np.sum(diff * diff, axis=-1)
    if z1.shape[1]:
        mism = (z1[:, None, :] != z2[None, :, :]).astype(float)
        sq = sq + np.sum(mism / (cat_ls[None, None, :] ** 2), axis=-1)
    r = np.sqrt(np.maximum(sq, 1e-20))
    return amp**2 * (1.0 + _SQRT5 * r + (5.0 / 3.0) * sq) * np.exp(-_SQRT5 * r)


class _Oracle:
    """Exact float64 GP posterior + marginal likelihood."""

    def __init__(self, x, z, y, amp, noise, cont_ls, cat_ls):
        self.x, self.z = x, z
        self.amp, self.cont_ls, self.cat_ls = amp, cont_ls, cat_ls
        k = _oracle_kernel(x, z, x, z, amp, cont_ls, cat_ls)
        self.gram = k + (noise**2 + _JITTER) * np.eye(len(x))
        self.alpha = np.linalg.solve(self.gram, y)
        self.y = y

    def predict(self, qx, qz):
        ks = _oracle_kernel(qx, qz, self.x, self.z, self.amp, self.cont_ls, self.cat_ls)
        mean = ks @ self.alpha
        kqq = _oracle_kernel(qx, qz, qx, qz, self.amp, self.cont_ls, self.cat_ls)
        cov = kqq - ks @ np.linalg.solve(self.gram, ks.T)
        return mean, cov

    def nll(self):
        sign, logdet = np.linalg.slogdet(self.gram)
        assert sign > 0
        return 0.5 * (
            self.y @ self.alpha + logdet + len(self.y) * _LOG_2PI
        )


def _make_data(x, z, y, n_pad):
    features = types.ContinuousAndCategorical(
        continuous=types.PaddedArray.from_array(
            x.astype(np.float32), (n_pad, x.shape[1])
        ),
        categorical=types.PaddedArray.from_array(
            z.astype(np.int32), (n_pad, z.shape[1]), fill_value=0
        ),
    )
    labels = types.PaddedArray.from_array(
        y[:, None].astype(np.float32), (n_pad, 1), fill_value=np.nan
    )
    return gp_lib.GPData.from_model_data(types.ModelData(features, labels))


def _constrained_params(model, amp, noise, cont_ls, cat_ls):
    p = {"amplitude": jnp.asarray(amp, jnp.float32),
         "noise_stddev": jnp.asarray(noise, jnp.float32)}
    if model.num_continuous:
        p["continuous_length_scales"] = jnp.asarray(cont_ls, jnp.float32)
    if model.num_categorical:
        p["categorical_length_scales"] = jnp.asarray(cat_ls, jnp.float32)
    return p


@pytest.fixture(params=[(6, 3, 0, 8), (7, 2, 2, 8), (5, 0, 3, 16)])
def case(request):
    n, dc, ds, n_pad = request.param
    rng = np.random.default_rng(n * 100 + dc * 10 + ds)
    x = rng.uniform(size=(n, dc))
    z = rng.integers(0, 3, size=(n, ds))
    y = rng.normal(size=n)
    amp, noise = 1.3, 0.1
    cont_ls = rng.uniform(0.3, 1.5, size=dc)
    cat_ls = rng.uniform(0.5, 2.0, size=ds)
    oracle = _Oracle(x, z, y, amp, noise, cont_ls, cat_ls)
    model = gp_lib.VizierGaussianProcess(num_continuous=dc, num_categorical=ds)
    data = _make_data(x, z, y, n_pad)
    params = _constrained_params(model, amp, noise, cont_ls, cat_ls)
    state = model.precompute_constrained(params, data)
    qx = rng.uniform(size=(9, dc))
    qz = rng.integers(0, 3, size=(9, ds))
    query = kernels.MixedFeatures(
        jnp.asarray(qx, jnp.float32), jnp.asarray(qz, jnp.int32)
    )
    return oracle, model, params, data, state, qx, qz, query


class TestPosteriorVsOracle:
    def test_mean_and_stddev(self, case):
        oracle, _, _, _, state, qx, qz, query = case
        mean, stddev = state.predict(query)
        o_mean, o_cov = oracle.predict(qx, qz)
        np.testing.assert_allclose(np.asarray(mean), o_mean, atol=2e-3)
        np.testing.assert_allclose(
            np.asarray(stddev), np.sqrt(np.maximum(np.diag(o_cov), 1e-12)),
            atol=2e-3,
        )

    def test_joint_covariance(self, case):
        oracle, _, _, _, state, qx, qz, query = case
        mean, cov = state.predict_joint(query)
        o_mean, o_cov = oracle.predict(qx, qz)
        np.testing.assert_allclose(np.asarray(mean), o_mean, atol=2e-3)
        # The implementation adds 1e-6 jitter on the diagonal.
        np.testing.assert_allclose(
            np.asarray(cov), o_cov + 1e-6 * np.eye(len(qx)), atol=5e-3
        )
        eigs = np.linalg.eigvalsh(np.asarray(cov))
        assert eigs.min() > -1e-5

    def test_nll_matches_oracle_plus_regularizer(self, case):
        oracle, model, params, data, _, _, _, _ = case
        coll = model.param_collection()
        unconstrained = coll.unconstrain(params)
        loss = float(model.neg_log_likelihood(unconstrained, data))
        # The ARD loss = exact NLL + log-normal regularization; recover the
        # regularizer from the roundtripped constrained params.
        reg = float(coll.regularization(coll.constrain(unconstrained)))
        # The likelihood is the Gram's as the model builds it: the noise
        # with its nugget (gp.NUGGET_TO_AMPLITUDE).
        built = model.constrain(unconstrained)
        with_nugget = _Oracle(
            oracle.x, oracle.z, oracle.y, oracle.amp, float(built["noise_stddev"]),
            oracle.cont_ls, oracle.cat_ls,
        )
        assert float(built["noise_stddev"]) > float(params["noise_stddev"])
        assert loss - reg == pytest.approx(with_nugget.nll(), abs=5e-2)

    def test_padding_rows_are_invisible(self, case):
        oracle, model, params, _, _, qx, qz, query = case
        # Same data at two padded capacities must give identical posteriors.
        n = len(oracle.y)
        data_a = _make_data(oracle.x, oracle.z, oracle.y, n_pad=n)
        data_b = _make_data(oracle.x, oracle.z, oracle.y, n_pad=4 * n)
        sa = model.precompute_constrained(params, data_a)
        sb = model.precompute_constrained(params, data_b)
        ma, va = sa.predict(query)
        mb, vb = sb.predict(query)
        # f32 reduction order differs with the padded Gram size; a mask
        # leak would show up at ~1e-1, not 1e-4.
        np.testing.assert_allclose(np.asarray(ma), np.asarray(mb), atol=1e-4)
        np.testing.assert_allclose(np.asarray(va), np.asarray(vb), atol=1e-4)

    def test_include_noise_adds_noise_variance(self, case):
        _, _, params, _, state, _, _, query = case
        _, s_noiseless = state.predict(query)
        _, s_noisy = state.predict(query, include_noise=True)
        noise_sq = float(params["noise_stddev"]) ** 2
        np.testing.assert_allclose(
            np.asarray(s_noisy) ** 2 - np.asarray(s_noiseless) ** 2,
            np.full(s_noisy.shape, noise_sq),
            atol=1e-4,
        )


class TestKernelProperties:
    def test_gram_is_psd_under_random_params(self):
        rng = np.random.default_rng(0)
        for trial in range(5):
            n, dc, ds = 12, 3, 2
            x = rng.uniform(size=(n, dc)).astype(np.float32)
            z = rng.integers(0, 4, size=(n, ds)).astype(np.int32)
            k = kernels.matern52_ard(
                kernels.MixedFeatures(jnp.asarray(x), jnp.asarray(z)),
                kernels.MixedFeatures(jnp.asarray(x), jnp.asarray(z)),
                amplitude=jnp.asarray(float(rng.uniform(0.1, 3.0))),
                continuous_length_scales=jnp.asarray(
                    rng.uniform(0.1, 2.0, size=dc), jnp.float32
                ),
                categorical_length_scales=jnp.asarray(
                    rng.uniform(0.3, 3.0, size=ds), jnp.float32
                ),
            )
            eigs = np.linalg.eigvalsh(np.asarray(k, np.float64))
            assert eigs.min() > -1e-4, eigs.min()

    def test_kernel_diagonal_is_amplitude_squared(self):
        x = jnp.asarray(np.random.default_rng(1).uniform(size=(5, 3)), jnp.float32)
        f = kernels.MixedFeatures(x, jnp.zeros((5, 0), jnp.int32))
        k = kernels.matern52_ard(
            f, f,
            amplitude=jnp.asarray(2.0),
            continuous_length_scales=jnp.ones((3,)),
            categorical_length_scales=jnp.ones((0,)),
        )
        np.testing.assert_allclose(np.diag(np.asarray(k)), 4.0, atol=1e-4)

    def test_ard_relevance_recovery(self):
        """ARD training shrinks the length scale of the active dim only."""
        from vizier_tpu.designers.gp_bandit import _train_gp
        from vizier_tpu.optimizers import lbfgs as lbfgs_lib

        rng = np.random.default_rng(7)
        n, dc = 48, 3
        x = rng.uniform(size=(n, dc))
        y = np.sin(7.0 * x[:, 0])  # only dim 0 matters
        y = (y - y.mean()) / y.std()
        model = gp_lib.VizierGaussianProcess(num_continuous=dc, num_categorical=0)
        data = _make_data(x, np.zeros((n, 0), np.int64), y, n_pad=64)
        states, _ = _train_gp(
            model, lbfgs_lib.LbfgsOptimizer(maxiter=60), data,
            jax.random.PRNGKey(0), num_restarts=4, ensemble_size=1,
        )
        ls = np.asarray(states.params["continuous_length_scales"])[0]
        # The active dim needs a materially shorter length scale than the
        # two inert dims.
        assert ls[0] < 0.6 * ls[1] and ls[0] < 0.6 * ls[2], ls
