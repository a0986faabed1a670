"""Tests for kernels, GP likelihood/predictive, masking, and warpers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vizier_tpu import types
from vizier_tpu.models import gp as gp_lib
from vizier_tpu.models import kernels
from vizier_tpu.models import output_warpers
from vizier_tpu.models import params as params_lib
from vizier_tpu.optimizers import lbfgs as lbfgs_lib


def _feats(cont, cat=None):
    cont = jnp.asarray(cont, jnp.float32)
    if cat is None:
        cat = jnp.zeros((cont.shape[0], 0), jnp.int32)
    return kernels.MixedFeatures(cont, jnp.asarray(cat, jnp.int32))


class TestKernels:
    def test_matern52_at_zero(self):
        assert float(kernels.matern52(jnp.asarray(0.0))) == pytest.approx(1.0)

    def test_ard_diagonal_is_amplitude_sq(self):
        f = _feats(np.random.default_rng(0).uniform(size=(5, 3)))
        k = kernels.matern52_ard(
            f, f,
            amplitude=jnp.asarray(2.0),
            continuous_length_scales=jnp.ones(3),
            categorical_length_scales=jnp.ones(0),
        )
        np.testing.assert_allclose(np.diag(k), 4.0, rtol=1e-5)
        np.testing.assert_allclose(k, k.T, rtol=1e-5)

    def test_categorical_mismatch_reduces_kernel(self):
        f1 = _feats(np.zeros((1, 1)), np.array([[0]]))
        f2 = _feats(np.zeros((1, 1)), np.array([[1]]))
        kw = dict(
            amplitude=jnp.asarray(1.0),
            continuous_length_scales=jnp.ones(1),
            categorical_length_scales=jnp.ones(1),
        )
        same = kernels.matern52_ard(f1, f1, **kw)[0, 0]
        diff = kernels.matern52_ard(f1, f2, **kw)[0, 0]
        assert float(same) == pytest.approx(1.0)
        assert float(diff) < float(same)

    def test_dim_mask_ignores_padded_dims(self):
        rng = np.random.default_rng(1)
        base = rng.uniform(size=(4, 2)).astype(np.float32)
        junk = rng.uniform(size=(4, 1)).astype(np.float32)
        padded = np.concatenate([base, junk], axis=1)
        kw = dict(amplitude=jnp.asarray(1.0), categorical_length_scales=jnp.ones(0))
        k_base = kernels.matern52_ard(
            _feats(base), _feats(base),
            continuous_length_scales=jnp.ones(2), **kw,
        )
        k_masked = kernels.matern52_ard(
            _feats(padded), _feats(padded),
            continuous_length_scales=jnp.ones(3),
            continuous_dim_mask=jnp.asarray([True, True, False]),
            **kw,
        )
        np.testing.assert_allclose(k_base, k_masked, rtol=1e-5)


def _make_data(n, n_pad, seed=0, dc=2):
    rng = np.random.default_rng(seed)
    x = rng.uniform(size=(n, dc)).astype(np.float32)
    y = np.sin(3 * x[:, 0]) + 0.1 * rng.normal(size=n)
    features = types.ContinuousAndCategorical(
        continuous=types.PaddedArray.from_array(x, (n_pad, dc)),
        categorical=types.PaddedArray.from_array(
            np.zeros((n, 0), np.int32), (n_pad, 0), fill_value=0
        ),
    )
    labels = types.PaddedArray.from_array(
        y[:, None].astype(np.float32), (n_pad, 1), fill_value=np.nan
    )
    return gp_lib.GPData.from_model_data(types.ModelData(features, labels))


class TestGPMasking:
    def test_padding_invariance_of_loss(self):
        """The load-bearing property: padding must not change the likelihood."""
        model = gp_lib.VizierGaussianProcess(num_continuous=2, num_categorical=0)
        coll = model.param_collection()
        params = coll.random_init_unconstrained(jax.random.PRNGKey(0))
        tight = _make_data(10, 10)
        padded = _make_data(10, 32)
        l1 = float(model.neg_log_likelihood(params, tight))
        l2 = float(model.neg_log_likelihood(params, padded))
        assert l1 == pytest.approx(l2, rel=1e-4)

    def test_padding_invariance_of_predictions(self):
        model = gp_lib.VizierGaussianProcess(num_continuous=2, num_categorical=0)
        coll = model.param_collection()
        params = coll.random_init_unconstrained(jax.random.PRNGKey(1))
        query = _feats(np.array([[0.2, 0.8], [0.5, 0.5]], np.float32))
        m1, s1 = model.precompute(params, _make_data(10, 10)).predict(query)
        m2, s2 = model.precompute(params, _make_data(10, 64)).predict(query)
        np.testing.assert_allclose(m1, m2, rtol=1e-3, atol=1e-5)
        np.testing.assert_allclose(s1, s2, rtol=1e-3, atol=1e-5)

    def test_interpolation_at_observed_points(self):
        model = gp_lib.VizierGaussianProcess(num_continuous=2, num_categorical=0)
        data = _make_data(12, 16, seed=3)
        coll = model.param_collection()
        # Small noise setting → near-interpolation.
        constrained = {
            "amplitude": jnp.asarray(1.0),
            "noise_stddev": jnp.asarray(1e-3),
            "continuous_length_scales": jnp.full((2,), 0.3),
        }
        params = coll.unconstrain(constrained)
        state = model.precompute(params, data)
        query = kernels.MixedFeatures(data.continuous[:12], data.categorical[:12])
        mean, stddev = state.predict(query)
        np.testing.assert_allclose(mean, data.labels[:12], atol=0.05)
        assert np.all(np.asarray(stddev) < 0.1)

    def test_uncertainty_grows_away_from_data(self):
        model = gp_lib.VizierGaussianProcess(num_continuous=2, num_categorical=0)
        data = _make_data(10, 16)
        params = model.param_collection().unconstrain(
            {
                "amplitude": jnp.asarray(1.0),
                "noise_stddev": jnp.asarray(0.01),
                "continuous_length_scales": jnp.full((2,), 0.1),
            }
        )
        state = model.precompute(params, data)
        near = kernels.MixedFeatures(data.continuous[:1], data.categorical[:1])
        far = _feats(np.full((1, 2), 5.0, np.float32))
        _, s_near = state.predict(near)
        _, s_far = state.predict(far)
        assert float(s_far[0]) > float(s_near[0])


class TestTraining:
    def test_lbfgs_improves_loss(self):
        model = gp_lib.VizierGaussianProcess(num_continuous=2, num_categorical=0)
        data = _make_data(16, 16)
        coll = model.param_collection()
        inits = coll.batch_random_init_unconstrained(jax.random.PRNGKey(0), 4)
        loss_fn = lambda p: model.neg_log_likelihood(p, data)
        init_losses = jax.vmap(loss_fn)(inits)
        result = lbfgs_lib.LbfgsOptimizer(maxiter=30)(loss_fn, inits)
        assert float(result.best_loss) < float(jnp.min(init_losses))

    def test_lbfgs_rosenbrock_not_stopped_prematurely(self):
        """ftol early stop must not quit inside Rosenbrock's flat valley."""
        from vizier_tpu.optimizers.lbfgs import lbfgs_minimize

        def rosen(v):
            return (1.0 - v[0]) ** 2 + 100.0 * (v[1] - v[0] ** 2) ** 2

        x, f = lbfgs_minimize(rosen, jnp.asarray([-1.2, 1.0]), maxiter=200)
        assert float(f) < 1e-5
        np.testing.assert_allclose(np.asarray(x), [1.0, 1.0], atol=1e-2)

    def test_lbfgs_ill_scaled_quadratic(self):
        """Step-size carryover must still converge when the curvature forces
        tiny steps early (condition number 1e4) and full steps later."""
        from vizier_tpu.optimizers.lbfgs import lbfgs_minimize

        scales = jnp.asarray([1.0, 1e2, 1e4])

        def quad(v):
            return jnp.sum(scales * v**2)

        x, f = lbfgs_minimize(quad, jnp.asarray([3.0, 2.0, 1.0]), maxiter=100)
        assert float(f) < 1e-6

    def test_lbfgs_condition_1e7_quadratic(self):
        """Regression: the line-search warm start + ftol stop must not stall
        a condition-1e7 quadratic far from its optimum (a capped-step
        cascade once did, stopping at f=100 from f0=1e2^2*1e-2)."""
        from vizier_tpu.optimizers.lbfgs import lbfgs_minimize

        scales = jnp.asarray([1e-2, 1e5])

        def quad(v):
            return jnp.sum(scales * v**2)

        x, f = lbfgs_minimize(quad, jnp.asarray([100.0, 1.0]), maxiter=300)
        assert float(f) < 1e-6, float(f)

    def test_lbfgs_does_not_jump_onto_a_flat_part_below_the_start(self):
        """A steep start (|g| ~ 1e3) beside a soft-clipped bound: the first
        step x - g lands where the loss is flat and lower than the start,
        Armijo accepts it and the run ends there "converged" (an ARD train
        on near noise-free labels did, PERF.md PR 29). Held to MAX_STEP a
        step, it walks down to the minimum at 3."""
        from vizier_tpu.optimizers.lbfgs import lbfgs_minimize

        def loss(v):
            bowl = 500.0 * (v[0] - 3.0) ** 2  # minimum 0 at 3; gradient -3000 at 0
            shelf = 4000.0 * jax.nn.sigmoid(-(v[0] - 50.0))  # flat 0 beyond ~60, where the bowl is cut off
            return jnp.minimum(bowl, 1000.0 + shelf)

        x, f = lbfgs_minimize(loss, jnp.asarray([0.0]), maxiter=50)
        assert abs(float(x[0]) - 3.0) < 1e-2 and float(f) < 1e-3, (x, f)

    def test_the_gram_carries_its_nugget_whatever_the_noise_parameter(self):
        """noise_stddev as the model builds and reports it is never under
        NUGGET_TO_AMPLITUDE of the amplitude, the condition number of a
        clustered noise-free Gram stays under n / nugget^2, and the noise
        parameter keeps a gradient under the nugget (no dead zone)."""
        model = gp_lib.VizierGaussianProcess(num_continuous=2, num_categorical=0)
        coll = model.param_collection()
        u = coll.random_init_unconstrained(jax.random.PRNGKey(1))
        u = {**u, "noise_stddev": jnp.asarray(-30.0)}  # the parameter on its lower bound
        bounded, p = coll.constrain(u), model.constrain(u)
        assert float(bounded["noise_stddev"]) < 2e-3
        ratio = float(p["noise_stddev"] / p["amplitude"])
        assert ratio == pytest.approx(gp_lib.NUGGET_TO_AMPLITUDE, rel=2e-2) and ratio >= gp_lib.NUGGET_TO_AMPLITUDE
        rng = np.random.default_rng(0)
        x = np.clip(0.5 + rng.normal(size=(64, 2)) * np.geomspace(0.2, 1e-5, 64)[:, None], 0, 1)
        data = gp_lib.GPData(
            continuous=jnp.asarray(x, jnp.float32), categorical=jnp.zeros((64, 0), jnp.int32),
            labels=jnp.asarray(-np.sum((x - 0.5) ** 2, -1), jnp.float32), row_mask=jnp.ones((64,), bool),
            cont_dim_mask=jnp.ones((2,), bool), cat_dim_mask=jnp.zeros((0,), bool))
        eig = np.linalg.eigvalsh(np.asarray(model._masked_gram(p, data), np.float64))
        assert eig[-1] / eig[0] <= 64 / gp_lib.NUGGET_TO_AMPLITUDE**2 * 1.01
        assert np.isfinite(np.asarray(model.precompute(u, data).chol)).all()
        u_free = {**u, "noise_stddev": jnp.asarray(-1.0)}
        grad = jax.grad(lambda v: model.neg_log_likelihood(v, data))(u_free)
        assert abs(float(grad["noise_stddev"])) > 0.0

    def test_best_n_ensemble_shapes(self):
        model = gp_lib.VizierGaussianProcess(num_continuous=1, num_categorical=0)
        data = _make_data(8, 8, dc=1)
        coll = model.param_collection()
        inits = coll.batch_random_init_unconstrained(jax.random.PRNGKey(0), 6)
        loss_fn = lambda p: model.neg_log_likelihood(p, data)
        result = lbfgs_lib.LbfgsOptimizer(maxiter=10)(loss_fn, inits, best_n=3)
        assert result.params["amplitude"].shape == (3,)
        states = jax.vmap(lambda p: model.precompute(p, data))(result.params)
        ens = gp_lib.EnsemblePredictive(states)
        mean, stddev = ens.predict(_feats(np.array([[0.5]], np.float32)))
        assert mean.shape == (1,) and stddev.shape == (1,)

    def test_adam_optimizer_works(self):
        model = gp_lib.VizierGaussianProcess(num_continuous=1, num_categorical=0)
        data = _make_data(8, 8, dc=1)
        coll = model.param_collection()
        inits = coll.batch_random_init_unconstrained(jax.random.PRNGKey(0), 2)
        loss_fn = lambda p: model.neg_log_likelihood(p, data)
        init_losses = jax.vmap(loss_fn)(inits)
        result = lbfgs_lib.AdamOptimizer(maxiter=100)(loss_fn, inits)
        assert float(result.best_loss) < float(jnp.min(init_losses))


class TestParams:
    def test_softclip_roundtrip(self):
        b = params_lib.SoftClip(1e-3, 10.0)
        y = jnp.asarray([0.01, 0.5, 5.0])
        np.testing.assert_allclose(b.forward(b.inverse(y)), y, rtol=1e-3)

    def test_forward_in_bounds(self):
        b = params_lib.SoftClip(0.1, 2.0)
        x = jnp.linspace(-20, 20, 100)
        y = np.asarray(b.forward(x))
        assert (y >= 0.1 - 1e-6).all() and (y <= 2.0 + 1e-6).all()

    def test_init_within_range(self):
        spec = params_lib.ParameterSpec(
            "a", (4,), params_lib.SoftClip(1e-3, 100.0), 0.1, 10.0
        )
        v = np.asarray(spec.sample_constrained(jax.random.PRNGKey(0)))
        assert (v >= 0.1).all() and (v <= 10.0).all()


class TestWarpers:
    def test_zscore(self):
        w = output_warpers.ZScoreWarper()
        y = w(np.array([1.0, 2.0, 3.0, 4.0]))
        assert np.mean(y) == pytest.approx(0.0, abs=1e-9)
        assert np.std(y) == pytest.approx(1.0, abs=1e-9)

    def test_halfrank_compresses_bad_tail(self):
        w = output_warpers.HalfRankWarper()
        y = np.array([0.0, 1.0, 2.0, 3.0, -1000.0])
        out = w(y)
        # The catastrophic outlier is pulled near the pack.
        assert out.min() > -100
        # Good half untouched.
        np.testing.assert_allclose(out[2:4], y[2:4])

    def test_infeasible_imputed_below_worst(self):
        w = output_warpers.InfeasibleWarper()
        out = w(np.array([1.0, np.nan, 3.0]))
        assert out[1] < 1.0
        assert np.isfinite(out).all()

    def test_default_pipeline(self):
        w = output_warpers.create_default_warper()
        y = np.array([5.0, np.nan, -2.0, 100.0, 3.0])
        out = w(y)
        assert np.isfinite(out).all()
        assert out[1] == out.min()  # infeasible is the worst


class TestInputWarping:
    """HEBO-style Kumaraswamy input warping (hebo_gp_model parity)."""

    def test_identity_at_unit_params(self):
        model = gp_lib.VizierGaussianProcess(
            num_continuous=2, num_categorical=0, use_input_warping=True
        )
        plain = gp_lib.VizierGaussianProcess(num_continuous=2, num_categorical=0)
        data = _make_data(8, 8)
        coll = model.param_collection()
        base = plain.param_collection().random_init_unconstrained(jax.random.PRNGKey(0))
        constrained = plain.param_collection().constrain(base)
        constrained["warp_a"] = jnp.ones(2)
        constrained["warp_b"] = jnp.ones(2)
        warp_params = coll.unconstrain(constrained)
        l_warp = float(model.neg_log_likelihood(warp_params, data))
        l_plain = float(plain.neg_log_likelihood(base, data))
        # a=b=1 warps are (numerically) the identity; likelihoods differ
        # only by the extra regularizer terms (zero at the prior mode).
        assert l_warp == pytest.approx(l_plain, rel=1e-3)

    def test_warped_fit_improves_on_nonstationary_data(self):
        # Objective varies fast near 0 and slow elsewhere: warping helps.
        rng = np.random.default_rng(0)
        x = rng.uniform(size=(24, 1)).astype(np.float32)
        y = np.sin(8 * np.sqrt(x[:, 0]))
        data = _make_data(24, 32, dc=1)
        data = gp_lib.GPData(
            continuous=jnp.asarray(np.pad(x, ((0, 8), (0, 0)))),
            categorical=data.categorical,
            labels=jnp.asarray(np.pad(y, (0, 8)).astype(np.float32)),
            row_mask=jnp.arange(32) < 24,
            cont_dim_mask=jnp.ones(1, bool),
            cat_dim_mask=data.cat_dim_mask,
        )
        def best_loss(model):
            coll = model.param_collection()
            inits = coll.batch_random_init_unconstrained(jax.random.PRNGKey(1), 6)
            result = lbfgs_lib.AdamOptimizer(maxiter=120)(
                lambda p: model.neg_log_likelihood(p, data), inits
            )
            return float(result.best_loss)

        warped = best_loss(
            gp_lib.VizierGaussianProcess(
                num_continuous=1, num_categorical=0, use_input_warping=True
            )
        )
        plain = best_loss(
            gp_lib.VizierGaussianProcess(num_continuous=1, num_categorical=0)
        )
        assert warped <= plain + 1.0  # warping never much worse; usually better

    def test_nonunit_warp_changes_likelihood(self):
        """Guard: the warp must actually be applied (not a silent no-op)."""
        model = gp_lib.VizierGaussianProcess(
            num_continuous=2, num_categorical=0, use_input_warping=True
        )
        data = _make_data(8, 8)
        coll = model.param_collection()
        base = coll.random_init_unconstrained(jax.random.PRNGKey(0))
        constrained = coll.constrain(base)
        constrained["warp_a"] = jnp.ones(2)
        constrained["warp_b"] = jnp.ones(2)
        identity = float(model.neg_log_likelihood(coll.unconstrain(constrained), data))
        constrained["warp_a"] = jnp.full(2, 3.0)
        constrained["warp_b"] = jnp.full(2, 0.4)
        warped = float(model.neg_log_likelihood(coll.unconstrain(constrained), data))
        assert warped != pytest.approx(identity, rel=1e-4)


class TestJointPosterior:
    def test_predict_joint_matches_marginals(self):
        model = gp_lib.VizierGaussianProcess(num_continuous=2, num_categorical=0)
        data = _make_data(10, 16)
        params = model.param_collection().random_init_unconstrained(jax.random.PRNGKey(2))
        state = model.precompute(params, data)
        query = _feats(np.random.default_rng(3).uniform(size=(5, 2)).astype(np.float32))
        mean_m, std_m = state.predict(query)
        mean_j, cov_j = state.predict_joint(query)
        np.testing.assert_allclose(mean_j, mean_m, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            np.sqrt(np.diag(np.asarray(cov_j))), std_m, rtol=1e-2, atol=1e-3
        )

    def test_duplicated_points_perfectly_correlated(self):
        """The property joint qEI relies on: copies share one posterior draw."""
        model = gp_lib.VizierGaussianProcess(num_continuous=1, num_categorical=0)
        data = _make_data(8, 8, dc=1)
        params = model.param_collection().random_init_unconstrained(jax.random.PRNGKey(0))
        state = model.precompute(params, data)
        x = np.array([[0.37], [0.37]], np.float32)  # same point twice
        _, cov = state.predict_joint(_feats(x))
        cov = np.asarray(cov)
        corr = cov[0, 1] / np.sqrt(cov[0, 0] * cov[1, 1])
        assert corr == pytest.approx(1.0, abs=1e-3)
