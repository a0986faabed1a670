"""Sparse-GP math: exact-recovery at Z=X, mask safety, k-center, training."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vizier_tpu.models import gp as gp_lib
from vizier_tpu.models import kernels
from vizier_tpu.optimizers import lbfgs as lbfgs_lib
from vizier_tpu.surrogates import sparse_bandit
from vizier_tpu.surrogates import sparse_gp


def _data(n, d, seed=0, pad_to=None):
    """GPData with ``n`` valid rows of a smooth function, padded to
    ``pad_to`` masked filler rows."""
    rng = np.random.default_rng(seed)
    n_pad = pad_to or n
    cont = np.zeros((n_pad, d), np.float32)
    cont[:n] = rng.uniform(size=(n, d)).astype(np.float32)
    labels = np.zeros(n_pad, np.float32)
    labels[:n] = np.sin(3.0 * cont[:n, 0]) + cont[:n, 1:].sum(axis=1)
    # z-score the valid labels (what the output warper feeds the GP).
    labels[:n] = (labels[:n] - labels[:n].mean()) / max(labels[:n].std(), 1e-6)
    mask = np.arange(n_pad) < n
    return gp_lib.GPData(
        continuous=jnp.asarray(cont),
        categorical=jnp.zeros((n_pad, 0), jnp.int32),
        labels=jnp.asarray(labels),
        row_mask=jnp.asarray(mask),
        cont_dim_mask=jnp.ones((d,), bool),
        cat_dim_mask=jnp.ones((0,), bool),
    )


def _models(d, m):
    base = gp_lib.VizierGaussianProcess(num_continuous=d, num_categorical=0)
    return base, sparse_gp.SparseGaussianProcess(base=base, num_inducing=m)


def _mid_params(coll):
    """Fixed well-conditioned constrained params, mapped to unconstrained."""
    vals = {"amplitude": 1.0, "noise_stddev": 0.1, "continuous_length_scales": 0.5}
    constrained = {
        spec.name: jnp.full(spec.shape, vals[spec.name], jnp.float32)
        for spec in coll.specs
    }
    return coll.unconstrain(constrained)


def _queries(d, q=32, seed=9):
    rng = np.random.default_rng(seed)
    return kernels.MixedFeatures(
        jnp.asarray(rng.uniform(size=(q, d)).astype(np.float32)),
        jnp.zeros((q, 0), jnp.int32),
    )


class TestExactRecovery:
    def test_full_inducing_set_recovers_exact_posterior(self):
        # SGPR with Z = X is mathematically the exact GP; the implementation
        # must agree to numerical jitter.
        n, d = 24, 3
        data = _data(n, d)
        base, sparse = _models(d, n)
        u = _mid_params(base.param_collection())

        exact_state = base.precompute(u, data)
        sdata = sparse_gp.SparseGPData(
            data=data,
            z_continuous=data.continuous,
            z_categorical=data.categorical,
            inducing_mask=data.row_mask,
            inducing_indices=jnp.arange(n, dtype=jnp.int32),
        )
        sparse_state = sparse.precompute(u, sdata)

        q = _queries(d)
        em, es = exact_state.predict(q)
        sm, ss = sparse_state.predict(q)
        np.testing.assert_allclose(np.asarray(em), np.asarray(sm), atol=2e-3)
        np.testing.assert_allclose(np.asarray(es), np.asarray(ss), atol=2e-3)

    def test_collapsed_bound_lower_bounds_exact_likelihood(self):
        # Titsias: ELBO <= log p(y), so -bound >= exact NLL (both sides
        # carry the same ARD regularizer, which cancels in the comparison);
        # at Z = X the bound is tight.
        n, d = 20, 2
        data = _data(n, d, seed=3)
        base, sparse_full = _models(d, n)
        u = _mid_params(base.param_collection())
        exact_nll = float(base.neg_log_likelihood(u, data))

        sdata_full = sparse_gp.SparseGPData(
            data=data,
            z_continuous=data.continuous,
            z_categorical=data.categorical,
            inducing_mask=data.row_mask,
            inducing_indices=jnp.arange(n, dtype=jnp.int32),
        )
        tight = float(sparse_full.neg_log_likelihood(u, sdata_full))
        assert abs(tight - exact_nll) < 0.5, (tight, exact_nll)

        _, sparse_small = _models(d, 6)
        sdata_small = sparse_gp.select_inducing_kcenter(data, 6)
        loose = float(sparse_small.neg_log_likelihood(u, sdata_small))
        assert loose >= exact_nll - 0.5, (loose, exact_nll)


class TestMaskSafety:
    def test_padded_rows_do_not_change_posterior(self):
        n, d, m = 18, 3, 8
        u = _mid_params(
            gp_lib.VizierGaussianProcess(
                num_continuous=d, num_categorical=0
            ).param_collection()
        )
        _, sparse = _models(d, m)
        q = _queries(d)

        plain = sparse.precompute(
            u, sparse_gp.select_inducing_kcenter(_data(n, d, seed=5), m)
        )
        padded = sparse.precompute(
            u, sparse_gp.select_inducing_kcenter(_data(n, d, seed=5, pad_to=32), m)
        )
        pm, ps = plain.predict(q)
        qm, qs = padded.predict(q)
        np.testing.assert_allclose(np.asarray(pm), np.asarray(qm), atol=1e-5)
        np.testing.assert_allclose(np.asarray(ps), np.asarray(qs), atol=1e-5)

    def test_padded_inducing_slots_do_not_change_posterior(self):
        # Fewer valid rows than inducing slots: the surplus slots repeat
        # chosen rows and MUST be masked out of the posterior — m=8 over 5
        # valid rows equals m=5 over the same rows.
        n, d = 5, 2
        data = _data(n, d, seed=7)
        q = _queries(d)
        u = _mid_params(
            gp_lib.VizierGaussianProcess(
                num_continuous=d, num_categorical=0
            ).param_collection()
        )

        _, tight_model = _models(d, n)
        tight = tight_model.precompute(
            u, sparse_gp.select_inducing_kcenter(data, n)
        )
        _, padded_model = _models(d, 8)
        sdata = sparse_gp.select_inducing_kcenter(data, 8)
        assert int(jnp.sum(sdata.inducing_mask)) == n
        padded = padded_model.precompute(u, sdata)

        tm, ts = tight.predict(q)
        pm, ps = padded.predict(q)
        np.testing.assert_allclose(np.asarray(tm), np.asarray(pm), atol=1e-4)
        np.testing.assert_allclose(np.asarray(ts), np.asarray(ps), atol=1e-4)


class TestKCenterSelection:
    def test_deterministic_and_starts_at_incumbent(self):
        data = _data(30, 3, seed=11)
        a = sparse_gp.select_inducing_kcenter(data, 10)
        b = sparse_gp.select_inducing_kcenter(data, 10)
        np.testing.assert_array_equal(
            np.asarray(a.inducing_indices), np.asarray(b.inducing_indices)
        )
        incumbent = int(jnp.argmax(data.labels))
        assert int(a.inducing_indices[0]) == incumbent

    def test_selects_distinct_spread_points(self):
        data = _data(30, 3, seed=13)
        sdata = sparse_gp.select_inducing_kcenter(data, 10)
        idx = np.asarray(sdata.inducing_indices)
        assert len(set(idx.tolist())) == 10  # no duplicates while n > m
        assert bool(jnp.all(sdata.inducing_mask))

    def test_ignores_masked_rows(self):
        # Padding rows (mask False) must never be selected as inducing
        # points even though they sit at the (distant) origin.
        data = _data(12, 3, seed=17, pad_to=32)
        sdata = sparse_gp.select_inducing_kcenter(data, 8)
        idx = np.asarray(sdata.inducing_indices)
        assert (idx < 12).all(), idx


class TestTraining:
    def test_train_fits_and_warm_restart_is_stable(self):
        n, d, m = 40, 3, 16
        data = _data(n, d, seed=19)
        _, model = _models(d, m)
        opt = lbfgs_lib.LbfgsOptimizer(maxiter=30)

        state, _ = sparse_bandit._train_sparse_gp(
            model, opt, data, jax.random.PRNGKey(0), 4, 1, None
        )
        mean, _ = jax.tree_util.tree_map(lambda a: a[0], state).predict(
            data.features()
        )
        mean = np.asarray(mean)[: n]
        labels = np.asarray(data.labels)[:n]
        corr = np.corrcoef(mean, labels)[0, 1]
        assert corr > 0.9, corr  # the collapsed bound trained a real fit

        # Warm restart: seeding with the trained optimum keeps the fit.
        coll = model.param_collection()
        warm = coll.unconstrain(
            jax.tree_util.tree_map(lambda a: a[0], state.params)
        )
        warm_state, _ = sparse_bandit._train_sparse_gp(
            model, opt, data, jax.random.PRNGKey(1), 2, 1, warm
        )
        mean2, _ = jax.tree_util.tree_map(lambda a: a[0], warm_state).predict(
            data.features()
        )
        corr2 = np.corrcoef(np.asarray(mean2)[:n], labels)[0, 1]
        assert corr2 > 0.9, corr2

    def test_posterior_tracks_exact_gp_closely(self):
        # m = n/2 inducing points on smooth data: the sparse posterior mean
        # must stay close to the exact GP's at the same hyperparameters.
        n, d, m = 32, 2, 16
        data = _data(n, d, seed=23)
        base, sparse = _models(d, m)
        u = _mid_params(base.param_collection())
        exact_state = base.precompute(u, data)
        sparse_state = sparse.precompute(
            u, sparse_gp.select_inducing_kcenter(data, m)
        )
        q = _queries(d)
        em, _ = exact_state.predict(q)
        sm, _ = sparse_state.predict(q)
        err = float(jnp.max(jnp.abs(em - sm)))
        spread = float(jnp.max(jnp.abs(em))) + 1e-6
        assert err / spread < 0.25, (err, spread)

    def test_ensemble_predictive_moment_matches(self):
        n, d, m = 20, 2, 8
        data = _data(n, d, seed=29)
        _, model = _models(d, m)
        opt = lbfgs_lib.LbfgsOptimizer(maxiter=10)
        states, _ = sparse_bandit._train_sparse_gp(
            model, opt, data, jax.random.PRNGKey(2), 4, 2, None
        )
        pred = sparse_gp.SparseEnsemblePredictive(states)
        mean, stddev = pred.predict(_queries(d, q=8))
        assert mean.shape == (8,) and stddev.shape == (8,)
        assert bool(jnp.all(jnp.isfinite(mean)))
        assert bool(jnp.all(stddev > 0))


class TestPosteriorMatmulPrecision:
    """On a TPU the default matmul precision is one bf16 pass, and the
    posterior variance — a difference of near-equal terms through L⁻¹ —
    came out negative there (PR 21's chip run). The CPU cannot show
    that, so this pins what fixed it: every matmul traced from a posterior
    query carries ``POSTERIOR_PRECISION``."""

    @pytest.mark.parametrize("which", ["exact", "exact_joint", "sparse"])
    def test_every_posterior_dot_is_pinned(self, which):
        d = 3
        base, sparse = _models(d, 8)
        data = _data(24, d)
        params = _mid_params(base.param_collection())
        if which == "sparse":
            state = sparse.precompute(
                params, sparse_gp.select_inducing_kcenter(data, 8)
            )
        else:
            state = base.precompute(params, data)
        query = _queries(d)
        fn = state.predict_joint if which == "exact_joint" else state.predict
        dots = [
            eqn
            for eqn in jax.make_jaxpr(fn)(query).jaxpr.eqns
            if eqn.primitive.name == "dot_general"
        ]
        assert len(dots) >= 2
        want = jax.lax.Precision.HIGHEST
        assert gp_lib.POSTERIOR_PRECISION == want
        for eqn in dots:
            assert eqn.params["precision"] in (want, (want, want)), eqn
