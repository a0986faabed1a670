"""One cross-covariance serves GP-UCB-PE's two posteriors.

``_exact_posterior_pair`` evaluates k(query, X_all) once a member and gives
the completed posterior its leading columns. It rests on the completed rows
being the leading rows of the all-points data, features equal to the bit,
and on the trained pad not exceeding the all-points pad: both are held
here on what the designer builds, with ACTIVE trials present.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.designers import gp_ucb_pe
from vizier_tpu.models import kernels
from vizier_tpu.optimizers import lbfgs as lbfgs_lib
from vizier_tpu.surrogates import SurrogateConfig
from vizier_tpu.surrogates import sparse_gp

_FAST_ARD = lbfgs_lib.AdamOptimizer(maxiter=20)
COUNT = 3


def _problem(num_metrics: int) -> vz.ProblemStatement:
    p = vz.ProblemStatement()
    for name in ("x", "y", "z"):
        p.search_space.root.add_float_param(name, 0.0, 1.0)
    p.search_space.root.add_categorical_param("c", ["a", "b", "c"])
    for m in range(num_metrics):
        p.metric_information.append(
            vz.MetricInformation(name=f"f{m}", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
        )
    return p


def _trial(i: int, rng, num_metrics: int, complete: bool) -> vz.Trial:
    x = rng.uniform(size=3)
    t = vz.Trial(
        id=i,
        parameters={"x": x[0], "y": x[1], "z": x[2], "c": "abc"[i % 3]},
    )
    if complete:
        t.complete(
            vz.Measurement(
                metrics={
                    f"f{m}": float(-np.sum((x - 0.2 * (m + 1)) ** 2) + 0.1 * (i % 3))
                    for m in range(num_metrics)
                }
            )
        )
    return t


def _designer(num_metrics, ensemble_size, completed, active, **kwargs):
    designer = gp_ucb_pe.VizierGPUCBPEBandit(
        _problem(num_metrics),
        max_acquisition_evaluations=300,
        ard_restarts=max(2, ensemble_size),
        ensemble_size=ensemble_size,
        ard_optimizer=_FAST_ARD,
        use_mesh=False,
        **kwargs,
    )
    rng = np.random.default_rng(completed)
    designer.update(
        core_lib.CompletedTrials(
            [_trial(i + 1, rng, num_metrics, True) for i in range(completed)]
        ),
        core_lib.ActiveTrials(
            [_trial(100 + i, rng, num_metrics, False) for i in range(active)]
        ),
    )
    return designer


def _both_posteriors(designer):
    """(states_completed [M, E], states_all [M, E], datas, all_data) as
    ``_suggest_batch`` holds them in a pick."""
    states_me, datas = designer._train_states_me(designer._encode_datas())
    all_data = designer._all_points_data(COUNT)
    pe_params, _, _ = gp_ucb_pe._pe_conditioning(states_me, all_data, designer.config)
    model = designer._model
    states_all = jax.vmap(
        jax.vmap(lambda q: model.precompute_constrained(q, all_data))
    )(pe_params)
    return states_me, states_all, datas, all_data


CASES = {
    # metrics, ensemble, completed, ACTIVE: trained pad 8 < all-points pad 16
    "one_metric": (1, 1, 7, 2, {}),
    "two_metrics": (2, 1, 7, 2, {}),
    "ensemble_of_three": (1, 3, 7, 2, {}),
    "two_metrics_ensemble_of_two": (2, 2, 6, 3, {}),
    "warped_inputs": (1, 1, 7, 2, {"use_input_warping": True}),
    # trained pad 16 == all-points pad 16: nothing to slice
    "one_pad": (1, 1, 9, 2, {}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_posterior_pair_is_the_two_predicts(case):
    metrics, ensemble, completed, active, kwargs = CASES[case]
    designer = _designer(metrics, ensemble, completed, active, **kwargs)
    states_me, states_all, datas, all_data = _both_posteriors(designer)
    assert states_me.alpha.shape[:2] == (metrics, ensemble)
    assert states_me.alpha.shape[-1] <= all_data.num_rows
    assert (states_me.alpha.shape[-1] < all_data.num_rows) == (case != "one_pad")
    assert int(np.sum(all_data.row_mask)) == completed + active

    rng = np.random.default_rng(1)
    query = kernels.MixedFeatures(
        jnp.asarray(rng.uniform(size=(50, 3)), jnp.float32),
        jnp.asarray(rng.integers(0, 3, size=(50, 1)), jnp.int32),
    )

    @jax.jit
    def pair(states_me, states_all, query):
        rows_all = jax.vmap(jax.vmap(lambda s: s.kernel_rows()))(states_all)
        return gp_ucb_pe._exact_posterior_pair(states_me, states_all, rows_all, query)

    @jax.jit
    def two_predicts(states_me, states_all, query):
        mean_c, std_c = gp_ucb_pe._mixture_predict(states_me, query)
        _, std_all = gp_ucb_pe._mixture_predict(states_all, query)
        return mean_c, std_c, std_all

    got = pair(states_me, states_all, query)
    want = two_predicts(states_me, states_all, query)
    for name, g, w in zip(("mean", "stddev", "stddev_from_all"), got, want):
        assert g.shape == (metrics, 50), name
        # The same arithmetic in the same order: what differs is what XLA
        # fuses, a rounding of the last place at most.
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-6, atol=2e-7, err_msg=name
        )
    # The pending rows deflate the all-points stddev: the two posteriors differ.
    assert np.all(np.asarray(got[2]) <= np.asarray(got[1]) + 1e-5)
    assert np.any(np.asarray(got[2]) < np.asarray(got[1]) - 1e-4)


def _pair_under_vmaps(states_me, states_all, rows_all, query):
    """``_exact_posterior_pair`` as PR 38 wrote it: a ``vmap`` over metrics
    of a ``vmap`` over members, whatever their number."""
    n_completed = states_me.alpha.shape[-1]

    def member(completed, everything, rows):
        k_all = everything.cross_covariance(query, rows)
        return (
            *completed.predict_from_cross(k_all[:, :n_completed]),
            *everything.predict_from_cross(k_all),
        )

    mean_c, std_c, mean_all, std_all = jax.vmap(jax.vmap(member))(
        states_me, states_all, rows_all
    )
    return (
        *gp_ucb_pe._moment_match(mean_c, std_c),
        gp_ucb_pe._moment_match(mean_all, std_all)[1],
    )


def _mixture_under_vmaps(states, query):
    return gp_ucb_pe._moment_match(
        *jax.vmap(jax.vmap(lambda s: s.predict(query)))(states)
    )


_SPARSE = SurrogateConfig(sparse_threshold_trials=1, hysteresis_trials=0, num_inducing=6)


@pytest.mark.parametrize(
    "what,metrics,ensemble",
    [
        (what, metrics, ensemble)
        for what in ("pair-exact", "mixture-exact", "mixture-sparse")
        for metrics, ensemble in ((1, 1), (2, 1), (1, 2))
        if (what, metrics) != ("mixture-sparse", 2)  # the sparse tier serves one metric
    ],
)
def test_unit_metric_and_member_axes_stay_out_of_the_posteriors(what, metrics, ensemble):
    """One metric x one member is evaluated unbatched, the two unit axes put
    back on the [Q] outputs (``_per_member``): the bits of the double
    ``vmap``, from a program that holds no ``[1, 1, Q, N]`` operand. Two
    metrics or two members keep the ``[M, E, Q, N]`` operand of the double
    ``vmap``."""
    sparse = what == "mixture-sparse"
    designer = _designer(
        metrics, ensemble, 7, 2, **({"surrogate": _SPARSE} if sparse else {})
    )
    if sparse:
        assert designer._refresh_ucb_pe_surrogate_mode() == "sparse"
        states_me, _ = designer._train_states_me(designer._encode_datas())
        assert isinstance(states_me, sparse_gp.SparseGPState)
    else:
        states_me, states_all, _, _ = _both_posteriors(designer)
    assert jax.tree_util.tree_leaves(states_me)[0].shape[:2] == (metrics, ensemble)
    rng = np.random.default_rng(2)
    query = kernels.MixedFeatures(
        jnp.asarray(rng.uniform(size=(50, 3)), jnp.float32),
        jnp.asarray(rng.integers(0, 3, size=(50, 1)), jnp.int32),
    )
    if what == "pair-exact":
        rows_all = jax.vmap(jax.vmap(lambda s: s.kernel_rows()))(states_all)
        ours, vmapped = gp_ucb_pe._exact_posterior_pair, _pair_under_vmaps
        arguments = (states_me, states_all, rows_all)
    else:
        ours, vmapped = gp_ucb_pe._mixture_predict, _mixture_under_vmaps
        arguments = (states_me,)

    got = jax.jit(ours)(*arguments, query)
    want = jax.jit(vmapped)(*arguments, query)
    for g, w in zip(got, want, strict=True):
        assert g.shape == (metrics, 50)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    traced = str(jax.make_jaxpr(ours)(*arguments, query))
    batched = f"f32[{metrics},{ensemble},50,"
    assert batched in str(jax.make_jaxpr(vmapped)(*arguments, query))
    assert (batched in traced) == ((metrics, ensemble) != (1, 1))


@pytest.mark.parametrize("metrics,completed,active", [(1, 7, 2), (2, 6, 3), (1, 9, 0)])
def test_completed_rows_lead_the_all_points_rows_to_the_bit(metrics, completed, active):
    designer = _designer(metrics, 1, completed, active)
    datas = designer._encode_datas()
    all_data = designer._all_points_data(COUNT)
    assert len(datas) == metrics
    for data in datas:
        assert data.num_rows <= all_data.num_rows
        assert int(np.sum(data.row_mask)) == completed
        lead = np.s_[:completed]
        np.testing.assert_array_equal(all_data.continuous[lead], data.continuous[lead])
        np.testing.assert_array_equal(all_data.categorical[lead], data.categorical[lead])
        np.testing.assert_array_equal(all_data.cont_dim_mask, data.cont_dim_mask)
        np.testing.assert_array_equal(all_data.cat_dim_mask, data.cat_dim_mask)
        assert np.all(data.row_mask[lead])
    assert np.all(all_data.row_mask[: completed + active])
    assert not np.any(all_data.row_mask[completed + active :])
    assert all_data.num_rows - completed - active >= COUNT


def test_reordered_all_points_rows_are_refused(monkeypatch):
    """ACTIVE rows ahead of the completed ones would hand the completed
    posterior another trial's covariances: ``_all_points_model_data`` raises."""
    designer = _designer(1, 1, 7, 2)
    padded = designer._padded_features

    def active_first(cont, cat, extra_rows=0):
        return padded(cont[::-1], cat[::-1], extra_rows=extra_rows)

    monkeypatch.setattr(designer, "_padded_features", active_first)
    with pytest.raises(RuntimeError, match="begin with the completed rows"):
        designer._all_points_model_data(COUNT)


def test_a_trained_pad_over_the_all_points_pad_is_refused():
    big = _designer(1, 1, 9, 0)  # trained pad 16
    small = _designer(1, 1, 3, 0)  # all-points pad 8
    states_me, _ = big._train_states_me(big._encode_datas())
    datas = big._encode_datas()
    labels_mn, labels_mask, ref_point, prior = big._sweep_inputs(datas)
    with pytest.raises(ValueError, match="trained pad 16 > all-points pad 8"):
        gp_ucb_pe._suggest_batch(
            big._model, big._vec_opt, states_me, small._all_points_data(COUNT),
            labels_mn, labels_mask, ref_point, prior, jax.random.PRNGKey(0),
            np.asarray(True), np.asarray(True), 1, big.config, True, None, None,
        )
