"""A fit's per-metric slice is made when somebody reads it, and no sooner
(PR 30's rule, kept on the sparse path since PR 44).

A served sequential GP-UCB-PE suggest replies when its picks are decoded:
nothing eager runs on the device between the designer's return and the
reply, the policy keeps no copy of the sparse state, and
``serving_stats()["fit_reads"]`` stays 0. The fit that IS read (an operator,
``sparse_inducing_state()``, the benchmark's check after its window) is
sliced by one jitted program and equals, to the bit, what the per-leaf eager
slice gave — for the exact, the sparse and the multitask state.
"""

import jax
import numpy as np
import pytest

from tests.eager_dispatches import EagerDispatches
from vizier_tpu import pyvizier as vz
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.designers import gp_ucb_pe
from vizier_tpu.models import gp as gp_lib
from vizier_tpu.models import multitask_gp as mtgp
from vizier_tpu.optimizers import lbfgs as lbfgs_lib
from vizier_tpu.service import vizier_client
from vizier_tpu.surrogates import SurrogateConfig
from vizier_tpu.surrogates import sparse_gp

# Six completed trials a study (the fixture's): sparse from the first suggest.
SURROGATES = {
    "exact": None,
    "sparse": SurrogateConfig(sparse_threshold_trials=4, hysteresis_trials=0, num_inducing=4),
}
STATE_TYPES = {"exact": gp_lib.GPState, "sparse": sparse_gp.SparseGPState}


def _ucb_pe(problem, surrogate=None, **kwargs):
    return gp_ucb_pe.VizierGPUCBPEBandit(
        problem,
        ard_optimizer=lbfgs_lib.AdamOptimizer(maxiter=15),
        ard_restarts=3,
        max_acquisition_evaluations=200,
        warm_start_min_trials=0,
        surrogate=surrogate,
        **kwargs,
    )


def _per_leaf_slice(states_me):
    """Metric 0 as the parent took it: an eager ``a[0]`` a leaf."""
    return jax.tree_util.tree_map(lambda a: a[0], states_me)


def _same_tree_bits(got, want):
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert (g.dtype, g.shape, g.tobytes()) == (w.dtype, w.shape, w.tobytes())


@pytest.mark.parametrize("mode", sorted(SURROGATES))
def test_a_served_suggest_reads_no_fit_and_a_read_is_one_program(
    mode, served_gp_stack, monkeypatch
):
    servicer, runtime, (study,) = served_gp_stack(
        1, designer_factory=lambda problem, **kw: _ucb_pe(problem, SURROGATES[mode])
    )
    counter = EagerDispatches()
    at_return = []
    designer_suggest = gp_ucb_pe.VizierGPUCBPEBandit.suggest

    def suggest(self, count=None):
        out = designer_suggest(self, count)
        at_return.append(counter.count)
        return out

    monkeypatch.setattr(gp_ucb_pe.VizierGPUCBPEBandit, "suggest", suggest)
    client = vizier_client.VizierClient(servicer, study, "worker")
    after_return = []
    with counter:
        for i in range(3):  # each one trains: a completion lies between them
            (trial,) = client.get_suggestions(1)
            after_return.append(counter.count - at_return[-1])
            client.complete_trial(trial.id, vz.Measurement(metrics={"obj": 0.1 * i}))
    # Between the designer's return and the reply: nothing eager on the device.
    assert len(at_return) == 3 and after_return == [0, 0, 0]
    stats = runtime.stats.snapshot()
    assert stats["fit_reads"] == 0
    assert stats["sparse_suggests"] == (3 if mode == "sparse" else 0)

    entry = runtime.designer_cache.peek(study, touch=False)
    designer = entry.designer
    assert entry.surrogate_mode == mode and not hasattr(entry, "sparse_state")
    assert designer.surrogate_counts["fit_reads"] == 0
    fit = designer._unread_fit
    assert isinstance(fit, STATE_TYPES[mode]) and designer._predictive is None
    want = _per_leaf_slice(fit)

    with entry.lock, EagerDispatches() as reading:
        got = (
            designer.sparse_inducing_state()
            if mode == "sparse"
            else designer._last_predictive.states
        )
    # ONE jitted program sliced it (16 eager ones of a sparse state before).
    assert reading.count == 0
    assert designer.surrogate_counts["fit_reads"] == 1
    _same_tree_bits(got, want)
    predictive = designer._last_predictive
    assert predictive.states is got and designer._unread_fit is None
    assert designer.surrogate_counts["fit_reads"] == 1  # made once, then held
    assert (designer.sparse_inducing_state() is got) == (mode == "sparse")
    # A read between suggests is the designer's count, not the served stat.
    assert runtime.stats.snapshot()["fit_reads"] == 0

    # ... and the next served suggest defers its fit again.
    client.get_suggestions(1)
    assert designer._unread_fit is not None
    assert runtime.stats.snapshot()["fit_reads"] == 0


def _problem(metrics):
    problem = vz.ProblemStatement()
    for d in range(3):
        problem.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
    for name in ("m1", "m2")[:metrics]:
        problem.metric_information.append(
            vz.MetricInformation(name=name, goal=vz.ObjectiveMetricGoal.MAXIMIZE)
        )
    return problem


def _loaded(designer, problem, n, seed):
    rng = np.random.default_rng(seed)
    trials = []
    for i in range(n):
        x = rng.uniform(size=3)
        trial = vz.Trial(id=i + 1, parameters={f"x{d}": float(x[d]) for d in range(3)})
        trial.complete(
            vz.Measurement(
                metrics={
                    m.name: float(-np.sum((x - 0.3 - 0.4 * k) ** 2))
                    for k, m in enumerate(problem.metric_information)
                }
            )
        )
        trials.append(trial)
    designer.update(core_lib.CompletedTrials(trials), core_lib.ActiveTrials([]))
    return designer


# kind -> (metrics, designer kwargs): the three state types a fit can be.
KINDS = {
    "exact": (1, {}),
    "exact_two_metrics": (2, {}),
    "sparse": (1, {"surrogate": SURROGATES["sparse"]}),
    "multitask": (
        2,
        {
            "config": gp_ucb_pe.UCBPEConfig(
                multitask_type=gp_ucb_pe.MultiTaskType.SEPARABLE, num_scalarizations=20
            )
        },
    ),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_predictive_read_after_a_suggest_equals_the_per_leaf_slice(kind):
    metrics, kwargs = KINDS[kind]
    problem = _problem(metrics)
    designer = _loaded(_ucb_pe(problem, **kwargs), problem, 8, seed=metrics)
    suggestions = designer.suggest(2)
    assert designer.surrogate_counts["fit_reads"] == 0
    fit = designer._unread_fit
    query = designer._encode_suggestions(suggestions)
    if isinstance(fit, mtgp.MultiTaskGPState):
        # A joint state has no metric axis to slice: the predictive holds
        # the fit itself and takes metric 0 of what it predicts.
        mean, stddev = gp_ucb_pe._mt_mixture_predict(fit, query)
        want_states, want = fit, (mean[0], stddev[0])
    else:
        want_states = _per_leaf_slice(fit)
        eager = (
            sparse_gp.SparseEnsemblePredictive
            if isinstance(fit, sparse_gp.SparseGPState)
            else gp_lib.EnsemblePredictive
        )(want_states)
        want = eager.predict(query)
    with EagerDispatches() as reading:
        predictive = designer._last_predictive
    assert reading.count == 0
    assert designer.surrogate_counts["fit_reads"] == 1
    got_states = predictive._states if kind == "multitask" else predictive.states
    _same_tree_bits(got_states, want_states)
    _same_tree_bits(predictive.predict(query), want)
    assert designer._last_predictive is predictive
    assert designer.surrogate_counts["fit_reads"] == 1
