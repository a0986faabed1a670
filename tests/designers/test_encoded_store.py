"""The designer's encoded-row store, its deferred predictive and its one-call
decode: what a suggest no longer recomputes must equal what it recomputed.

Every comparison here is bitwise: the store replaces ``encoder.encode`` of
the whole study on every suggest, so a designer with the store and one whose
store is dropped before every suggest must return the same suggestions,
parameter for parameter and metadata string for metadata string.
"""

import datetime

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.eager_dispatches import EagerDispatches
from vizier_tpu import pyvizier as vz
from vizier_tpu import types
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.converters import core as converters
from vizier_tpu.designers import gp_bandit
from vizier_tpu.designers import gp_ucb_pe
from vizier_tpu.models import gp as gp_lib
from vizier_tpu.optimizers import lbfgs as lbfgs_lib

T0 = datetime.datetime(2026, 1, 1)


def _problem(kind):
    problem = vz.ProblemStatement()
    root = problem.search_space.root
    if kind == "mixed":
        root.add_float_param("lr", 1e-4, 1.0, scale_type=vz.ScaleType.LOG)
        root.add_int_param("layers", 1, 8)
        root.add_categorical_param("opt", ["adam", "sgd", "lion"])
        root.add_discrete_param("bs", [16, 32, 64, 128])
        root.add_float_param("x", -1.0, 1.0)
    else:
        for i in range(3):
            root.add_float_param(f"x{i}", 0.0, 1.0)
    problem.metric_information.append(
        vz.MetricInformation(name="a", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    if kind == "two_metrics":
        problem.metric_information.append(
            vz.MetricInformation(name="b", goal=vz.ObjectiveMetricGoal.MINIMIZE)
        )
    return problem


def _designer(problem, cls=gp_ucb_pe.VizierGPUCBPEBandit, **kwargs):
    kwargs.setdefault("max_acquisition_evaluations", 200)
    return cls(
        problem,
        ard_optimizer=lbfgs_lib.LbfgsOptimizer(maxiter=4),
        rng_seed=3,
        **kwargs,
    )


def _random_parameters(problem, rng):
    out = {}
    for config in problem.search_space.parameters:
        if config.type == vz.ParameterType.CATEGORICAL:
            out[config.name] = str(rng.choice(config.feasible_values))
        elif config.type == vz.ParameterType.DISCRETE:
            out[config.name] = float(rng.choice(config.feasible_values))
        elif config.type == vz.ParameterType.INTEGER:
            out[config.name] = int(rng.integers(config.bounds[0], config.bounds[1] + 1))
        else:
            lo, hi = config.bounds
            out[config.name] = float(rng.uniform(lo, hi))
    return out


def _complete(problem, trial, rng, step, infeasible=False):
    metrics = {m.name: float(rng.normal()) for m in problem.metric_information}
    if infeasible:
        trial.complete(vz.Measurement(metrics=metrics), infeasibility_reason="bad")
    else:
        trial.complete(vz.Measurement(metrics=metrics))
    trial.completion_time = T0 + datetime.timedelta(seconds=1000 + step)
    return trial


def _same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


def _same_tree_bits(got, want):
    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        _same_bits(g, w)


def _assert_store_equals_a_full_encode(designer, count):
    """The rows, the padded features and labels, and the all-points data a
    suggest of ``count`` would read, against ``encode`` of the full lists."""
    conv = designer._converter
    trials = list(designer._trials)
    cont, cat = conv.encoder.encode(trials)
    raw = conv.metrics.encode(trials)
    got_cont, got_cat, got_raw = designer._completed_rows()
    _same_bits(got_cont, cont.astype(np.float32))
    _same_bits(got_cat, cat)
    _same_bits(got_raw, raw)
    want_features, n_pad = designer._padded_features(cont.astype(np.float32), cat)
    got_features, got_pad = designer._padded_features(got_cont, got_cat)
    assert got_pad == n_pad
    _same_tree_bits(got_features, want_features)
    for data, j in zip(designer._encode_datas(), designer._objective_indices()):
        warped, _ = designer._warp_column(raw[:, j])
        want = gp_lib.GPData.from_model_data(
            types.ModelData(
                want_features, designer._padded_labels(warped, n_pad)
            )
        )
        _same_tree_bits(data, want)
    everyone = trials + list(designer._active_trials)
    all_cont, all_cat = conv.encoder.encode(everyone)
    want_all, all_pad = designer._padded_features(
        all_cont.astype(np.float32), all_cat, extra_rows=count
    )
    got_all = designer._all_points_model_data(count)
    _same_tree_bits(got_all.features, want_all)
    assert got_all.labels.shape == (all_pad, 1)
    assert int(np.sum(~got_all.labels.is_missing[0])) == len(everyone)


def _flatten(suggestions):
    return [
        (
            s.parameters.as_dict(),
            sorted((str(ns), k, str(v)) for ns, k, v in s.metadata.all_items()),
        )
        for s in suggestions
    ]


# One suggest count and the completed trials handed over before it, a round:
# random trials are loaded beside the completed suggestions so that the
# trained pad crosses 8 -> 16 -> 64 -> 256 in a few suggests.
ROUNDS = {
    "mixed": [(1, 0), (2, 3), (1, 0), (3, 4), (1, 0), (1, 30), (2, 0), (1, 100), (1, 0)],
    "two_metrics": [(2, 0), (1, 6), (3, 0), (1, 0)],
}


@pytest.mark.parametrize("kind", sorted(ROUNDS))
def test_suggestions_equal_a_designer_whose_store_is_reset_every_time(kind):
    """Completions out of id order, an ACTIVE set that grows and shrinks,
    an infeasible trial, a categorical + integer + discrete + log-scaled
    space, two metrics, the pads 8 to 256: the same suggestions and the same
    metadata strings with the store as with a full encode every time."""
    problem = _problem(kind)
    kept = _designer(problem)
    reset = _designer(problem)
    rng = np.random.default_rng(11)
    next_id, active, pads = 0, [], set()
    for step, (count, loaded) in enumerate(ROUNDS[kind]):
        reset._store.reset()
        want = reset.suggest(count)
        got = kept.suggest(count)
        assert _flatten(got) == _flatten(want)
        _assert_store_equals_a_full_encode(kept, count)
        pads.add(kept._converter.padding.pad_trials(len(kept._trials)))
        new = []
        for s in got:
            next_id += 1
            trial = s.to_trial(next_id)
            trial.creation_time = T0 + datetime.timedelta(seconds=next_id)
            new.append(trial)
        active.extend(new)
        # The highest ids complete first; every third round everything does,
        # so the ACTIVE set grows over two rounds and is then emptied.
        finishing = sorted(active, key=lambda t: -t.id)
        if step % 3 != 2:
            finishing = finishing[::2]
        done = [
            _complete(problem, t, rng, step, infeasible=(t.id % 5 == 2))
            for t in finishing
        ]
        for _ in range(loaded):
            next_id += 1
            trial = vz.Trial(id=next_id, parameters=_random_parameters(problem, rng))
            done.append(_complete(problem, trial, rng, step))
        active = [t for t in active if t not in finishing]
        for d in (kept, reset):
            d.update(
                core_lib.CompletedTrials(list(done)),
                core_lib.ActiveTrials(list(active)),
            )
    if kind == "mixed":
        assert {8, 16, 64, 256} <= pads
        assert any(t.infeasible for t in kept._trials)
    counts = kept.encoded_row_counts
    assert counts["reused"] > counts["encoded"] > 0
    assert reset.encoded_row_counts["reused"] == 0


def test_base_designer_reads_the_store_too():
    problem = _problem("float")
    kept = _designer(problem, cls=gp_bandit.VizierGPBandit)
    reset = _designer(problem, cls=gp_bandit.VizierGPBandit)
    rng = np.random.default_rng(5)
    next_id = 0
    for count in (1, 2, 3, 2):
        reset._store.reset()
        want, got = reset.suggest(count), kept.suggest(count)
        assert _flatten(got) == _flatten(want)
        done = []
        for s in got:
            next_id += 1
            done.append(_complete(problem, s.to_trial(next_id), rng, next_id))
        for d in (kept, reset):
            d.update(core_lib.CompletedTrials(list(done)))
    assert kept.encoded_row_counts["reused"] > 0
    assert reset.encoded_row_counts["reused"] == 0


def _loaded(problem, n, seed=0, **kwargs):
    rng = np.random.default_rng(seed)
    designer = _designer(problem, **kwargs)
    trials = [
        _complete(
            problem,
            vz.Trial(id=i + 1, parameters=_random_parameters(problem, rng)),
            rng,
            i,
        )
        for i in range(n)
    ]
    designer.update(core_lib.CompletedTrials(trials), core_lib.ActiveTrials([]))
    return designer, trials, rng


REBINDINGS = {
    # the list a restore would put there: other objects, same length
    "restored": lambda d, twin: setattr(d, "_trials", list(twin)),
    "truncated": lambda d, twin: setattr(d, "_trials", d._trials[:5]),
    "last_replaced": lambda d, twin: d._trials.__setitem__(-1, twin[-1]),
    "emptied_and_refilled": lambda d, twin: (d._trials.clear(), d._trials.extend(twin[:7])),
}


@pytest.mark.parametrize("how", sorted(REBINDINGS))
def test_a_rebound_trial_list_is_caught_by_the_store_itself(how):
    """Nothing tells the store: its own check of length and last trial
    finds a ``_trials`` that is no longer the list it encoded."""
    problem = _problem("mixed")
    designer, _, _ = _loaded(problem, 12, seed=1)
    _, twin, _ = _loaded(problem, 12, seed=2)  # other trials, other values
    designer.suggest(1)
    before = dict(designer.encoded_row_counts)
    REBINDINGS[how](designer, twin)
    designer._cached_states = None
    _assert_store_equals_a_full_encode(designer, 1)
    after = designer.encoded_row_counts
    # The read after the rebinding took nothing from the store.
    assert after["reused"] == before["reused"]
    assert after["encoded"] - before["encoded"] == len(designer._trials)


def test_set_priors_leaves_a_store_that_equals_a_full_encode():
    problem = _problem("float")
    designer, trials, _ = _loaded(problem, 9, seed=3)
    fresh, _, _ = _loaded(problem, 9, seed=3)
    designer.suggest(1)
    _, prior_trials, _ = _loaded(problem, 6, seed=4)
    for d in (designer, fresh):
        d.set_priors([prior_trials])
    fresh._rng = designer._rng
    assert _flatten(designer.suggest(2)) == _flatten(fresh.suggest(2))
    _assert_store_equals_a_full_encode(designer, 2)


def test_appended_rows_equal_one_encode_of_the_whole_list():
    """The store alone, without a designer: appends of 1, 2, 3 ... rows."""
    problem = _problem("mixed")
    conv = converters.TrialToModelInputConverter.from_problem(problem)
    rng = np.random.default_rng(9)
    trials = [
        _complete(
            problem,
            vz.Trial(id=i + 1, parameters=_random_parameters(problem, rng)),
            rng,
            i,
            infeasible=(i % 7 == 3),
        )
        for i in range(45)
    ]
    store = converters.EncodedTrials(conv.encoder, conv.metrics)
    held, step, reused = [], 1, 0
    while len(held) < len(trials):
        reused += len(held)
        held.extend(trials[len(held) : len(held) + step])
        step += 1
        store.sync(held)
        store.tally()
    cont, cat = conv.encoder.encode(trials)
    _same_bits(store.features()[0], cont.astype(np.float32))
    _same_bits(store.features()[1], cat)
    _same_bits(store.labels(), conv.metrics.encode(trials))
    assert (store.rows_encoded, store.rows_reused) == (len(trials), reused)


def test_a_batch_decodes_to_what_its_rows_decode_to_one_at_a_time():
    problem = _problem("mixed")
    conv = converters.TrialToModelInputConverter.from_problem(problem)
    rng = np.random.default_rng(2)
    cont = rng.uniform(size=(25, conv.encoder.num_continuous)).astype(np.float32)
    cat = rng.integers(0, 3, size=(25, conv.encoder.num_categorical)).astype(np.int32)
    batch = conv.to_parameters(cont, cat)
    for i, params in enumerate(batch):
        (alone,) = conv.to_parameters(cont[i : i + 1], cat[i : i + 1])
        assert params.as_dict() == alone.as_dict()
        for name, value in params.as_dict().items():
            if isinstance(value, float):
                assert np.float64(value).tobytes() == np.float64(
                    alone.as_dict()[name]
                ).tobytes()


@pytest.mark.parametrize("kind", ["float", "two_metrics"])
def test_metadata_vectors_are_the_strings_clients_parse(kind):
    """One entry a metric, as ``np.array2string(..., separator=",")`` writes
    them: ``chipbench/lib/program.py pick_metadata`` and upstream clients
    read ``s.strip("[]").split(",")``."""
    problem = _problem(kind)
    designer, _, _ = _loaded(problem, 10, seed=4)
    metrics = len(problem.metric_information)
    for suggestion in designer.suggest(3):
        pred = suggestion.metadata.ns("gp_ucb_pe").ns("prediction_in_warped_y_space")
        read = {
            key: [float(word) for word in pred[key].strip("[]").split(",")]
            for key in ("mean", "stddev", "stddev_from_all")
        }
        for key, values in read.items():
            assert len(values) == metrics and np.isfinite(values).all(), (key, pred[key])
            assert pred[key] == np.array2string(
                np.asarray(values, np.float32), separator=","
            )
        assert min(read["stddev"]) > 0.0


# -- the predictive is built when somebody reads it --------------------------


def test_a_suggest_builds_no_predictive_and_a_read_builds_the_eager_one():
    problem = _problem("float")
    designer, _, _ = _loaded(problem, 10, seed=6)
    suggestions = designer.suggest(2)
    assert designer._predictive is None and designer._unread_fit is not None
    states_me = designer._cached_states[0]
    eager = gp_lib.EnsemblePredictive(
        jax.tree_util.tree_map(lambda a: a[0], states_me)
    )
    built = designer._last_predictive
    assert designer._unread_fit is None and designer._last_predictive is built
    _same_tree_bits(built.states, eager.states)
    query = designer._encode_suggestions(suggestions)
    _same_tree_bits(built.predict(query), eager.predict(query))


def test_predict_and_sample_after_a_suggest_equal_the_eager_predictive():
    problem = _problem("float")
    lazy, _, _ = _loaded(problem, 10, seed=6)
    eager, _, _ = _loaded(problem, 10, seed=6)
    suggestions = lazy.suggest(2)
    assert _flatten(eager.suggest(2)) == _flatten(suggestions)
    eager._last_predictive  # built at once, as every suggest did before
    for d in (lazy, eager):
        d.prediction = d.predict(suggestions, rng=np.random.default_rng(1))
        d.samples = d.sample(suggestions, rng=np.random.default_rng(2), num_samples=7)
    _same_bits(lazy.prediction.mean, eager.prediction.mean)
    _same_bits(lazy.prediction.stddev, eager.prediction.stddev)
    _same_bits(lazy.samples, eager.samples)
    assert lazy._unread_fit is not None  # GP-UCB-PE predicts from its cached fit


def test_base_designer_predicts_after_a_suggest():
    problem = _problem("float")
    designer, _, _ = _loaded(problem, 8, seed=7, cls=gp_bandit.VizierGPBandit)
    suggestions = designer.suggest(2)
    assert designer._last_predictive is not None
    prediction = designer.predict(suggestions, rng=np.random.default_rng(1))
    assert prediction.mean.shape == (2,) and np.all(np.isfinite(prediction.mean))
    designer._last_predictive = None  # what a surrogate crossover does
    assert designer._last_predictive is None and designer._unread_fit is None


# -- eager dispatches outside the compiled programs --------------------------


def test_the_counter_counts_eager_operations():
    x = jnp.arange(4.0)
    with EagerDispatches() as eager:
        jax.lax.add(x, x)
        x[1:3]
    assert eager.count >= 2


def test_a_suggest_launches_few_programs_of_its_own():
    """ISSUE 30 allows a cached-fit ``suggest(1)`` 15 eager dispatches outside
    its compiled programs (41 before) and a training ``suggest(25)`` 25 (77
    before). Both read 0 here; the bound of 3 leaves room for a JAX release
    that fetches or splits a key eagerly, and none for a per-leaf slice, a
    stack or an append coming back (12, 12 and 17 dispatches)."""
    problem = _problem("float")
    designer, _, rng = _loaded(problem, 30, seed=8, max_acquisition_evaluations=600)
    suggestions = designer.suggest(25)  # compiles
    designer.suggest(1)
    with EagerDispatches() as cached:
        designer.suggest(1)
    assert cached.count <= 3
    done = [
        _complete(problem, s.to_trial(100 + i), rng, i)
        for i, s in enumerate(suggestions)
    ]
    designer.update(core_lib.CompletedTrials(done), core_lib.ActiveTrials([]))
    assert designer._cached_states is None
    with EagerDispatches() as training:
        assert len(designer.suggest(25)) == 25
    assert training.count <= 3
