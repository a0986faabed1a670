"""A sequential GP-UCB-PE suggest enqueues every device program before the
host waits for any of them (PR 45).

A suggest that trains makes the sweeps' inputs and dispatches the sweeps
inside the train's ``device.wait`` phase, before the block on the trained
states; a suggest on a cached fit has no train to hide behind and keeps the
older order (inputs in ``designer.prepare``, sweeps under the acquire phase).
The eager programs that map the train's optimum back for the next train's
seed go out behind the sweeps: between the train and the sweeps they filled
the device's queue of programs in flight and made the host wait for the
train's end. The order changes no program and no input: the two orders give
one seeded study the same picks, scores and metadata to the bit, exact and
sparse, in its first suggest and in the warm-seeded one after it.
"""

import jax
import numpy as np
import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.designers import gp_ucb_pe
from vizier_tpu.observability import config as config_lib
from vizier_tpu.observability import jax_timing
from vizier_tpu.optimizers import lbfgs as lbfgs_lib
from vizier_tpu.surrogates import SurrogateConfig

SURROGATES = {
    "exact": None,
    "sparse": SurrogateConfig(sparse_threshold_trials=4, hysteresis_trials=0, num_inducing=4),
}
# The module-level programs a suggest dispatches, and the host half of the
# sweeps' inputs (a method).
PROGRAMS = ("_sweep_inputs", "_sparse_all_points", "_suggest_batch", "_append_first_pick")
INPUTS = {"_all_points_data", "_sweep_inputs"}
SWEEPS = {
    25: ["_suggest_batch", "_append_first_pick", "_suggest_batch"],
    1: ["_suggest_batch"],
}


def _problem() -> vz.ProblemStatement:
    problem = vz.ProblemStatement()
    for name in "xyz":
        problem.search_space.root.add_float_param(name, 0.0, 1.0)
    problem.metric_information.append(
        vz.MetricInformation(name="f", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    return problem


def _designer(mode: str) -> gp_ucb_pe.VizierGPUCBPEBandit:
    designer = gp_ucb_pe.VizierGPUCBPEBandit(
        _problem(),
        ard_optimizer=lbfgs_lib.LbfgsOptimizer(maxiter=20),
        ard_restarts=3,
        max_acquisition_evaluations=300,
        warm_start_min_trials=0,
        use_mesh=False,
        surrogate=SURROGATES[mode],
    )
    rng = np.random.default_rng(9)
    trials = []
    for i in range(9):
        x = rng.uniform(size=3)
        trial = vz.Trial(id=i + 1, parameters=dict(zip("xyz", x)))
        trial.complete(vz.Measurement(metrics={"f": float(-np.sum((x - 0.3) ** 2))}))
        trials.append(trial)
    pending = vz.Trial(id=50, parameters=dict(zip("xyz", rng.uniform(size=3))))
    designer.update(core_lib.CompletedTrials(trials), core_lib.ActiveTrials([pending]))
    return designer


@pytest.fixture
def events(monkeypatch):
    """What a suggest did, in order: the phases it opened, the programs it
    dispatched, the host inputs it made, the train's block."""
    seen = []

    def recording(name, real):
        def stub(*args, **kwargs):
            seen.append(name)
            return real(*args, **kwargs)

        return stub

    for name in PROGRAMS:
        monkeypatch.setattr(gp_ucb_pe, name, recording(name, getattr(gp_ucb_pe, name)))
    monkeypatch.setattr(
        gp_ucb_pe.VizierGPUCBPEBandit,
        "_all_points_data",
        recording("_all_points_data", gp_ucb_pe.VizierGPUCBPEBandit._all_points_data),
    )
    monkeypatch.setattr(
        jax_timing._Phase, "block", recording("block", jax_timing._Phase.block)
    )
    seed = gp_ucb_pe.VizierGPUCBPEBandit._seed_next_trains

    def seeding(self):
        if self._unseeded_best is not None:  # else there is nothing to map back
            seen.append("seed")
        return seed(self)

    monkeypatch.setattr(gp_ucb_pe.VizierGPUCBPEBandit, "_seed_next_trains", seeding)
    device_phase = jax_timing.device_phase

    def phase(name, **kwargs):
        seen.append("phase:" + kwargs["stage"])
        return device_phase(name, **kwargs)

    monkeypatch.setattr(jax_timing, "device_phase", phase)
    jax_timing.set_config(config_lib.ObservabilityConfig())
    yield seen
    jax_timing.set_config(None)


def _decoded(designer, monkeypatch):
    """The device results a suggest decodes: [(result, aux, rows)]."""
    kept = []
    decode = designer._decode_ucb_pe

    def keeping(results):
        kept.extend(results)
        return decode(results)

    monkeypatch.setattr(designer, "_decode_ucb_pe", keeping)
    return kept


def _bits(tree):
    return [
        (np.asarray(leaf).dtype, np.asarray(leaf).shape, np.asarray(leaf).tobytes())
        for leaf in jax.tree_util.tree_leaves(tree)
    ]


@pytest.mark.parametrize("count", sorted(SWEEPS))
@pytest.mark.parametrize("mode", sorted(SURROGATES))
def test_a_training_suggest_dispatches_its_sweeps_before_the_trains_block(
    mode, count, events, monkeypatch
):
    sparse = ["_sparse_all_points"] * (mode == "sparse")
    ahead = _designer(mode)
    ahead_results = _decoded(ahead, monkeypatch)
    ahead_trials = ahead.suggest(count)
    train, block, acquire = (events.index(e) for e in ("phase:train", "block", "phase:acquire"))
    # Under the train, before its block: the sweeps' inputs, then every
    # sweep program; the acquire phase dispatches the next train's seed.
    assert train < block < acquire and events[acquire + 1 :] == ["seed"]
    under_train = events[train + 1 : block]
    assert set(under_train[:2]) == INPUTS
    assert under_train[2:] == sparse + SWEEPS[count]
    assert INPUTS.isdisjoint(events[:train])
    counts = ahead.ard_train_counts
    assert counts["sequential_trains"] == 1 >= counts["sweeps_ahead"] and counts["cached"] == 0

    # The parent's order on the same seed: the fit is there before the
    # suggest (``_train_states_me`` draws the train's key as the suggest
    # would), so the suggest blocks on a cached fit and sweeps afterwards.
    del events[:]
    after = _designer(mode)
    after._train_states_me()
    assert events == ["seed"]  # at once, ahead of the sweeps
    del events[:]
    after_results = _decoded(after, monkeypatch)
    after_trials = after.suggest(count)
    train, block, acquire = (events.index(e) for e in ("phase:train", "block", "phase:acquire"))
    assert set(events[:train]) == INPUTS  # made in designer.prepare
    assert events[train + 1 : acquire] == ["block"]
    assert events[acquire + 1 :] == sparse + SWEEPS[count]
    counts = after.ard_train_counts
    assert (counts["sequential_trains"], counts["sweeps_ahead"], counts["cached"]) == (0, 0, 1)

    def same_to_the_bit():
        assert len(ahead_trials) == len(after_trials) == count
        assert [rows for _, _, rows in ahead_results] == [rows for _, _, rows in after_results]
        assert _bits([r[:2] for r in ahead_results]) == _bits([r[:2] for r in after_results])
        for got, want in zip(ahead_trials, after_trials, strict=True):
            assert got.parameters.as_dict() == want.parameters.as_dict()
            assert got.metadata == want.metadata  # acquisition, mean, stddevs, trust radius

    same_to_the_bit()
    assert _bits(ahead._warm_params_me) == _bits(after._warm_params_me)

    # A completion later both train from the seed the first train left.
    for designer, suggested in ((ahead, ahead_trials), (after, after_trials)):
        done = suggested[0].to_trial(60)
        done.complete(vz.Measurement(metrics={"f": -0.05}))
        designer.update(core_lib.CompletedTrials([done]), core_lib.ActiveTrials([]))
    del ahead_results[:], after_results[:]
    ahead_trials = ahead.suggest(count)
    after._train_states_me()
    after_trials = after.suggest(count)
    assert ahead.ard_train_counts["warm"] == after.ard_train_counts["warm"] == 1
    same_to_the_bit()


def test_a_second_suggest_on_the_cached_fit_keeps_the_older_order(events):
    designer = _designer("exact")
    designer.suggest(1)
    del events[:]
    designer.suggest(1)  # nothing completed in between: the fit is cached
    train, acquire = events.index("phase:train"), events.index("phase:acquire")
    # Only the all-points rows are made again (``_sweep_inputs`` is held a fit).
    assert events[:train] == ["_all_points_data"]
    assert events[train + 1 :] == ["block", "phase:acquire", "_suggest_batch"]
    counts = designer.ard_train_counts
    assert (counts["sequential_trains"], counts["cached"]) == (1, 1)


def test_with_the_jax_knob_off_the_order_is_the_same_and_nothing_blocks(events, monkeypatch):
    jax_timing.set_config(config_lib.ObservabilityConfig(jax_profiling=False))
    blocked = []
    real = jax.block_until_ready

    def blocking(tree):
        blocked.append(len(events))
        return real(tree)

    monkeypatch.setattr(jax, "block_until_ready", blocking)
    designer = _designer("exact")
    designer.suggest(25)
    train = events.index("phase:train")
    assert set(events[train + 1 : train + 3]) == INPUTS
    # The phase's block is inert: the first wait is the acquire phase's, on
    # the last sweep's scores, after everything was dispatched.
    assert events[train + 3 :] == SWEEPS[25] + ["block", "phase:acquire", "seed"]
    assert blocked == [len(events)]
    assert designer.ard_train_counts["sequential_trains"] == 1
