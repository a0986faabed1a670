"""Bit-identity of the IR-ported programs vs their pre-port path.

The port moved the designers' ``batch_*`` method bodies into registered
``DesignerProgram`` classes; the pre-port contract — slot i of a batched
flush is bit-identical to study i run alone through the sequential
``suggest`` at the same seed, and a singleton through the executor IS the
sequential path — must survive the move for every ported kind."""

import numpy as np

from vizier_tpu import pyvizier as vz
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.compute import registry as compute_registry
from vizier_tpu.designers.gp_bandit import VizierGPBandit
from vizier_tpu.designers.gp_ucb_pe import VizierGPUCBPEBandit
from vizier_tpu.optimizers import lbfgs as lbfgs_lib
from vizier_tpu.parallel.batch_executor import BatchExecutor
from vizier_tpu.surrogates import SurrogateConfig

from tests import program_driver

_FAST = dict(
    ard_optimizer=lbfgs_lib.AdamOptimizer(maxiter=15),
    ard_restarts=3,
    max_acquisition_evaluations=200,
    warm_start_min_trials=0,
)

_SPARSE = SurrogateConfig(
    sparse_threshold_trials=1, hysteresis_trials=0, num_inducing=6
)


def _problem():
    p = vz.ProblemStatement()
    for d in range(2):
        p.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
    p.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    return p


def _feed(designer, seed, n=5):
    rng = np.random.default_rng(seed)
    trials = []
    for i in range(n):
        t = vz.Trial(
            parameters={"x0": float(rng.uniform()), "x1": float(rng.uniform())},
            id=i + 1,
        )
        t.complete(vz.Measurement(metrics={"obj": float(rng.uniform())}))
        trials.append(t)
    designer.update(core_lib.CompletedTrials(trials))
    return designer


_FACTORIES = {
    "gp_bandit": lambda seed: _feed(
        VizierGPBandit(_problem(), rng_seed=seed, **_FAST), seed
    ),
    "gp_bandit_sparse": lambda seed: _feed(
        VizierGPBandit(
            _problem(), rng_seed=seed, surrogate=_SPARSE, num_seed_trials=1,
            **_FAST,
        ),
        seed,
    ),
    "gp_ucb_pe": lambda seed: _feed(
        VizierGPUCBPEBandit(_problem(), rng_seed=seed, **_FAST), seed
    ),
    "gp_ucb_pe_sparse": lambda seed: _feed(
        VizierGPUCBPEBandit(
            _problem(), rng_seed=seed, surrogate=_SPARSE, **_FAST
        ),
        seed,
    ),
}


def _params(suggestions):
    return [s.parameters.as_dict() for s in suggestions]


def _assert_bit_identical(a, b):
    assert len(a) == len(b)
    for pa, pb in zip(a, b):
        assert pa.keys() == pb.keys()
        for k in pa:
            # Same program, same keys, same inputs: float-EQUAL, not close.
            assert pa[k] == pb[k], (k, pa[k], pb[k])


class TestPortedProgramBitIdentity:
    """For each ported kind: batched slots == sequential runs, bit-for-bit."""

    def _run_kind(self, kind, count, batch_seeds):
        factory = _FACTORIES[kind]
        sequential = [factory(s).suggest(count) for s in batch_seeds]

        batched = [factory(s) for s in batch_seeds]
        assert program_driver.bucket_key(batched[0], count).kind == kind
        results = program_driver.flush(
            batched, count, pad_to=max(4, len(batched))
        )
        for seq, res in zip(sequential, results):
            _assert_bit_identical(_params(seq), _params(res))

    def test_gp_bandit_exact(self):
        self._run_kind("gp_bandit", count=2, batch_seeds=(11, 12, 13))

    def test_gp_bandit_sparse(self):
        self._run_kind("gp_bandit_sparse", count=2, batch_seeds=(21, 22, 23))

    def test_gp_ucb_pe_exact_two_phase(self):
        self._run_kind("gp_ucb_pe", count=3, batch_seeds=(31, 32))

    def test_gp_ucb_pe_exact_count_1(self):
        self._run_kind("gp_ucb_pe", count=1, batch_seeds=(41, 42))

    def test_gp_ucb_pe_sparse_two_phase(self):
        self._run_kind("gp_ucb_pe_sparse", count=3, batch_seeds=(51, 52))

    def test_gp_ucb_pe_sparse_count_1(self):
        self._run_kind("gp_ucb_pe_sparse", count=1, batch_seeds=(61, 62))


class TestExecutorSingletonIsSequential:
    """A lone slot through the IR-routed executor takes the plain
    sequential path — bit-identical to batching off."""

    def _run_kind(self, kind, seed=77):
        reference = _FACTORIES[kind](seed).suggest(1)
        executor = BatchExecutor(max_batch_size=8, max_wait_ms=1.0)
        try:
            routed = executor.suggest(_FACTORIES[kind](seed), 1)
        finally:
            executor.close()
        _assert_bit_identical(_params(reference), _params(routed))

    def test_gp_bandit_exact(self):
        self._run_kind("gp_bandit")

    def test_gp_bandit_sparse(self):
        self._run_kind("gp_bandit_sparse")

    def test_gp_ucb_pe_exact(self):
        self._run_kind("gp_ucb_pe")

    def test_gp_ucb_pe_sparse(self):
        self._run_kind("gp_ucb_pe_sparse")


class TestLoneSlotThroughThePaddedProgram:
    """A lone prepare survivor still rides the fused program, padded with
    copies of itself: bit-identical to its sequential run."""

    def test_padded_singleton_flush_matches_sequential(self):
        d = _FACTORIES["gp_bandit"](7)
        program, key = compute_registry.resolve(d, 1)
        assert program is compute_registry.get("gp_bandit")
        assert key == program.bucket_key(_FACTORIES["gp_bandit"](7), 1)
        (result,) = program_driver.flush([d], 1, pad_to=2)
        reference = _FACTORIES["gp_bandit"](7).suggest(1)
        _assert_bit_identical(_params(reference), _params(result))
