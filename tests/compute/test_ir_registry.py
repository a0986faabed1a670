"""Compute-IR registry: resolution order, adapters, and the registered set."""

import numpy as np
import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.compute import ir as compute_ir
from vizier_tpu.compute import registry as compute_registry
from vizier_tpu.designers import gp_bandit as gp_bandit_lib
from vizier_tpu.designers import gp_ucb_pe as gp_ucb_pe_lib
from vizier_tpu.optimizers import lbfgs as lbfgs_lib
from vizier_tpu.surrogates import SurrogateConfig
from vizier_tpu.testing import chaos as chaos_lib

_FAST = dict(
    ard_optimizer=lbfgs_lib.AdamOptimizer(maxiter=10),
    ard_restarts=2,
    max_acquisition_evaluations=200,
    warm_start_min_trials=0,
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


_SPARSE = SurrogateConfig(
    sparse_threshold_trials=1, hysteresis_trials=0, num_inducing=6
)


class TestRegisteredSet:
    def test_builtin_kinds(self):
        assert set(compute_registry.kinds()) >= {
            "gp_bandit",
            "gp_bandit_sparse",
            "gp_ucb_pe",
            "gp_ucb_pe_sparse",
        }

    def test_every_program_satisfies_the_contract(self):
        for program in compute_registry.programs():
            assert program.kind
            assert program.device_phase
            assert program.surrogate_family in ("exact", "sparse")
            assert isinstance(program, compute_ir.DesignerProgram)
            # prewarm coverage: the factory builds a real designer.
            d = program.prewarm_factory(_problem())
            assert hasattr(d, "suggest")

    def test_get_by_kind(self):
        assert compute_registry.get("gp_bandit").kind == "gp_bandit"
        assert compute_registry.get("nope") is None

    def test_programs_for_algorithm(self):
        default = compute_registry.programs_for_algorithm("DEFAULT")
        assert {p.kind for p in default} == {"gp_ucb_pe", "gp_ucb_pe_sparse"}
        gpb = compute_registry.programs_for_algorithm("gaussian_process_bandit")
        assert {p.kind for p in gpb} == {"gp_bandit", "gp_bandit_sparse"}
        assert compute_registry.programs_for_algorithm("RANDOM_SEARCH") == ()


class TestResolution:
    def test_gp_bandit_resolves_exact(self):
        d = _feed(gp_bandit_lib.VizierGPBandit(_problem(), rng_seed=0, **_FAST), 0)
        program, key = compute_registry.resolve(d, 1)
        assert program.kind == key.kind == "gp_bandit"

    def test_gp_bandit_sparse_mode_resolves_sparse_program(self):
        d = _feed(
            gp_bandit_lib.VizierGPBandit(
                _problem(), rng_seed=0, surrogate=_SPARSE, num_seed_trials=1,
                **_FAST,
            ),
            0,
        )
        program, key = compute_registry.resolve(d, 1)
        assert program.kind == key.kind == "gp_bandit_sparse"

    def test_ucb_pe_subclass_resolves_its_own_programs(self):
        # VizierGPUCBPEBandit subclasses VizierGPBandit: MRO resolution must
        # stop at the most-derived registered type.
        d = _feed(
            gp_ucb_pe_lib.VizierGPUCBPEBandit(_problem(), rng_seed=0, **_FAST), 0
        )
        program, key = compute_registry.resolve(d, 1)
        assert program.kind == key.kind == "gp_ucb_pe"

    def test_ucb_pe_sparse_mode_resolves_sparse_program(self):
        d = _feed(
            gp_ucb_pe_lib.VizierGPUCBPEBandit(
                _problem(), rng_seed=0, surrogate=_SPARSE, **_FAST
            ),
            0,
        )
        program, key = compute_registry.resolve(d, 1)
        assert program.kind == key.kind == "gp_ucb_pe_sparse"

    def test_seeding_stage_resolves_none(self):
        d = gp_bandit_lib.VizierGPBandit(_problem(), rng_seed=0, **_FAST)
        assert compute_registry.resolve(d, 1) is None

    def test_unregistered_designer_is_served_by_suggest(self):
        # No registered program and no compute_program hook: the designer
        # resolves to None and the executor serves it through its
        # suggest().
        from vizier_tpu.parallel.batch_executor import BatchExecutor

        class Unregistered:
            def suggest(self, count=1):
                return ["s"] * (count or 1)

        designer = Unregistered()
        assert compute_registry.resolve(designer, 2) is None
        executor = BatchExecutor(max_batch_size=4, max_wait_ms=5000)
        try:
            assert executor.suggest(designer, 2) == ["s", "s"]
            assert executor.pending_counts() == {}
        finally:
            executor.close()

    def test_plain_designer_resolves_none(self):
        class Plain:
            def suggest(self, count=1):
                return []

        assert compute_registry.resolve(Plain(), 1) is None

    def test_chaos_wrapper_resolves_chaos_program(self):
        monkey = chaos_lib.ChaosMonkey(seed=0, failure_prob=0.0)
        inner = _feed(
            gp_bandit_lib.VizierGPBandit(_problem(), rng_seed=0, **_FAST), 0
        )
        wrapped = chaos_lib.ChaosDesigner(inner, monkey)
        program, key = compute_registry.resolve(wrapped, 1)
        assert isinstance(program, chaos_lib.ChaosProgram)
        assert key.kind == "gp_bandit"
        assert program.kind == "gp_bandit"
        assert program.device_phase == "gp_bandit.suggest_batched"

    def test_register_validates_kind(self):
        class NoKind(compute_ir.DesignerProgram):
            def bucket_key(self, designer, count):
                return None

            def prepare(self, designer, count):
                return {}

            def device_program(self, items, pad_to=None):
                return []

            def finalize(self, designer, item, output):
                return []

            def prewarm_factory(self, problem, **kwargs):
                raise NotImplementedError

        with pytest.raises(ValueError):
            compute_registry.register(object, NoKind())
