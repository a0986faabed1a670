"""Sparse studies through the cross-study batch executor: bucket
separation from exact studies, slot parity, chaos isolation, prewarm."""

import threading

import numpy as np
import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.designers.gp_bandit import VizierGPBandit
from vizier_tpu.optimizers import lbfgs as lbfgs_lib
from vizier_tpu.parallel.batch_executor import BatchExecutor
from vizier_tpu.serving.stats import ServingStats
from vizier_tpu.surrogates import SurrogateConfig
from vizier_tpu.testing import chaos as chaos_lib

from tests import program_driver

_FAST = dict(
    ard_optimizer=lbfgs_lib.AdamOptimizer(maxiter=15),
    ard_restarts=3,
    max_acquisition_evaluations=200,
    warm_start_min_trials=0,
    num_seed_trials=1,
)

# Sparse from 4 completed trials on; m=6 pads into the 8-slot bucket.
_SPARSE = SurrogateConfig(
    sparse_threshold_trials=4, hysteresis_trials=0, num_inducing=6
)


def _problem():
    p = vz.ProblemStatement()
    for d in range(2):
        p.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
    p.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    return p


def _feed(designer, seed, n=6):
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


def _sparse_designer(seed):
    return VizierGPBandit(_problem(), rng_seed=seed, surrogate=_SPARSE, **_FAST)


def _exact_designer(seed):
    return VizierGPBandit(_problem(), rng_seed=seed, **_FAST)


def _params(suggestions):
    return [s.parameters.as_dict() for s in suggestions]


def _assert_params_equal(a, b, atol=1e-6):
    assert len(a) == len(b)
    for pa, pb in zip(a, b):
        assert pa.keys() == pb.keys()
        for k in pa:
            assert abs(pa[k] - pb[k]) <= atol, (k, pa[k], pb[k])


def _run_concurrent(executor, designers, count=1):
    results = [None] * len(designers)
    errors = [None] * len(designers)

    def run(i):
        try:
            results[i] = executor.suggest(designers[i], count)
        except BaseException as e:  # noqa: BLE001 - tests inspect the error
            errors[i] = e

    threads = [
        threading.Thread(target=run, args=(i,)) for i in range(len(designers))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
    return results, errors


class TestBucketSeparation:
    def test_sparse_and_exact_studies_land_in_different_buckets(self):
        sparse_key = program_driver.bucket_key(_feed(_sparse_designer(1), 1), 1)
        exact_key = program_driver.bucket_key(_feed(_exact_designer(2), 2), 1)
        assert sparse_key is not None and exact_key is not None
        assert sparse_key.kind == "gp_bandit_sparse"
        assert exact_key.kind == "gp_bandit"
        assert sparse_key != exact_key

    def test_same_sparse_config_same_bucket(self):
        a = program_driver.bucket_key(_feed(_sparse_designer(3), 3), 1)
        b = program_driver.bucket_key(_feed(_sparse_designer(4), 4), 1)
        assert a == b

    def test_different_inducing_bucket_different_key(self):
        # m=6 pads to 8 slots; m=12 pads to 16 — a different compiled
        # program family, so a different bucket.
        big_m = SurrogateConfig(
            sparse_threshold_trials=4, hysteresis_trials=0, num_inducing=12
        )
        d_big = VizierGPBandit(_problem(), rng_seed=5, surrogate=big_m, **_FAST)
        a = program_driver.bucket_key(_feed(_sparse_designer(5), 5), 1)
        b = program_driver.bucket_key(_feed(d_big, 6), 1)
        assert a != b

    def test_below_threshold_uses_exact_bucket(self):
        cfg = SurrogateConfig(
            sparse_threshold_trials=100, hysteresis_trials=0, num_inducing=6
        )
        d = VizierGPBandit(_problem(), rng_seed=7, surrogate=cfg, **_FAST)
        key = program_driver.bucket_key(_feed(d, 7), 1)
        assert key.kind == "gp_bandit"


class TestSparseBatchedParity:
    def test_batched_slots_match_sequential_sparse(self):
        seeds = (11, 12)
        sequential = [_feed(_sparse_designer(s), s).suggest(1) for s in seeds]

        batched = [_feed(_sparse_designer(s), s) for s in seeds]
        assert program_driver.bucket_key(batched[0], 1).kind == "gp_bandit_sparse"
        batched_out = program_driver.flush(batched, 1, pad_to=4)
        for i in range(len(seeds)):
            _assert_params_equal(_params(sequential[i]), _params(batched_out[i]))
        # Batched sparse suggests update the designer's sparse bookkeeping.
        assert batched[0].surrogate_counts["sparse_suggests"] == 1
        assert batched[0].sparse_inducing_state() is not None
        assert batched[0]._warm_is_trained

    # ~26 s end-to-end soak on a 1-core box; the per-kind slot parity it
    # composes is asserted directly by the faster tests in this class, so
    # the mixed-traffic composition rides the slow tier (tier-1 timing,
    # ROADMAP.md).
    @pytest.mark.slow
    def test_mixed_workload_end_to_end(self):
        # 2 exact + 2 sparse studies submitted concurrently: each kind
        # fuses into its own flush, and every slot matches its sequential
        # twin exactly.
        exact_seeds, sparse_seeds = (21, 22), (23, 24)
        seq_exact = [_feed(_exact_designer(s), s).suggest(1) for s in exact_seeds]
        seq_sparse = [
            _feed(_sparse_designer(s), s).suggest(1) for s in sparse_seeds
        ]

        stats = ServingStats()
        ex = BatchExecutor(max_batch_size=2, max_wait_ms=10_000, stats=stats)
        try:
            designers = [_feed(_exact_designer(s), s) for s in exact_seeds] + [
                _feed(_sparse_designer(s), s) for s in sparse_seeds
            ]
            results, errors = _run_concurrent(ex, designers)
            assert errors == [None] * 4
            for i in range(2):
                _assert_params_equal(_params(seq_exact[i]), _params(results[i]))
                _assert_params_equal(
                    _params(seq_sparse[i]), _params(results[i + 2])
                )
            assert stats.snapshot()["batched_suggests"] == 4
        finally:
            ex.close()


class TestSparseChaosIsolation:
    def test_faulting_sparse_slot_degrades_only_its_own_study(self):
        monkey = chaos_lib.ChaosMonkey(seed=0, failure_prob=1.0)
        chaotic = chaos_lib.ChaosDesigner(_feed(_sparse_designer(31), 31), monkey)
        healthy = [_feed(_sparse_designer(s), s) for s in (32, 33)]
        sequential = [_feed(_sparse_designer(s), s).suggest(1) for s in (32, 33)]
        stats = ServingStats()
        ex = BatchExecutor(max_batch_size=3, max_wait_ms=10_000, stats=stats)
        try:
            results, errors = _run_concurrent(ex, [chaotic] + healthy)
            assert isinstance(errors[0], chaos_lib.failing.FailedSuggestError)
            assert errors[1] is None and errors[2] is None
            for i, seq in enumerate(sequential):
                _assert_params_equal(_params(seq), _params(results[i + 1]))
            snap = stats.snapshot()
            assert snap["batch_slot_errors"] == 1
            assert snap["batched_suggests"] == 2
        finally:
            ex.close()


class TestSparsePrewarm:
    def test_prewarm_compiles_the_sparse_flush_program(self):
        from vizier_tpu.surrogates import sparse_bandit

        # Threshold 2 makes every prewarm bucket (>= 8 synthetic trials)
        # sparse, exercising the sparse program family end to end.
        cfg = SurrogateConfig(
            sparse_threshold_trials=2, hysteresis_trials=0, num_inducing=6
        )
        # A search-space shape no other test compiles, so the cache-growth
        # assertion holds regardless of in-process test order.
        problem = vz.ProblemStatement()
        for d in range(3):
            problem.search_space.root.add_float_param(f"p{d}", 0.0, 1.0)
        problem.metric_information.append(
            vz.MetricInformation(
                name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE
            )
        )
        before = sparse_bandit._sparse_flush_program._cache_size()
        ex = BatchExecutor(max_batch_size=2, max_wait_ms=10)
        try:
            report = ex.prewarm(
                problem,
                lambda p: VizierGPBandit(p, rng_seed=0, surrogate=cfg, **_FAST),
                max_trials=8,
                counts=(1,),
            )
            assert [r["pad_trials"] for r in report] == [8, 8]
            assert all(r["status"] == "ok" for r in report)
            # The batched (size=max) prewarm leg compiled the sparse flush.
            assert sparse_bandit._sparse_flush_program._cache_size() > before
        finally:
            ex.close()
