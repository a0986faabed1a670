"""Cross-study batch executor: bucketing, parity, masking, fail isolation."""

import threading
import time

import numpy as np
import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.compute import ir as compute_ir
from vizier_tpu.designers.gp_bandit import VizierGPBandit
from vizier_tpu.designers.gp_ucb_pe import VizierGPUCBPEBandit
from vizier_tpu.optimizers import lbfgs as lbfgs_lib
from vizier_tpu.parallel.batch_executor import (
    BatchExecutor,
    BatchSlotError,
    BucketKey,
)
from vizier_tpu.serving.stats import ServingStats
from vizier_tpu.testing import chaos as chaos_lib

from tests import program_driver

_FAST = dict(
    ard_optimizer=lbfgs_lib.AdamOptimizer(maxiter=15),
    ard_restarts=3,
    max_acquisition_evaluations=200,
    # Parity tests feed ~5 trials and assert warm-state writeback; keep
    # warm seeding engaged below the production floor.
    warm_start_min_trials=0,
)


def _problem(num_params=2, num_metrics=1):
    p = vz.ProblemStatement()
    for d in range(num_params):
        p.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
    for m in range(num_metrics):
        p.metric_information.append(
            vz.MetricInformation(
                name=f"obj{m}" if num_metrics > 1 else "obj",
                goal=vz.ObjectiveMetricGoal.MAXIMIZE,
            )
        )
    return p


def _feed(designer, seed, n=5, num_metrics=1):
    rng = np.random.default_rng(seed)
    trials = []
    for i in range(n):
        t = vz.Trial(
            parameters={"x0": float(rng.uniform()), "x1": float(rng.uniform())},
            id=i + 1,
        )
        names = ["obj"] if num_metrics == 1 else [f"obj{m}" for m in range(num_metrics)]
        t.complete(
            vz.Measurement(metrics={nm: float(rng.uniform()) for nm in names})
        )
        trials.append(t)
    designer.update(core_lib.CompletedTrials(trials))
    return designer


def _gp_bandit(seed):
    return VizierGPBandit(_problem(), rng_seed=seed, **_FAST)


def _gp_ucb_pe(seed):
    return VizierGPUCBPEBandit(_problem(), rng_seed=seed, **_FAST)


def _params(suggestions):
    return [s.parameters.as_dict() for s in suggestions]


def _assert_params_equal(a, b, atol=1e-6):
    assert len(a) == len(b)
    for pa, pb in zip(a, b):
        assert pa.keys() == pb.keys()
        for k in pa:
            assert abs(pa[k] - pb[k]) <= atol, (k, pa[k], pb[k])


# -- a designer-shaped stub for executor mechanics (no GP cost) -------------


def _stub_suggestion(value):
    return vz.TrialSuggestion(parameters={"x": float(value)})


class StubProgram(compute_ir.DesignerProgram):
    """The four hooks with trivial arithmetic (subclasses plant faults)."""

    kind = "stub"
    device_phase = "stub.flush"

    def bucket_key(self, designer, count):
        if not designer.batchable:
            return None
        return BucketKey(
            kind=self.kind,
            pad_trials=8,
            cont_width=1,
            cat_width=0,
            metric_count=1,
            count=count or 1,
            statics=(designer.group,),
        )

    def prepare(self, designer, count):
        return dict(designer=designer, count=count or 1, value=designer.value)

    def device_program(self, items, pad_to=None, placement=None):
        return [dict(value=item["value"]) for item in items]

    def finalize(self, designer, item, output):
        designer.batched = True
        return [_stub_suggestion(output["value"])] * item["count"]

    def prewarm_factory(self, problem, **kwargs):
        raise NotImplementedError


class StubDesigner:
    """An unregistered designer: ``suggest`` and, behind the
    ``compute_program`` hook, its class's stub program."""

    program = StubProgram()

    def __init__(self, value, group="g", batchable=True):
        self.value = value
        self.group = group
        self.batchable = batchable
        self.sequential_calls = 0
        self.batched = False

    def suggest(self, count=1):
        self.sequential_calls += 1
        return [_stub_suggestion(self.value)] * (count or 1)

    def compute_program(self, count=1):
        key = self.program.bucket_key(self, count)
        return None if key is None else (self.program, key)


class FailPrepareProgram(StubProgram):
    def prepare(self, designer, count):
        raise RuntimeError("prepare exploded")


class FailPrepareStub(StubDesigner):
    program = FailPrepareProgram()


class FailExecuteProgram(StubProgram):
    def device_program(self, items, pad_to=None, placement=None):
        raise RuntimeError("device program exploded")


class FailExecuteStub(StubDesigner):
    program = FailExecuteProgram()


class NanProgram(StubProgram):
    def finalize(self, designer, item, output):
        return [_stub_suggestion(float("nan"))]


class NanStub(StubDesigner):
    program = NanProgram()


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
        t.join(timeout=120)
    return results, errors


class TestBucketKeys:
    def test_seeding_stage_unbatchable(self):
        d = _gp_bandit(0)  # no trials yet: quasi-random seeding path
        assert program_driver.bucket_key(d, 1) is None

    def test_multiobjective_unbatchable(self):
        d = VizierGPBandit(_problem(num_metrics=2), rng_seed=0, **_FAST)
        _feed(d, 0, num_metrics=2)
        assert program_driver.bucket_key(d, 1) is None

    def test_priors_unbatchable(self):
        d = _feed(_gp_bandit(0), 0)
        d.set_priors([])
        # An empty priors list is falsy.
        assert program_driver.bucket_key(d, 1) is not None
        d.set_priors([[t for t in d._trials]])
        assert program_driver.bucket_key(d, 1) is None

    def test_same_config_same_bucket(self):
        a, b = _feed(_gp_bandit(1), 1), _feed(_gp_bandit(2), 2)
        assert program_driver.bucket_key(a, 1) == program_driver.bucket_key(b, 1)

    def test_different_shape_different_bucket(self):
        a = _feed(_gp_bandit(1), 1, n=5)  # pad bucket 8
        b = _feed(_gp_bandit(2), 2, n=9)  # pad bucket 16
        assert program_driver.bucket_key(a, 1) != program_driver.bucket_key(b, 1)

    def test_ucb_pe_cached_fit_unbatchable(self):
        d = _feed(_gp_ucb_pe(3), 3, n=4)
        assert program_driver.bucket_key(d, 1) is not None
        d.suggest(1)  # populates the cached fit
        assert program_driver.bucket_key(d, 1) is None


class TestExecutorMechanics:
    def test_full_flush_batches_and_demuxes(self):
        stats = ServingStats()
        ex = BatchExecutor(
            max_batch_size=3, max_wait_ms=5000, stats=stats,
            metrics=stats.registry,
        )
        try:
            designers = [StubDesigner(v) for v in (0.1, 0.2, 0.3)]
            results, errors = _run_concurrent(ex, designers)
            assert errors == [None, None, None]
            for d, r in zip(designers, results):
                assert r[0].parameters.as_dict()["x"] == pytest.approx(d.value)
                assert d.batched and d.sequential_calls == 0
            snap = stats.snapshot()
            assert snap["batch_flushes"] == 1
            assert snap["batched_suggests"] == 3
            text = stats.registry.prometheus_text()
            assert "vizier_batch_occupancy" in text
            assert 'reason="full"' in text
        finally:
            ex.close()

    def test_timeout_flush_singleton_takes_sequential_path(self):
        stats = ServingStats()
        ex = BatchExecutor(max_batch_size=8, max_wait_ms=10, stats=stats)
        try:
            d = StubDesigner(0.7)
            out = ex.suggest(d, 1)
            assert out[0].parameters.as_dict()["x"] == pytest.approx(0.7)
            # A batch of one is the plain per-study path: bit-identical to
            # batching off, no vmap overhead.
            assert d.sequential_calls == 1 and not d.batched
            assert stats.snapshot()["batch_flushes"] == 1
        finally:
            ex.close()

    def test_unbatchable_runs_inline(self):
        ex = BatchExecutor(max_batch_size=4, max_wait_ms=5000)
        try:
            d = StubDesigner(0.4, batchable=False)
            out = ex.suggest(d, 2)
            assert len(out) == 2 and d.sequential_calls == 1
        finally:
            ex.close()

    def test_different_groups_do_not_batch(self):
        ex = BatchExecutor(max_batch_size=2, max_wait_ms=50)
        try:
            a, b = StubDesigner(0.1, group="g1"), StubDesigner(0.2, group="g2")
            results, errors = _run_concurrent(ex, [a, b])
            assert errors == [None, None]
            # Each bucket flushed alone (timeout), hence sequentially.
            assert a.sequential_calls == 1 and b.sequential_calls == 1
        finally:
            ex.close()

    def test_prepare_fault_isolated_to_its_slot(self):
        stats = ServingStats()
        ex = BatchExecutor(max_batch_size=3, max_wait_ms=5000, stats=stats)
        try:
            good = [StubDesigner(0.1), StubDesigner(0.2)]
            bad = FailPrepareStub(0.9)
            results, errors = _run_concurrent(ex, good + [bad])
            assert errors[0] is None and errors[1] is None
            assert isinstance(errors[2], RuntimeError)
            assert all(d.batched for d in good)
            snap = stats.snapshot()
            assert snap["batch_slot_errors"] == 1
            assert snap["batched_suggests"] == 2
        finally:
            ex.close()

    def test_execute_failure_falls_back_to_sequential_per_slot(self):
        stats = ServingStats()
        ex = BatchExecutor(max_batch_size=2, max_wait_ms=5000, stats=stats)
        try:
            designers = [FailExecuteStub(0.3), FailExecuteStub(0.6)]
            results, errors = _run_concurrent(ex, designers)
            assert errors == [None, None]
            for d, r in zip(designers, results):
                assert r[0].parameters.as_dict()["x"] == pytest.approx(d.value)
                assert d.sequential_calls == 1
            assert stats.snapshot()["batch_fallbacks"] == 2
        finally:
            ex.close()

    def test_nan_slot_gets_typed_transient_error(self):
        stats = ServingStats()
        ex = BatchExecutor(max_batch_size=2, max_wait_ms=5000, stats=stats)
        try:
            good, bad = StubDesigner(0.5), NanStub(0.5)
            results, errors = _run_concurrent(ex, [good, bad])
            assert errors[0] is None and good.batched
            assert isinstance(errors[1], BatchSlotError)
            assert "TRANSIENT" in str(errors[1])
            assert stats.snapshot()["batch_slot_errors"] == 1
        finally:
            ex.close()

    def test_close_drains_pending(self):
        ex = BatchExecutor(max_batch_size=8, max_wait_ms=60_000)
        d = StubDesigner(0.8)
        out = [None]
        t = threading.Thread(target=lambda: out.__setitem__(0, ex.suggest(d, 1)))
        t.start()
        import time

        for _ in range(200):  # wait until the slot is queued
            if ex.pending_counts():
                break
            time.sleep(0.005)
        ex.close()
        t.join(timeout=30)
        assert out[0] is not None and out[0][0].parameters.as_dict()["x"] == 0.8


class TestBatchedVsSequentialParity:
    """Same seeds ⇒ identical suggestions slot-by-slot (CPU, f32)."""

    def test_gp_bandit_parity_and_partial_batch_masking(self):
        seeds = (11, 12)
        sequential = [_feed(_gp_bandit(s), s).suggest(1) for s in seeds]

        # Padded partial batch (2 real slots padded to 4) ...
        padded = [_feed(_gp_bandit(s), s) for s in seeds]
        padded_out = program_driver.flush(padded, 1, pad_to=4)
        # ... and the unpadded batch must both match the sequential run:
        # masked filler slots never leak into real slots' posteriors.
        plain = [_feed(_gp_bandit(s), s) for s in seeds]
        plain_out = program_driver.flush(plain, 1, pad_to=None)
        for i in range(len(seeds)):
            _assert_params_equal(_params(sequential[i]), _params(padded_out[i]))
            _assert_params_equal(_params(padded_out[i]), _params(plain_out[i]))
        # Batched designers carry the same trained warm state forward.
        assert padded[0]._warm_is_trained

    def test_gp_ucb_pe_parity_count_1(self):
        seeds = (21, 22)
        sequential = [_feed(_gp_ucb_pe(s), s, n=4).suggest(1) for s in seeds]
        batched = [_feed(_gp_ucb_pe(s), s, n=4) for s in seeds]
        batched_out = program_driver.flush(batched, 1, pad_to=4)
        for i in range(len(seeds)):
            _assert_params_equal(_params(sequential[i]), _params(batched_out[i]))
        # predict() after a batched suggest reuses the cached fit.
        pred = batched[0].predict(batched_out[0])
        assert np.isfinite(pred.mean).all()

    def test_gp_ucb_pe_parity_two_phase_batch(self):
        # count > 1 under first_pick_full: full-budget first pick, split
        # budget for the rest — two vmapped device sweeps.
        seeds = (31, 32)
        sequential = [_feed(_gp_ucb_pe(s), s, n=4).suggest(2) for s in seeds]
        batched = [_feed(_gp_ucb_pe(s), s, n=4) for s in seeds]
        batched_out = program_driver.flush(batched, 2, pad_to=None)
        for i in range(len(seeds)):
            assert len(batched_out[i]) == 2
            _assert_params_equal(_params(sequential[i]), _params(batched_out[i]))

    def test_executor_end_to_end_matches_sequential(self):
        seeds = (41, 42, 43)
        sequential = [_feed(_gp_bandit(s), s).suggest(1) for s in seeds]
        stats = ServingStats()
        ex = BatchExecutor(max_batch_size=3, max_wait_ms=10_000, stats=stats)
        try:
            designers = [_feed(_gp_bandit(s), s) for s in seeds]
            results, errors = _run_concurrent(ex, designers)
            assert errors == [None] * 3
            for i in range(3):
                _assert_params_equal(_params(sequential[i]), _params(results[i]))
            assert stats.snapshot()["batched_suggests"] == 3
        finally:
            ex.close()


class TestChaosIsolation:
    def test_faulting_slot_degrades_only_its_own_study(self):
        monkey = chaos_lib.ChaosMonkey(seed=0, failure_prob=1.0)
        chaotic = chaos_lib.ChaosDesigner(_feed(_gp_bandit(51), 51), monkey)
        healthy = [_feed(_gp_bandit(s), s) for s in (52, 53)]
        sequential = [_feed(_gp_bandit(s), s).suggest(1) for s in (52, 53)]
        stats = ServingStats()
        ex = BatchExecutor(max_batch_size=3, max_wait_ms=10_000, stats=stats)
        try:
            results, errors = _run_concurrent(ex, [chaotic] + healthy)
            # The chaos slot fails at its prepare strike and is dropped from
            # the batch; its error reaches only its own study's waiter.
            assert isinstance(errors[0], chaos_lib.failing.FailedSuggestError)
            assert errors[1] is None and errors[2] is None
            for i, seq in enumerate(sequential):
                _assert_params_equal(_params(seq), _params(results[i + 1]))
            snap = stats.snapshot()
            assert snap["batch_slot_errors"] == 1
            assert snap["batched_suggests"] == 2
            assert monkey.total_faults() == 1
        finally:
            ex.close()

    def test_chaos_execute_poisons_batch_but_sequential_fallback_recovers(self):
        # One strike in device_program kills the shared device program; every
        # slot recovers through its own sequential run (chaos designer's
        # plain suggest also strikes -> ITS slot errors, batchmate succeeds).
        monkey = chaos_lib.ChaosMonkey(seed=0, failure_prob=1.0)
        chaotic = chaos_lib.ChaosDesigner(_feed(_gp_bandit(61), 61), monkey)
        healthy = _feed(_gp_bandit(62), 62)
        # Force the chaos slot to pass prepare: only strike execute/suggest.
        program_driver.pass_prepare(chaotic)
        stats = ServingStats()
        ex = BatchExecutor(max_batch_size=2, max_wait_ms=10_000, stats=stats)
        try:
            import time

            results = [None, None]
            errors = [None, None]

            def run(i, designer):
                try:
                    results[i] = ex.suggest(designer, 1)
                except BaseException as e:  # noqa: BLE001
                    errors[i] = e

            # The chaos designer must arrive FIRST so the flush dispatches
            # through ITS ChaosProgram (the executor runs the first live
            # slot's device_program).
            t0 = threading.Thread(target=run, args=(0, chaotic))
            t0.start()
            for _ in range(400):
                if ex.pending_counts():
                    break
                time.sleep(0.005)
            t1 = threading.Thread(target=run, args=(1, healthy))
            t1.start()
            t0.join(timeout=120)
            t1.join(timeout=120)
            assert isinstance(errors[0], chaos_lib.failing.FailedSuggestError)
            assert errors[1] is None and results[1]
            assert stats.snapshot()["batch_fallbacks"] == 2
        finally:
            ex.close()


class TestPrewarm:
    def test_prewarm_walks_bucket_grid_and_compiles(self):
        ex = BatchExecutor(max_batch_size=2, max_wait_ms=10)
        try:
            report = ex.prewarm(
                _problem(),
                lambda p: VizierGPBandit(p, rng_seed=0, **_FAST),
                max_trials=8,
                counts=(1,),
            )
            # One grid bucket (pad 8) x batch sizes {1, max}.
            assert [r["pad_trials"] for r in report] == [8, 8]
            assert sorted(r["batch_size"] for r in report) == [1, 2]
            assert all(r["status"] == "ok" for r in report)
            assert all(r["seconds"] >= 0 for r in report)
        finally:
            ex.close()


class TestSpeculativeLane:
    """The low-priority lane for serving.speculative pre-computes."""

    def test_queue_depth_reports_lanes(self):
        executor = BatchExecutor(max_batch_size=8, max_wait_ms=10_000)
        try:
            order = []

            def run(designer, speculative):
                order.append(executor.suggest(designer, 1, speculative=speculative))

            live = StubDesigner(1.0, group="live")
            spec = StubDesigner(2.0, group="spec")
            threads = [
                threading.Thread(target=run, args=(spec, True)),
                threading.Thread(target=run, args=(live, False)),
            ]
            for t in threads:
                t.start()
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                depth = executor.queue_depth()
                if depth == {"live": 1, "speculative": 1}:
                    break
                time.sleep(0.001)
            assert executor.queue_depth() == {"live": 1, "speculative": 1}
            assert executor.live_pending() == 1
        finally:
            executor.close()
            for t in threads:
                t.join(timeout=10)

    def test_live_singleton_never_waits_behind_speculative_flush(self):
        """A queued speculative-only bucket must not become due while a
        live slot is queued: the live singleton flushes first.

        Deterministic via a fake clock: nothing becomes due until the
        clock advances, so the speculative bucket cannot sneak an
        idle-window flush in before the live slot is even submitted (a
        real-time race on a loaded machine). Flush ORDER is observed at
        the scheduler's _execute (sequential on its thread) — the
        waiter-side suggest/finalize calls run on racing client threads
        and cannot order-assert reliably."""
        clock = [0.0]
        executor = BatchExecutor(
            max_batch_size=8,
            max_wait_ms=30.0,
            speculative_max_wait_ms=10_000,
            time_fn=lambda: clock[0],
        )
        flush_order = []
        original_execute = executor._execute

        def recording_execute(key, slots, reason, placement=None):
            flush_order.append(
                "spec" if all(s.speculative for s in slots) else "live"
            )
            return original_execute(key, slots, reason, placement)

        executor._execute = recording_execute

        class Recording(StubDesigner):
            def __init__(self, value, group, tag):
                super().__init__(value, group=group)
                self.tag = tag

        try:
            results = {}

            def run(tag, designer, speculative):
                results[tag] = executor.suggest(
                    designer, 1, speculative=speculative
                )

            # Two speculative slots share a bucket (so they'd flush
            # batched); the live singleton arrives afterwards in its own
            # bucket, i.e. with a LATER timeout window — yet must run
            # first because pure-speculative buckets defer to queued live.
            spec_a = Recording(1.0, "spec", "spec")
            spec_b = Recording(2.0, "spec", "spec")
            live = Recording(3.0, "live", "live")
            t1 = threading.Thread(target=run, args=("a", spec_a, True))
            t2 = threading.Thread(target=run, args=("b", spec_b, True))
            t1.start()
            t2.start()
            deadline = time.monotonic() + 5.0
            while (
                executor.queue_depth()["speculative"] < 2
                and time.monotonic() < deadline
            ):
                time.sleep(0.001)
            t3 = threading.Thread(target=run, args=("live", live, False))
            t3.start()
            deadline = time.monotonic() + 5.0
            while (
                executor.live_pending() < 1 and time.monotonic() < deadline
            ):
                time.sleep(0.001)
            # Everything queued at t=0; advance past every window at once.
            clock[0] = 1.0
            for t in (t1, t2, t3):
                t.join(timeout=30)
            assert flush_order[0] == "live", flush_order
            assert set(flush_order) == {"live", "spec"}
        finally:
            executor.close()

    def test_speculative_flushes_in_idle_window(self):
        executor = BatchExecutor(max_batch_size=8, max_wait_ms=5.0)
        try:
            spec = StubDesigner(1.0, group="spec")
            out = executor.suggest(spec, 1, speculative=True)
            assert [s.parameters["x"].value for s in out] == [1.0]
        finally:
            executor.close()

    def test_speculative_rides_a_live_flush(self):
        """A speculative slot in a bucket a live slot joins flushes WITH
        the live batch (shared compute is the good case)."""
        executor = BatchExecutor(max_batch_size=2, max_wait_ms=10_000)
        try:
            spec = StubDesigner(1.0, group="g")
            live = StubDesigner(2.0, group="g")
            results, errors = [None, None], [None, None]

            def run(i, designer, speculative):
                results[i] = executor.suggest(designer, 1, speculative=speculative)

            t1 = threading.Thread(target=run, args=(0, spec, True))
            t1.start()
            deadline = time.monotonic() + 5.0
            while (
                executor.queue_depth()["speculative"] < 1
                and time.monotonic() < deadline
            ):
                time.sleep(0.001)
            t2 = threading.Thread(target=run, args=(1, live, False))
            t2.start()
            t1.join(timeout=30)
            t2.join(timeout=30)
            # Full flush at size 2: both went through the batched path.
            assert spec.batched and live.batched
        finally:
            executor.close()

    def test_starvation_cap_flushes_speculative_under_constant_live(self):
        """speculative_max_wait bounds the hold: a speculative slot is
        flushed eventually even while live slots keep the queues busy."""
        executor = BatchExecutor(
            max_batch_size=8,
            max_wait_ms=10_000,  # live bucket never times out on its own
            speculative_max_wait_ms=30.0,
        )
        try:
            spec = StubDesigner(1.0, group="spec")
            live = StubDesigner(2.0, group="live")
            results = {}

            def run(tag, designer, speculative):
                results[tag] = executor.suggest(
                    designer, 1, speculative=speculative
                )

            t_live = threading.Thread(target=run, args=("live", live, False))
            t_spec = threading.Thread(target=run, args=("spec", spec, True))
            t_live.start()
            t_spec.start()
            # The speculative slot must complete despite the live slot
            # still parked in its (never-due) bucket.
            t_spec.join(timeout=10)
            assert not t_spec.is_alive()
            assert results["spec"] is not None
        finally:
            executor.close()
            t_live.join(timeout=10)

    def test_close_drains_speculative_slots(self):
        executor = BatchExecutor(
            max_batch_size=8, max_wait_ms=10_000, speculative_max_wait_ms=10_000
        )
        spec = StubDesigner(1.0, group="spec")
        result = []
        t = threading.Thread(
            target=lambda: result.append(
                executor.suggest(spec, 1, speculative=True)
            )
        )
        t.start()
        deadline = time.monotonic() + 5.0
        while (
            executor.queue_depth()["speculative"] < 1
            and time.monotonic() < deadline
        ):
            time.sleep(0.001)
        executor.close()
        t.join(timeout=10)
        assert result and result[0] is not None
