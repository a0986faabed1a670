"""Retrace regression guard: the jit cache must be stable within a padding
bucket and grow by exactly one entry at a bucket boundary.

Padding (``converters.padding``) exists so the designers' jitted programs
compile once per ``(pad_trials, features)`` bucket — every retrace costs
~seconds of XLA compile on TPU and silently destroys serving latency. This
test pins that contract for the hot entry points of both GP designers:
growing a study within one bucket must not add cache entries; crossing a
bucket boundary must add exactly one.
"""

import numpy as np
import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.designers import gp_bandit as gp_bandit_lib
from vizier_tpu.designers import gp_ucb_pe as gp_ucb_pe_lib
from vizier_tpu.optimizers import lbfgs as lbfgs_lib

from tests import program_driver

_FAST = dict(
    ard_optimizer=lbfgs_lib.AdamOptimizer(maxiter=10),
    ard_restarts=2,
    max_acquisition_evaluations=200,
)


def _problem():
    p = vz.ProblemStatement()
    for d in range(2):
        p.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
    p.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    return p


def _trials(start_id, n, seed):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        t = vz.Trial(
            parameters={"x0": float(rng.uniform()), "x1": float(rng.uniform())},
            id=start_id + i,
        )
        t.complete(vz.Measurement(metrics={"obj": float(rng.uniform())}))
        out.append(t)
    return out


def _cache_sizes(fns):
    return tuple(fn._cache_size() for fn in fns)


class TestGPBanditJitStability:
    def test_stable_within_bucket_one_retrace_at_boundary(self):
        fns = (gp_bandit_lib._train_gp, gp_bandit_lib._maximize_acquisition)
        designer = gp_bandit_lib.VizierGPBandit(_problem(), rng_seed=0, **_FAST)

        designer.update(core_lib.CompletedTrials(_trials(1, 4, seed=0)))
        designer.suggest(1)
        baseline = _cache_sizes(fns)

        # Growing 4 -> 8 trials stays inside the pad_trials=8 bucket: the
        # jit cache must not move while the study grows within it.
        for step in range(4):
            designer.update(
                core_lib.CompletedTrials(_trials(5 + step, 1, seed=10 + step))
            )
            designer.suggest(1)
            assert _cache_sizes(fns) == baseline, (
                f"retrace inside padding bucket at {5 + step} trials"
            )

        # Trial 9 crosses into the pad_trials=16 bucket: exactly one new
        # cache entry per program, never more.
        designer.update(core_lib.CompletedTrials(_trials(9, 1, seed=99)))
        designer.suggest(1)
        grown = _cache_sizes(fns)
        assert grown == tuple(b + 1 for b in baseline), (
            f"bucket boundary must add exactly one entry: {baseline} -> {grown}"
        )

        # And the new bucket is itself stable.
        designer.update(core_lib.CompletedTrials(_trials(10, 1, seed=100)))
        designer.suggest(1)
        assert _cache_sizes(fns) == grown


class TestGPUCBPEJitStability:
    def test_stable_within_bucket_one_retrace_at_boundary(self):
        fns = (gp_bandit_lib._train_gp, gp_ucb_pe_lib._suggest_batch)
        designer = gp_ucb_pe_lib.VizierGPUCBPEBandit(
            _problem(), rng_seed=0, **_FAST
        )

        designer.update(core_lib.CompletedTrials(_trials(1, 3, seed=0)))
        designer.suggest(1)
        baseline = _cache_sizes(fns)

        # 3 -> 7 completed trials: training data stays in the pad=8 bucket
        # AND the all-points set (trials + 1 batch pick) stays <= 8, so
        # neither program may retrace.
        for step in range(4):
            designer.update(
                core_lib.CompletedTrials(_trials(4 + step, 1, seed=10 + step))
            )
            designer.suggest(1)
            assert _cache_sizes(fns) == baseline, (
                f"retrace inside padding bucket at {4 + step} trials"
            )

        # Trial 8: training data still pads to 8, but the all-points set
        # (8 + 1 pick = 9 rows) crosses into the 16 bucket — the batch-loop
        # program retraces once, the ARD program must not.
        designer.update(core_lib.CompletedTrials(_trials(8, 1, seed=99)))
        designer.suggest(1)
        train_base, sweep_base = baseline
        assert gp_bandit_lib._train_gp._cache_size() == train_base
        assert gp_ucb_pe_lib._suggest_batch._cache_size() == sweep_base + 1


class TestSparseJitStability:
    """The sparse programs compile once per (n-bucket, m-bucket) pair."""

    def _sparse_designer(self, seed, num_inducing=6):
        from vizier_tpu.surrogates import SurrogateConfig

        cfg = SurrogateConfig(
            sparse_threshold_trials=1, hysteresis_trials=0,
            num_inducing=num_inducing,
        )
        return gp_bandit_lib.VizierGPBandit(
            _problem(), rng_seed=seed, surrogate=cfg, num_seed_trials=1,
            **_FAST,
        )

    def test_stable_within_bucket_one_retrace_at_n_boundary(self):
        from vizier_tpu.surrogates import sparse_bandit

        fns = (
            sparse_bandit._train_sparse_gp,
            sparse_bandit._maximize_sparse_acquisition,
        )
        designer = self._sparse_designer(seed=0)
        designer.update(core_lib.CompletedTrials(_trials(1, 4, seed=0)))
        designer.suggest(1)
        assert designer.surrogate_mode == "sparse"
        baseline = _cache_sizes(fns)

        # Growing 4 -> 8 trials stays inside the pad_trials=8 bucket (the
        # m-bucket is fixed at 8 inducing slots): no retrace allowed.
        for step in range(4):
            designer.update(
                core_lib.CompletedTrials(_trials(5 + step, 1, seed=10 + step))
            )
            designer.suggest(1)
            assert _cache_sizes(fns) == baseline, (
                f"sparse retrace inside padding bucket at {5 + step} trials"
            )

        # Trial 9 crosses into the pad_trials=16 n-bucket: exactly one new
        # entry per program.
        designer.update(core_lib.CompletedTrials(_trials(9, 1, seed=99)))
        designer.suggest(1)
        grown = _cache_sizes(fns)
        assert grown == tuple(b + 1 for b in baseline), (
            f"n-bucket boundary must add exactly one entry: {baseline} -> {grown}"
        )

        # And the new (n, m) pair is itself stable.
        designer.update(core_lib.CompletedTrials(_trials(10, 1, seed=100)))
        designer.suggest(1)
        assert _cache_sizes(fns) == grown

    def test_m_bucket_boundary_and_same_bucket_m_values(self):
        from vizier_tpu.surrogates import sparse_bandit

        train = sparse_bandit._train_sparse_gp
        base = self._sparse_designer(seed=1, num_inducing=6)
        base.update(core_lib.CompletedTrials(_trials(1, 4, seed=1)))
        base.suggest(1)
        size = train._cache_size()

        # m=7 pads to the SAME 8-slot m-bucket as m=6: one shared program.
        same_bucket = self._sparse_designer(seed=2, num_inducing=7)
        same_bucket.update(core_lib.CompletedTrials(_trials(1, 4, seed=2)))
        same_bucket.suggest(1)
        assert train._cache_size() == size, (
            "m values inside one inducing bucket must share a program"
        )

        # m=12 pads to 16 slots: a new m-bucket, exactly one new entry.
        new_bucket = self._sparse_designer(seed=3, num_inducing=12)
        new_bucket.update(core_lib.CompletedTrials(_trials(1, 4, seed=3)))
        new_bucket.suggest(1)
        assert train._cache_size() == size + 1

    def test_sparse_flush_program_stable_across_flushes_within_bucket(self):
        from vizier_tpu.surrogates import sparse_bandit

        def fresh(seed, n):
            d = self._sparse_designer(seed)
            d.update(core_lib.CompletedTrials(_trials(1, n, seed=seed)))
            return d

        def flush(seeds, n):
            designers = [fresh(s, n) for s in seeds]
            # Same calling convention as the executor: resolving the bucket
            # refreshes each designer's surrogate mode before prepare.
            assert (
                program_driver.bucket_key(designers[0], 1).kind
                == "gp_bandit_sparse"
            )
            program_driver.flush(designers, 1, pad_to=len(designers))

        program = sparse_bandit._sparse_flush_program
        flush((40, 41), n=4)
        size = program._cache_size()
        flush((42, 43), n=5)  # same (n, m) bucket pair, different studies
        assert program._cache_size() == size

        flush((44, 45), n=9)  # n-bucket boundary: exactly one new entry
        assert program._cache_size() == size + 1


class TestSparseUCBPEJitStability:
    """The sparse UCB-PE programs compile once per (n-bucket, m-bucket)
    pair — including the augmented-capacity re-conditioning model."""

    def _designer(self, seed, num_inducing=6):
        from vizier_tpu.surrogates import SurrogateConfig

        cfg = SurrogateConfig(
            sparse_threshold_trials=1, hysteresis_trials=0,
            num_inducing=num_inducing,
        )
        return gp_ucb_pe_lib.VizierGPUCBPEBandit(
            _problem(), rng_seed=seed, surrogate=cfg, **_FAST
        )

    def test_sequential_stable_within_bucket_one_retrace_at_n_boundary(self):
        from vizier_tpu.surrogates import sparse_bandit

        fns = (sparse_bandit._train_sparse_gp, gp_ucb_pe_lib._suggest_batch)
        designer = self._designer(seed=0)
        designer.update(core_lib.CompletedTrials(_trials(1, 3, seed=0)))
        designer.suggest(1)
        assert designer.surrogate_mode == "sparse"
        baseline = _cache_sizes(fns)

        # 3 -> 7 completed trials: the n-bucket stays 8 and the all-points
        # set (trials + 1 pick) stays <= 8 — no retrace of either program.
        for step in range(4):
            designer.update(
                core_lib.CompletedTrials(_trials(4 + step, 1, seed=10 + step))
            )
            designer.suggest(1)
            assert _cache_sizes(fns) == baseline, (
                f"sparse UCB-PE retrace inside bucket at {4 + step} trials"
            )

        # Trial 8: the all-points set (8 + 1 pick) crosses into the 16
        # bucket — the batch-loop program retraces once, the ARD must not.
        designer.update(core_lib.CompletedTrials(_trials(8, 1, seed=99)))
        designer.suggest(1)
        train_base, sweep_base = baseline
        from vizier_tpu.surrogates import sparse_bandit as sb

        assert sb._train_sparse_gp._cache_size() == train_base
        assert gp_ucb_pe_lib._suggest_batch._cache_size() == sweep_base + 1

    def test_m_bucket_boundary_and_same_bucket_m_values(self):
        from vizier_tpu.surrogates import sparse_bandit

        # 10 trials put the study in the n=16 bucket: an (n, m) grid point
        # no other test's train program touches (the sparse ARD program is
        # deliberately SHARED with the gp_bandit sparse path, so colliding
        # grid points would hide real retraces).
        train = sparse_bandit._train_sparse_gp
        base = self._designer(seed=1, num_inducing=6)
        base.update(core_lib.CompletedTrials(_trials(1, 10, seed=1)))
        base.suggest(1)
        size = train._cache_size()

        # m=7 pads to the SAME 8-slot m-bucket as m=6: one shared program.
        same_bucket = self._designer(seed=2, num_inducing=7)
        same_bucket.update(core_lib.CompletedTrials(_trials(1, 10, seed=2)))
        same_bucket.suggest(1)
        assert train._cache_size() == size, (
            "m values inside one inducing bucket must share a program"
        )

        # m=12 pads to 16 slots: a new (n=16, m=16) pair, exactly one new
        # entry.
        new_bucket = self._designer(seed=3, num_inducing=12)
        new_bucket.update(core_lib.CompletedTrials(_trials(1, 10, seed=3)))
        new_bucket.suggest(1)
        assert train._cache_size() == size + 1

    def test_sparse_flush_program_stable_across_flushes_within_bucket(self):
        def fresh(seed, n):
            d = self._designer(seed)
            d.update(core_lib.CompletedTrials(_trials(1, n, seed=seed)))
            return d

        def flush(seeds, n):
            designers = [fresh(s, n) for s in seeds]
            assert (
                program_driver.bucket_key(designers[0], 1).kind
                == "gp_ucb_pe_sparse"
            )
            program_driver.flush(designers, 1, pad_to=len(designers))

        program = gp_ucb_pe_lib._sparse_ucb_pe_flush_program
        flush((40, 41), n=3)
        size = program._cache_size()
        flush((42, 43), n=4)  # same (n, m) bucket pair, different studies
        assert program._cache_size() == size

        flush((44, 45), n=9)  # n-bucket boundary: exactly one new entry
        assert program._cache_size() == size + 1


class TestIRRoutedProgramJitStability:
    """The compute-IR port must not change compile-cache behavior: flushes
    routed through the registered programs share one compiled body per
    bucket, +1 exactly at a bucket boundary."""

    def test_ir_routed_flushes_share_the_bucket_program(self):
        def fresh(seed, n):
            d = gp_bandit_lib.VizierGPBandit(_problem(), rng_seed=seed, **_FAST)
            d.update(core_lib.CompletedTrials(_trials(1, n, seed=seed)))
            return d

        # count=2 keeps this test's compiled programs disjoint from the
        # count=1 flushes other tests in this file drive (count is a jit
        # static of the same shared flush body).
        def flush(seeds, n):
            designers = [fresh(s, n) for s in seeds]
            assert program_driver.bucket_key(designers[0], 2).kind == "gp_bandit"
            program_driver.flush(designers, 2, pad_to=len(designers))

        body = gp_bandit_lib._gp_bandit_flush_program
        flush((60, 61), n=4)
        size = body._cache_size()
        flush((62, 63), n=5)  # same bucket through the IR: no retrace
        assert body._cache_size() == size
        flush((64, 65), n=9)  # boundary: exactly one new entry
        assert body._cache_size() == size + 1


class TestBatchedProgramJitStability:
    def test_batched_programs_stable_across_flushes_within_bucket(self):
        # Two batched flushes over different studies in the same bucket
        # must share one compiled multi-study program.
        def fresh(seed, n):
            d = gp_bandit_lib.VizierGPBandit(_problem(), rng_seed=seed, **_FAST)
            d.update(core_lib.CompletedTrials(_trials(1, n, seed=seed)))
            return d

        def flush(seeds, n):
            designers = [fresh(s, n) for s in seeds]
            program_driver.flush(designers, 1, pad_to=len(designers))

        program = gp_bandit_lib._gp_bandit_flush_program
        flush((0, 1), n=4)
        size = program._cache_size()
        flush((2, 3), n=5)  # same pad bucket, different studies/data
        assert program._cache_size() == size

        flush((4, 5), n=9)  # bucket boundary: exactly one new entry
        assert program._cache_size() == size + 1


class TestMeshJitStability:
    """Mesh-mode compile contract: one compiled flush program per (bucket,
    placement, shard-granularity grid step).

    The mesh executor pads a placement's flushes to
    ``DevicePlacement.pad_to`` (power-of-two multiples of its device
    count) instead of the flat pad-to-max — so the compiled-shape set per
    (bucket, placement) is exactly the small ``pad_grid``, stable at fixed
    occupancy, +1 when the occupancy crosses a grid step, and +1 when the
    SAME bucket compiles on a different placement (sticky assignment makes
    that a prewarm-only event in production)."""

    def test_one_program_per_bucket_placement_grid_step(self):
        import jax

        from vizier_tpu.compute import registry as compute_registry
        from vizier_tpu.parallel.mesh import DevicePlacement

        def fresh(seed, n):
            d = gp_bandit_lib.VizierGPBandit(_problem(), rng_seed=seed, **_FAST)
            d.update(core_lib.CompletedTrials(_trials(1, n, seed=seed)))
            return d

        # count=3 keeps this test's compiled programs disjoint from the
        # count=1/2 flushes other tests in this file drive.
        def flush(seeds, placement):
            designers = [fresh(s, 4) for s in seeds]
            resolved = [compute_registry.resolve(d, 3) for d in designers]
            assert all(r is not None for r in resolved)
            program = resolved[0][0]
            assert program.shardable_batch_axis == "study"
            items = [program.prepare(d, 3) for d in designers]
            pad_to = placement.pad_to(len(items), 8)
            outs = program.device_program(
                items, pad_to=pad_to, placement=placement
            )
            for d, i, o in zip(designers, items, outs):
                program.finalize(d, i, o)

        body = gp_bandit_lib._gp_bandit_flush_program
        devices = jax.devices()
        p0 = DevicePlacement(0, devices[:1])
        p1 = DevicePlacement(1, devices[1:2])

        flush((70, 71), p0)  # occupancy 2 -> padded 2 on placement 0
        size = body._cache_size()
        flush((72, 73), p0)  # same (bucket, placement, grid step): stable
        assert body._cache_size() == size
        flush((74, 75, 76), p0)  # occupancy 3 -> grid step 4: one new entry
        assert body._cache_size() == size + 1
        flush((77, 78, 79, 80), p0)  # occupancy 4 -> same grid step: stable
        assert body._cache_size() == size + 1
        # The same bucket on a DIFFERENT placement compiles its own
        # program (sticky assignment keeps this out of the serving path).
        flush((81, 82), p1)
        assert body._cache_size() == size + 2
        flush((83, 84), p1)  # and stays stable there too
        assert body._cache_size() == size + 2
