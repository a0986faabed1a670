"""Mesh execution plane: placements, scheduling, parity, fail isolation.

Runs on the suite's virtual 8-device CPU mesh (tests/conftest.py sets
``--xla_force_host_platform_device_count=8``). The contracts pinned here:

- ``VIZIER_MESH=0`` / ``MeshConfig()`` never builds placements — the
  executor is the bit-identical single-device seed path;
- a mesh of size 1 serves suggestions bit-identical to the single-device
  executor, and an 8-device sharded flush is slot-by-slot bit-identical
  to the sequential path;
- buckets are sticky-assigned across placements and execute on
  per-placement workers concurrently;
- a device-program failure on ONE placement degrades only that flush's
  slots (sequential fallback / isolated errors) while other placements
  keep serving.
"""

import threading
import time

import numpy as np
import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.designers.gp_ucb_pe import VizierGPUCBPEBandit
from vizier_tpu.optimizers import lbfgs as lbfgs_lib
from vizier_tpu.parallel.batch_executor import BatchExecutor
from vizier_tpu.parallel.mesh import DevicePlacement, MeshConfig, build_placements
from vizier_tpu.serving.stats import ServingStats
from vizier_tpu.testing import chaos as chaos_lib

from tests import program_driver
from tests.parallel.test_batch_executor import (  # noqa: F401  (shared idioms)
    StubDesigner,
    StubProgram,
    _run_concurrent,
)

_FAST = dict(
    ard_optimizer=lbfgs_lib.AdamOptimizer(maxiter=15),
    ard_restarts=3,
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


def _designer(seed, n=5, **overrides):
    kwargs = dict(_FAST, **overrides)
    d = VizierGPUCBPEBandit(_problem(), rng_seed=seed, **kwargs)
    rng = np.random.default_rng(seed)
    trials = []
    for i in range(n):
        t = vz.Trial(
            parameters={"x0": float(rng.uniform()), "x1": float(rng.uniform())},
            id=i + 1,
        )
        t.complete(vz.Measurement(metrics={"obj": float(rng.uniform())}))
        trials.append(t)
    d.update(core_lib.CompletedTrials(trials))
    return d


def _params(suggestions):
    return [s.parameters.as_dict() for s in suggestions]


class TestMeshConfigAndPlacements:
    def test_default_config_is_off(self):
        config = MeshConfig.from_env()
        assert not config.enabled

    def test_executor_without_mesh_has_no_placements(self):
        ex = BatchExecutor(mesh=MeshConfig())  # enabled=False
        assert not ex.mesh_enabled
        assert ex.placements() == []
        ex.close()

    def test_build_placements_shard_groups(self):
        ones = build_placements(MeshConfig(enabled=True, shard_devices=1))
        assert len(ones) == 8
        assert all(p.num_devices == 1 for p in ones)
        pairs = build_placements(MeshConfig(enabled=True, shard_devices=2))
        assert len(pairs) == 4
        assert all(p.num_devices == 2 for p in pairs)
        whole = build_placements(MeshConfig(enabled=True, shard_devices=8))
        assert len(whole) == 1 and whole[0].num_devices == 8
        capped = build_placements(
            MeshConfig(enabled=True, num_devices=4, shard_devices=2)
        )
        assert len(capped) == 2
        # Devices are disjoint across placements.
        seen = [d.id for p in pairs for d in p.devices]
        assert len(seen) == len(set(seen))

    def test_multihost_carve_prefers_process_local_groups(self):
        from vizier_tpu.parallel.mesh import _carve_device_groups

        class FakeDevice:
            def __init__(self, device_id, process_index):
                self.id = device_id
                self.process_index = process_index

        # 2 hosts x 4 devices, divisible shard count: every group stays
        # on one host (the flat slice would already do this — sanity).
        devices = [FakeDevice(i, i // 4) for i in range(8)]
        groups = _carve_device_groups(devices, 2)
        assert len(groups) == 4
        for group in groups:
            assert len({d.process_index for d in group}) == 1
        # Non-divisible shard count: the old flat slice produced [0,1,2]
        # and [3,4,5] — the second group SPANS hosts. Process-local
        # carving keeps one full group per host; the per-host remainders
        # (3 and 7) pool to fewer than s and are dropped, like any
        # trailing remainder.
        groups = _carve_device_groups(devices, 3)
        assert [[d.id for d in g] for g in groups] == [[0, 1, 2], [4, 5, 6]]
        for group in groups:
            assert len({d.process_index for d in group}) == 1
        # Remainders still pool into a (necessarily) cross-host group when
        # they add up to a full shard group: 2 hosts x 3 devices at s=2
        # gives one local pair per host plus the pooled [2, 5].
        tight = [FakeDevice(i, i // 3) for i in range(6)]
        groups = _carve_device_groups(tight, 2)
        assert [[d.id for d in g] for g in groups] == [[0, 1], [3, 4], [2, 5]]
        # Single-host meshes are untouched by the preference: same carve
        # as the flat slice.
        single = [FakeDevice(i, 0) for i in range(8)]
        assert [[d.id for d in g] for g in _carve_device_groups(single, 2)] == [
            [0, 1], [2, 3], [4, 5], [6, 7],
        ]

    def test_pad_to_shard_granularity(self):
        import jax

        p1 = DevicePlacement(0, jax.devices()[:1])
        assert [p1.pad_to(o, 8) for o in (1, 2, 3, 4, 5, 8)] == [1, 2, 4, 4, 8, 8]
        assert p1.pad_grid(8) == [1, 2, 4, 8]
        p4 = DevicePlacement(0, jax.devices()[:4])
        assert [p4.pad_to(o, 8) for o in (1, 4, 5, 8)] == [4, 4, 8, 8]
        assert p4.pad_grid(8) == [4, 8]
        # Padded batches always divide by the device count and cover the
        # occupancy.
        p3 = DevicePlacement(0, jax.devices()[:3])
        for occupancy in range(1, 9):
            padded = p3.pad_to(occupancy, 8)
            assert padded >= occupancy and padded % 3 == 0


class TestMeshScheduling:
    def test_distinct_buckets_spread_and_stick(self):
        ex = BatchExecutor(
            max_batch_size=8,
            max_wait_ms=5.0,
            mesh=MeshConfig(enabled=True, shard_devices=1),
        )
        try:
            groups = [
                [StubDesigner(10 * g + c, group=f"g{g}") for c in range(2)]
                for g in range(4)
            ]
            for _ in range(2):  # two rounds: assignments must not move
                flat = [d for group in groups for d in group]
                results, errors = _run_concurrent(ex, flat)
                assert all(e is None for e in errors), errors
                assert all(r for r in results)
            placements = ex.bucket_placements()["stub/t8/f1x0/m1/q1"]
            # 4 distinct buckets spread over 4 distinct placements
            # (least-loaded assignment never doubles up before all 8
            # placements hold a bucket).
            assert len(placements) == 4
            assert len(set(placements)) == 4
            flushes = ex.placement_flush_counts()
            assert sum(flushes.values()) >= 4
        finally:
            ex.close()

    def test_worker_threads_execute_flushes(self):
        ex = BatchExecutor(
            max_batch_size=4,
            max_wait_ms=5.0,
            mesh=MeshConfig(enabled=True, shard_devices=1),
        )
        try:
            seen_threads = set()

            class RecordingProgram(StubProgram):
                def device_program(self, items, pad_to=None, placement=None):
                    seen_threads.add(threading.current_thread().name)
                    return super().device_program(items, pad_to=pad_to)

            class Recorder(StubDesigner):
                program = RecordingProgram()

            results, errors = _run_concurrent(
                ex, [Recorder(i) for i in range(4)]
            )
            assert all(e is None for e in errors)
            assert seen_threads and all(
                name.startswith("vizier-mesh-worker-") for name in seen_threads
            )
        finally:
            ex.close()

    def test_close_drains_mesh_queues(self):
        ex = BatchExecutor(
            max_batch_size=8,
            max_wait_ms=10_000,  # nothing flushes on its own
            mesh=MeshConfig(enabled=True, shard_devices=1),
        )
        designers = [StubDesigner(i) for i in range(3)]
        results = [None] * 3

        def run(i):
            results[i] = ex.suggest(designers[i], 1)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for _ in range(400):
            if ex.queue_depth()["live"] == 3:
                break
            time.sleep(0.005)
        ex.close()  # drain through the workers
        for t in threads:
            t.join(timeout=60)
        assert all(r for r in results)


class TestMeshParity:
    """Slot values must not depend on the execution plane."""

    def test_mesh_size_1_bit_identical_to_single_device(self):
        seeds = (21, 22, 23)
        single = BatchExecutor(max_batch_size=8, max_wait_ms=60.0)
        mesh1 = BatchExecutor(
            max_batch_size=8,
            max_wait_ms=60.0,
            mesh=MeshConfig(enabled=True, num_devices=1),
        )
        try:
            ref, errors = _run_concurrent(
                single, [_designer(s) for s in seeds]
            )
            assert all(e is None for e in errors)
            out, errors = _run_concurrent(mesh1, [_designer(s) for s in seeds])
            assert all(e is None for e in errors)
            assert len(mesh1.placements()) == 1
            for r, o in zip(ref, out):
                assert _params(r) == _params(o)  # bitwise, not approx
        finally:
            single.close()
            mesh1.close()

    def test_sharded_flush_slot_parity_at_mesh_8(self):
        seeds = tuple(range(31, 39))
        sequential = [_designer(s).suggest(1) for s in seeds]
        ex = BatchExecutor(
            max_batch_size=8,
            max_wait_ms=120.0,
            mesh=MeshConfig(enabled=True, shard_devices=8),
        )
        try:
            results, errors = _run_concurrent(
                ex, [_designer(s) for s in seeds]
            )
            assert all(e is None for e in errors)
            (placement,) = ex.placements()
            assert placement.num_devices == 8
            for seq, out in zip(sequential, results):
                assert _params(seq) == _params(out)  # bitwise slot parity
        finally:
            ex.close()

    def test_mesh_off_config_is_seed_executor(self):
        # MeshConfig.from_env() with VIZIER_MESH unset must change nothing
        # observable: same slot values as an executor built without mesh.
        seeds = (41, 42)
        plain = BatchExecutor(max_batch_size=8, max_wait_ms=60.0)
        from_env = BatchExecutor(
            max_batch_size=8, max_wait_ms=60.0, mesh=MeshConfig.from_env()
        )
        try:
            assert not from_env.mesh_enabled
            ref, _ = _run_concurrent(plain, [_designer(s) for s in seeds])
            out, _ = _run_concurrent(from_env, [_designer(s) for s in seeds])
            for r, o in zip(ref, out):
                assert _params(r) == _params(o)
        finally:
            plain.close()
            from_env.close()


class TestMeshChaosIsolation:
    def test_device_failure_on_one_placement_isolated(self):
        # Two distinct buckets -> two placements. Bucket A's device
        # program is chaos-poisoned: its slots recover through their own
        # sequential runs (the chaos designer's plain suggest also strikes
        # -> ITS slot errors; the healthy same-bucket slot succeeds).
        # Bucket B, on ANOTHER placement, is untouched and stays batched.
        monkey = chaos_lib.ChaosMonkey(seed=0, failure_prob=1.0)
        chaotic = chaos_lib.ChaosDesigner(_designer(51), monkey)
        program_driver.pass_prepare(chaotic)  # reach device_program
        mate = _designer(52)
        other_bucket = [
            _designer(s, max_acquisition_evaluations=208) for s in (53, 54)
        ]
        other_sequential = [
            _designer(s, max_acquisition_evaluations=208).suggest(1)
            for s in (53, 54)
        ]
        stats = ServingStats()
        ex = BatchExecutor(
            max_batch_size=2,
            max_wait_ms=10_000,
            stats=stats,
            mesh=MeshConfig(enabled=True, shard_devices=1),
        )
        try:
            results = [None] * 4
            errors = [None] * 4

            def run(i, designer):
                try:
                    results[i] = ex.suggest(designer, 1)
                except BaseException as e:  # noqa: BLE001
                    errors[i] = e

            # The chaos designer must arrive first so the poisoned bucket's
            # flush dispatches through ITS device program.
            t0 = threading.Thread(target=run, args=(0, chaotic))
            t0.start()
            for _ in range(400):
                if ex.pending_counts():
                    break
                time.sleep(0.005)
            rest = [
                threading.Thread(target=run, args=(i, d))
                for i, d in ((1, mate), (2, other_bucket[0]), (3, other_bucket[1]))
            ]
            for t in rest:
                t.start()
            t0.join(timeout=120)
            for t in rest:
                t.join(timeout=120)

            assert isinstance(
                errors[0], chaos_lib.failing.FailedSuggestError
            )
            assert errors[1] is None and results[1]
            assert errors[2] is None and errors[3] is None
            for seq, out in zip(other_sequential, (results[2], results[3])):
                assert _params(seq) == _params(out)
            snap = stats.snapshot()
            assert snap["batch_fallbacks"] == 2  # only the poisoned flush
            assert snap["mesh_flushes"] >= 2
            # Both buckets really lived on different placements.
            assignments = ex.bucket_placements()
            placements = {p for ps in assignments.values() for p in ps}
            assert len(placements) == 2
        finally:
            ex.close()


class TestMeshServingIntegration:
    def test_runtime_threads_mesh_config(self):
        from vizier_tpu.serving import runtime as runtime_lib

        rt = runtime_lib.ServingRuntime(
            mesh=MeshConfig(enabled=True, num_devices=2)
        )
        try:
            assert rt.batch_executor is not None
            assert rt.batch_executor.mesh_enabled
            assert len(rt.batch_executor.placements()) == 2
        finally:
            rt.shutdown()

    def test_runtime_default_env_is_single_device(self):
        from vizier_tpu.serving import runtime as runtime_lib

        rt = runtime_lib.ServingRuntime()
        try:
            assert rt.batch_executor is not None
            assert not rt.batch_executor.mesh_enabled
        finally:
            rt.shutdown()

    def test_pythia_servicer_threads_mesh_config(self):
        from vizier_tpu.service import pythia_service

        servicer = pythia_service.PythiaServicer(
            mesh_config=MeshConfig(enabled=True, num_devices=2)
        )
        try:
            executor = servicer.serving_runtime.batch_executor
            assert executor is not None and executor.mesh_enabled
        finally:
            servicer.shutdown()
