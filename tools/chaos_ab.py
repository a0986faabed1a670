#!/usr/bin/env python
"""Chaos A/B: study completion under injected faults, reliability on vs off.

Runs the same seeded fault schedule (probabilistic designer failures plus
transport faults between client and service) against two arms:

- **reliability_on** — retries + deadline propagation + circuit breaker +
  quasi-random fallback (the vizier_tpu.reliability defaults, with the
  breaker window compressed to match test-speed suggest rates);
- **reliability_off** — ``ReliabilityConfig.disabled()``, the seed's
  fail-hard behavior.

Evidence lands in ``CHAOS_AB.json``: completed trials, fallback rate,
retry/breaker/deadline counters, and per-site injection counts. The
expected shape: the ON arm completes every trial with a bounded fallback
rate (≈ the injected designer-fault rate); the OFF arm dies at the first
injected fault that reaches the client.

Usage:  python tools/chaos_ab.py [--trials 50] [--seed 11] [--fault-prob 0.1]
        [--distributed N] [--kill-at K] [--no-shared-fs]
        [--replica-mode subprocess] [--partition]
        [--instrument-locks] [--mesh-devices N]

``--replica-mode subprocess`` (with ``--distributed``) adds the
**subprocess_partition** arm: an N-replica fleet of REAL ``replica_main``
processes managed by the lease-based ``SubprocessReplicaManager`` —
standby logs stream between processes over the ``ReplicationService``
gRPC surface, heartbeat leases detect death, and failover replays from
standby logs collected over the wire. The schedule SIGKILLs the owner
mid-run and (with ``--partition``) later partitions the next owner away
from the driver via ``testing/netchaos.py`` (heartbeats and client RPCs
drop; the replica itself keeps running), heals the partition, and drives
one stale append directly at the zombie. The verdict asserts all trials
completed, zero lost studies (every driven trial accounted through the
failed-over tier, the zombie's stale trial NOT among them), >= 1 standby
recovery, and >= 1 fenced stale-append rejection observed via heartbeat.
The same invocation also runs the **replication_off_identity** check:
the in-process kill-the-owner arm under ``VIZIER_DISTRIBUTED_REPLICATION
=0`` must produce a bit-identical suggestion trajectory to the
replication-on arm (the off switch IS the PR 12 legacy path).

``--no-shared-fs`` (with ``--distributed``) adds the **replicated_failover**
arm: same kill-the-owner schedule, but the dead replica's WAL directory is
``rm -rf``'d at the moment of the kill — the run can only complete via the
rendezvous successors' replication standby logs
(``distributed/replication.py``), proving failover needs no shared
filesystem. The verdict asserts all trials completed AND >= 1 study was
recovered from source ``standby``.

``--mesh-devices N`` adds a mesh-executor chaos arm: chaos-wrapped GP
designers across multiple shape buckets drive a mesh-sharded
``BatchExecutor`` (``parallel.mesh``, N simulated devices, per-placement
dispatch workers) under the same seeded fault schedule. A device-program
strike poisons ONE placement's flush; the arm asserts the strike degrades
only that flush's slots (sequential fallback / isolated designer errors)
while other placements keep serving — and, with ``--instrument-locks``,
that the per-placement worker threads' runtime lock order is a subset of
the static graph.

``--distributed N`` adds a third arm: the same seeded fault schedule
against an N-replica sharded tier (``vizier_tpu.distributed``) with
snapshot+WAL persistence, and at trial ``--kill-at`` (default: halfway)
the replica that owns the study is KILLED. The run must still complete
every trial: the routed stub surfaces the dead replica, the manager fails
its studies over to the rendezvous successors by WAL replay, and the
client's retry machinery lands on the successor — with the breaker /
fallback counters still visible in the shared-Pythia serving stats.

``--instrument-locks`` runs every arm under
``analysis.debug_locks.instrument()`` and cross-checks the runtime
acquisition order against the static lock-order graph (now including the
router/WAL locks) when the soak finishes; an observed edge the static
pass missed fails the run. This is the chaos-soak ↔ static-analysis
cross-check the long `slow`-marked soak in
``tests/distributed/test_chaos_soak.py`` runs in CI.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("VIZIER_DISABLE_MESH", "1")


def _peek_int_flag(name: str, default: int) -> int:
    """Reads an int flag from argv BEFORE the jax-importing modules below
    (the mesh arm must set --xla_force_host_platform_device_count before
    jax's backend initializes)."""
    for i, arg in enumerate(sys.argv):
        if arg == name and i + 1 < len(sys.argv):
            return int(sys.argv[i + 1])
        if arg.startswith(name + "="):
            return int(arg.split("=", 1)[1])
    return default


_MESH_DEVICES = _peek_int_flag("--mesh-devices", 0)
if _MESH_DEVICES:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags
            + f" --xla_force_host_platform_device_count={_MESH_DEVICES}"
        ).strip()

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from vizier_tpu import pyvizier as vz
from vizier_tpu.algorithms import designer_policy
from vizier_tpu.designers import random as random_designer
from vizier_tpu.observability import MetricsRegistry, ObservabilityConfig
from vizier_tpu.reliability import ReliabilityConfig, is_fallback_suggestion
from vizier_tpu.service import proto_converters as pc
from vizier_tpu.service import pythia_service, vizier_client, vizier_service
from vizier_tpu.service.protos import vizier_service_pb2
from vizier_tpu.testing import chaos

STUDY = "owners/chaos/studies/ab"


def _study_config() -> vz.StudyConfig:
    config = vz.StudyConfig(algorithm="RANDOM_SEARCH")
    config.search_space.root.add_float_param("x", 0.0, 1.0)
    config.search_space.root.add_float_param("y", -1.0, 1.0)
    config.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    return config


class _ChaosPolicyFactory:
    def __init__(self, monkey: chaos.ChaosMonkey):
        self._monkey = monkey

    def __call__(self, problem, algorithm, supporter, study_name):
        return designer_policy.DesignerPolicy(
            supporter,
            chaos.chaos_designer_factory(
                lambda p, **kw: random_designer.RandomDesigner(
                    p.search_space, seed=0
                ),
                self._monkey,
            ),
        )


def run_arm(
    *, trials: int, seed: int, fault_prob: float, reliability: ReliabilityConfig
) -> dict:
    monkey = chaos.ChaosMonkey(seed=seed, failure_prob=fault_prob)
    servicer = vizier_service.VizierServicer(reliability_config=reliability)
    pythia = pythia_service.PythiaServicer(
        servicer, _ChaosPolicyFactory(monkey), reliability_config=reliability
    )
    servicer.set_pythia(pythia)
    servicer.CreateStudy(
        vizier_service_pb2.CreateStudyRequest(
            parent="owners/chaos",
            study=pc.study_to_proto(_study_config(), STUDY),
        )
    )
    client = vizier_client.VizierClient(
        chaos.ChaosServiceStub(servicer, monkey),
        STUDY,
        "chaos-worker",
        reliability=reliability,
    )

    # Per-suggest latency distribution via the observability histogram —
    # under injected faults the tail (retries, breaker cooldowns, fallback
    # detours) is the story a bare mean would bury.
    suggest_hist = MetricsRegistry().histogram(
        "chaos_suggest_latency_seconds", help="chaos_ab per-suggest wall time"
    )
    completed = fallback_trials = 0
    error = None
    start = time.perf_counter()
    try:
        for i in range(trials):
            t0 = time.perf_counter()
            (trial,) = client.get_suggestions(1)
            suggest_hist.observe(time.perf_counter() - t0)
            if is_fallback_suggestion(trial.metadata):
                fallback_trials += 1
            client.complete_trial(
                trial.id, vz.Measurement(metrics={"obj": 0.01 * i})
            )
            completed += 1
    except Exception as e:  # the OFF arm is expected to land here
        error = f"{type(e).__name__}: {e}"
    elapsed = time.perf_counter() - start

    def _ms(q: float):
        value = suggest_hist.percentile(q)
        return round(value * 1000.0, 2) if value is not None else None

    stats = pythia.serving_stats()
    return {
        "completed_trials": completed,
        "target_trials": trials,
        "failed": error is not None,
        "error": error,
        "fallback_trials": fallback_trials,
        "fallback_rate": fallback_trials / max(1, completed),
        "elapsed_secs": round(elapsed, 3),
        "suggest_latency_ms": {"p50": _ms(50), "p95": _ms(95), "p99": _ms(99)},
        "serving_stats": {k: v for k, v in sorted(stats.items()) if v},
        "injected": monkey.counts(),
    }


def run_distributed_arm(
    *,
    trials: int,
    seed: int,
    fault_prob: float,
    reliability: ReliabilityConfig,
    num_replicas: int,
    kill_at: int,
    delete_wal_dir: bool = False,
) -> dict:
    """Kill-one-replica failover under the same seeded fault schedule.

    With ``delete_wal_dir`` the dead replica's entire WAL directory is
    ``rm -rf``'d at the moment of the kill — the shared-nothing proof:
    the run must still complete every trial, with recovery sourced from
    the rendezvous successors' replication standby logs instead of the
    corpse's (now nonexistent) disk.
    """
    import shutil
    import tempfile

    from vizier_tpu.distributed import ReplicaManager

    monkey = chaos.ChaosMonkey(seed=seed, failure_prob=fault_prob)
    wal_root = tempfile.mkdtemp(prefix="vizier-chaos-wal-")
    manager = ReplicaManager(
        num_replicas,
        wal_root=wal_root,
        policy_factory=_ChaosPolicyFactory(monkey),
        reliability_config=reliability,
    )
    study_name = "owners/chaos/studies/dist-ab"
    manager.stub.CreateStudy(
        vizier_service_pb2.CreateStudyRequest(
            parent="owners/chaos",
            study=pc.study_to_proto(_study_config(), study_name),
        )
    )
    # Transport faults injected BETWEEN the client and the router: they
    # exercise client retries without implicating any replica (the manager
    # verifies liveness before failing over).
    client = vizier_client.VizierClient(
        chaos.ChaosServiceStub(manager.stub, monkey),
        study_name,
        "chaos-worker",
        reliability=reliability,
    )
    owner_before = manager.router.replica_for(study_name)

    suggest_hist = MetricsRegistry().histogram(
        "chaos_suggest_latency_seconds", help="chaos_ab per-suggest wall time"
    )
    completed = fallback_trials = 0
    error = None
    killed = False
    trajectory = []  # per-trial suggested parameters (bit-identity checks)
    start = time.perf_counter()
    try:
        for i in range(trials):
            if i == kill_at:
                if delete_wal_dir:
                    # Drain the streamer, then vaporize the owner's disk
                    # BEFORE the kill: nothing local remains to fail over
                    # from — the standby logs must carry the recovery.
                    manager.flush_replication(owner_before)
                    shutil.rmtree(
                        os.path.join(wal_root, owner_before),
                        ignore_errors=True,
                    )
                manager.kill_replica(owner_before)
                killed = True
            t0 = time.perf_counter()
            (trial,) = client.get_suggestions(1)
            suggest_hist.observe(time.perf_counter() - t0)
            trajectory.append(
                tuple(
                    sorted(
                        (name, round(float(value), 12))
                        for name, value in trial.parameters.as_dict().items()
                    )
                )
            )
            if is_fallback_suggestion(trial.metadata):
                fallback_trials += 1
            client.complete_trial(
                trial.id, vz.Measurement(metrics={"obj": 0.01 * i})
            )
            completed += 1
    except Exception as e:  # a failed failover lands here
        error = f"{type(e).__name__}: {e}"
    elapsed = time.perf_counter() - start

    def _ms(q: float):
        value = suggest_hist.percentile(q)
        return round(value * 1000.0, 2) if value is not None else None

    stats = manager.serving_stats()
    owner_after = manager.router.replica_for(study_name)
    manager.shutdown()
    import hashlib

    return {
        "trajectory_sha256": hashlib.sha256(
            repr(trajectory).encode("utf-8")
        ).hexdigest(),
        "_trajectory": trajectory,  # popped before JSON (identity checks)
        "completed_trials": completed,
        "target_trials": trials,
        "failed": error is not None,
        "error": error,
        "replicas": num_replicas,
        "wal_root": wal_root,
        "dead_wal_dir_deleted": bool(delete_wal_dir and killed),
        "killed_replica": owner_before if killed else None,
        "killed_at_trial": kill_at if killed else None,
        "owner_after_failover": owner_after,
        "failovers": stats["failovers"],
        "restored_studies": stats["restored_studies"],
        "recovery_sources": stats.get("recovery_sources", {}),
        "replication": stats.get("replication", {}),
        "router": stats["router"],
        "fallback_trials": fallback_trials,
        "fallback_rate": fallback_trials / max(1, completed),
        "elapsed_secs": round(elapsed, 3),
        "suggest_latency_ms": {"p50": _ms(50), "p95": _ms(95), "p99": _ms(99)},
        "serving_stats": {
            k: v
            for k, v in sorted(stats.items())
            if isinstance(v, int) and v
        },
        "injected": monkey.counts(),
    }


def run_replication_off_identity(
    *,
    trials: int,
    seed: int,
    fault_prob: float,
    reliability: ReliabilityConfig,
    num_replicas: int,
    kill_at: int,
) -> dict:
    """``VIZIER_DISTRIBUTED_REPLICATION=0`` must BE the legacy path.

    Runs the in-process kill-the-owner arm twice — replication on (the
    default) and off (the PR 12 local-disk failover) — on the same seeded
    schedule and asserts the suggestion trajectories are bit-identical:
    the replication plane is pure redundancy, invisible to what clients
    are served, and the off switch reproduces the legacy path exactly.
    """
    import unittest.mock

    arms = {}
    trajectories = {}
    for name, value in (("replication_on", "1"), ("replication_off", "0")):
        with unittest.mock.patch.dict(
            os.environ, {"VIZIER_DISTRIBUTED_REPLICATION": value}
        ):
            result = run_distributed_arm(
                trials=trials,
                seed=seed,
                fault_prob=fault_prob,
                reliability=reliability,
                num_replicas=num_replicas,
                kill_at=kill_at,
            )
        trajectories[name] = result.pop("_trajectory")
        arms[name] = {
            "completed_trials": result["completed_trials"],
            "failed": result["failed"],
            "recovery_sources": result["recovery_sources"],
            "replication_armed": bool(result["replication"]),
            "trajectory_sha256": result["trajectory_sha256"],
        }
    return {
        "arms": arms,
        "bit_identical": trajectories["replication_on"]
        == trajectories["replication_off"],
    }


def run_subprocess_partition_arm(
    *,
    trials: int,
    seed: int,
    num_replicas: int,
    kill_at: int,
    partition: bool,
    lease_timeout_s: float = 1.0,
    heartbeat_interval_s: float = 0.1,
) -> dict:
    """Kill-the-owner + partition-then-heal against REAL replica processes.

    The schedule: at ``kill_at`` the owning ``replica_main`` process is
    SIGKILLed (lease expiry / the routed stub's dead-process check detects
    it; failover replays from standby logs collected over gRPC); with
    ``partition`` armed, at ``kill_at + (trials - kill_at) // 3`` the NEXT
    owner is partitioned away from the driver (netchaos severs heartbeats
    and client RPCs; the process keeps running), the lease expires, the
    manager fences the zombie's epoch everywhere reachable and fails its
    studies over; the window heals two-thirds in, and one stale append is
    driven directly at the zombie — its delivery must be REJECTED by the
    fenced standby stores (counted via heartbeat) and must NOT surface in
    the routed tier's final listing (no split-brain write wins).
    """
    import tempfile

    from vizier_tpu.distributed import subprocess_fleet
    from vizier_tpu.service import grpc_stubs
    from vizier_tpu.service.protos import (
        replication_service_pb2 as rpb,
        study_pb2,
    )
    from vizier_tpu.testing import netchaos as netchaos_lib

    wal_root = tempfile.mkdtemp(prefix="vizier-chaos-subproc-")
    net = netchaos_lib.NetChaos(seed=seed)
    fleet = subprocess_fleet.SubprocessReplicaManager(
        num_replicas,
        wal_root=wal_root,
        netchaos=net,
        lease_timeout_s=lease_timeout_s,
        heartbeat_interval_s=heartbeat_interval_s,
    )
    study_name = "owners/chaos/studies/subproc-ab"
    partition_at = kill_at + max(1, (trials - kill_at) // 3)
    heal_at = kill_at + max(2, 2 * (trials - kill_at) // 3)
    # Client-side reliability must ride out a full lease expiry plus the
    # failover replay before its attempts run dry.
    reliability = ReliabilityConfig(
        retry_max_attempts=16,
        retry_base_delay_secs=0.1,
        retry_max_delay_secs=0.5,
        breaker_window_secs=0.5,
        breaker_cooldown_secs=0.2,
    )
    owners: list = []
    partitioned_replica = None
    stale_trial_id = 10_000 + trials
    error = None
    completed = 0
    fenced_rejections = 0
    suggest_hist = MetricsRegistry().histogram(
        "chaos_suggest_latency_seconds", help="chaos_ab per-suggest wall time"
    )
    start = time.perf_counter()
    try:
        fleet.stub.CreateStudy(
            vizier_service_pb2.CreateStudyRequest(
                parent="owners/chaos",
                study=pc.study_to_proto(_study_config(), study_name),
            )
        )
        client = vizier_client.VizierClient(
            fleet.stub, study_name, "chaos-worker", reliability=reliability
        )
        owners.append(fleet.owner_of(study_name))
        for i in range(trials):
            if i == kill_at:
                fleet.kill_replica(owners[-1])
            if partition and i == partition_at:
                owner_now = fleet.owner_of(study_name)
                # Pin the partition to an acked-replication boundary (the
                # client is sequential, so this is exact): replication is
                # asynchronous, and the partition must test FENCING, not
                # whether an arbitrary in-flight batch won a race.
                fleet._control.call_once(
                    owner_now,
                    "FlushStream",
                    rpb.FlushStreamRequest(timeout_secs=5.0),
                )
                fleet.partition_replica(owner_now)
                partitioned_replica = owner_now
            if partition and i == heal_at and partitioned_replica is not None:
                fleet.heal_partition(partitioned_replica)
                # The zombie still serves its (stale) study copy: a
                # client with stale routing writes one trial directly at
                # it. The append lands in the zombie's local WAL, its
                # streamer delivers — and every fenced standby store
                # rejects the dead generation.
                zombie_stub = grpc_stubs.create_vizier_stub(
                    fleet.endpoint_of(partitioned_replica)
                )
                zombie_stub.CreateTrial(
                    vizier_service_pb2.CreateTrialRequest(
                        parent=study_name,
                        trial=study_pb2.Trial(
                            name=f"{study_name}/trials/{stale_trial_id}"
                        ),
                    )
                )
            t0 = time.perf_counter()
            (trial,) = client.get_suggestions(1)
            suggest_hist.observe(time.perf_counter() - t0)
            client.complete_trial(
                trial.id, vz.Measurement(metrics={"obj": 0.01 * i})
            )
            completed += 1
            current = fleet.owner_of(study_name)
            if current != owners[-1]:
                owners.append(current)
        # The fenced rejection is observed via heartbeat from whichever
        # live replica the zombie's delivery reached; give the zombie's
        # streamer a bounded window to drain and be fenced.
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            fleet.check_health()
            fenced_rejections = fleet.serving_stats()["replication"][
                "fenced_rejections"
            ]
            if not partition or fenced_rejections >= 1:
                break
            time.sleep(0.25)
        listed = client.list_trials()
        listed_ids = sorted(t.id for t in listed)
    except Exception as e:
        error = f"{type(e).__name__}: {e}"
        listed, listed_ids = [], []
    elapsed = time.perf_counter() - start
    stats = fleet.serving_stats()
    fleet.shutdown()

    def _ms(q: float):
        value = suggest_hist.percentile(q)
        return round(value * 1000.0, 2) if value is not None else None

    return {
        "completed_trials": completed,
        "target_trials": trials,
        "failed": error is not None,
        "error": error,
        "replicas": num_replicas,
        "replica_mode": "subprocess",
        "wal_root": wal_root,
        "owner_chain": owners,
        "killed_replica": owners[0] if owners else None,
        "killed_at_trial": kill_at,
        "partitioned_replica": partitioned_replica,
        "partitioned_at_trial": partition_at if partition else None,
        "healed_at_trial": heal_at if partition else None,
        "lease_timeout_s": lease_timeout_s,
        "heartbeat_interval_s": heartbeat_interval_s,
        "failovers": stats["failovers"],
        "restored_studies": stats["restored_studies"],
        "recovery_sources": stats["recovery_sources"],
        "fenced_rejections": fenced_rejections,
        "stale_append_rejected": bool(partition)
        and stale_trial_id not in listed_ids,
        "listed_trials": len(listed),
        "zero_lost": len(listed) == completed
        and stale_trial_id not in listed_ids,
        "router": stats["router"],
        "leases": stats["leases"],
        "netchaos": net.counts(),
        "elapsed_secs": round(elapsed, 3),
        "suggest_latency_ms": {"p50": _ms(50), "p95": _ms(95), "p99": _ms(99)},
    }


def run_mesh_executor_arm(
    *,
    devices: int,
    seed: int,
    fault_prob: float,
    rounds: int = 4,
    buckets: int = 2,
    studies_per_bucket: int = 2,
) -> dict:
    """Chaos soak on the mesh-sharded batch executor itself.

    Chaos-wrapped UCB-PE designers across ``buckets`` distinct shape
    buckets (sticky-assigned to different placements) run concurrent
    suggest rounds through a mesh executor while the seeded monkey strikes
    the batch hooks. A ``device_program`` strike poisons one placement's
    flush — the executor must degrade only that flush (per-slot sequential
    fallback; a re-struck fallback surfaces as that slot's own designer
    error) while other placements' flushes keep completing. After the
    soak, a fault-free designer must still be served (no dead workers, no
    poisoned queues).
    """
    import threading

    import numpy as np

    from vizier_tpu.algorithms import core as core_lib
    from vizier_tpu.designers import gp_ucb_pe
    from vizier_tpu.optimizers import lbfgs as lbfgs_lib
    from vizier_tpu.testing import failing
    from vizier_tpu.parallel.batch_executor import BatchExecutor
    from vizier_tpu.parallel.mesh import MeshConfig
    from vizier_tpu.serving.stats import ServingStats

    def problem(dim=2):
        p = vz.ProblemStatement()
        for d in range(dim):
            p.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
        p.metric_information.append(
            vz.MetricInformation(
                name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE
            )
        )
        return p

    def designer(bucket_index: int, study_seed: int):
        d = gp_ucb_pe.VizierGPUCBPEBandit(
            problem(),
            rng_seed=study_seed,
            # Distinct acquisition budgets -> distinct jit statics ->
            # distinct buckets.
            max_acquisition_evaluations=200 + 8 * bucket_index,
            ard_restarts=2,
            ard_optimizer=lbfgs_lib.AdamOptimizer(maxiter=10),
            warm_start_min_trials=0,
        )
        rng = np.random.default_rng(study_seed)
        trials = []
        for i in range(5):
            t = vz.Trial(
                parameters={
                    "x0": float(rng.uniform()),
                    "x1": float(rng.uniform()),
                },
                id=i + 1,
            )
            t.complete(vz.Measurement(metrics={"obj": float(rng.uniform())}))
            trials.append(t)
        d.update(core_lib.CompletedTrials(trials))
        return d

    monkey = chaos.ChaosMonkey(seed=seed, failure_prob=fault_prob)
    stats = ServingStats()
    executor = BatchExecutor(
        max_batch_size=8,
        max_wait_ms=30.0,
        stats=stats,
        metrics=stats.registry,
        mesh=MeshConfig(enabled=True, num_devices=devices),
    )
    pool = [
        chaos.ChaosDesigner(designer(b, b * 100 + c + 1), monkey)
        for b in range(buckets)
        for c in range(studies_per_bucket)
    ]

    completed = injected = 0
    count_lock = threading.Lock()

    def client(d):
        nonlocal completed, injected
        for _ in range(rounds):
            try:
                out = executor.suggest(d, 1)
                assert out, "empty suggestion batch"
                with count_lock:
                    completed += 1
            except failing.FailedSuggestError:
                with count_lock:
                    injected += 1

    threads = [threading.Thread(target=client, args=(d,)) for d in pool]
    start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - start

    # Post-soak liveness: a fault-free designer must still be served by the
    # same (possibly previously poisoned) placements.
    clean = executor.suggest(designer(0, 999), 1)
    placement_flushes = executor.placement_flush_counts()
    executor.close()

    snap = stats.snapshot()
    attempts = len(pool) * rounds
    return {
        "devices": devices,
        "buckets": buckets,
        "studies_per_bucket": studies_per_bucket,
        "rounds": rounds,
        "attempts": attempts,
        "completed": completed,
        "isolated_designer_errors": injected,
        "all_accounted": completed + injected == attempts,
        "post_soak_liveness": bool(clean),
        "batch_fallbacks": snap.get("batch_fallbacks", 0),
        "batch_slot_errors": snap.get("batch_slot_errors", 0),
        "mesh_flushes": snap.get("mesh_flushes", 0),
        "placement_flushes": placement_flushes,
        "elapsed_secs": round(elapsed, 3),
        "injected": monkey.counts(),
    }


class _SlowSuggestDesigner:
    """Wraps a designer so every ``slow_every``-th suggest stalls — the
    induced latency regression the SLO soak must catch as a p99 breach.
    ``tick`` is a shared per-study counter held by the factory: policies
    are rebuilt per request, so the cadence must outlive the instance."""

    def __init__(self, designer, tick, slow_every: int, delay_secs: float):
        self._designer = designer
        self._tick = tick
        self._slow_every = max(1, slow_every)
        self._delay_secs = delay_secs

    def __getattr__(self, name):
        return getattr(self._designer, name)

    def suggest(self, count=None):
        if self._tick() % self._slow_every == 0:
            time.sleep(self._delay_secs)
        return self._designer.suggest(count)


class _SlowChaosPolicyFactory:
    def __init__(self, monkey: chaos.ChaosMonkey, slow_every: int, delay_secs: float):
        import threading

        self._monkey = monkey
        self._slow_every = slow_every
        self._delay_secs = delay_secs
        self._lock = threading.Lock()
        self._counts: dict = {}

    def _tick(self, study_name: str) -> int:
        with self._lock:
            self._counts[study_name] = self._counts.get(study_name, 0) + 1
            return self._counts[study_name]

    def __call__(self, problem, algorithm, supporter, study_name):
        return designer_policy.DesignerPolicy(
            supporter,
            chaos.chaos_designer_factory(
                lambda p, **kw: _SlowSuggestDesigner(
                    random_designer.RandomDesigner(p.search_space, seed=0),
                    tick=lambda: self._tick(study_name),
                    slow_every=self._slow_every,
                    delay_secs=self._delay_secs,
                ),
                self._monkey,
            ),
        )


def run_slo_soak_arm(
    *,
    trials: int,
    seed: int,
    fault_prob: float,
    reliability: ReliabilityConfig,
    num_replicas: int,
    kill_at: int,
    out_dir: str,
    p99_threshold_ms: float = 25.0,
    slow_every: int = 5,
    delay_secs: float = 0.12,
) -> dict:
    """SLOs armed + flight recorder on, over a 2-study / N-replica tier.

    Induces a latency breach (every ``slow_every``-th suggest stalls
    ``delay_secs`` — far past the ``p99_threshold_ms`` objective), kills
    the first study's owning replica mid-run, then checks the whole
    observability plane end to end: the breach produced a black-box dump
    whose exemplar trace_ids resolve to complete traces in the merged
    per-replica span dumps, and the fleet merge stitches cross-source
    traces plus the failover timeline from the recorder events.
    """
    import tempfile

    from vizier_tpu.distributed import ReplicaManager
    from vizier_tpu.observability import fleet as fleet_lib
    from vizier_tpu.observability import flight_recorder as recorder_lib
    from vizier_tpu.observability import tracing as tracing_lib

    import unittest.mock

    os.makedirs(out_dir, exist_ok=True)
    env_overrides = {
        "VIZIER_SLO": "1",
        # Short fast window + a long one; manual evaluation cadence keeps
        # the soak deterministic on loaded CI machines.
        "VIZIER_SLO_WINDOWS": "10,120",
        "VIZIER_SLO_EVAL_INTERVAL_S": "0",
        "VIZIER_SLO_SUGGEST_P99_MS": str(p99_threshold_ms),
        "VIZIER_SLO_DUMP_DIR": out_dir,
        "VIZIER_FLIGHT_RECORDER": "1",
    }
    # patch.dict restores the environment on exit (no hand-rolled
    # save/restore — environ reads stay literal for the env_registry pass).
    env_patch = unittest.mock.patch.dict(os.environ, env_overrides)
    env_patch.start()
    # Fresh global tracer + recorder so the soak's artifacts are self-
    # contained (and the recorder re-derives as ENABLED from the env).
    prev_tracer = tracing_lib.set_tracer(tracing_lib.Tracer(max_spans=65536))
    prev_recorder = recorder_lib.set_recorder(None)
    manager = None
    try:
        monkey = chaos.ChaosMonkey(seed=seed, failure_prob=fault_prob)
        wal_root = tempfile.mkdtemp(prefix="vizier-slo-wal-")
        manager = ReplicaManager(
            num_replicas,
            wal_root=wal_root,
            policy_factory=_SlowChaosPolicyFactory(monkey, slow_every, delay_secs),
            reliability_config=reliability,
        )
        runtime = manager.pythia.serving_runtime
        assert runtime.slo_engine is not None, "SLO engine failed to arm"

        # Two studies owned by two DIFFERENT replicas, so the merged span
        # dump covers >= 2 replica sources.
        studies = []
        owners = set()
        i = 0
        while len(studies) < 2 and i < 1000:
            name = f"owners/chaos/studies/slo-{i}"
            i += 1
            owner = manager.router.replica_for(name)
            if owner not in owners:
                owners.add(owner)
                studies.append((name, owner))
        clients = {}
        for study_name, _owner in studies:
            manager.stub.CreateStudy(
                vizier_service_pb2.CreateStudyRequest(
                    parent="owners/chaos",
                    study=pc.study_to_proto(_study_config(), study_name),
                )
            )
            clients[study_name] = vizier_client.VizierClient(
                chaos.ChaosServiceStub(manager.stub, monkey),
                study_name,
                "chaos-worker",
                reliability=reliability,
            )

        killed_replica = studies[0][1]
        completed = 0
        start = time.perf_counter()
        for t in range(trials):
            if t == kill_at:
                manager.kill_replica(killed_replica)
            study_name, _ = studies[t % len(studies)]
            client = clients[study_name]
            (trial,) = client.get_suggestions(1)
            client.complete_trial(
                trial.id, vz.Measurement(metrics={"obj": 0.01 * t})
            )
            completed += 1
            if (t + 1) % 10 == 0:
                runtime.slo_engine.evaluate()
        elapsed = time.perf_counter() - start
        slo_report = runtime.slo_report()

        # Fleet dump: per-replica span files split from the shared ring,
        # plus the registry snapshot and recorder events.
        manager.dump_observability(out_dir)
        fleet_report = fleet_lib.fleet_report(out_dir)
        merged = fleet_lib.merge_spans(fleet_lib.load_fleet_dir(out_dir)["spans"])
        by_trace = {}
        for span in merged:
            by_trace.setdefault(span.get("trace_id"), []).append(span)

        # The black box must point at real, complete traces: every
        # exemplar trace_id resolves in the merged span dump with a root
        # span and a service-side span.
        dumps = list(runtime.slo_engine.dumps)
        exemplar_trace_ids = []
        exemplars_resolve = False
        if dumps:
            with open(dumps[0]) as f:
                blackbox = json.load(f)
            exemplar_trace_ids = sorted(blackbox.get("exemplar_traces", {}))
            def _complete(trace_id):
                spans = by_trace.get(trace_id, [])
                names = {s.get("name") for s in spans}
                has_root = any(s.get("parent_id") is None for s in spans)
                return (
                    len(spans) >= 3
                    and has_root
                    and "service.suggest_trials" in names
                )
            exemplars_resolve = bool(exemplar_trace_ids) and all(
                _complete(tid) for tid in exemplar_trace_ids
            )

        timeline = fleet_report["failover_timeline"]
        breached = set(slo_report["breaching"])
        span_sources = set(fleet_report["sources"])
        replica_sources = {s for s in span_sources if s.startswith("replica-")}
        return {
            "trials": trials,
            "completed_trials": completed,
            "elapsed_secs": round(elapsed, 3),
            "studies": [
                {"study": name, "owner": owner} for name, owner in studies
            ],
            "killed_replica": killed_replica,
            "killed_at_trial": kill_at,
            "p99_threshold_ms": p99_threshold_ms,
            "induced_delay_ms": delay_secs * 1e3,
            "slo": slo_report,
            "slo_breached": sorted(breached),
            "p99_breached": any(b.startswith("suggest_p99") for b in breached),
            "blackbox_dumps": dumps,
            "exemplar_trace_ids": exemplar_trace_ids,
            "exemplars_resolve_to_complete_traces": exemplars_resolve,
            "fleet": fleet_report,
            "fleet_replica_sources": sorted(replica_sources),
            "cross_replica_traces": fleet_report["cross_replica_traces"],
            "failover_timeline_kinds": sorted(
                {e["kind"] for e in timeline}
            ),
            "serving_stats": {
                k: v
                for k, v in sorted(manager.serving_stats().items())
                if isinstance(v, int) and v
            },
            "injected": monkey.counts(),
            "out_dir": out_dir,
        }
    finally:
        if manager is not None:
            manager.shutdown()
        tracing_lib.set_tracer(prev_tracer)
        recorder_lib.set_recorder(prev_recorder)
        env_patch.stop()


def write_observability_e2e(arm: dict, out_path: str) -> dict:
    """OBSERVABILITY_E2E.json v2: the SLO-armed soak as evidence."""
    fleet = arm["fleet"]
    evidence = {
        "version": 2,
        "what": (
            "PR 11 acceptance: SLO-armed chaos soak over a 2-replica tier "
            "with an induced p99 breach -> black-box dump whose exemplar "
            "trace_ids resolve to complete traces in the merged per-replica "
            "span dumps; fleet merge stitches cross-replica traces and the "
            "failover timeline from flight-recorder events"
        ),
        "slo": {
            "config": arm["slo"]["config"],
            "breached": arm["slo_breached"],
            "p99_breached": arm["p99_breached"],
            "statuses": arm["slo"]["statuses"],
            "blackbox_dump": arm["blackbox_dumps"][:1],
            "exemplar_trace_ids": arm["exemplar_trace_ids"],
            "exemplars_resolve_to_complete_traces": arm[
                "exemplars_resolve_to_complete_traces"
            ],
        },
        "fleet": {
            "sources": fleet["sources"],
            "spans": fleet["spans"],
            "traces": fleet["traces"],
            "cross_replica_traces": fleet["cross_replica_traces"],
            "cross_replica_examples": fleet["cross_replica_examples"][:3],
            "failover_timeline": fleet["failover_timeline"],
        },
        "soak": {
            "trials": arm["trials"],
            "completed_trials": arm["completed_trials"],
            "killed_replica": arm["killed_replica"],
            "killed_at_trial": arm["killed_at_trial"],
            "p99_threshold_ms": arm["p99_threshold_ms"],
            "induced_delay_ms": arm["induced_delay_ms"],
            "serving_stats": arm["serving_stats"],
            "injected": arm["injected"],
        },
    }
    pathlib.Path(out_path).write_text(json.dumps(evidence, indent=2) + "\n")
    return evidence


def _cross_check_locks(observatory, out: dict) -> bool:
    """Diffs the soak's observed lock order against the static graph."""
    from vizier_tpu.analysis import debug_locks, suite

    repo_root = str(pathlib.Path(__file__).resolve().parent.parent)
    static = suite.run_suite(repo_root, passes=["lock_order"]).lock_result
    check = debug_locks.check_against_static(observatory, static, repo_root)
    out["lock_check"] = {
        "acquisitions": observatory.acquisitions,
        "confirmed_edges": sorted(set(check.confirmed)),
        "missing_from_static_graph": [
            {"src": src, "dst": dst, "thread": edge.thread}
            for src, dst, edge in check.missing_static
        ],
        "unmapped_sites": [s.short() for s in check.unmapped_sites],
    }
    return not check.missing_static


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--trials", type=int, default=50)
    parser.add_argument("--seed", type=int, default=11)
    parser.add_argument("--fault-prob", type=float, default=0.1)
    parser.add_argument(
        "--distributed",
        type=int,
        default=0,
        metavar="N",
        help="add the N-replica kill-one-replica failover arm (0 = skip)",
    )
    parser.add_argument(
        "--kill-at",
        type=int,
        default=-1,
        help="trial index at which the owning replica dies (-1 = halfway)",
    )
    parser.add_argument(
        "--no-shared-fs",
        action="store_true",
        help="with --distributed: add the replicated_failover arm — the "
        "dead replica's WAL directory is DELETED at the kill, so the "
        "run can only complete via the successors' replication standby "
        "logs (the shared-nothing durability proof)",
    )
    parser.add_argument(
        "--replica-mode",
        choices=("inprocess", "subprocess"),
        default="inprocess",
        help="with --distributed: 'subprocess' adds the "
        "subprocess_partition arm (real replica_main processes, "
        "lease-based failure detection, cross-process standby "
        "replication) plus the replication-off bit-identity check",
    )
    parser.add_argument(
        "--partition",
        action="store_true",
        help="with --replica-mode subprocess: add a partition-then-heal "
        "window (netchaos) on the post-failover owner, and assert the "
        "healed zombie's stale append is fenced out",
    )
    parser.add_argument(
        "--mesh-devices",
        type=int,
        default=0,
        metavar="N",
        help="add the mesh-executor chaos arm on N simulated devices "
        "(0 = skip); composes with --instrument-locks so the per-placement "
        "dispatch workers enter the runtime lock-order cross-check",
    )
    parser.add_argument(
        "--instrument-locks",
        action="store_true",
        help="record runtime lock order during the soak and fail on edges "
        "the static lock_order graph does not predict",
    )
    parser.add_argument(
        "--slo-soak",
        action="store_true",
        help="add the SLO-armed observability arm: 2-replica tier, induced "
        "p99 breach -> black-box dump + fleet-merged cross-replica traces; "
        "regenerates OBSERVABILITY_E2E.json (v2)",
    )
    parser.add_argument(
        "--slo-replicas",
        type=int,
        default=2,
        help="replica count for the --slo-soak arm",
    )
    parser.add_argument(
        "--obs-dump-dir",
        default="",
        help="dump directory for the --slo-soak arm's span/metric/recorder "
        "+ black-box files (default: a fresh temp dir)",
    )
    parser.add_argument(
        "--obs-e2e-out",
        default=str(
            pathlib.Path(__file__).resolve().parent.parent
            / "OBSERVABILITY_E2E.json"
        ),
        help="where --slo-soak writes the v2 evidence JSON",
    )
    parser.add_argument(
        "--out",
        default=str(pathlib.Path(__file__).resolve().parent.parent / "CHAOS_AB.json"),
    )
    args = parser.parse_args()

    # Fast client backoffs: the A/B measures completion/fallback behavior,
    # not wall-clock sleeps.
    vizier_client.environment_variables.polling_delay_secs = 0.005

    arms = {
        "reliability_on": ReliabilityConfig(
            retry_base_delay_secs=0.01,
            retry_max_delay_secs=0.1,
            # The breaker's sliding window assumes production suggest rates
            # (designer runs are seconds apart); at test speed 50 suggests
            # land inside one 60 s window, so the window is compressed to
            # keep "N failures within a window" meaning the same thing.
            breaker_window_secs=0.5,
            breaker_cooldown_secs=0.2,
        ),
        "reliability_off": ReliabilityConfig.disabled(),
    }
    report = {
        "config": {
            "trials": args.trials,
            "seed": args.seed,
            "designer_fault_prob": args.fault_prob,
            "transport_fault_prob": args.fault_prob,
            "algorithm": "RANDOM_SEARCH (chaos-wrapped designer)",
            "observability": ObservabilityConfig.from_env().as_dict(),
            "instrument_locks": bool(args.instrument_locks),
            "mesh_devices": args.mesh_devices,
        },
        "arms": {},
    }
    if args.instrument_locks:
        from vizier_tpu.analysis import debug_locks

        instrumentation = debug_locks.instrument()
    else:
        import contextlib

        instrumentation = contextlib.nullcontext(None)

    kill_at = args.kill_at if args.kill_at >= 0 else args.trials // 2
    with instrumentation as observatory:
        for name, reliability in arms.items():
            print(f"[chaos_ab] running arm: {name}")
            report["arms"][name] = run_arm(
                trials=args.trials,
                seed=args.seed,
                fault_prob=args.fault_prob,
                reliability=reliability,
            )
        if args.distributed:
            print(
                f"[chaos_ab] running arm: distributed_failover "
                f"({args.distributed} replicas, kill at trial {kill_at})"
            )
            report["arms"]["distributed_failover"] = run_distributed_arm(
                trials=args.trials,
                seed=args.seed,
                fault_prob=args.fault_prob,
                reliability=arms["reliability_on"],
                num_replicas=args.distributed,
                kill_at=kill_at,
            )
            report["arms"]["distributed_failover"].pop("_trajectory", None)
            if args.no_shared_fs:
                print(
                    "[chaos_ab] running arm: replicated_failover "
                    f"({args.distributed} replicas, dead WAL dir DELETED "
                    f"at trial {kill_at})"
                )
                report["arms"]["replicated_failover"] = run_distributed_arm(
                    trials=args.trials,
                    seed=args.seed,
                    fault_prob=args.fault_prob,
                    reliability=arms["reliability_on"],
                    num_replicas=args.distributed,
                    kill_at=kill_at,
                    delete_wal_dir=True,
                )
                report["arms"]["replicated_failover"].pop("_trajectory", None)
            if args.replica_mode == "subprocess":
                print(
                    "[chaos_ab] running check: replication_off_identity "
                    f"({args.distributed} replicas, in-process, "
                    "VIZIER_DISTRIBUTED_REPLICATION=0 vs 1)"
                )
                report["replication_off_identity"] = (
                    run_replication_off_identity(
                        trials=args.trials,
                        seed=args.seed,
                        fault_prob=args.fault_prob,
                        reliability=arms["reliability_on"],
                        num_replicas=args.distributed,
                        kill_at=kill_at,
                    )
                )
                print(
                    f"[chaos_ab] running arm: subprocess_partition "
                    f"({args.distributed} replica_main processes, kill at "
                    f"trial {kill_at}, partition={args.partition})"
                )
                report["arms"]["subprocess_partition"] = (
                    run_subprocess_partition_arm(
                        trials=args.trials,
                        seed=args.seed,
                        num_replicas=args.distributed,
                        kill_at=kill_at,
                        partition=args.partition,
                    )
                )
        if args.mesh_devices:
            print(
                f"[chaos_ab] running arm: mesh_executor "
                f"({args.mesh_devices} devices)"
            )
            report["arms"]["mesh_executor"] = run_mesh_executor_arm(
                devices=args.mesh_devices,
                seed=args.seed,
                fault_prob=args.fault_prob,
            )
        if args.slo_soak:
            import tempfile

            out_dir = args.obs_dump_dir or tempfile.mkdtemp(
                prefix="vizier-obs-dump-"
            )
            print(
                f"[chaos_ab] running arm: slo_soak "
                f"({args.slo_replicas} replicas, dumps -> {out_dir})"
            )
            report["arms"]["slo_soak"] = run_slo_soak_arm(
                trials=args.trials,
                seed=args.seed,
                fault_prob=args.fault_prob,
                reliability=arms["reliability_on"],
                num_replicas=args.slo_replicas,
                kill_at=kill_at,
                out_dir=out_dir,
            )

    on, off = report["arms"]["reliability_on"], report["arms"]["reliability_off"]
    report["verdict"] = {
        "on_completed_all": on["completed_trials"] == args.trials,
        "on_fallback_rate": round(on["fallback_rate"], 4),
        "off_failed": off["failed"],
        "off_completed": off["completed_trials"],
    }
    ok = True
    if args.distributed:
        dist = report["arms"]["distributed_failover"]
        report["verdict"].update(
            {
                "distributed_completed_all": dist["completed_trials"]
                == args.trials,
                "distributed_failovers": dist["failovers"],
                "distributed_killed_replica": dist["killed_replica"],
            }
        )
        ok = ok and dist["completed_trials"] == args.trials and dist["failovers"] >= 1
        if args.no_shared_fs:
            repl = report["arms"]["replicated_failover"]
            standby_recoveries = int(
                repl["recovery_sources"].get("standby", 0)
            )
            report["verdict"].update(
                {
                    "replicated_completed_all": repl["completed_trials"]
                    == args.trials,
                    "replicated_wal_dir_deleted": repl[
                        "dead_wal_dir_deleted"
                    ],
                    "replicated_standby_recoveries": standby_recoveries,
                }
            )
            ok = ok and (
                repl["completed_trials"] == args.trials
                and repl["dead_wal_dir_deleted"]
                and standby_recoveries >= 1
            )
        if args.replica_mode == "subprocess":
            identity = report["replication_off_identity"]
            sub = report["arms"]["subprocess_partition"]
            subprocess_standby = int(
                sub["recovery_sources"].get("standby", 0)
            )
            report["verdict"].update(
                {
                    "subprocess_completed_all": sub["completed_trials"]
                    == args.trials,
                    "subprocess_zero_lost": sub["zero_lost"],
                    "subprocess_standby_recoveries": subprocess_standby,
                    "subprocess_fenced_rejections": sub[
                        "fenced_rejections"
                    ],
                    "subprocess_stale_append_rejected": sub[
                        "stale_append_rejected"
                    ],
                    "replication_off_bit_identical": identity[
                        "bit_identical"
                    ],
                }
            )
            ok = ok and (
                sub["completed_trials"] == args.trials
                and sub["zero_lost"]
                and subprocess_standby >= 1
                and identity["bit_identical"]
            )
            if args.partition:
                ok = ok and (
                    sub["fenced_rejections"] >= 1
                    and sub["stale_append_rejected"]
                )
    if args.mesh_devices:
        mesh_arm = report["arms"]["mesh_executor"]
        report["verdict"].update(
            {
                "mesh_all_accounted": mesh_arm["all_accounted"],
                "mesh_post_soak_liveness": mesh_arm["post_soak_liveness"],
                "mesh_isolated_errors": mesh_arm["isolated_designer_errors"],
            }
        )
        ok = ok and mesh_arm["all_accounted"] and mesh_arm["post_soak_liveness"]
    if args.slo_soak:
        slo_arm = report["arms"]["slo_soak"]
        report["verdict"].update(
            {
                "slo_completed_all": slo_arm["completed_trials"]
                == args.trials,
                "slo_p99_breached": slo_arm["p99_breached"],
                "slo_blackbox_dumped": bool(slo_arm["blackbox_dumps"]),
                "slo_exemplars_resolve": slo_arm[
                    "exemplars_resolve_to_complete_traces"
                ],
                "fleet_replica_sources": len(
                    slo_arm["fleet_replica_sources"]
                ),
                "fleet_cross_replica_traces": slo_arm["cross_replica_traces"],
                "fleet_failover_in_timeline": "replica_failover"
                in slo_arm["failover_timeline_kinds"],
            }
        )
        ok = ok and (
            slo_arm["completed_trials"] == args.trials
            and slo_arm["p99_breached"]
            and bool(slo_arm["blackbox_dumps"])
            and slo_arm["exemplars_resolve_to_complete_traces"]
            and len(slo_arm["fleet_replica_sources"]) >= 2
            and slo_arm["cross_replica_traces"] >= 1
            and "replica_failover" in slo_arm["failover_timeline_kinds"]
        )
        write_observability_e2e(slo_arm, args.obs_e2e_out)
        print(f"[chaos_ab] wrote {args.obs_e2e_out}")
    if args.instrument_locks:
        locks_ok = _cross_check_locks(observatory, report)
        report["verdict"]["lock_order_confirmed"] = locks_ok
        ok = ok and locks_ok
    pathlib.Path(args.out).write_text(json.dumps(report, indent=2) + "\n")
    print(json.dumps(report["verdict"], indent=2))
    print(f"[chaos_ab] wrote {args.out}")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
