"""SubprocessReplicaManager: real replica_main processes, end to end.

These tests spawn actual OS processes serving gRPC — the whole point of
the cross-process plane — so they are the slowest in this directory
(~10-20 s of fleet spin-up each). The kill/failover lifecycle rides in
one compact tier-1 test; the partition/lease matrix and the graceful-
shutdown contract get their own.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

grpc = pytest.importorskip("grpc")

from vizier_tpu import pyvizier as vz
from vizier_tpu.distributed import subprocess_fleet
from vizier_tpu.reliability import ReliabilityConfig
from vizier_tpu.service import grpc_stubs
from vizier_tpu.service import proto_converters as pc
from vizier_tpu.service import vizier_client
from vizier_tpu.service.protos import (
    replication_service_pb2 as rpb,
    study_pb2,
    vizier_service_pb2,
)
from vizier_tpu.testing import netchaos as netchaos_lib

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _study_config() -> vz.StudyConfig:
    config = vz.StudyConfig(algorithm="RANDOM_SEARCH")
    config.search_space.root.add_float_param("x", 0.0, 1.0)
    config.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    return config


def _reliability() -> ReliabilityConfig:
    # Must ride out a full lease expiry + wire failover before the
    # attempt budget runs dry.
    return ReliabilityConfig(
        retry_max_attempts=16,
        retry_base_delay_secs=0.1,
        retry_max_delay_secs=0.5,
    )


def _fleet(tmp_path, n=3, **kwargs):
    kwargs.setdefault("lease_timeout_s", 1.0)
    kwargs.setdefault("heartbeat_interval_s", 0.1)
    return subprocess_fleet.SubprocessReplicaManager(
        n, wal_root=str(tmp_path / "fleet"), **kwargs
    )


def _drive(client, start, stop):
    for i in range(start, stop):
        (trial,) = client.get_suggestions(1)
        client.complete_trial(
            trial.id, vz.Measurement(metrics={"obj": 0.01 * i})
        )


class TestKillFailoverRevive:
    def test_sigkill_owner_fails_over_from_standby_and_revives(self, tmp_path):
        fleet = _fleet(tmp_path)
        try:
            study = "owners/sub/studies/kfr"
            fleet.stub.CreateStudy(
                vizier_service_pb2.CreateStudyRequest(
                    parent="owners/sub",
                    study=pc.study_to_proto(_study_config(), study),
                )
            )
            client = vizier_client.VizierClient(
                fleet.stub, study, "w", reliability=_reliability()
            )
            owner = fleet.owner_of(study)
            _drive(client, 0, 6)
            fleet.kill_replica(owner)  # SIGKILL; detection + failover are
            _drive(client, 6, 12)  # absorbed by the client's retries
            assert fleet.owner_of(study) != owner
            stats = fleet.serving_stats()
            assert stats["failovers"] >= 1
            assert stats["recovery_sources"].get("standby", 0) >= 1
            assert not fleet.is_alive(owner)
            # Every driven trial is accounted through the failed-over
            # tier (the records crossed the wire via standby logs).
            assert len(client.list_trials()) == 12
            # Revive: fenced restart on the old port + copy-back; the
            # study routes home and the fleet serves on.
            fleet.revive_replica(owner)
            assert fleet.is_alive(owner)
            assert fleet.owner_of(study) == owner
            _drive(client, 12, 14)
            assert len(client.list_trials()) == 14
        finally:
            fleet.shutdown()


@pytest.mark.slow
class TestPartitionMatrix:
    def test_partition_lease_expiry_fencing_and_slow_replica(self, tmp_path):
        net = netchaos_lib.NetChaos(seed=5)
        fleet = _fleet(tmp_path, netchaos=net)
        try:
            study = "owners/sub/studies/pmx"
            fleet.stub.CreateStudy(
                vizier_service_pb2.CreateStudyRequest(
                    parent="owners/sub",
                    study=pc.study_to_proto(_study_config(), study),
                )
            )
            client = vizier_client.VizierClient(
                fleet.stub, study, "w", reliability=_reliability()
            )
            owner = fleet.owner_of(study)
            _drive(client, 0, 4)

            # SLOW-BUT-ALIVE: heartbeat renewals under injected delay
            # (well under the 1.0 s lease) must never trigger failover.
            net.set_link("manager", owner, delay_prob=1.0, delay_secs=0.3)
            time.sleep(1.5)
            fleet.check_health()
            assert fleet.is_alive(owner)
            assert fleet.serving_stats()["failovers"] == 0
            net.clear_link("manager", owner)

            # PARTITION: total silence expires the lease; the manager
            # fences the zombie's generation and fails over — while the
            # zombie process keeps running.
            fleet._control.call_once(
                owner, "FlushStream", rpb.FlushStreamRequest(timeout_secs=5.0)
            )
            fleet.partition_replica(owner)
            _drive(client, 4, 8)  # retries ride lease expiry + failover
            assert fleet.owner_of(study) != owner
            with fleet._lock:
                zombie_running = fleet._replicas[owner].running()
            assert zombie_running

            # HEAL + stale append at the zombie: rejected by the fenced
            # standby stores (observable via heartbeat) and invisible to
            # the routed tier — no split-brain write wins.
            fleet.heal_partition(owner)
            zombie_stub = grpc_stubs.create_vizier_stub(
                fleet.endpoint_of(owner)
            )
            zombie_stub.CreateTrial(
                vizier_service_pb2.CreateTrialRequest(
                    parent=study,
                    trial=study_pb2.Trial(name=f"{study}/trials/888"),
                )
            )
            deadline = time.monotonic() + 10.0
            fenced = 0
            while time.monotonic() < deadline and not fenced:
                fleet.check_health()
                fenced = fleet.serving_stats()["replication"][
                    "fenced_rejections"
                ]
                time.sleep(0.2)
            assert fenced >= 1
            ids = sorted(t.id for t in client.list_trials())
            assert 888 not in ids and len(ids) == 8
        finally:
            fleet.shutdown()


class TestGracefulShutdown:
    def test_sigterm_drains_flushes_and_dumps(self, tmp_path):
        """The PR 15 shutdown contract: SIGTERM → drain → flush standby →
        compact WAL → observability dump, all before exit."""

        def hold():
            # A port that stays ours until the child listens on it: bound
            # with SO_REUSEPORT (gRPC's own default on a listener), so the
            # child binds beside this socket while the kernel hands the
            # port to nobody else — a picked-then-closed port could go to
            # another xdist worker's socket before the child's bind.
            s = socket.socket()
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
            s.bind(("localhost", 0))
            return s

        held = [hold(), hold()]
        ports = [s.getsockname()[1] for s in held]
        peers = ",".join(
            f"replica-{i}=localhost:{ports[i]}" for i in range(2)
        )
        dump_dir = str(tmp_path / "obs")
        wal_dirs = [str(tmp_path / f"replica-{i}") for i in range(2)]
        procs = []
        for i in range(2):
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable,
                        "-m",
                        "vizier_tpu.distributed.replica_main",
                        "--replica-id",
                        f"replica-{i}",
                        "--port",
                        str(ports[i]),
                        "--wal-dir",
                        wal_dirs[i],
                        "--peers",
                        peers,
                        "--obs-dump-dir",
                        dump_dir,
                    ],
                    stdout=subprocess.PIPE,
                    stderr=subprocess.DEVNULL,
                    text=True,
                    cwd=_REPO_ROOT,
                    env={**os.environ, "JAX_PLATFORMS": "cpu"},
                )
            )
        endpoints = []
        try:
            for proc in procs:
                line = proc.stdout.readline().strip()
                assert line.startswith("READY "), line
                endpoints.append(line.split(" ", 1)[1])
            for s in held:
                s.close()
            # One mutation on replica-0 so there is WAL + standby state
            # for the shutdown to make durable.
            study = study_pb2.Study(name="owners/sub/studies/gs")
            study.study_spec.algorithm = "RANDOM_SEARCH"
            vstub = grpc_stubs.create_vizier_stub(endpoints[0])
            vstub.CreateStudy(
                vizier_service_pb2.CreateStudyRequest(
                    parent="owners/sub", study=study
                )
            )
            rstub = grpc_stubs.create_replication_stub(endpoints[0])
            rstub.FlushStream(rpb.FlushStreamRequest(timeout_secs=10.0))

            for proc in procs:
                proc.send_signal(signal.SIGTERM)
            for proc in procs:
                assert proc.wait(timeout=30) == 0

            # WAL compacted on the way out: the snapshot holds the study.
            assert os.path.exists(os.path.join(wal_dirs[0], "snapshot.bin"))
            # The successor's standby log for replica-0 survived its own
            # graceful close.
            standby = os.path.join(
                wal_dirs[1], "standby", "replica-0", "standby.log"
            )
            assert os.path.exists(standby) and os.path.getsize(standby) > 0
            # Observability dumped per replica, after the stores closed.
            for i in range(2):
                metrics_path = os.path.join(
                    dump_dir, f"replica-{i}-metrics.json"
                )
                assert os.path.exists(metrics_path)
                json.load(open(metrics_path))
        finally:
            for s in held:
                s.close()
            for proc in procs:
                if proc.poll() is None:
                    proc.kill()
            for endpoint in endpoints:
                grpc_stubs.close_channel(endpoint)


class TestOneProcessOwnsTheChip:
    """Which child may take the accelerator: none of the frontends, and the
    compute server by inheritance (``child_env`` pins it for tests)."""

    @pytest.mark.parametrize(
        "child_env, want_frontend, want_compute",
        [
            ({}, "cpu", "tpu"),
            ({"JAX_PLATFORMS": "cpu"}, "cpu", "cpu"),
        ],
    )
    def test_platform_of_each_child(
        self, monkeypatch, tmp_path, child_env, want_frontend, want_compute
    ):
        fleet = object.__new__(subprocess_fleet.SubprocessReplicaManager)
        fleet.config = subprocess_fleet.config_lib.DistributedConfig()
        fleet._wal_root = str(tmp_path)
        fleet._child_env = child_env
        fleet._obs_dump_dir = ""
        fleet._compute = None
        fleet._peers_arg = "replica-0=localhost:1"
        spawned = {}

        def fake_popen(args, **kwargs):
            spawned[args[2]] = kwargs["env"]

        monkeypatch.setattr(subprocess_fleet.subprocess, "Popen", fake_popen)
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")  # a machine with a chip
        rec = subprocess_fleet._ReplicaProcess(
            "replica-0", 1, str(tmp_path / "replica-0")
        )
        fleet._spawn(rec, epoch=1)
        fleet._spawn_compute(rec)
        assert (
            spawned["vizier_tpu.distributed.replica_main"]["JAX_PLATFORMS"]
            == want_frontend
        )
        assert (
            spawned["vizier_tpu.distributed.pythia_server_main"]["JAX_PLATFORMS"]
            == want_compute
        )

    def test_manager_import_initializes_no_backend(self):
        """The manager parent must stay off JAX's backends, or it would hold
        the chip its compute server needs."""
        code = (
            "import vizier_tpu.distributed.subprocess_fleet\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n"
        )
        subprocess.run(
            [sys.executable, "-c", code],
            check=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            timeout=120,
        )
