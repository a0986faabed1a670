"""Multi-client service stress over REAL gRPC.

Parity with the reference's ``performance_test.py:44-89`` topology: one
``DefaultVizierServer``, N thread-pool clients each running its own
suggest→complete loop against one shared study, wall-time logged (the
reference asserts nothing beyond completion either — the invariants checked
here are stronger: trial-count accounting and per-worker trial disjointness).
"""

import concurrent.futures as cf

import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.service import clients as clients_lib
from vizier_tpu.service import vizier_server


@pytest.fixture(scope="module")
def server():
    return vizier_server.DefaultVizierServer(host="localhost")


from vizier_tpu.testing import stress

_study_config = stress.stress_study_config


@pytest.mark.parametrize(
    "num_clients,num_trials_each",
    [(1, 10), (2, 10), (10, 5), (25, 3)],
)
def test_multi_client_suggest_complete_over_grpc(
    server, num_clients, num_trials_each
):
    clients_lib.environment_variables.server_endpoint = server.endpoint
    try:
        study = clients_lib.Study.from_study_config(
            _study_config(),
            owner="perf",
            study_id=f"stress-{num_clients}x{num_trials_each}",
        )
        # Upstream's topology (the cell perftest2d.shared50x5 times it on the chip).
        elapsed, completed, per_worker = stress.run_stress_round(
            study, num_clients, num_trials_each
        )
        all_ids = [tid for ids in per_worker for tid in ids]
        # Every worker's completions are distinct trials — no cross-worker
        # reuse, no lost updates under the per-study locks.
        assert len(set(all_ids)) == len(all_ids) == num_clients * num_trials_each
        assert completed == num_clients * num_trials_each
        print(
            f"[perf] {num_clients} clients x {num_trials_each} trials over gRPC: "
            f"{elapsed:.2f}s ({len(all_ids) / elapsed:.1f} trials/s)"
        )
    finally:
        clients_lib.environment_variables.server_endpoint = clients_lib.NO_ENDPOINT


def test_distributed_pythia_topology_under_load(server):
    """Split Vizier/Pythia servers (two gRPC processes' worth of servicers),
    several concurrent workers using an algorithmic policy."""
    dist = vizier_server.DistributedPythiaVizierServer(host="localhost")
    clients_lib.environment_variables.server_endpoint = dist.endpoint
    try:
        sc = _study_config()
        sc.algorithm = "QUASI_RANDOM_SEARCH"
        study = clients_lib.Study.from_study_config(
            sc, owner="perf", study_id="dist-stress"
        )

        def worker(worker_id: int):
            for _ in range(3):
                (trial,) = study.suggest(count=1, client_id=f"w{worker_id}")
                trial.complete(vz.Measurement(metrics={"obj": float(trial.id)}))
            return worker_id

        with cf.ThreadPoolExecutor(4) as ex:
            done = list(ex.map(worker, range(4)))
        assert sorted(done) == [0, 1, 2, 3]
        completed = list(
            study.trials(vz.TrialFilter(status=[vz.TrialStatus.COMPLETED]))
        )
        assert len(completed) == 12
    finally:
        clients_lib.environment_variables.server_endpoint = clients_lib.NO_ENDPOINT


class TestSharedChannelLifecycle:
    def test_failed_ready_wait_evicts_entry_and_retries_fail_fast(self):
        from vizier_tpu.service import grpc_stubs

        dead = "127.0.0.1:1"  # nothing listens on port 1
        for _ in range(2):  # retry must re-attempt readiness, not hang
            with pytest.raises(Exception):
                grpc_stubs.create_vizier_stub(dead, timeout=0.5)
            assert dead not in grpc_stubs._CHANNELS

    def test_channel_closed_and_evicted_on_server_stop(self):
        from vizier_tpu.service import grpc_stubs

        srv = vizier_server.DefaultVizierServer(host="localhost")
        grpc_stubs.create_vizier_stub(srv.endpoint)
        assert srv.endpoint in grpc_stubs._CHANNELS
        srv.stop(0)
        assert srv.endpoint not in grpc_stubs._CHANNELS

    def test_broken_cached_channel_evicted_and_reconnected(self):
        from vizier_tpu.service import grpc_stubs

        srv = vizier_server.DefaultVizierServer(host="localhost")
        try:
            stub = grpc_stubs.create_vizier_stub(srv.endpoint)
            entry = grpc_stubs._CHANNELS[srv.endpoint]
            # Simulate a server dying WITHOUT close_channel(): the watcher
            # flags the entry; the next stub creation must not serve it.
            entry.broken = True
            stub2 = grpc_stubs.create_vizier_stub(srv.endpoint)
            new_entry = grpc_stubs._CHANNELS[srv.endpoint]
            assert new_entry is not entry
            assert not new_entry.broken
            assert stub2 is not stub  # fresh stub on the fresh channel
        finally:
            srv.stop(0)

    def test_only_shutdown_marks_entry_broken(self):
        import grpc as grpc_lib

        from vizier_tpu.service import grpc_stubs

        srv = vizier_server.DefaultVizierServer(host="localhost")
        try:
            grpc_stubs.create_vizier_stub(srv.endpoint)
            entry = grpc_stubs._CHANNELS[srv.endpoint]
            assert not entry.broken
            # TRANSIENT_FAILURE is a normal reconnect state (server restart
            # blip): it must NOT flag the channel — evicting on it would
            # close() the channel underneath every stub sharing it while
            # gRPC's auto-reconnect would have recovered.
            entry._watch(grpc_lib.ChannelConnectivity.TRANSIENT_FAILURE)
            assert not entry.broken
            entry._watch(grpc_lib.ChannelConnectivity.READY)
            assert not entry.broken
            # Only SHUTDOWN (the channel is permanently dead) flags it.
            entry._watch(grpc_lib.ChannelConnectivity.SHUTDOWN)
            assert entry.broken
        finally:
            srv.stop(0)


class TestSuggestScalesConstantTime:
    """Regression gate for the round-5 open/undone indexes: the per-suggest
    datastore work must not grow with completed history. Counted in proto
    copies (deterministic) rather than wall time (flaky)."""

    def test_copies_per_suggest_independent_of_history(self, monkeypatch):
        from tests.service.test_service import _make_servicer
        from vizier_tpu.service import proto_converters as pcv
        from vizier_tpu.service import ram_datastore
        from vizier_tpu.service.protos import study_pb2, vizier_service_pb2 as V
        from vizier_tpu.testing import stress

        servicer = _make_servicer()
        study = pcv.study_to_proto(
            stress.stress_study_config(), "owners/p/studies/s"
        )
        servicer.CreateStudy(V.CreateStudyRequest(parent="owners/p", study=study))
        name = "owners/p/studies/s"

        def round_():
            op = servicer.SuggestTrials(
                V.SuggestTrialsRequest(
                    parent=name, suggestion_count=1, client_id="w"
                )
            )
            assert not op.error, op.error
            t = op.response.trials[0]
            m = study_pb2.Measurement()
            m.metrics.add(name="obj", value=0.5)
            servicer.CompleteTrial(
                V.CompleteTrialRequest(name=t.name, final_measurement=m)
            )

        counter = {"copies": 0}
        real_copy = ram_datastore._copy

        def counting_copy(proto):
            counter["copies"] += 1
            return real_copy(proto)

        monkeypatch.setattr(ram_datastore, "_copy", counting_copy)

        def copies_for_round():
            counter["copies"] = 0
            round_()
            return counter["copies"]

        baseline = max(copies_for_round() for _ in range(3))
        for _ in range(300):  # grow the completed history
            round_()
        at_scale = max(copies_for_round() for _ in range(3))
        # Identical datastore work regardless of history size; allow +2
        # copies of slack for incidental bookkeeping.
        assert at_scale <= baseline + 2, (baseline, at_scale)
