"""The serving policy's delta trial read.

Through a real ``VizierServicer`` and a recording designer: every
``designer.update`` receives exactly the trials the former read (list the
whole study, convert every proto, then filter; once for COMPLETED, once for
ACTIVE) would have handed it: same ids, same order, same field values.
"""

import numpy as np
import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.distributed import sharded_datastore, wal
from vizier_tpu.observability import tracing as tracing_lib
from vizier_tpu.pythia import policy as pythia_policy
from vizier_tpu.reliability import fallback
from vizier_tpu.service import proto_converters as pc
from vizier_tpu.service import pythia_service, ram_datastore
from vizier_tpu.service import service_policy_supporter, sql_datastore
from vizier_tpu.service import vizier_client, vizier_service
from vizier_tpu.service.protos import vizier_service_pb2
from vizier_tpu.serving import policy as serving_policy

from tests.service.test_policy_supporter import RpcSurface

STUDY = "owners/delta/studies/s"
DIMS = 3


class RecordingDesigner:
    """Keeps what every ``update`` was given; suggests seeded noise."""

    def __init__(self, problem, **_):
        self.updates = []
        self._rng = np.random.default_rng(0)

    def update(self, completed, active):
        self.updates.append((list(completed.trials), list(active.trials)))

    def suggest(self, count=1):
        return [
            vz.TrialSuggestion(
                {f"x{d}": float(self._rng.uniform()) for d in range(DIMS)}
            )
            for _ in range(count)
        ]


def _datastore(backend, tmp_path):
    if backend == "sql":
        return sql_datastore.SQLDataStore("sqlite:///:memory:")
    if backend == "sharded":
        return sharded_datastore.ShardedDataStore(
            [ram_datastore.NestedDictRAMDataStore() for _ in range(3)]
        )
    if backend == "wal":
        return wal.PersistentDataStore(str(tmp_path))
    return ram_datastore.NestedDictRAMDataStore()  # "ram", "stub"


class Stack:
    """A servicer, its Pythia and one study served by the cached policy."""

    def __init__(self, backend, tmp_path):
        self.servicer = vizier_service.VizierServicer(
            datastore=_datastore(backend, tmp_path)
        )
        self.designers = []
        vizier = RpcSurface(self.servicer) if backend == "stub" else self.servicer
        self.pythia = pythia_service.PythiaServicer(vizier, self._policy)
        self.runtime = self.pythia.serving_runtime
        self.servicer.set_pythia(self.pythia)
        config = vz.StudyConfig(algorithm="DEFAULT")
        for d in range(DIMS):
            config.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
        config.metric_information.append(
            vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
        )
        self.servicer.CreateStudy(
            vizier_service_pb2.CreateStudyRequest(
                parent="owners/delta", study=pc.study_to_proto(config, STUDY)
            )
        )
        self.expected = []  # what the former read would have handed over
        self._fed = set()  # ids fed into the live designer

    def _policy(self, problem, algorithm, supporter, study_name):
        return serving_policy.CachedDesignerStatePolicy(
            supporter, self._designer, self.runtime, study_name
        )

    def _designer(self, problem, **kwargs):
        self.designers.append(RecordingDesigner(problem))
        return self.designers[-1]

    def client(self, worker="w0"):
        return vizier_client.VizierClient(self.servicer, STUDY, worker)

    def add_completed(self, n, seed=0):
        rng = np.random.default_rng(seed)
        for _ in range(n):
            trial = vz.Trial(
                parameters={f"x{d}": float(rng.uniform()) for d in range(DIMS)}
            )
            trial.complete(vz.Measurement(metrics={"obj": float(rng.uniform())}))
            self.client("loader").create_trial(trial)

    def suggest(self, count):
        """One suggest; first notes what convert-all-then-filter gives.

        Each comes from a worker of its own: a worker that still holds
        ACTIVE trials is handed those back and no policy runs.
        """
        worker = f"w{len(self.expected)}"
        trials = [
            pc.trial_from_proto(p)
            for p in self.servicer.ListTrials(
                vizier_service_pb2.ListTrialsRequest(parent=STUDY)
            ).trials
        ]
        if self.runtime.designer_cache.peek(STUDY, touch=False) is None:
            self._fed = set()  # the next designer starts from nothing
        new_completed = [
            t for t in trials
            if t.status == vz.TrialStatus.COMPLETED and t.id not in self._fed
        ]
        active = [t for t in trials if t.status == vz.TrialStatus.ACTIVE]
        before = sum(len(d.updates) for d in self.designers)
        suggestions = self.client(worker).get_suggestions(count)
        assert len(suggestions) == count
        assert sum(len(d.updates) for d in self.designers) == before + 1
        self.expected.append((new_completed, active))
        self._fed.update(t.id for t in new_completed)
        return suggestions

    def complete(self, trial, value=0.5, worker="w0", infeasible=False):
        if infeasible:
            self.client(worker).complete_trial(trial.id, infeasibility_reason="nan")
        else:
            self.client(worker).complete_trial(
                trial.id, vz.Measurement(metrics={"obj": value})
            )

    def received(self):
        return [u for d in self.designers for u in d.updates]

    def assert_same_as_former_read(self):
        received = self.received()
        assert len(received) == len(self.expected)
        for (got_done, got_active), (want_done, want_active) in zip(
            received, self.expected
        ):
            assert [t.id for t in got_done] == [t.id for t in want_done]
            assert [t.id for t in got_active] == [t.id for t in want_active]
            assert got_done == want_done  # every field of every trial
            assert got_active == want_active
        for designer in self.designers:  # nothing fed twice into one designer
            fed = [t.id for done, _ in designer.updates for t in done]
            assert len(fed) == len(set(fed))


@pytest.fixture
def stack(tmp_path):
    old_tracer = tracing_lib.set_tracer(tracing_lib.Tracer())
    stacks = []

    def build(backend="ram"):
        stacks.append(Stack(backend, tmp_path))
        return stacks[-1]

    yield build
    for s in stacks:
        s.pythia.shutdown()
    tracing_lib.set_tracer(old_tracer)


def _in_order(s):
    for round_ in range(3):
        for i, trial in enumerate(s.suggest(3)):
            s.complete(trial, value=0.1 * i + round_)
    s.suggest(2)


def _out_of_order(s):
    first = s.suggest(3)  # ids 5, 6, 7 after the four loaded ones
    s.complete(first[2])
    s.suggest(1)  # 7 incorporated; 5 and 6 still ACTIVE
    s.complete(first[0])  # a lower id completes after a higher one went in
    s.suggest(1)
    s.complete(first[1])
    s.suggest(1)
    updates = s.received()
    assert [t.id for t in updates[1][0]] == [first[2].id]
    assert [t.id for t in updates[2][0]] == [first[0].id]
    assert [t.id for t in updates[3][0]] == [first[1].id]


def _infeasible(s):
    a, b = s.suggest(2)
    s.complete(a, infeasible=True)
    s.complete(b)
    s.suggest(1)
    done = s.received()[-1][0]
    assert [t.id for t in done] == [a.id, b.id] and done[0].infeasible


def _other_workers_active(s):
    theirs = s.suggest(2)  # stay ACTIVE under another worker
    mine = s.suggest(2)
    s.complete(mine[0])
    s.suggest(1)
    active_ids = [t.id for t in s.received()[-1][1]]
    assert set(t.id for t in theirs) <= set(active_ids)
    assert active_ids == sorted(active_ids)


def _deleted(s):
    a, b, c = s.suggest(3)
    s.complete(a)
    s.suggest(1)  # a incorporated
    s.client().delete_trial(a.id)  # after incorporation: not noticed
    s.complete(b)
    s.client().delete_trial(b.id)  # before incorporation: never seen
    s.complete(c)
    s.suggest(1)
    assert [t.id for t in s.received()[-1][0]] == [c.id]


def _invalidated(s):
    _in_order(s)
    s.runtime.designer_cache.invalidate(STUDY)
    s.complete(s.suggest(1)[0])  # a new designer: the whole study, once
    s.suggest(1)
    assert len(s.designers) == 2
    replay = s.designers[1].updates[0][0]
    assert len(replay) == 4 + 9  # loaded + completed so far


def _evicted(s):
    _in_order(s)
    s.runtime.designer_cache.clear()
    s.suggest(1)
    assert len(s.designers) == 2 and len(s.designers[1].updates[0][0]) == 13


SCENARIOS = {
    "in_order": _in_order,
    "out_of_order": _out_of_order,
    "infeasible": _infeasible,
    "other_workers_active": _other_workers_active,
    "deleted": _deleted,
    "invalidated": _invalidated,
    "evicted": _evicted,
}


@pytest.mark.parametrize("backend", ["ram", "stub", "sql", "sharded", "wal"])
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_update_receives_what_the_former_read_gave(stack, scenario, backend):
    s = stack(backend)
    s.add_completed(4)
    SCENARIOS[scenario](s)
    s.assert_same_as_former_read()


def test_deleted_between_frontier_and_fetch_is_skipped(stack):
    """The frontier names a completed trial that is gone by the fetch."""
    s = stack()
    s.add_completed(5)
    frontier = s.servicer.trial_frontier

    def racing_frontier(study_name):
        result = frontier(study_name)
        s.servicer.datastore.delete_trial(f"{STUDY}/trials/2")
        return result

    s.servicer.trial_frontier = racing_frontier
    s.client().get_suggestions(1)
    done, _ = s.received()[-1]
    assert [t.id for t in done] == [1, 3, 4, 5]


@pytest.mark.parametrize("held", [0, 2], ids=["cold_entry", "warm_entry"])
def test_completed_between_frontier_and_fetch_is_fed_once(stack, held):
    """ACTIVE at the frontier, completed by the fetch: fed as completed
    now, counted as fetched and never as reused (a cold entry over a study
    with nothing completed would otherwise count -1 and fail the suggest),
    and not fed again by the next suggest."""
    s = stack()
    s.add_completed(held)
    (pending,) = s.client("w1").get_suggestions(1)
    if not held:  # the policy ran for w1: start over from an empty entry
        s.runtime.designer_cache.invalidate(STUDY)
    frontier = s.servicer.trial_frontier

    def racing_frontier(study_name):
        result = frontier(study_name)
        if pending.id in result[1]:
            s.complete(pending, worker="w1")
        return result

    s.servicer.trial_frontier = racing_frontier
    before = s.servicer.serving_stats()
    (suggestion,) = s.client().get_suggestions(1)
    after = s.servicer.serving_stats()
    assert not fallback.is_fallback_suggestion(suggestion.metadata)
    for counter in ("designer_failures", "fallbacks"):
        assert after[counter] == before[counter] == 0
    assert after["trials_fetched"] - before["trials_fetched"] == 1
    assert after["trials_reused"] - before["trials_reused"] == held
    s.client().get_suggestions(1)
    fed = [t.id for done, _ in s.designers[-1].updates for t in done]
    assert sorted(fed) == list(range(1, held + 1)) + [pending.id]


def test_supporter_without_a_service_behind_it(stack):
    """A plain PolicySupporter serves the same call from one listing."""
    from vizier_tpu.pythia import local_policy_supporters

    s = stack()
    supporter = local_policy_supporters.InRamPolicySupporter(
        s.client().get_study_config()
    )
    trials = []
    for i in range(4):
        trial = vz.Trial(parameters={f"x{d}": 0.1 * i for d in range(DIMS)})
        if i < 3:
            trial.complete(vz.Measurement(metrics={"obj": float(i)}))
        trials.append(trial)
    supporter.AddTrials(trials)
    new_completed, active, num_completed = supporter.GetTrialDelta({2})
    assert [t.id for t in new_completed] == [1, 3]
    assert [t.id for t in active] == [4]
    assert num_completed == 3
    policy = serving_policy.CachedDesignerStatePolicy(
        supporter, s._designer, s.runtime, "owners/delta/studies/local"
    )
    decision = policy.suggest(
        pythia_policy.SuggestRequest(
            study_descriptor=supporter.study_descriptor(), count=1
        )
    )
    assert len(decision.suggestions) == 1
    done, pending = s.designers[-1].updates[-1]
    assert [t.id for t in done] == [1, 2, 3] and [t.id for t in pending] == [4]


@pytest.mark.parametrize("backend", ["ram", "sql"])
def test_a_replay_is_one_listing_and_a_warm_read_is_by_name(stack, backend):
    """Most of the study missing: one filtered listing, no by-name get;
    little missing: by-name gets of just those, no listing."""
    s = stack(backend)
    s.add_completed(6)
    store = s.servicer.datastore
    calls = []
    get_trial, list_trials = store.get_trial, store.list_trials
    store.get_trial = lambda name: calls.append("get") or get_trial(name)
    store.list_trials = lambda name, **kw: (
        calls.append(("list", kw.get("states"))) or list_trials(name, **kw)
    )
    supporter = service_policy_supporter.ServicePolicySupporter(STUDY, s.servicer)
    frontier_lists = 0 if backend == "ram" else 1  # trial_states' default
    done, _, num_completed = supporter.GetTrialDelta(set())
    assert [t.id for t in done] == [1, 2, 3, 4, 5, 6] and num_completed == 6
    assert "get" not in calls
    assert len(calls) == frontier_lists + 1 and calls[-1][1] is not None
    del calls[:]
    done, _, num_completed = supporter.GetTrialDelta({1, 2, 3, 4})
    assert [t.id for t in done] == [5, 6] and num_completed == 6
    assert calls.count("get") == 2 and len(calls) == frontier_lists + 2


@pytest.mark.parametrize("backend", ["ram", "stub"])
def test_a_warm_suggest_converts_only_what_it_lacks(stack, backend, monkeypatch):
    """400 trials held, 25 new, 3 of another worker's ACTIVE: a warm
    suggest converts 28 protos, and the counters and the span say so."""
    s = stack(backend)
    s.add_completed(400)
    others = s.client("w1").get_suggestions(3)  # cold round: the whole study
    batch = s.client().get_suggestions(25)
    for i, trial in enumerate(batch):
        s.complete(trial, value=0.01 * i)
    converted = []
    convert = pc.trial_from_proto
    monkeypatch.setattr(
        pc, "trial_from_proto", lambda p: converted.append(p.id) or convert(p)
    )
    before = s.servicer.serving_stats()
    tracer = tracing_lib.get_tracer()
    seen = len(tracer.finished_spans())
    s.client("w2").get_suggestions(2)
    # Ids over 428 are the client converting its two new suggestions.
    in_policy = [i for i in converted if i <= 428]
    assert len(converted) == len(in_policy) + 2
    monkeypatch.undo()
    after = s.servicer.serving_stats()

    assert sorted(in_policy) == sorted(t.id for t in others + batch)
    assert len(in_policy) == 25 + 3
    assert after["trials_fetched"] - before["trials_fetched"] == 28
    assert after["trials_reused"] - before["trials_reused"] == 400
    assert before["trials_reused"] == 400  # w0's suggest; 0 in the cold round
    (load,) = [
        sp for sp in tracer.finished_spans()[seen:] if sp.name == "policy.load_trials"
    ]
    assert load.attributes["completed"] == 425
    assert load.attributes["fetched"] == 28
    done, active = s.received()[-1]
    assert [t.id for t in done] == [t.id for t in batch]
    assert [t.id for t in active] == [t.id for t in others]


class TestEncodedRowCounters:
    """``rows_encoded`` / ``rows_reused`` through ``serving_stats()``: what a
    suggest of the real DEFAULT designer encoded against what it took from
    the designer's store of encoded rows."""

    @staticmethod
    def _real_designer(problem, **_):
        from vizier_tpu.designers import gp_ucb_pe
        from vizier_tpu.optimizers import lbfgs

        return gp_ucb_pe.VizierGPUCBPEBandit(
            problem,
            max_acquisition_evaluations=100,
            ard_optimizer=lbfgs.LbfgsOptimizer(maxiter=2),
        )

    def _round(self, s, count):
        before = s.servicer.serving_stats()
        suggestions = s.client(f"w{len(s.expected)}").get_suggestions(count)
        s.expected.append(None)  # one more worker name used
        after = s.servicer.serving_stats()
        assert after["fallbacks"] == before["fallbacks"] == 0
        return suggestions, {
            k: after[k] - before[k] for k in ("rows_encoded", "rows_reused")
        }

    def test_a_round_of_25_new_trials_reuses_the_rows_it_held(self, stack):
        s = stack()
        s._designer = self._real_designer
        s.add_completed(360)
        suggestions, first = self._round(s, 25)
        assert first == {"rows_encoded": 360, "rows_reused": 0}  # a cold entry
        for trial in suggestions:
            s.complete(trial, worker="w0")
        _, second = self._round(s, 25)
        assert second == {"rows_encoded": 25, "rows_reused": 360}
        share = second["rows_reused"] / sum(second.values())
        assert 0.93 < share < 0.94
        # An invalidated entry replays the study into a new designer: every
        # row is encoded again and none is taken from a store.
        s.runtime.designer_cache.invalidate(STUDY)
        _, third = self._round(s, 1)
        assert third == {"rows_encoded": 385 + 25, "rows_reused": 0}

    def test_pending_rows_are_encoded_every_turn(self, stack):
        s = stack()
        s._designer = self._real_designer
        s.add_completed(6)
        self._round(s, 2)  # two trials stay ACTIVE under their worker
        _, turn = self._round(s, 1)
        assert turn == {"rows_encoded": 2, "rows_reused": 6}
