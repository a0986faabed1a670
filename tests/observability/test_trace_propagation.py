"""End-to-end trace propagation: client → service → Pythia → designer.

One client ``suggest()`` against the in-process stack must yield ONE
``trace_id`` whose spans cover all four hops with correct parentage and
start-time ordering — including across the ResponseWaiter worker-thread
hop (deadlines on) — plus the coalesced-follower case where the follower's
Pythia span links to the leader's computation span.
"""

import threading
import time

import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.algorithms import designer_policy
from vizier_tpu.designers import random as random_designer
from vizier_tpu.observability import tracing as tracing_lib
from vizier_tpu.reliability import config as reliability_config_lib
from vizier_tpu.service import proto_converters as pc
from vizier_tpu.service import pythia_service, vizier_client, vizier_service
from vizier_tpu.service.protos import vizier_service_pb2

STUDY = "owners/obs/studies/trace"


def _study_config():
    config = vz.StudyConfig(algorithm="RANDOM_SEARCH")
    config.search_space.root.add_float_param("x", 0.0, 1.0)
    config.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    return config


class _RandomDesignerPolicyFactory:
    """Routes every algorithm through DesignerPolicy → designer spans."""

    def __call__(self, problem, algorithm, supporter, study_name):
        return designer_policy.DesignerPolicy(
            supporter,
            lambda p, **kw: random_designer.RandomDesigner(p.search_space, seed=0),
        )


class _SlowDesignerPolicyFactory(_RandomDesignerPolicyFactory):
    """Same, but the designer's suggest dawdles so concurrents coalesce."""

    def __init__(self, delay_secs: float):
        self._delay = delay_secs

    def __call__(self, problem, algorithm, supporter, study_name):
        delay = self._delay

        class _SlowRandom(random_designer.RandomDesigner):
            def suggest(self, count=None):
                time.sleep(delay)
                return super().suggest(count)

        return designer_policy.DesignerPolicy(
            supporter, lambda p, **kw: _SlowRandom(p.search_space, seed=0)
        )


def _make_stack(policy_factory=None, reliability=None):
    servicer = vizier_service.VizierServicer(reliability_config=reliability)
    pythia = pythia_service.PythiaServicer(
        servicer, policy_factory, reliability_config=reliability
    )
    servicer.set_pythia(pythia)
    servicer.CreateStudy(
        vizier_service_pb2.CreateStudyRequest(
            parent="owners/obs",
            study=pc.study_to_proto(_study_config(), STUDY),
        )
    )
    return servicer, pythia


@pytest.fixture
def tracer():
    t = tracing_lib.Tracer()
    old = tracing_lib.set_tracer(t)
    yield t
    tracing_lib.set_tracer(old)


class TestFourHopTrace:
    def test_single_trace_with_ordered_spans(self, tracer):
        servicer, _ = _make_stack(policy_factory=_RandomDesignerPolicyFactory())
        client = vizier_client.VizierClient(servicer, STUDY, "worker-0")
        (trial,) = client.get_suggestions(1)
        assert trial.parameters

        spans = tracer.finished_spans()
        roots = [s for s in spans if s.name == "client.suggest"]
        assert len(roots) == 1
        trace_id = roots[0].trace_id
        # Every span this exchange produced belongs to ONE trace.
        assert {s.trace_id for s in spans} == {trace_id}

        chain = tracer.spans_for_trace(trace_id)
        names = [s.name for s in chain]
        hops = [
            "client.suggest",
            "service.suggest_trials",
            "service.pythia_dispatch",
            "pythia.suggest",
            "pythia.suggest_compute",
            "designer.update",
            "designer.suggest",
        ]
        for hop in hops:
            assert hop in names, f"missing span {hop!r} (got {names})"
        # Start-time order follows the request's path downward.
        positions = [names.index(h) for h in hops[:4]]
        assert positions == sorted(positions)

        by_name = {s.name: s for s in chain}
        # Parentage: each hop is a child of the previous one.
        assert by_name["client.suggest"].parent_id is None
        assert (
            by_name["service.suggest_trials"].parent_id
            == by_name["client.suggest"].span_id
        )
        assert (
            by_name["service.pythia_dispatch"].parent_id
            == by_name["service.suggest_trials"].span_id
        )
        # The Pythia hop crossed the ResponseWaiter worker thread (deadlines
        # default on) — its parent comes from the proto's trace_context.
        assert (
            by_name["pythia.suggest"].parent_id
            == by_name["service.pythia_dispatch"].span_id
        )
        assert (
            by_name["pythia.suggest_compute"].parent_id
            == by_name["pythia.suggest"].span_id
        )
        assert (
            by_name["designer.suggest"].parent_id
            == by_name["pythia.suggest_compute"].span_id
        )
        # Deadline budget was stamped at the service + pythia hops.
        assert by_name["service.suggest_trials"].attributes[
            "deadline_budget_secs"
        ] > 0
        assert by_name["pythia.suggest"].attributes["deadline_remaining_secs"] > 0

    def test_two_suggests_two_traces(self, tracer):
        servicer, _ = _make_stack(policy_factory=_RandomDesignerPolicyFactory())
        client = vizier_client.VizierClient(servicer, STUDY, "worker-0")
        client.get_suggestions(1)
        client.get_suggestions(1)
        roots = [s for s in tracer.finished_spans() if s.name == "client.suggest"]
        assert len(roots) == 2
        assert roots[0].trace_id != roots[1].trace_id


class TestCoalescedFollowerLink:
    # Driven at the Pythia servicer, where coalescing lives: two
    # SuggestTrials of one study take turns and never meet there.
    def test_follower_span_links_to_leader_computation(self, tracer):
        from vizier_tpu.service.protos import pythia_service_pb2

        servicer, pythia = _make_stack(
            policy_factory=_SlowDesignerPolicyFactory(delay_secs=0.4)
        )
        n = 2
        study = servicer.GetStudy(vizier_service_pb2.GetStudyRequest(name=STUDY))
        preq = pythia_service_pb2.PythiaSuggestRequest(
            count=1, algorithm=study.study_spec.algorithm, study_name=STUDY
        )
        preq.study_descriptor.config.CopyFrom(study.study_spec)
        preq.study_descriptor.guid = STUDY
        responses = [None] * n
        barrier = threading.Barrier(n)

        def worker(i):
            barrier.wait(timeout=10)
            responses[i] = pythia.Suggest(preq)

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        for resp in responses:
            assert resp is not None and not resp.error
        assert pythia.serving_stats()["coalesced_requests"] == n - 1

        spans = tracer.finished_spans()
        computes = [s for s in spans if s.name == "pythia.suggest_compute"]
        assert len(computes) == 1  # ONE designer computation served both
        leader_compute = computes[0]

        pythia_spans = [s for s in spans if s.name == "pythia.suggest"]
        assert len(pythia_spans) == n
        followers = [s for s in pythia_spans if s.attributes.get("coalesced")]
        assert len(followers) == n - 1
        for follower in followers:
            # Different trace (different client request)...
            assert follower.trace_id != leader_compute.trace_id
            # ...but linked to the computation that produced its answer.
            assert {
                "trace_id": leader_compute.trace_id,
                "span_id": leader_compute.span_id,
                "name": "coalesced_leader",
            } in follower.links


class TestDisabledTracing:
    def test_noop_tracer_produces_no_spans_and_still_serves(self):
        old = tracing_lib.set_tracer(tracing_lib.NOOP_TRACER)
        try:
            servicer, _ = _make_stack(
                policy_factory=_RandomDesignerPolicyFactory()
            )
            client = vizier_client.VizierClient(servicer, STUDY, "worker-0")
            (trial,) = client.get_suggestions(1)
            assert trial.parameters
            assert tracing_lib.get_tracer().finished_spans() == []
        finally:
            tracing_lib.set_tracer(old)

    def test_untraced_request_starts_fresh_trace_at_service(self, tracer):
        servicer, _ = _make_stack(policy_factory=_RandomDesignerPolicyFactory())
        op = servicer.SuggestTrials(
            vizier_service_pb2.SuggestTrialsRequest(
                parent=STUDY, suggestion_count=1, client_id="bare"
            )
        )
        assert op.done and not op.error
        service_spans = [
            s for s in tracer.finished_spans() if s.name == "service.suggest_trials"
        ]
        assert len(service_spans) == 1
        assert service_spans[0].parent_id is None  # no client span upstream
