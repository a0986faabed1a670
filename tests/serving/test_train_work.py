"""What the ARD train programs count of their own work reaches
``serving_stats()`` (PR 39): once a program — a sequential training suggest,
a fused flush whatever its members — by the arrays the program returned,
and not at all from a suggest that found its fit cached. Beside them (PR 45)
the sequential trains that enqueued their sweeps while they still ran
(``sweeps_ahead`` of ``sequential_trains``), on two ``device.wait`` spans that
stay disjoint and in order."""

import threading

import numpy as np
import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.designers import gp_ucb_pe
from vizier_tpu.observability import config as config_lib
from vizier_tpu.observability import jax_timing
from vizier_tpu.observability import tracing as tracing_lib
from vizier_tpu.optimizers import lbfgs as lbfgs_lib
from vizier_tpu.service import vizier_client

COUNTERS = (
    "train_programs", "train_loop_trips", "train_row_trips",
    "train_row_iterations", "train_evaluations",
)


def _lbfgs_ucb_pe(problem, **kwargs):
    """GP-UCB-PE at test size with the L-BFGS trainer: rows that stop at
    different iterations, as the served default's do."""
    return gp_ucb_pe.VizierGPUCBPEBandit(
        problem,
        ard_optimizer=lbfgs_lib.LbfgsOptimizer(maxiter=30),
        ard_restarts=3,
        max_acquisition_evaluations=200,
        warm_start_min_trials=0,
    )


@pytest.fixture
def fetched(monkeypatch):
    """Every work array a phase read from the device, as fetched."""
    seen = []
    real = lbfgs_lib.work_counts

    def recording(work):
        seen.append(np.array(work))
        return real(work)

    monkeypatch.setattr(lbfgs_lib, "work_counts", recording)
    jax_timing.set_config(config_lib.ObservabilityConfig())
    yield seen
    jax_timing.set_config(None)


def _gained(runtime, before):
    after = runtime.stats.snapshot()
    return {name: after[name] - before[name] for name in (*COUNTERS, "cached_fit_suggests",
                                                          "warm_trains", "cold_trains", "batch_flushes",
                                                          "sequential_trains", "sweeps_ahead")}


def _train_spans():
    return [
        s for s in tracing_lib.get_tracer().finished_spans()
        if s.name == "device.wait" and "loop_trips" in s.attributes
    ]


def _last_suggest_spans():
    """(designer.prepare, device.wait train, device.wait acquire) of the
    last sequential suggest, as (start, end) on the spans' own clock."""
    spans = tracing_lib.get_tracer().finished_spans()
    prepare = [s for s in spans if s.name == "designer.prepare"][-1]
    train, acquire = [s for s in spans if s.name == "device.wait"][-2:]
    assert (train.attributes["stage"], acquire.attributes["stage"]) == ("train", "acquire")
    assert prepare.span_id not in (train.parent_id, acquire.parent_id)
    return [(s._t0, s._t0 + s.duration_secs) for s in (prepare, train, acquire)]


def test_a_sequential_suggest_counts_its_one_train_program(served_gp_stack, fetched):
    servicer, runtime, (study,) = served_gp_stack(1, designer_factory=_lbfgs_ucb_pe)
    client = vizier_client.VizierClient(servicer, study, "worker")
    before = runtime.stats.snapshot()
    trials = client.get_suggestions(2)
    gained = _gained(runtime, before)
    (work,) = fetched  # ONE read: one metric, one train program
    iterations, evaluations = work  # [2, rows]
    assert work.dtype == np.int32 and work.shape == (2, 4)  # 3 restarts + the seed's row
    assert gained["train_programs"] == 1 == gained["warm_trains"] + gained["cold_trains"]
    assert gained["train_loop_trips"] == iterations.max() > iterations.min()  # lockstep idles
    assert gained["train_row_trips"] == iterations.size * iterations.max()
    assert gained["train_row_iterations"] == iterations.sum()
    assert gained["train_evaluations"] == evaluations.sum()
    (span,) = _train_spans()
    assert span.attributes["stage"] == "train"
    assert span.attributes["loop_trips"] == gained["train_loop_trips"]
    assert span.attributes["rows"] == iterations.size
    assert span.attributes["row_iterations"] == gained["train_row_iterations"]
    assert span.attributes["evaluations"] == gained["train_evaluations"]
    # The sweeps went out under the train: the span says whether the train
    # still ran then (polled), and the two waits stay disjoint and in order,
    # after the host's prepare.
    assert gained["sequential_trains"] == 1
    assert span.attributes["sweeps_ahead"] == gained["sweeps_ahead"] in (0, 1)
    prepare, train, acquire = _last_suggest_spans()
    assert prepare[0] <= prepare[1] <= train[0] <= train[1] <= acquire[0] <= acquire[1]

    # Asked again with nothing completed: the fit is cached, nothing trains,
    # nothing is read and no counter moves.
    before = runtime.stats.snapshot()
    client_two = vizier_client.VizierClient(servicer, study, "worker2")
    assert len(client_two.get_suggestions(1)) == 1
    gained = _gained(runtime, before)
    assert gained["cached_fit_suggests"] == 1
    assert [gained[name] for name in COUNTERS] == [0] * 5
    assert len(fetched) == 1 and len(_train_spans()) == 1
    assert (gained["sequential_trains"], gained["sweeps_ahead"]) == (0, 0)
    prepare, train, acquire = _last_suggest_spans()  # the older order, the same spans
    assert prepare[1] <= train[0] <= train[1] <= acquire[0] <= acquire[1]
    cached_train = [s for s in tracing_lib.get_tracer().finished_spans() if s.name == "device.wait"][-2]
    assert "sweeps_ahead" not in cached_train.attributes

    # A completion later the next suggest trains warm: one more program.
    client.complete_trial(trials[0].id, vz.Measurement(metrics={"obj": 0.25}))
    before = runtime.stats.snapshot()
    client_three = vizier_client.VizierClient(servicer, study, "worker3")
    assert len(client_three.get_suggestions(1)) == 1
    gained = _gained(runtime, before)
    assert gained["train_programs"] == 1 == gained["warm_trains"]
    assert gained["sweeps_ahead"] <= gained["sequential_trains"] == 1
    assert fetched[1].shape == (2, 4) and len(_train_spans()) == 2
    assert gained["train_row_iterations"] == fetched[1][0].sum()


def test_a_fused_flush_is_one_train_program_whatever_its_members(served_gp_stack, fetched):
    servicer, runtime, studies = served_gp_stack(
        2, designer_factory=_lbfgs_ucb_pe, batch_max_size=2, batch_max_wait_ms=60_000.0
    )
    results, errors = {}, {}
    barrier = threading.Barrier(len(studies))

    def run(study):
        barrier.wait()
        try:
            results[study] = vizier_client.VizierClient(servicer, study, "worker").get_suggestions(2)
        except BaseException as e:  # noqa: BLE001 - the test shows it
            errors[study] = e

    threads = [threading.Thread(target=run, args=(s,)) for s in studies]
    before = runtime.stats.snapshot()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors and all(not t.is_alive() for t in threads), errors
    assert all(len(trials) == 2 for trials in results.values())
    gained = _gained(runtime, before)
    stats = runtime.stats.snapshot()
    assert stats["batched_suggests"] == 2 and gained["batch_flushes"] == 1
    # Two members trained (two cold trains), ONE program ran: the flush's.
    assert gained["cold_trains"] + gained["warm_trains"] == 2
    assert gained["train_programs"] == 1
    (work,) = fetched
    assert work.shape == (2, 2, 4)  # [slots, (iterations, evaluations), rows]
    iterations, evaluations = work[:, 0], work[:, 1]
    assert gained["train_loop_trips"] == iterations.max()  # one loop over both slots' rows
    assert gained["train_row_trips"] == 8 * iterations.max()
    assert gained["train_row_iterations"] == iterations.sum()
    assert gained["train_evaluations"] == evaluations.sum()
    (span,) = _train_spans()
    assert span.attributes["per"] == "flush" and span.attributes["rows"] == 8
    # A fused flush is one program: no sweeps to send ahead of its train.
    assert (gained["sequential_trains"], gained["sweeps_ahead"]) == (0, 0)
    assert "sweeps_ahead" not in span.attributes


def test_with_the_jax_knob_off_no_train_counter_moves(served_gp_stack, fetched):
    jax_timing.set_config(config_lib.ObservabilityConfig(jax_profiling=False))
    servicer, runtime, (study,) = served_gp_stack(1, designer_factory=_lbfgs_ucb_pe)
    before = runtime.stats.snapshot()
    assert len(vizier_client.VizierClient(servicer, study, "worker").get_suggestions(2)) == 2
    gained = _gained(runtime, before)
    assert gained["cold_trains"] == 1  # it trained ...
    assert [gained[name] for name in COUNTERS] == [0] * 5  # ... and nothing was read
    assert fetched == [] and _train_spans() == []
    # The order does not hang on the knob: the sweeps still went out under
    # the train, which costs a poll and no read.
    assert gained["sweeps_ahead"] <= gained["sequential_trains"] == 1
