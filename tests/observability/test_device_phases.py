"""The device half of a suggest, named from inside the program (PR 39):
``device.wait`` is observed under a ``phase`` label (train / acquire / flush),
and a train phase reads, after its block, what the program counted of its own
work (a copy it asked for when the train was dispatched: PR 45) — on the span,
and nowhere when the JAX knob is off."""

import collections

import numpy as np
import pytest

from vizier_tpu.designers import gp_bandit
from vizier_tpu.observability import config as config_lib
from vizier_tpu.observability import jax_timing
from vizier_tpu.observability import metrics as metrics_lib
from vizier_tpu.observability import tracing as tracing_lib


@pytest.fixture
def fresh_state():
    """Isolated tracer + bound registry + compile tracking per test."""
    tracer = tracing_lib.Tracer()
    old_tracer = tracing_lib.set_tracer(tracer)
    registry = metrics_lib.MetricsRegistry()
    tracer.bind_registry(registry)  # as the serving runtime does
    jax_timing.set_config(config_lib.ObservabilityConfig())
    jax_timing.reset_compile_tracking()
    yield tracer, registry
    tracing_lib.set_tracer(old_tracer)
    jax_timing.set_config(None)
    jax_timing.reset_compile_tracking()


def _series(registry):
    hist = registry.get(tracing_lib.STAGE_HISTOGRAM)
    return {
        tuple(dict(key)[name] for name in ("stage", "path", "per", "phase")): count
        for key, (_, count, _) in hist.series_data().items()
    }


def _a_suggest_of_each_path(tracer):
    with tracer.span("designer.prepare"):
        pass
    with jax_timing.device_phase("unit.train", stage="train"):
        pass
    with jax_timing.device_phase("unit.acquire", stage="acquire"):
        pass
    with jax_timing.device_phase("unit.flush", **tracing_lib.FUSED_FLUSH):
        pass
    with jax_timing.device_phase("unit.unstaged"):
        pass
    with tracer.span("designer.decode", **tracing_lib.FUSED_FLUSH):
        pass


def test_device_wait_is_observed_by_phase(fresh_state):
    tracer, registry = fresh_state
    _a_suggest_of_each_path(tracer)
    assert _series(registry) == {
        ("designer.prepare", "sequential", "request", ""): 1,
        ("device.wait", "sequential", "request", "train"): 1,
        ("device.wait", "sequential", "request", "acquire"): 1,
        ("device.wait", "fused", "flush", "flush"): 1,
        ("device.wait", "sequential", "request", ""): 1,
        # Only a device wait has a phase: a fused flush's other stages none.
        ("designer.decode", "fused", "flush", ""): 1,
    }
    # The span, its attributes and the ring are as they were: ``phase`` on
    # the span is still the name the site gave.
    waits = [s for s in tracer.finished_spans() if s.name == "device.wait"]
    assert [s.attributes["phase"] for s in waits] == [
        "unit.train", "unit.acquire", "unit.flush", "unit.unstaged"
    ]
    assert [s.attributes.get("stage") for s in waits] == ["train", "acquire", None, None]


def test_pooled_over_phase_the_histogram_is_the_parents(fresh_state):
    """(stage, path, per) → count and seconds, the label pooled away, is
    what a tracer without the label observes: the benchmark's five stage
    readers pool so (``chipbench/lib/stages.py`` ``series``)."""
    tracer, registry = fresh_state
    _a_suggest_of_each_path(tracer)
    pooled = collections.Counter()
    seconds = collections.Counter()
    hist = registry.get(tracing_lib.STAGE_HISTOGRAM)
    for key, (_, count, total) in hist.series_data().items():
        labels = dict(key)
        pooled[labels["stage"], labels["path"], labels["per"]] += count
        seconds[labels["stage"], labels["path"], labels["per"]] += total
    assert pooled == {
        ("designer.prepare", "sequential", "request"): 1,
        ("device.wait", "sequential", "request"): 3,
        ("device.wait", "fused", "flush"): 1,
        ("designer.decode", "fused", "flush"): 1,
    }
    by_span = collections.Counter()
    for span in tracer.finished_spans():
        attrs = span.attributes
        by_span[span.name, attrs.get("path", "sequential"), attrs.get("per", "request")] += (
            span.duration_secs
        )
    for key, total in seconds.items():
        assert total == pytest.approx(by_span[key], rel=1e-9)


WORK = np.asarray([[3, 5], [7, 12]], np.int32)  # two rows: iterations, evaluations


def test_the_train_span_carries_what_the_program_counted(fresh_state):
    tracer, _ = fresh_state
    with jax_timing.device_phase("unit.train", stage="train") as phase:
        counts = gp_bandit.read_train_work(phase, (WORK, WORK))
    assert counts == dict(
        programs=2, loop_trips=10, rows=4, row_trips=20, row_iterations=16, evaluations=38
    )
    (span,) = tracer.finished_spans()
    assert {k: span.attributes[k] for k in ("loop_trips", "rows", "row_iterations", "evaluations")} == {
        "loop_trips": 10, "rows": 4, "row_iterations": 16, "evaluations": 38
    }
    # JSON-ready: the span log writes them per request.
    assert all(type(v) is int for v in counts.values())
    span.to_dict()


class _OnDevice:
    """Stands for a program's work array on the device: counts the copies
    to the host it was asked to start."""

    def __init__(self):
        self.copies_started = 0

    def copy_to_host_async(self):
        self.copies_started += 1

    def __array__(self, *args, **kwargs):
        return WORK


def test_ahead_starts_the_copy_that_the_read_after_the_block_finds(fresh_state):
    """``phase.ahead`` at the train's dispatch, ``phase.read`` after its
    block (PR 45): the counts travel while the train runs."""
    works = (_OnDevice(), _OnDevice())
    with jax_timing.device_phase("unit.train", stage="train") as phase:
        phase.ahead(works)
        assert [w.copies_started for w in works] == [1, 1]
        counts = gp_bandit.read_train_work(phase, works)
    assert counts["programs"] == 2 and counts["loop_trips"] == 10


def test_a_phase_that_trained_nothing_says_nothing(fresh_state):
    tracer, _ = fresh_state
    with jax_timing.device_phase("unit.train", stage="train") as phase:
        assert gp_bandit.read_train_work(phase, ()) is None
    (span,) = tracer.finished_spans()
    assert "loop_trips" not in span.attributes


class _Unreadable:
    """Stands for a device array: any block on it or read of it raises."""

    def block_until_ready(self):
        raise AssertionError("blocked with the JAX knob off")

    def __array__(self, *args, **kwargs):
        raise AssertionError("read with the JAX knob off")


@pytest.mark.parametrize(
    "config",
    [config_lib.ObservabilityConfig(jax_profiling=False), config_lib.ObservabilityConfig.disabled()],
    ids=["VIZIER_OBSERVABILITY_JAX=0", "VIZIER_OBSERVABILITY=0"],
)
def test_with_the_knob_off_nothing_blocks_and_nothing_is_read(fresh_state, config):
    tracer, registry = fresh_state
    jax_timing.set_config(config)
    work = _Unreadable()
    with jax_timing.device_phase("unit.train", stage="train") as phase:
        assert not phase.enabled
        assert phase.block(work) is work
        phase.ahead(work)  # asks the device for nothing
        assert phase.read(work) is None
        phase.set_attributes(loop_trips=1)
        assert gp_bandit.read_train_work(phase, (work,)) is None
    assert tracer.finished_spans() == []
    assert registry.get(tracing_lib.STAGE_HISTOGRAM).series_data() == {}
