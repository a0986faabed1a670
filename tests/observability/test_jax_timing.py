"""device_phase: the ``device.wait`` stage span — compile-vs-execute split,
the one stage histogram, disabled no-op."""

import pytest

from vizier_tpu.observability import config as config_lib
from vizier_tpu.observability import jax_timing
from vizier_tpu.observability import metrics as metrics_lib
from vizier_tpu.observability import tracing as tracing_lib


@pytest.fixture
def fresh_state():
    """Isolated tracer + registry + compile tracking per test."""
    tracer = tracing_lib.Tracer()
    old_tracer = tracing_lib.set_tracer(tracer)
    registry = metrics_lib.MetricsRegistry()
    tracer.bind_registry(registry)  # as the serving runtime does
    old_registry_state = metrics_lib._default_registry
    metrics_lib.set_default_registry(metrics_lib.MetricsRegistry())
    jax_timing.set_config(config_lib.ObservabilityConfig())
    jax_timing.reset_compile_tracking()
    yield tracer, registry
    tracing_lib.set_tracer(old_tracer)
    metrics_lib.set_default_registry(old_registry_state)
    jax_timing.set_config(None)
    jax_timing.reset_compile_tracking()


class TestDevicePhase:
    def test_first_call_is_compile_then_execute(self, fresh_state):
        tracer, registry = fresh_state
        for _ in range(3):
            with jax_timing.device_phase("unit.phase"):
                pass
        hist = registry.get("vizier_suggest_stage_seconds")
        assert hist.count(stage="device.wait", path="sequential", per="request", phase="") == 3
        modes = [s.attributes["mode"] for s in tracer.finished_spans()]
        assert modes == ["compile", "execute", "execute"]

    def test_phase_names_tracked_independently(self, fresh_state):
        tracer, registry = fresh_state
        with jax_timing.device_phase("a", stage="train"):
            pass
        with jax_timing.device_phase("b", path="fused", per="flush"):
            pass
        hist = registry.get("vizier_suggest_stage_seconds")
        assert hist.count(
            stage="device.wait", path="sequential", per="request", phase="train"
        ) == 1
        assert hist.count(stage="device.wait", path="fused", per="flush", phase="flush") == 1
        by_phase = {s.attributes["phase"]: s.attributes for s in tracer.finished_spans()}
        assert by_phase["a"]["mode"] == by_phase["b"]["mode"] == "compile"
        assert by_phase["a"]["stage"] == "train" and "stage" not in by_phase["b"]

    def test_span_carries_mode_attribute(self, fresh_state):
        tracer, _ = fresh_state
        with jax_timing.device_phase("unit.span"):
            pass
        with jax_timing.device_phase("unit.span"):
            pass
        spans = [s for s in tracer.finished_spans() if s.name == "device.wait"]
        assert [s.attributes["phase"] for s in spans] == ["unit.span"] * 2
        assert [s.attributes["mode"] for s in spans] == ["compile", "execute"]
        assert spans[0].attributes["first_call"] is True
        assert spans[1].attributes["first_call"] is False

    def test_block_syncs_jax_outputs(self, fresh_state):
        import jax.numpy as jnp

        with jax_timing.device_phase("unit.block") as phase:
            out = phase.block(jnp.ones((4,)) * 2.0)
        assert float(out.sum()) == 8.0

    def test_exception_skips_observation_but_propagates(self, fresh_state):
        _, registry = fresh_state
        with pytest.raises(RuntimeError):
            with jax_timing.device_phase("unit.err"):
                raise RuntimeError("boom")
        # The failed phase was not observed: a stage whose body raised is
        # not a sample of that stage's time.
        hist = registry.get("vizier_suggest_stage_seconds")
        assert hist.count(stage="device.wait", path="sequential", per="request", phase="") == 0

    def test_disabled_is_inert(self, fresh_state):
        tracer, registry = fresh_state
        jax_timing.set_config(config_lib.ObservabilityConfig.disabled())
        with jax_timing.device_phase("unit.off") as phase:
            # No device sync requested, no histogram, no span.
            assert phase.block("anything") == "anything"
            assert not phase.enabled
        hist = registry.get("vizier_suggest_stage_seconds")
        assert hist.count(stage="device.wait", path="sequential", per="request", phase="") == 0
        assert tracer.finished_spans() == []
        # Nothing on the served path observes into the process-global registry.
        assert metrics_lib.default_registry().names() == []
