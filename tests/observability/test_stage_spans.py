"""Stage spans of a served suggest on the sequential path: one clock with
the profiler, the one histogram in the runtime's registry, sums that add up
to the service-side time."""

import os
import sys
import time
import types

from vizier_tpu import pyvizier as vz
from vizier_tpu.observability import jax_timing
from vizier_tpu.observability import tracing as tracing_lib
from vizier_tpu.service import vizier_client

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SEQUENTIAL_STAGES = sorted(tracing_lib.STAGES - {"flush.stack"})


def _stage_counts(runtime):
    hist = runtime.metrics.get(tracing_lib.STAGE_HISTOGRAM)
    return {
        (dict(key)["stage"], dict(key)["path"], dict(key)["per"]): count
        for key, (_, count, _) in hist.series_data().items()
    }


def test_every_stage_is_in_the_profiler_trace_inside_the_service_span(
    served_gp_stack, tmp_path
):
    import jax

    from chipbench.lib import trace_reduce

    servicer, runtime, (study,) = served_gp_stack(1)
    client = vizier_client.VizierClient(servicer, study, "worker")
    client.get_suggestions(2)  # compiles outside the trace
    for trial in client.list_trials():
        if not trial.is_completed:
            client.complete_trial(trial.id, vz.Measurement(metrics={"obj": 0.5}))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert len(client.get_suggestions(2)) == 2
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(str(tmp_path))
    wanted = ["service.suggest_trials", "designer.suggest", *SEQUENTIAL_STAGES]
    _, host = trace_reduce.read_xplane(path, wanted)
    ((lo, hi),) = host["service.suggest_trials"]
    for stage in SEQUENTIAL_STAGES:
        assert host[stage], f"no {stage!r} event in the trace's host planes"
        for a, b in host[stage]:  # the profiler's clock: one for all of them
            assert lo <= a <= b <= hi, (stage, (a, b), (lo, hi))
    # The sequential path waits for the device twice: train, then acquire.
    assert len(host["device.wait"]) == 2
    # The tracer's ring and the runtime's histogram saw the same request.
    assert _stage_counts(runtime)[("service.read", "sequential", "request")] == 2
    assert all(path_ == "sequential" for _, path_, _ in _stage_counts(runtime))


class _SleepingDesigner:
    """A designer whose whole suggest is one wait for the device."""

    def __init__(self, problem, **kwargs):
        self._n = 0

    def update(self, completed, all_active=None):
        pass

    def suggest(self, count=None):
        with jax_timing.device_phase("stub.sleep"):
            time.sleep(0.2)
        self._n += 1
        return [
            vz.TrialSuggestion(parameters={"x0": 0.1 * self._n, "x1": 0.5})
            for _ in range(count or 1)
        ]


def test_on_the_sequential_path_the_stages_add_up_to_the_service_time(served_gp_stack):
    from chipbench import run
    from chipbench.lib import program

    servicer, runtime, (study,) = served_gp_stack(1, designer_factory=_SleepingDesigner)
    client = vizier_client.VizierClient(servicer, study, "worker")
    for _ in range(3):
        trials = client.get_suggestions(2)
        assert len(trials) == 2
        for trial in trials:  # or the next suggest hands the same two back
            client.complete_trial(trial.id, vz.Measurement(metrics={"obj": 0.5}))
    # The benchmark's own snapshot of the runtime's registry, and its reader.
    histograms = program.Server.histograms(types.SimpleNamespace(runtime=runtime))
    evidence = {"histograms_window": histograms, "stats_window": runtime.stats.snapshot()}
    coverage = run.load_reader("stage_coverage.lone").read(evidence)
    assert 80.0 <= coverage <= 100.5, coverage
    assert run.load_reader("device_wait_ms.lone").read(evidence) >= 200.0
    assert run.load_reader("host_store_ms.lone").read(evidence) > 0.0
    counts = _stage_counts(runtime)
    for stage in ("service.read", "policy.load_trials", "designer.update", "device.wait",
                  "service.write"):
        assert counts[(stage, "sequential", "request")] == 3, (stage, counts)
