"""A forced fused flush with the stage spans live: two studies of one
bucket meet in the batch window, the flush's stages land under
``path=fused``, and no span bookkeeping can turn into a slot error or a
fallback — the counters that fail a benchmark cell."""

import threading

import pytest

from vizier_tpu.observability import tracing as tracing_lib
from vizier_tpu.service import vizier_client

ZERO_COUNTERS = ("batch_fallbacks", "batch_slot_errors", "fallbacks", "designer_failures")


def _suggest_together(servicer, studies, count=2):
    """One suggest per study, all submitted at once; returns the batches."""
    results, errors = {}, {}
    barrier = threading.Barrier(len(studies))

    def run(study):
        client = vizier_client.VizierClient(servicer, study, "worker")
        barrier.wait()
        try:
            results[study] = client.get_suggestions(count)
        except BaseException as e:  # noqa: BLE001 - the test shows it
            errors[study] = e

    threads = [threading.Thread(target=run, args=(s,)) for s in studies]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    return results


def _fused_series(runtime):
    hist = runtime.metrics.get(tracing_lib.STAGE_HISTOGRAM)
    return {
        (dict(key)["stage"], dict(key)["per"]): count
        for key, (_, count, _) in hist.series_data().items()
        if dict(key)["path"] == tracing_lib.PATH_FUSED
    }


def _build_pair(served_gp_stack):
    # A full bucket flushes at once; until then the window holds the first
    # arrival for its batchmate.
    return served_gp_stack(2, batch_max_size=2, batch_max_wait_ms=60_000.0)


def test_a_forced_fused_flush_names_its_stages_and_holds_the_zero_counters(served_gp_stack):
    servicer, runtime, studies = _build_pair(served_gp_stack)
    results = _suggest_together(servicer, studies)
    assert all(len(trials) == 2 for trials in results.values())
    stats = runtime.stats.snapshot()
    assert stats["batched_suggests"] == 2
    assert all(stats[name] == 0 for name in ZERO_COUNTERS), stats
    fused = _fused_series(runtime)
    # Once a flush ...
    assert fused[("flush.stack", "flush")] == 1
    assert fused[("device.wait", "flush")] == 1
    assert fused[("designer.decode", "flush")] == 1
    # ... and once a member.
    assert fused[("designer.prepare", "request")] == 2
    assert fused[("designer.decode", "request")] == 2
    tracer = tracing_lib.get_tracer()
    spans = {s.name: s for s in tracer.finished_spans()}
    assert spans["flush.stack"].attributes["bytes"] > 0
    assert spans["flush.stack"].attributes["members"] == 2
    flush = spans["batch_executor.flush"]
    # Work done once a flush hangs under the flush span; a member's prepare
    # under that member's own request, whichever thread ran it.
    assert spans["flush.stack"].parent_id == flush.span_id
    assert spans["device.wait"].parent_id == flush.span_id
    requests = {s.trace_id for s in tracer.finished_spans() if s.name == "service.suggest_trials"}
    prepares = [s for s in tracer.finished_spans() if s.name == "designer.prepare"]
    assert {s.trace_id for s in prepares} == requests and len(requests) == 2
    assert runtime.metrics.get(tracing_lib.ERRORS_COUNTER).value() == 0


class _RaisingAnnotation:
    def __init__(self, name):
        pass

    def __enter__(self):
        raise RuntimeError("annotation exploded")

    def __exit__(self, *exc):
        raise RuntimeError("annotation exploded")


@pytest.mark.parametrize("path", ["sequential", "fused"])
def test_span_bookkeeping_that_raises_never_reaches_the_request(
    served_gp_stack, monkeypatch, path
):
    servicer, runtime, studies = _build_pair(served_gp_stack)

    def explode(*args, **kwargs):
        raise RuntimeError("observe exploded")

    tracer = tracing_lib.get_tracer()
    monkeypatch.setattr(tracer._stage_seconds, "observe", explode)
    monkeypatch.setattr(tracing_lib, "_annotation_cls", _RaisingAnnotation)
    if path == "fused":
        results = _suggest_together(servicer, studies)
    else:
        client = vizier_client.VizierClient(servicer, studies[0], "worker")
        results = {studies[0]: client.get_suggestions(2)}
    assert all(len(trials) == 2 for trials in results.values())
    stats = runtime.stats.snapshot()
    assert stats["batched_suggests"] == (2 if path == "fused" else 0)
    assert all(stats[name] == 0 for name in ZERO_COUNTERS), stats
    assert runtime.metrics.get(tracing_lib.ERRORS_COUNTER).value() > 0
    hist = runtime.metrics.get(tracing_lib.STAGE_HISTOGRAM)
    assert all(count == 0 for _, count, _ in hist.series_data().values())
    # The spans themselves still reached the operator's ring.
    assert {"service.read", "device.wait", "service.write"} <= {
        s.name for s in tracer.finished_spans()
    }
