"""The per-layer metrics that read the program's stage spans
(``vizier_suggest_stage_seconds``): each reader on synthetic evidence, and a
rehearsal of the fused cell whose ``layers`` line carries them."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
STAGE_METRICS = [m for m in BENCH["per_layer"] if m["source"] == "program_span"]
BOUNDS = [0.001, 0.01, 0.1, 1.0]


def _series(count, total):
    counts = [0] * (len(BOUNDS) + 1)
    counts[2] = count
    return (counts, count, total)


def _evidence(stage_series, queue=None, service=None, batched=0):
    hists = {}
    if stage_series is not None:
        hists["vizier_suggest_stage_seconds"] = {
            "bounds": BOUNDS,
            "series": {
                f"path={path},per={per},stage={stage}": _series(count, total)
                for (stage, path, per), (count, total) in stage_series.items()
            },
        }
    if queue is not None:
        hists["vizier_batch_queue_wait_seconds"] = {
            "bounds": BOUNDS, "series": {"bucket=b": _series(*queue)}}
    if service is not None:
        hists["vizier_suggest_latency_seconds"] = {
            "bounds": BOUNDS,
            "series": {"hop=service": _series(*service), "hop=pythia": _series(service[0], 99.0)},
        }
    return {"histograms_window": hists, "stats_window": {"batched_suggests": batched},
            "latencies_ms": [], "trace": None, "seconds": 1.0, "completed_in_window": 0}


# Ten requests: four sequential, six served from two fused flushes of three.
FILLED = _evidence(
    {
        ("service.read", "sequential", "request"): (10, 0.10),
        ("policy.load_trials", "sequential", "request"): (10, 0.30),
        ("service.write", "sequential", "request"): (10, 0.20),
        ("designer.update", "sequential", "request"): (10, 0.01),
        ("designer.prepare", "sequential", "request"): (4, 0.08),
        ("designer.prepare", "fused", "request"): (6, 0.12),
        ("designer.decode", "sequential", "request"): (4, 0.04),
        ("designer.decode", "fused", "request"): (6, 0.06),
        ("designer.decode", "fused", "flush"): (2, 0.10),
        ("flush.stack", "fused", "flush"): (2, 0.04),
        ("device.wait", "sequential", "request"): (8, 0.80),
        ("device.wait", "fused", "flush"): (2, 1.20),
    },
    queue=(10, 0.50),
    service=(10, 6.97),
    batched=6,
)
EXPECTED = {
    "host_store_ms": (0.10 + 0.30 + 0.20) / 10 * 1e3,
    "host_codec_ms": (0.01 + 0.08 + 0.12 + 0.04 + 0.06 + 0.10) / 10 * 1e3,
    "device_wait_ms": (0.80 + 1.20) / 10 * 1e3,
    "flush_host_ms": (0.04 + 0.10) / 2 * 1e3,
    # Every per-request stage once, every per-flush stage once per member
    # (6 batched / 2 flushes = 3), and the queue wait, over the service time.
    "stage_coverage": 100.0 * (1.71 + 3 * (0.10 + 0.04 + 1.20) + 0.50) / 6.97,
}


def _reader(metric):
    from chipbench import run

    return run.load_reader(metric["name"])


@pytest.mark.parametrize("metric", STAGE_METRICS, ids=lambda m: m["name"])
def test_a_reader_gives_a_value_from_a_filled_histogram(metric):
    expected = EXPECTED[metric["name"].partition(".")[0]]
    assert _reader(metric).read(FILLED) == pytest.approx(expected, rel=1e-9)


@pytest.mark.parametrize("metric", STAGE_METRICS, ids=lambda m: m["name"])
def test_a_reader_gives_nothing_from_an_empty_or_missing_histogram(metric):
    reader = _reader(metric)
    # A parent commit: no such histogram at all.
    assert reader.read(_evidence(None, queue=(10, 0.5), service=(10, 7.0))) is None
    # The histogram registered, nothing observed in the window.
    empty = {key: (0, 0.0) for key in (("service.read", "sequential", "request"),
                                       ("device.wait", "sequential", "request"))}
    assert reader.read(_evidence(empty, queue=(0, 0.0), service=(0, 0.0))) is None
    # Requests, but a stage the metric sums was never sampled.
    only_reads = {("service.read", "sequential", "request"): (5, 0.05)}
    if not metric["name"].startswith("stage_coverage"):
        assert reader.read(_evidence(only_reads, service=(5, 1.0))) is None


def test_the_new_entries_are_spans_with_cells_and_known_layers():
    assert len(STAGE_METRICS) == 9
    for metric in STAGE_METRICS:
        assert metric["workloads"] and metric["moves"] and metric["layer"]
    assert {m["name"].partition(".")[0] for m in STAGE_METRICS} == set(EXPECTED)


def test_a_rehearsal_of_the_fused_cell_reports_the_stage_metrics(tmp_path):
    # As tests/chipbench/test_harness.py _run does it: the command as a
    # child on the CPU, two cores, the look for a chip skipped.
    args = ["--workload", "default20d.tenants16", "--seed", "2147483777", "--seconds", "3",
            "--rehearse", "--trace", "0"]
    code = (
        "import os, sys; os.sched_setaffinity(0, sorted(os.sched_getaffinity(0))[:2])\n"
        "sys.path.insert(0, %r); from chipbench import run; run.REQUIRED_PLATFORM = 'cpu'\n"
        "sys.exit(run.main(%r))" % (ROOT, args)
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "VIZIER_DISABLE_MESH": "1",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    env.pop("XLA_FLAGS", None)
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          env=env, timeout=600, cwd=ROOT)
    objs = [json.loads(l) for l in done.stdout.splitlines() if l.startswith("{")]
    assert done.returncode == 0, done.stderr[-2000:]
    assert objs[-1]["correct"] is True and objs[-1]["failed"] == 0
    layers = [o for o in objs if o.get("phase") == "layers"][0]
    wanted = {m["name"] for m in STAGE_METRICS if "default20d.tenants16" in m["workloads"]}
    # flush_host_ms needs a fused flush in the window; three clients with
    # think time need not form one (test_fused_flush_stages.py forces one).
    assert wanted - {"flush_host_ms"} <= set(layers), sorted(layers)
    # A 3 s window of ~15 requests: those in flight at its edges put a stage
    # and the latency it belongs to on different sides of a snapshot.
    assert layers["stage_coverage.pool"] > 50.0
    assert layers["device_wait_ms.pool"] > 0 and layers["host_store_ms.pool"] > 0
