"""obs_report: JSON-lines span file round-trip + breakdown rendering."""

import json
import pathlib
import sys

from vizier_tpu.observability import fleet as fleet_lib
from vizier_tpu.observability import flight_recorder as recorder_lib
from vizier_tpu.observability import metrics as metrics_lib
from vizier_tpu.observability import slo as slo_lib
from vizier_tpu.observability import tracing as tracing_lib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2] / "tools"))
import obs_report  # noqa: E402  (tools/ is not a package)


def _trace_file(tmp_path) -> str:
    tracer = tracing_lib.Tracer()
    for _ in range(3):
        with tracer.span("client.suggest"):
            with tracer.span("designer.suggest"):
                pass
    path = tmp_path / "spans.jsonl"
    tracer.dump_jsonl(str(path))
    return str(path)


class TestRoundTrip:
    def test_dump_then_load(self, tmp_path):
        path = _trace_file(tmp_path)
        spans = obs_report.load_spans(path)
        assert len(spans) == 6
        assert {s["name"] for s in spans} == {"client.suggest", "designer.suggest"}
        # Every span survived with its timing + identity intact.
        for span in spans:
            assert span["duration_secs"] > 0
            assert span["trace_id"] and span["span_id"]

    def test_corrupt_lines_skipped(self, tmp_path):
        path = tmp_path / "bad.jsonl"
        good = json.dumps(
            {"name": "x", "trace_id": "t", "span_id": "s", "duration_secs": 0.1}
        )
        path.write_text(f"{good}\nnot json at all\n\n{good}\n")
        assert len(obs_report.load_spans(str(path))) == 2


class TestBreakdown:
    def test_phase_table(self, tmp_path):
        spans = obs_report.load_spans(_trace_file(tmp_path))
        rows = obs_report.phase_breakdown(spans)
        by_phase = {r["phase"]: r for r in rows}
        assert by_phase["client.suggest"]["count"] == 3
        row = by_phase["designer.suggest"]
        assert 0 < row["p50_ms"] <= row["p95_ms"] <= row["p99_ms"] <= row["max_ms"]
        # The outer span contains the inner one, so it owns more total time.
        assert (
            by_phase["client.suggest"]["total_ms"]
            >= by_phase["designer.suggest"]["total_ms"]
        )
        table = obs_report.render_table(rows)
        assert "client.suggest" in table and "p99 ms" in table

    def test_exact_percentiles(self):
        spans = [
            {"name": "p", "duration_secs": v / 1000.0} for v in range(1, 101)
        ]
        (row,) = obs_report.phase_breakdown(spans)
        assert row["p50_ms"] == 50.5  # interpolated median of 1..100 ms
        assert row["max_ms"] == 100.0

    def test_trace_tree(self, tmp_path):
        spans = obs_report.load_spans(_trace_file(tmp_path))
        trace_id = spans[0]["trace_id"]
        tree = obs_report.render_trace(spans, trace_id)
        lines = tree.splitlines()
        assert lines[0] == f"trace {trace_id}"
        # Child indented under its parent.
        assert any(l.startswith("  client.suggest") for l in lines)
        assert any(l.startswith("    designer.suggest") for l in lines)

    def test_trace_tree_missing(self, tmp_path):
        spans = obs_report.load_spans(_trace_file(tmp_path))
        assert "No spans" in obs_report.render_trace(spans, "nope")


class TestSurrogateActivity:
    def _spans(self, names):
        # A device phase is a ``device.wait`` stage span's ``phase``; any
        # other name is a span of its own.
        return [
            {"name": "device.wait", "duration_secs": 0.01, "attributes": {"phase": n}}
            if "." in n
            else {"name": n, "duration_secs": 0.01}
            for n in names
        ]

    def test_exact_only(self):
        act = obs_report.surrogate_activity(
            self._spans(["gp_bandit.train_gp", "gp_ucb_pe.train_gp", "other"])
        )
        assert act == {"mode": "exact", "exact": 2, "sparse": 0}

    def test_sparse_only(self):
        act = obs_report.surrogate_activity(
            self._spans(["sparse_gp.train", "sparse_gp.acquisition"])
        )
        assert act == {"mode": "sparse", "exact": 0, "sparse": 2}

    def test_mixed_and_none(self):
        mixed = obs_report.surrogate_activity(
            self._spans(["sparse_gp.train", "gp_bandit.train_gp"])
        )
        assert mixed["mode"] == "mixed"
        assert obs_report.surrogate_activity(self._spans(["rpc"]))["mode"] == "none"


class TestSpeculativeActivity:
    def test_counts_serve_events_and_precompute_spans(self):
        spans = [
            {
                "name": "pythia.suggest",
                "duration_secs": 0.001,
                "events": [{"name": "speculative.hit", "attributes": {}}],
            },
            {
                "name": "pythia.suggest",
                "duration_secs": 0.8,
                "events": [{"name": "speculative.miss", "attributes": {}}],
            },
            {
                "name": "pythia.suggest",
                "duration_secs": 0.9,
                "events": [{"name": "speculative.stale", "attributes": {}}],
            },
            {
                "name": "speculative.precompute",
                "duration_secs": 0.7,
                "attributes": {"outcome": "stored"},
            },
            {
                "name": "speculative.precompute",
                "duration_secs": 0.7,
                "attributes": {"outcome": "superseded"},
            },
        ]
        act = obs_report.speculative_activity(spans)
        assert act["hit"] == 1 and act["miss"] == 1 and act["stale"] == 1
        assert act["precomputes"] == 2 and act["stored"] == 1
        assert act["hit_rate"] == round(1 / 3, 4)

    def test_no_activity_is_all_zero(self):
        act = obs_report.speculative_activity(
            [{"name": "pythia.suggest", "duration_secs": 0.1}]
        )
        assert act["hit"] == act["miss"] == act["precomputes"] == 0
        assert act["hit_rate"] == 0.0


def _armed_registry():
    """A registry that has been through one real SLO evaluation."""
    registry = metrics_lib.MetricsRegistry()
    hist = registry.histogram("vizier_suggest_latency_seconds")
    for _ in range(9):
        hist.observe(0.001, hop="pythia")
    hist.observe(0.9, trace_id="t-slow", hop="pythia")
    engine = slo_lib.SloEngine(
        slo_lib.SloConfig(
            enabled=True, windows=(5.0,), min_samples=1, suggest_p99_ms=25.0
        ),
        registry,
        recorder=recorder_lib.FlightRecorder(),
    )
    engine.evaluate()
    return registry


class TestSloActivity:
    def test_round_trip_from_fresh_metrics_dump(self, tmp_path):
        # The full path every future PR must keep working: armed engine ->
        # registry snapshot -> JSON file -> load_metrics -> slo_activity.
        registry = _armed_registry()
        path = tmp_path / "metrics.json"
        path.write_text(registry.dump_json())
        slo = obs_report.slo_activity(obs_report.load_metrics(str(path)))
        assert slo["armed"] is True
        assert slo["evaluations"] == 1
        assert "suggest_p99:pythia" in slo["breached"]
        assert slo["burn_rates"]["suggest_p99:pythia"]["5s"] >= 5.0
        assert slo["values"]["suggest_p99:pythia"]["5s"] > 0.025
        rendered = obs_report.render_slo(slo)
        assert "BREACHED" in rendered and "suggest_p99:pythia" in rendered

    def test_unarmed_dump(self, tmp_path):
        registry = metrics_lib.MetricsRegistry()
        registry.counter("vizier_serving_fallbacks").inc()
        path = tmp_path / "metrics.json"
        path.write_text(registry.dump_json())
        slo = obs_report.slo_activity(obs_report.load_metrics(str(path)))
        assert slo["armed"] is False and slo["breached"] == []
        assert "not armed" in obs_report.render_slo(slo)

    def test_label_parser(self):
        labels = obs_report._parse_label_str(
            '{slo="suggest_p99:pythia",window="60s"}'
        )
        assert labels == {"slo": "suggest_p99:pythia", "window": "60s"}


class TestFleetSection:
    def _dump_dir(self, tmp_path):
        for source, spans in {
            "client": [
                {"name": "client.suggest", "trace_id": "t1", "span_id": "c",
                 "parent_id": None, "start_time": 1.0, "duration_secs": 0.2},
            ],
            "replica-0": [
                {"name": "service.suggest_trials", "trace_id": "t1",
                 "span_id": "s", "parent_id": "c", "start_time": 1.1,
                 "duration_secs": 0.1},
            ],
        }.items():
            fleet_lib.write_spans(str(tmp_path), source, spans)
        recorder = recorder_lib.FlightRecorder()
        recorder.record(None, "replica_failover", replica="replica-0",
                        successors=["replica-1"])
        recorder.dump_json(
            str(tmp_path / ("fleet" + fleet_lib.RECORDER_SUFFIX))
        )
        return str(tmp_path)

    def test_fleet_section_from_fresh_dump(self, tmp_path):
        section = obs_report.fleet_section(self._dump_dir(tmp_path))
        assert section["sources"] == ["client", "replica-0"]
        assert section["cross_replica_traces"] == 1
        assert section["failover_timeline"][0]["kind"] == "replica_failover"

    def test_json_report_schema_is_stable(self, tmp_path, capsys, monkeypatch):
        """Guards the --json contract: device_activity,
        speculative_activity, slo, and fleet sections must all parse from
        freshly-dumped span/metric files."""
        span_path = _trace_file(tmp_path)
        metrics_path = tmp_path / "metrics.json"
        metrics_path.write_text(_armed_registry().dump_json())
        dump_dir = self._dump_dir(tmp_path / "fleet")
        monkeypatch.setattr(
            sys, "argv",
            ["obs_report.py", span_path, "--json",
             "--slo", str(metrics_path), "--fleet", dump_dir],
        )
        obs_report.main()
        report = json.loads(capsys.readouterr().out)
        assert {
            "spans", "surrogate_activity", "speculative_activity",
            "program_kind_activity", "device_activity", "slo", "fleet",
            "phases",
        } <= set(report)
        assert report["spans"] == 6
        assert report["slo"]["armed"] is True
        assert report["slo"]["burn_rates"]["suggest_p99:pythia"]["5s"] >= 5.0
        assert report["fleet"]["cross_replica_traces"] == 1
        assert report["device_activity"] == {}
        assert report["speculative_activity"]["hit"] == 0

    def test_json_report_without_slo_or_fleet_keeps_keys(
        self, tmp_path, capsys, monkeypatch
    ):
        monkeypatch.setattr(
            sys, "argv", ["obs_report.py", _trace_file(tmp_path), "--json"]
        )
        obs_report.main()
        report = json.loads(capsys.readouterr().out)
        assert report["slo"] is None and report["fleet"] is None


class TestDeviceActivity:
    def _flush_span(self, device=None, occupancy=2, duration=0.01):
        attrs = {"bucket": "gp_ucb_pe/t16/f4x0/m1/q1", "occupancy": occupancy}
        if device is not None:
            attrs["device"] = device
        return {
            "name": "batch_executor.flush",
            "duration_secs": duration,
            "attributes": attrs,
        }

    def test_per_device_breakdown(self):
        spans = [
            self._flush_span("mesh0", occupancy=2, duration=0.010),
            self._flush_span("mesh0", occupancy=4, duration=0.030),
            self._flush_span("mesh1", occupancy=1, duration=0.020),
            {"name": "pythia.suggest", "duration_secs": 0.5},
        ]
        out = obs_report.device_activity(spans)
        assert set(out) == {"mesh0", "mesh1"}
        assert out["mesh0"]["flushes"] == 2
        assert out["mesh0"]["busy_ms"] == 40.0
        assert out["mesh0"]["mean_occupancy"] == 3.0
        assert out["mesh1"]["flushes"] == 1

    def test_single_device_run_is_empty(self):
        # VIZIER_MESH=0 stamps no device attribute -> no breakdown rows.
        spans = [self._flush_span(device=None) for _ in range(3)]
        assert obs_report.device_activity(spans) == {}

    def test_live_mesh_flush_spans_carry_device(self, tmp_path):
        # End-to-end: a real mesh-executor flush emits a device-attributed
        # span the report rolls up.
        from vizier_tpu.parallel.batch_executor import BatchExecutor
        from vizier_tpu.parallel.mesh import MeshConfig
        from tests.parallel.test_batch_executor import (
            StubDesigner,
            _run_concurrent,
        )

        tracer = tracing_lib.Tracer()
        previous = tracing_lib.set_tracer(tracer)
        try:
            ex = BatchExecutor(
                max_batch_size=4,
                max_wait_ms=5.0,
                mesh=MeshConfig(enabled=True, shard_devices=1),
            )
            try:
                results, errors = _run_concurrent(
                    ex, [StubDesigner(i) for i in range(3)]
                )
                assert all(e is None for e in errors)
            finally:
                ex.close()
            path = tmp_path / "mesh_spans.jsonl"
            tracer.dump_jsonl(str(path))
        finally:
            tracing_lib.set_tracer(previous)
        out = obs_report.device_activity(obs_report.load_spans(str(path)))
        assert out, "no device-attributed flush spans recorded"
        assert all(device.startswith("mesh") for device in out)
