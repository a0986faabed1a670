#!/usr/bin/env python3
"""One cell of the benchmark, once: ``python3 chipbench/run.py --workload
<cell> --seed <n> --seconds <s> --trace <0|1>``.

One process, which holds the chip from its first JAX call to exit, starts
``DefaultVizierServer`` with every serving default as shipped and drives it
over loopback gRPC with the cell's traffic: set-up (studies from the seed,
one cold round per study, warm rounds until nothing compiles), the window,
then — outside every timed region — the checks that decide ``correct``.
Earlier stdout lines are free-form JSON, one object per phase; the last line
is the contract's object, which ends with every number compared beside its
limit (``compared``; the same rows are the last lines of stderr). Exits non-zero, printing no result, when the
platform is not ``tpu`` or the device count is not the cell's. ``--rehearse``
takes each file's ``rehearse`` sizes so that the control flow runs in a
sandbox; off a TPU such a run still ends ``"correct": false`` and non-zero.
``--control 1`` (the posterior's matmuls at the TPU's default precision) and
``--control 2`` (the acquisition sweeps cut short) must end
``"correct": false`` on the chip.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up is counted from here: imports are set-up

import argparse
import contextlib
import json
import os
import shutil
import sys
import threading
from typing import Any, Dict, List, Optional, Sequence

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.join(ROOT, "chipbench")
REQUIRED_PLATFORM = "tpu"
TRACE_SECONDS = 1.0  # ~1.7 million device events per busy second, ~50 s to collect each;
# a traffic file whose second holds more (many small programs) asks for less: ``trace_seconds``
TRACE_LEAD_SHARE = 0.4  # of the window: past its start, where every client is released at once
TRACE_DIR = os.path.join(ROOT, "chiprun_out", "chipbench_trace")
from chipbench.lib.stages import GAP_ANNOTATIONS as ANNOTATIONS  # noqa: E402  (names of idle gaps)


def emit(obj: Dict[str, Any]) -> None:
    print(json.dumps(obj, sort_keys=True, default=str), flush=True)


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def sized(data: Dict[str, Any], rehearse: bool) -> Dict[str, Any]:
    """A data file's parameters, with its ``rehearse`` sizes laid over them."""
    out = {k: v for k, v in data.items() if k != "rehearse"}
    for key, value in (data.get("rehearse", {}) if rehearse else {}).items():
        both_groups = isinstance(value, dict) and isinstance(out.get(key), dict)
        out[key] = {**out[key], **value} if both_groups else value
    return out


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py``, found by the name an entry gives."""
    import importlib.util

    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"chipbench.{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(metric: str):
    """A per-layer metric's reader: ``layer_metrics/<metric>.py``, or, for a
    quantity split by suffix over cells that report different end-to-end
    metrics (``<quantity>.<suffix>``), the one ``layer_metrics/<quantity>.py``."""
    if not os.path.exists(os.path.join(HERE, "layer_metrics", metric + ".py")):
        metric = metric.rpartition(".")[0]
    return load_module("layer_metrics", metric)


def reports(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


# -- end-to-end metrics, from the window's own records ------------------------


def end_to_end(name: str, evidence: Dict[str, Any]) -> float:
    from chipbench.lib import reduce

    latencies = evidence["latencies_ms"]
    name = name.partition(".")[0]  # a quantity split over cells by suffix is one arithmetic
    if name == "suggest_p50_ms":
        return reduce.percentile(latencies, 50)
    if name == "suggest_p95_ms":
        return reduce.percentile(latencies, 95)
    if name == "suggestions_per_s":
        return evidence["suggestions_in_window"] / evidence["seconds"]
    if name == "setup_s":
        return evidence["setup_s"]
    raise KeyError(f"no end-to-end metric {name!r}")


# -- the checks that decide `correct` -----------------------------------------


def check_run(server, generator, config, traffic, evidence, seed) -> List[Dict[str, Any]]:
    """Every number compared, beside its limit: {name, value, limit, ok}."""
    import numpy as np

    from chipbench.lib import checks

    reference = load_module("references", config["reference"])
    compared: List[Dict[str, Any]] = []

    def compare(name: str, value, limit) -> None:
        compared.append({"name": name, "value": value, "limit": limit,
                         "ok": value is not None and checks.judge(value, limit)})

    # No reliability rescue and no other surrogate anywhere in the process.
    for name in config["zero_counters"]:
        compare(f"stats.{name}", evidence["stats_total"].get(name), 0)
    # Which path served the window's own requests: the share that met other
    # studies in a fused flush, against the limits the traffic file gives.
    compare("window.batched_share_pct", evidence["batched_share_pct"], traffic["batched_share_pct"])
    compare("clients_out_of_studies", len(generator.exhausted), 0)
    compare("failed_requests", evidence["failed"], 0)
    compare("requests", evidence["attempted"], {"min": 1})

    # The guarantee and the numerics, on a seeded sample of the studies the
    # window served, the one with most trials among them: what each one's
    # last suggest returned, against the reference (its module compares).
    served = sorted({r["study"] for r in generator.records if not r["failures"]})
    sample = [int(i) for i in np.random.default_rng([seed, 4]).permutation(served)[: int(config["check_studies"])]]
    if served:
        longest = max(served, key=lambda i: generator.studies[i].completed_at_last_suggest)
        if longest not in sample:
            sample[-1] = longest
    seen = []
    for index in sample:
        study = generator.studies[index]
        trained = server.trained(study.handle)
        compare(f"study{index}.cached_designer_missing", int(trained is None), 0)
        if trained is None:
            continue
        result = reference.compare(
            study.record_at_last_suggest(), trained, config, np.random.default_rng([seed, 5, index])
        )
        for name, value in result["numbers"].items():
            compare(f"study{index}.{name}", value, config["limits"][name])
        seen.append({"study": index, **result["seen"]})
    compare("studies_checked", len(sample), {"min": 1})
    emit({"phase": "fitted", "studies": seen})
    return compared


# -- the run -------------------------------------------------------------------


def trace_window(delay: float, seconds: float, done: threading.Event) -> None:
    """Traces ``seconds`` of the window from ``delay`` in, marking the span."""
    import jax

    from chipbench.lib import trace_reduce

    if done.wait(delay):
        return
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # every Python call otherwise: slow, and 7 MB/s
    options.enable_hlo_proto = False
    jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.SPAN_ANNOTATION):
            done.wait(seconds)
    finally:
        jax.profiler.stop_trace()


def run_cell(args) -> int:
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; have {sorted(cells)}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = sized(load_json(ROOT, config_entry["file"]), args.rehearse)
    traffic = sized(load_json(HERE, "traffic", cell["traffic"] + ".json"), args.rehearse)
    generator_lib = load_module("generators", traffic["generator"])

    import jax

    from vizier_tpu.serving import compile_cache

    cache_dir = compile_cache.configure_entry_point()
    # Also when the directory comes from the environment: without this the
    # ~100 sub-second programs of a suggest recompile in every process.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()  # from here on this process holds the chip
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    emit({"phase": "device", **device, "jax": jax.__version__, "compile_cache_dir": cache_dir,
          "workload": cell["name"], "seed": args.seed, "seconds": args.seconds,
          "trace": args.trace, "rehearse": args.rehearse, "control": args.control})
    on_platform = device["platform"] == REQUIRED_PLATFORM and len(devices) == cell["chips"]
    if not on_platform and not args.rehearse:
        print(f"need {cell['chips']} {REQUIRED_PLATFORM} device(s), found {device}", file=sys.stderr)
        return 2

    from chipbench.lib import compile_counter
    from chipbench.lib import program
    from chipbench.lib import reduce
    from chipbench.lib import trace_reduce

    if args.control == 1:
        program.lower_posterior_precision()
    if args.control == 2:
        config["max_acquisition_evaluations"] = config["control_acquisition_evaluations"]
    compiles = compile_counter.CompileCounter()
    annotate = jax.profiler.TraceAnnotation if args.trace else (lambda name: contextlib.nullcontext())
    server = program.Server()
    try:
        generator = generator_lib.Generator(server, config, traffic, args.seed, annotate)
        setup_report = generator.setup(lambda: compiles.compiles)
        setup_s = time.perf_counter() - _T0
        emit({"phase": "setup", "setup_s": setup_s, "wall_time": time.time(), **setup_report, **compiles.snapshot()})

        # What the cell's studies can still serve, before the window takes
        # from it: a window that comes near it is about to fail for being fast.
        available = generator.requests_available()
        stats_before, hist_before = server.stats(), server.histograms()
        compiles_before = compiles.snapshot()
        done = threading.Event()
        tracer = None
        if args.trace:
            lead = TRACE_LEAD_SHARE * args.seconds
            span = min(float(traffic.get("trace_seconds", TRACE_SECONDS)), max(0.1, args.seconds - 2 * lead))
            tracer = threading.Thread(target=trace_window, args=(lead, span, done))
            tracer.start()
        try:
            window = generator.window(args.seconds)
        finally:
            done.set()
            if tracer is not None:
                tracer.join()  # stopping the trace collects it: seconds, after the window
        stats_total = server.stats()
        records = generator.records
        good = [r for r in records if not r["failures"]]
        evidence: Dict[str, Any] = {
            "platform": device["platform"],
            "seconds": args.seconds,
            "setup_s": setup_s,
            "attempted": len(records),
            "requests_available": available,
            "failed": len(records) - len(good),
            "latencies_ms": [(r["t1"] - r["t0"]) * 1e3 for r in good],
            "completed_in_window": sum(r["t1"] <= window["t1"] for r in good),
            "suggestions_in_window": sum(r["suggestions"] for r in good if r["t1"] <= window["t1"]),
            "stats_total": stats_total,
            "stats_window": reduce.counter_delta(stats_total, stats_before),
            "histograms_window": reduce.histogram_delta(server.histograms(), hist_before),
            "compiles_window": compiles.since(compiles_before),
            "trace": None,
        }
        evidence["batched_share_pct"] = (
            100.0 * evidence["stats_window"]["batched_suggests"] / len(records) if records else None
        )
        emit({"phase": "window", "wall_time": time.time(), "requests": len(records),
              "requests_available": available, "failed": evidence["failed"],
              "drained_after_window": sum(r["t1"] > window["t1"] for r in records),
              "failures": [f for r in records for f in r["failures"]][:10],
              "stats_window": {k: v for k, v in evidence["stats_window"].items() if v},
              "compiles_window": evidence["compiles_window"]})
        if args.trace:
            t_reduce = time.perf_counter()
            path = trace_reduce.find_xplane(TRACE_DIR)
            evidence["trace"] = trace_reduce.reduce_trace(path, ANNOTATIONS) if path else {}
            emit({"phase": "trace", "xplane_bytes": os.path.getsize(path) if path else 0,
                  "reduce_s": time.perf_counter() - t_reduce,
                  **{k: v for k, v in evidence["trace"].items() if k not in ("device_ops", "idle_gaps")}})
            shutil.rmtree(TRACE_DIR, ignore_errors=True)

        # What the per-layer readers find without a trace, in every run.
        layers = {m["name"]: load_reader(m["name"]).read(evidence)
                  for m in bench["per_layer"] if reports(m, cell["name"]) and m["source"] != "device_trace"}
        emit({"phase": "layers", **{k: v for k, v in layers.items() if v is not None}})
        compared = check_run(server, generator, config, traffic, evidence, args.seed)
        if not on_platform:
            compared.append({"name": "platform", "value": device, "ok": False,
                             "limit": f"{cell['chips']} x {REQUIRED_PLATFORM}"})
        emit({"phase": "correct", "compared": compared})
        memory = [d.memory_stats() or {} for d in devices]
        device["memory_peak_bytes"] = max(m.get("peak_bytes_in_use", 0) for m in memory)
    finally:
        server.stop()

    metrics: Dict[str, Dict[str, Any]] = {}
    if args.trace:
        trace = evidence["trace"] or {}
        device["busy_s"], device["window_s"] = trace.get("busy_s", 0.0), trace.get("span_s", 0.0)
        for metric in bench["per_layer"]:
            if reports(metric, cell["name"]):
                reader = load_reader(metric["name"])
                value = reader.read(evidence)
                if value is not None:
                    metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    else:
        for metric in bench["end_to_end"]:
            if reports(metric, cell["name"]) and (evidence["latencies_ms"] or metric["name"] == "setup_s"):
                metrics[metric["name"]] = {"value": end_to_end(metric["name"], evidence), "unit": metric["unit"]}
    result = {
        "correct": all(c["ok"] for c in compared),
        "attempted": evidence["attempted"],
        "failed": evidence["failed"],
        "metrics": metrics,
        "device": device,
    }
    if args.trace and evidence["trace"]:
        result["breakdown"] = {
            "device_ops": [list(p) for p in evidence["trace"]["device_ops"]],
            "idle_gaps": [list(p) for p in evidence["trace"]["idle_gaps"]],
        }
    # Each number compared beside its limit, the failing ones last: as the
    # last lines of stderr and under the result line's last key, since the
    # end of each is all that a record of a run that was not correct keeps.
    rows = sorted(compared, key=lambda c: not c["ok"])
    for c in rows:
        print(f"{'ok' if c['ok'] else 'NOT OK'} {c['name']} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    result["compared"] = {c["name"]: [c["value"], c["limit"]] for c in rows}
    print(json.dumps(result, default=str), flush=True)  # keys as inserted: `compared` stays last
    return 0 if result["correct"] else 1


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="the files' rehearse sizes; never correct off a TPU")
    parser.add_argument("--control", type=int, choices=(0, 1, 2), default=0,
                        help="must end not correct: 1 = posterior matmuls at default precision, "
                             "2 = the acquisition sweeps cut to the config's control_acquisition_evaluations")
    return run_cell(parser.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
