"""The contract's checks, as functions of a benchmark and the root it lives
under: ``bench`` is a ``BENCHMARK.json`` as a dict, ``root`` the directory
that holds it and its ``chipbench/``. ``test_harness.py`` runs them on the
repo's benchmark, ``test_contract_fixture.py`` on one made of fixture files
that no run ever executes. What belongs to one arrival process is asked of
its generator (``check_data``, ``study_count``); nothing here reads a
generator's own parameters.
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MAX_STUDIES = 64  # the designer cache's cache_max_entries: a rule of the server


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def reports(metric, cell):
    return cell in metric.get("workloads", [cell])


@contextlib.contextmanager
def harness_at(root):
    """``chipbench/run.py`` looking for a cell's files under ``root``: a
    file is found here as a run finds it, by the harness's own resolution."""
    from chipbench import run

    here = run.HERE
    run.HERE = os.path.join(root, "chipbench")
    try:
        yield run
    finally:
        run.HERE = here


def cell_files(bench, root, cell):
    """(configuration, traffic, generator module) of a cell, full size."""
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load(root, config_entry["file"])
    traffic = load(root, "chipbench", "traffic", cell["traffic"] + ".json")
    with harness_at(root) as run:
        return config, traffic, run.load_module("generators", traffic["generator"])


def top_level_keys_and_limits(bench, root):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    assert bench["paths"] == ["chipbench", "tests/chipbench"]
    assert bench["command"][1].startswith("chipbench/")
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 * 1024
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def entries(bench):
    return bench["configs"] + bench["workloads"] + bench["end_to_end"] + bench["per_layer"]


def entry_names_units_and_lines(bench, entry):
    cells = {w["name"] for w in bench["workloads"]}
    assert NAME.match(entry["name"])
    for key in ("config", "traffic", "moves"):
        if key in entry:
            assert NAME.match(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] and "\t" not in entry[key]
    for cell in entry.get("workloads", []):
        assert cell in cells
    if "bound" in entry:
        assert 0.01 <= entry["bound"] <= 0.25


def config_file_states_the_deployment(bench, root, config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["file"].startswith("chipbench/configs/")
    data = load(root, config["file"])
    assert data["name"] == config["name"]
    assert sorted(data["reduced"]) == sorted(config["reduced"])
    assert data["guarantees"] and data["zero_counters"] and data["check_studies"] >= 1
    assert os.path.exists(os.path.join(root, "chipbench", "references", data["reference"] + ".py"))
    for name, limit in data["limits"].items():  # a ceiling, or a floor and/or a ceiling
        assert isinstance(limit, (int, float)) or set(limit) <= {"min", "max"}, name
    assert any(w["config"] == config["name"] for w in bench["workloads"])


def cell_files_resolve_by_name(bench, root, cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and cell["config"] in {c["name"] for c in bench["configs"]}
    traffic = load(root, "chipbench", "traffic", cell["traffic"] + ".json")
    assert os.path.exists(os.path.join(root, "chipbench", "generators", traffic["generator"] + ".py"))
    assert set(traffic["batched_share_pct"]) <= {"min", "max"}
    # Every cell reports setup_s, one more end-to-end metric and a per-layer one.
    reported = [m["name"] for m in bench["end_to_end"] if reports(m, cell["name"])]
    assert "setup_s" in reported and len(reported) >= 2
    assert any(reports(m, cell["name"]) for m in bench["per_layer"])
    with harness_at(root) as run:
        at_most_64_studies(run.load_module("generators", traffic["generator"]), traffic)


def at_most_64_studies(generator, traffic):
    """A rule of every cell, whatever its generator: the designer cache
    keeps 64 entries, and a study evicted from it trains cold again."""
    studies = generator.study_count(traffic)
    assert studies <= MAX_STUDIES, (
        f"the traffic opens {studies} studies; the designer cache keeps {MAX_STUDIES}")


def cell_data_keeps_its_generators_rules(bench, root, cell):
    """The rules of a cell's data are its generator's, and every generator
    states them: a module without ``check_data`` fails here."""
    config, traffic, generator = cell_files(bench, root, cell)
    assert callable(getattr(generator, "Generator", None)), (
        f"generators/{traffic['generator']}.py has no class Generator")
    assert callable(getattr(generator, "check_data", None)), (
        f"generators/{traffic['generator']}.py states no rules: it has no check_data(config, traffic)")
    generator.check_data(config, traffic)


def layer_metric_has_a_reader_and_moves_a_reported_metric(bench, root, metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    with harness_at(root) as run:
        assert callable(run.load_reader(metric["name"]).read)
    moved = next(m for m in bench["end_to_end"] if m["name"] == metric["moves"])
    for cell in bench["workloads"]:
        if reports(metric, cell["name"]):
            assert reports(moved, cell["name"]), (metric["name"], cell["name"])


def readers_return_nothing_when_there_is_nothing_to_read(bench, root):
    empty = {
        "histograms_window": {}, "latencies_ms": [], "trace": None, "seconds": 1.0,
        "completed_in_window": 0,
        "stats_window": {"warm_trains": 0, "cold_trains": 0, "trials_reused": 0, "trials_fetched": 0},
    }
    with harness_at(root) as run:
        for metric in bench["per_layer"]:
            if not metric["name"].startswith("compiles_in_window"):
                assert run.load_reader(metric["name"]).read(empty) is None, metric["name"]
