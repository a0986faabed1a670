"""The benchmark's contract, from the CPU side.

The command only measures on a TPU. Here: ``BENCHMARK.json`` against the
contract's rules and the files it names; the generator's bucket arithmetic
against the program's; and child runs at ``--rehearse`` size — the command
as the driver calls it (result line, ``correct`` false off a TPU, no result
at full size), and, with the look for a chip skipped, a sound run that ends
correct and runs broken underneath that do not.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.join(ROOT, "chipbench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = {w["name"]: w for w in BENCH["workloads"]}
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
END_TO_END = {m["name"]: m for m in BENCH["end_to_end"]}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _reports(metric, cell):
    return cell in metric.get("workloads", [cell])


def test_top_level_keys_and_limits():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert BENCH["paths"] == ["chipbench", "tests/chipbench"]
    assert BENCH["command"][1].startswith("chipbench/")
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize(
    "entry",
    BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + BENCH["per_layer"],
    ids=lambda e: e["name"],
)
def test_entry_names_units_and_lines(entry):
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
        assert cell in CELLS
    if "bound" in entry:
        assert 0.01 <= entry["bound"] <= 0.25


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_the_deployment(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["file"].startswith("chipbench/configs/")
    data = _load(ROOT, config["file"])
    assert data["name"] == config["name"]
    assert sorted(data["reduced"]) == sorted(config["reduced"])
    assert data["guarantees"] and data["zero_counters"] and data["check_studies"] >= 1
    assert os.path.exists(os.path.join(HERE, "references", data["reference"] + ".py"))
    for name, limit in data["limits"].items():  # a ceiling, or a floor and/or a ceiling
        assert isinstance(limit, (int, float)) or set(limit) <= {"min", "max"}, name
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_resolve_by_name(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and cell["config"] in CONFIGS
    traffic = _load(HERE, "traffic", cell["traffic"] + ".json")
    assert os.path.exists(os.path.join(HERE, "generators", traffic["generator"] + ".py"))
    assert set(traffic["batched_share_pct"]) <= {"min", "max"}
    # Every cell reports setup_s, one more end-to-end metric and a per-layer one.
    reported = [m["name"] for m in BENCH["end_to_end"] if _reports(m, cell["name"])]
    assert "setup_s" in reported and len(reported) >= 2
    assert any(_reports(m, cell["name"]) for m in BENCH["per_layer"])
    # At most 64 studies: the designer cache keeps 64 entries.
    assert traffic["clients"] * traffic["studies_per_client"] <= 64


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_has_a_reader_and_moves_a_reported_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    from chipbench import run

    assert callable(run.load_reader(metric["name"]).read)
    moved = END_TO_END[metric["moves"]]
    for cell in CELLS:
        if _reports(metric, cell):
            assert _reports(moved, cell), (metric["name"], cell)


def test_readers_return_nothing_when_there_is_nothing_to_read():
    from chipbench import run

    empty = {
        "histograms_window": {}, "latencies_ms": [], "trace": None, "seconds": 1.0,
        "completed_in_window": 0, "stats_window": {"warm_trains": 0, "cold_trains": 0},
    }
    for metric in BENCH["per_layer"]:
        if not metric["name"].startswith("compiles_in_window"):
            assert run.load_reader(metric["name"]).read(empty) is None


def test_a_rehearse_overlay_merges_into_groups_and_replaces_numbers():
    from chipbench import run

    data = {"a": 1, "limits": {"x": 1, "y": 2}, "rehearse": {"a": 3, "limits": {"y": 9}}}
    assert run.sized(data, False) == {"a": 1, "limits": {"x": 1, "y": 2}}
    assert run.sized(data, True) == {"a": 3, "limits": {"x": 1, "y": 9}}


@pytest.mark.parametrize(
    "value,limit,ok",
    [(0, 0, True), (1, 0, False), (0.5, 1.0, True), (3, {"min": 1}, True), (0, {"min": 1}, False),
     (0.0, {"max": 0}, True), (2.0, {"max": 0}, False), (60, {"min": 50, "max": 100}, True),
     (float("inf"), 1e-6, False), (float("nan"), 1.0, False)],
)
def test_a_limit_is_a_ceiling_or_a_floor_and_a_ceiling(value, limit, ok):
    from chipbench.lib import checks

    assert checks.judge(value, limit) is ok


@pytest.mark.parametrize("n", [0, 1, 7, 8, 9, 66, 127, 128, 129, 256, 257, 485, 487, 488, 512, 513])
def test_pad_arithmetic_is_the_programs(n):
    from chipbench.lib import studies
    from vizier_tpu.converters import padding

    assert studies.pad_power_of_two(n) == padding.PaddingType.POWERS_OF_2.pad(n)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_a_study_is_retired_before_it_leaves_its_bucket(cell):
    from chipbench.lib import studies

    config = _load(ROOT, CONFIGS[cell["config"]]["file"])
    traffic = _load(HERE, "traffic", cell["traffic"] + ".json")
    start, count = traffic["start_trials"], traffic["suggest_count"]
    rounds = studies.rounds_in_bucket(start, count)
    home = studies.bucket(start, count)
    assert home[0] == config["trial_padding_bucket"]
    last = start + (rounds - 1) * count  # completed trials at the last suggest
    assert studies.bucket(last, count) == home
    assert studies.bucket(last + count, count) != home
    assert last <= config["completed_trials"] < 512  # never the sparse side
    # Room for the window: 1.5x the rounds a client completed on the chip
    # (PERF.md section 4), after the set-up's rounds on its first study.
    assert rounds >= 9


# -- child runs at rehearse size ------------------------------------------------


def _run(args, tmp, patch=""):
    """``chipbench/run.py`` as a child on the CPU; ``patch`` runs first, in
    the child, with the module as ``run``."""
    # Two cores: the suite's other workers run timing-sensitive tests.
    code = (
        "import os, sys; os.sched_setaffinity(0, sorted(os.sched_getaffinity(0))[:2])\n"
        "sys.path.insert(0, %r); from chipbench import run; %s\n"
        "sys.exit(run.main(%r))" % (ROOT, patch, list(args))
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "VIZIER_DISABLE_MESH": "1",
           "JAX_COMPILATION_CACHE_DIR": str(tmp)}
    env.pop("XLA_FLAGS", None)
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          env=env, timeout=600, cwd=ROOT)
    lines = [l for l in done.stdout.splitlines() if l.startswith("{")]
    return done, [json.loads(l) for l in lines]


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


SKIP_CHIP = "run.REQUIRED_PLATFORM = 'cpu'"
# Each breaks the timed path underneath: the posterior the sweeps score with,
# an answer where it is produced, the labels a train sees.
BREAK_POSTERIOR = SKIP_CHIP + (
    "; from vizier_tpu.models import gp; _p = gp.GPState.predict"
    "; gp.GPState.predict = lambda self, q, *a, **k: (lambda m, s: (m, 1.5 * s))(*_p(self, q, *a, **k))"
)
BREAK_LABELS = SKIP_CHIP + (
    "; from vizier_tpu.models import output_warpers as w; _c = w.create_default_warper"
    "; w.create_default_warper = lambda **k: _c(log_warp=False)"
)
BREAK_ANSWER = SKIP_CHIP + (
    "; from vizier_tpu.designers import gp_ucb_pe as d; _s = d.VizierGPUCBPEBandit.suggest"
    "; d.VizierGPUCBPEBandit.suggest = lambda self, count=None: [_s(self, count)[0]] * (count or 1)"
)
REHEARSE = ["--workload", "default20d.lone25", "--seed", "2147483659", "--seconds", "2", "--rehearse"]


def test_off_a_tpu_the_command_prints_a_result_that_is_not_correct(cache_dir):
    done, objs = _run(REHEARSE + ["--trace", "0"], cache_dir)
    result = objs[-1]
    assert done.returncode != 0
    assert set(result) == RESULT_KEYS and result["correct"] is False
    assert set(result["metrics"]) == {"suggest_p50_ms", "setup_s"}
    for name, metric in result["metrics"].items():
        assert metric["unit"] == END_TO_END[name]["unit"] and metric["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    compared = [o for o in objs if o.get("phase") == "correct"][0]["compared"]
    assert [c["name"] for c in compared if not c["ok"]] == ["platform"]
    assert all("limit" in c and "value" in c for c in compared)


def test_at_full_size_without_a_tpu_it_prints_no_result(cache_dir):
    done, objs = _run(["--workload", "default20d.lone25", "--seed", "1", "--seconds", "1",
                       "--trace", "0"], cache_dir)
    assert done.returncode != 0
    assert not any("correct" in o for o in objs)


def test_with_the_chip_check_skipped_a_sound_traced_run_is_correct(cache_dir):
    done, objs = _run(["--workload", "default20d.tenants16", "--seed", "5", "--seconds", "3",
                       "--rehearse", "--trace", "1"], cache_dir, SKIP_CHIP)
    result = objs[-1]
    assert done.returncode == 0, done.stderr[-2000:]
    assert set(result) - {"breakdown"} == RESULT_KEYS and result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"busy_s", "window_s"} <= set(result["device"])
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    assert {"client_overhead_ms.pool", "cache_warm_share.pool", "batch_occupancy", "batched_share",
            "compiles_in_window.pool"} <= set(
        result["metrics"]
    )
    for name, metric in result["metrics"].items():
        assert metric["unit"] == per_layer[name]["unit"]
        assert _reports(per_layer[name], "default20d.tenants16")


@pytest.mark.parametrize(
    "patch,failing",
    [(BREAK_POSTERIOR, "pick_stddev_err"), (BREAK_ANSWER, "failed_requests"),
     (BREAK_LABELS, "trained_labels_max_abs_diff")],
    ids=["posterior_scaled", "answer_duplicated", "labels_warped_otherwise"],
)
def test_broken_underneath_the_run_is_not_correct(cache_dir, patch, failing):
    done, objs = _run(REHEARSE + ["--trace", "0"], cache_dir, patch)
    result = objs[-1]
    assert set(result) == RESULT_KEYS and result["correct"] is False
    assert done.returncode != 0
    compared = [o for o in objs if o.get("phase") == "correct"][0]["compared"]
    assert any(failing in c["name"] for c in compared if not c["ok"])
