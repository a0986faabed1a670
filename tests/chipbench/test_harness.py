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
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _path in (ROOT, os.path.dirname(os.path.abspath(__file__))):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import contract_checks as contract  # noqa: E402  (beside this file)

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
BENCH = contract.load(ROOT, "BENCHMARK.json")
END_TO_END = {m["name"]: m for m in BENCH["end_to_end"]}


def test_top_level_keys_and_limits():
    contract.top_level_keys_and_limits(BENCH, ROOT)


@pytest.mark.parametrize("entry", contract.entries(BENCH), ids=lambda e: e["name"])
def test_entry_names_units_and_lines(entry):
    contract.entry_names_units_and_lines(BENCH, entry)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_the_deployment(config):
    contract.config_file_states_the_deployment(BENCH, ROOT, config)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_resolve_by_name(cell):
    contract.cell_files_resolve_by_name(BENCH, ROOT, cell)


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_has_a_reader_and_moves_a_reported_metric(metric):
    contract.layer_metric_has_a_reader_and_moves_a_reported_metric(BENCH, ROOT, metric)


def test_readers_return_nothing_when_there_is_nothing_to_read():
    contract.readers_return_nothing_when_there_is_nothing_to_read(BENCH, ROOT)


@pytest.mark.parametrize(
    "stats,share",
    [
        ({"trials_reused": 350, "trials_fetched": 25}, 100.0 * 350 / 375),
        ({"trials_reused": 0, "trials_fetched": 375}, 0.0),  # the whole study read again: 0, not nothing
        ({"trials_reused": 0, "trials_fetched": 0}, None),
        ({}, None),  # a program from before the delta read has no such counters
    ],
    ids=["delta_read", "full_read", "no_suggest", "no_counters"],
)
def test_trial_reuse_share_is_the_share_a_suggest_did_not_read_again(stats, share):
    from chipbench import run

    for name in ("trial_reuse_share.lone", "trial_reuse_share.pool"):
        value = run.load_reader(name).read({"stats_window": stats})
        assert value == (share if share is None else pytest.approx(share))


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


def test_the_idle_gaps_names_are_the_programs_stages():
    from chipbench import run
    from chipbench.lib import stages
    from vizier_tpu.observability import tracing

    assert len(set(stages.STAGE_NAMES)) == len(stages.STAGE_NAMES)
    assert set(stages.STAGE_NAMES) == tracing.STAGES
    assert stages.GAP_ANNOTATIONS == (
        *stages.STAGE_NAMES, "batch_executor.queue_wait", "client.complete", "client.think")
    assert stages.THINK == "client.think" and stages.THINK not in stages.STAGE_NAMES  # the harness's, not a stage
    assert stages.REQUEST_STAGE in stages.STAGE_NAMES
    assert run.ANNOTATIONS is stages.GAP_ANNOTATIONS  # what a traced run hands the reduction


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_a_study_is_retired_before_it_leaves_its_bucket(cell):
    # The rule is the cell's generator's (``closed_rounds.check_data`` holds
    # what this test held); another arrival process states another.
    contract.cell_data_keeps_its_generators_rules(BENCH, ROOT, cell)


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
    # Each number beside its limit ends the result line and stderr, the
    # failing row last: the end of each is what a failed run's record keeps.
    assert list(result)[-1] == "compared" and set(result["compared"]) == {c["name"] for c in compared}
    assert list(result["compared"])[-1] == "platform"
    assert all(len(pair) == 2 for pair in result["compared"].values())
    last = done.stderr.strip().splitlines()[-len(compared):]
    assert last[-1].startswith("NOT OK platform ") and all(l.startswith("ok ") for l in last[:-1])


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
        assert contract.reports(per_layer[name], "default20d.tenants16")


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
