"""``default20d-sparse.tenants16`` from the CPU side: ONE fused sparse flush
(``UCBPESparseProgram``, kind ``gp_ucb_pe_sparse``) of studies of different
sizes in one bucket, at a size the CPU can hold — every slot's answers held
against the float64 SGPR reference under the configuration's limits and
against the same study served alone through the sequential sparse path;
faults that only the slot path can have, planted in the host's stack and in
the demux; what a fused sparse flush leaves in ``serving_stats()``; the rules
``generators/closed_rounds_1k.py`` holds the cell's files to; the one new
reader (``sparse_flush_share``); and a rehearsal of the cell as a child whose
server switches to the sparse tier at 32 trials (the child's environment, not
the cell's: on the chip the cell sets nothing).
"""

from __future__ import annotations

import copy
import os
import sys
import threading
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _path in (ROOT, os.path.dirname(os.path.abspath(__file__))):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import contract_checks as contract  # noqa: E402  (beside this file)
from chipbench import run  # noqa: E402
from chipbench.lib import checks  # noqa: E402
from chipbench.lib import program  # noqa: E402
from chipbench.lib import studies  # noqa: E402
from test_harness import SKIP_CHIP, _run, cache_dir  # noqa: E402,F401  (the child-run helpers)

CELL = "default20d-sparse.tenants16"
LONE = "default20d-sparse.lone25"  # the sequential twin, one of the cell's two controls
POOL = "default20d.tenants16"  # the exact twin, the other
BENCH = contract.load(ROOT, "BENCHMARK.json")
CONFIG = contract.load(ROOT, "chipbench", "configs", "default20d-sparse-shared.json")
TRAFFIC = contract.load(ROOT, "chipbench", "traffic", "tenants16-1k.json")
# The rehearsal's sizes (300 evaluations, its loosened shortfall and bound-gain
# limits) at 4 floats and 16 inducing rows: at 20 floats a study of 36-60 trials
# is fitted noise-only, and a slot that was handed another's rows moves nothing.
SMALL = {**run.sized(CONFIG, True), "num_float_parameters": 4, "num_inducing": 16}
REHEARSAL = run.sized(TRAFFIC, True)
reference = run.load_module("references", "sgpr_ucb_pe")
generator = run.load_module("generators", "closed_rounds_1k")

THRESHOLD = 32  # the rehearsal's switch (``VIZIER_SPARSE_THRESHOLD``), as a designer's own config here
SIZES = (36, 45, 54, 60)  # four studies of one bucket (pad 64, all-points pad 64), none the same size
COUNT = REHEARSAL["suggest_count"]
# Studies on which no two restart rows of a train end within a thousandth of a
# nat a trial of each other. Where they do (seeds 43..46: the second study's
# rows end at bounds -0.24673 and -0.24662 a trial, amplitudes 0.30 and 1.88)
# float32's order of summation decides which row the ranking keeps, a slot may
# keep another than its study alone, and both fits are sound.
SEED = 143


# -- a fused sparse flush by hand, and the same studies alone -------------------------


def _designer(seed: int, trials: int):
    """(the served default's designer at the rehearsal's sizes, its client's
    rows, its client's raw labels) of one seeded study of the configuration."""
    from vizier_tpu.algorithms import core as core_lib
    from vizier_tpu.designers import gp_ucb_pe
    from vizier_tpu.surrogates import SurrogateConfig

    made, x, labels = studies.seeded_trials(SMALL, np.random.default_rng([seed, 1, trials]), trials)
    for i, trial in enumerate(made):
        trial.id = i + 1
    designer = gp_ucb_pe.VizierGPUCBPEBandit(
        studies.study_config(SMALL).to_problem(), rng_seed=seed,
        surrogate=SurrogateConfig(sparse_threshold_trials=THRESHOLD, num_inducing=SMALL["num_inducing"]),
        max_acquisition_evaluations=SMALL["max_acquisition_evaluations"])
    designer.update(core_lib.CompletedTrials(made))
    return designer, x, labels


def _record(suggestions, x, labels):
    """The client's record of a study's suggest, as ``closed_rounds`` keeps it."""
    names = studies.param_names(SMALL)
    rows = np.asarray([[s.parameters[name].value for name in names] for s in suggestions], np.float64)
    meta = [program.Server.pick_metadata(types.SimpleNamespace(_snapshot=s)) for s in suggestions]
    return {"rows": x, "labels": labels, "picks": rows,
            "meta": {k: np.asarray([m[k] for m in meta]) for k in meta[0]}}


def _trained(designer):
    """What ``lib/program.py`` ``Server.trained`` hands a reference, read by
    that very function from a stand-in for the server's designer cache."""
    cache = types.SimpleNamespace(peek=lambda name, touch=False: types.SimpleNamespace(designer=designer))
    server = types.SimpleNamespace(runtime=types.SimpleNamespace(designer_cache=cache))
    return program.Server.trained(server, types.SimpleNamespace(resource_name="study"))


def _flush(seed: int = SEED):
    """One fused flush of ``SIZES``' studies, padded to the executor's 8
    slots, by hand as ``tests/program_driver.py`` drives one (resolve,
    prepare each, ONE device program, finalize each): per study (designer,
    record, trained)."""
    from tests import program_driver

    made = [_designer(seed + i, n) for i, n in enumerate(SIZES)]
    designers = [d for d, _, _ in made]
    assert program_driver.bucket_key(designers[0], COUNT).kind == "gp_ucb_pe_sparse"
    answers = program_driver.flush(designers, COUNT, pad_to=8)
    return [(d, _record(s, x, labels), _trained(d)) for (d, x, labels), s in zip(made, answers)]


def _judged(record, trained, index=0):
    """(names of the numbers over their limits, every number) of one study."""
    result = reference.compare(record, trained, SMALL, np.random.default_rng([7, 5, index]))
    assert set(result["numbers"]) == set(SMALL["limits"])
    return sorted(n for n, v in result["numbers"].items() if not checks.judge(v, SMALL["limits"][n])), result


@pytest.fixture(scope="module")
def flushed():
    return _flush()


def test_every_slot_of_a_fused_sparse_flush_keeps_the_configurations_limits(flushed):
    assert SMALL["limits"]["pick_stddev_err_label_std"] == CONFIG["limits"]["pick_stddev_err_label_std"]
    for index, (designer, record, trained) in enumerate(flushed):
        assert trained["surrogate_mode"] == "sparse" and trained["completed"] == SIZES[index]
        assert record["picks"].shape == (COUNT, SMALL["num_float_parameters"])
        broken, result = _judged(record, trained, index)
        assert broken == [], (index, {n: result["numbers"][n] for n in broken})
        # On the CPU's float32 a sound slot reads a hundred times under the limits.
        for name in ("pick_mean_err_label_std", "pick_stddev_err_label_std", "pick_stddev_all_err_label_std"):
            assert result["numbers"][name] < 1e-4, (index, name, result["numbers"][name])
        assert result["seen"]["nystrom_augments"] == designer.surrogate_counts["nystrom_augments"]


def test_a_slot_is_its_study_served_alone_through_the_sequential_sparse_path(flushed):
    # The program's own promise (``_sparse_ucb_pe_flush_program``'s docstring).
    for index, (_, record, trained) in enumerate(flushed):
        alone, x, labels = _designer(SEED + index, SIZES[index])
        assert alone.surrogate_mode == "exact"  # decided at the suggest
        record_alone = _record(alone.suggest(COUNT), x, labels)
        assert alone.surrogate_mode == "sparse"
        trained_alone = _trained(alone)
        # Not to the bit at this size (as it is with 2 floats, 5 trials and 15
        # Adam steps: tests/compute/test_program_parity.py): a batched matmul
        # sums in another order than a lone one and 50 L-BFGS iterations carry
        # that into the fourth digit of a hyperparameter. The same picks, and
        # every stamped number within a tenth of what the configuration allows
        # against float64.
        for key in ("amplitude", "noise_stddev", "length_scales"):
            np.testing.assert_allclose(trained[key], trained_alone[key], rtol=5e-3, err_msg=f"{index} {key}")
        np.testing.assert_allclose(record["picks"], record_alone["picks"], atol=1e-3, err_msg=str(index))
        assert record["meta"]["use_ucb"].tolist() == record_alone["meta"]["use_ucb"].tolist()
        scale = float(np.std(reference.warp_labels(record["labels"], SMALL["goal"])))
        for key, limit in (("mean", "pick_mean_err_label_std"), ("stddev", "pick_stddev_err_label_std"),
                           ("stddev_from_all", "pick_stddev_all_err_label_std"),
                           ("acquisition", "pick_acquisition_err_label_std")):
            apart = float(np.max(np.abs(record["meta"][key] - record_alone["meta"][key]))) / scale
            assert apart < 0.1 * CONFIG["limits"][limit], (index, key, apart)


# -- faults only the slot path can have ------------------------------------------------


def _roll_the_all_points_rows(monkeypatch):
    """The host's stack hands slot i the all-points rows (completed + pending)
    of slot i + 1: each slot's 24 reconditionings run on another study's rows."""
    from vizier_tpu.parallel import batch_executor

    real = batch_executor.stack_members

    def planted(items, names, pad_to=None, placement=None):
        rolled = [dict(item, all_md=items[(i + 1) % len(items)]["all_md"]) for i, item in enumerate(items)]
        return real(rolled, names, pad_to, placement)

    monkeypatch.setattr(batch_executor, "stack_members", planted)


def _swap_two_slots_answers(monkeypatch):
    """The demux hands slots 0 and 1 each other's picks and stamped readings."""
    from vizier_tpu.designers import gp_ucb_pe

    real = gp_ucb_pe._ucb_pe_demux

    def planted(*args):
        outputs = real(*args)
        outputs[0]["segments"], outputs[1]["segments"] = outputs[1]["segments"], outputs[0]["segments"]
        return outputs

    monkeypatch.setattr(gp_ucb_pe, "_ucb_pe_demux", planted)


@pytest.mark.parametrize(
    "plant,number,slots",
    [(_roll_the_all_points_rows, "pick_stddev_all_err_label_std", range(len(SIZES))),
     (_swap_two_slots_answers, "pick_mean_err_label_std", (0, 1))],
    ids=["another_slots_pending_rows", "another_slots_answers"],
)
def test_a_planted_slot_mix_up_ends_not_correct_by_its_number(monkeypatch, plant, number, slots):
    plant(monkeypatch)
    broken = [_judged(record, trained, index)[0] for index, (_, record, trained) in enumerate(_flush())]
    assert all(number in broken[index] for index in slots), broken
    # (and the slots the fault did not reach keep every limit)
    assert all(broken[index] == [] for index in range(len(SIZES)) if index not in slots), broken


# -- what a fused sparse flush leaves in serving_stats() ---------------------------------


def _sparse_ucb_pe(problem, **kwargs):
    """GP-UCB-PE at test size, sparse from its first trial, with the L-BFGS
    trainer (rows that stop at different iterations, as the served default's)."""
    from vizier_tpu.designers import gp_ucb_pe
    from vizier_tpu.optimizers import lbfgs as lbfgs_lib
    from vizier_tpu.surrogates import SurrogateConfig

    return gp_ucb_pe.VizierGPUCBPEBandit(
        problem, ard_optimizer=lbfgs_lib.LbfgsOptimizer(maxiter=30), ard_restarts=3,
        max_acquisition_evaluations=200, warm_start_min_trials=0,
        surrogate=SurrogateConfig(sparse_threshold_trials=1, hysteresis_trials=0, num_inducing=6))


def test_serving_stats_after_a_fused_sparse_flush_of_three_slots(served_gp_stack):
    from vizier_tpu.observability import config as config_lib
    from vizier_tpu.observability import jax_timing
    from vizier_tpu.service import vizier_client

    servicer, runtime, names = served_gp_stack(
        3, designer_factory=_sparse_ucb_pe, batch_max_size=3, batch_max_wait_ms=60_000.0)
    jax_timing.set_config(config_lib.ObservabilityConfig())  # the train's work is read, as served
    results, errors = {}, {}
    barrier = threading.Barrier(len(names))

    def ask(study):
        barrier.wait()
        try:
            results[study] = vizier_client.VizierClient(servicer, study, "worker").get_suggestions(3)
        except BaseException as e:  # noqa: BLE001 - the test shows it
            errors[study] = e

    threads = [threading.Thread(target=ask, args=(name,)) for name in names]
    before = runtime.stats.snapshot()
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        jax_timing.set_config(None)
    assert not errors and all(not t.is_alive() for t in threads), errors
    after = runtime.stats.snapshot()
    gained = {name: after[name] - before.get(name, 0) for name in after}
    assert gained["batch_flushes"] == 1 and gained["lone_handbacks"] == 0
    assert gained["batched_suggests"] == 3 == gained["sparse_suggests"]
    assert gained["batch_fallbacks"] == 0 == gained["batch_slot_errors"]
    designers = [runtime.designer_cache.peek(name, touch=False).designer for name in names]
    assert all(d.surrogate_mode == "sparse" for d in designers)
    joined = [d.surrogate_counts["nystrom_augments"] for d in designers]
    assert gained["nystrom_augments"] == sum(joined) and all(0 <= j <= 3 for j in joined)
    # Three members trained, ONE program ran, and it counted its own work.
    assert gained["cold_trains"] + gained["warm_trains"] == 3 and gained["train_programs"] == 1
    assert gained["train_loop_trips"] > 0 and gained["train_evaluations"] >= gained["train_row_iterations"] > 0
    # (3 slots x 5 rows in one lockstep loop: 3 restarts, the seed's row, the data-scaled start)
    assert gained["train_row_trips"] == 3 * 5 * gained["train_loop_trips"]
    # The flush is the sparse program's, by the label the new reader reads.
    occupancy = program.Server.histograms(types.SimpleNamespace(runtime=runtime))["vizier_batch_occupancy"]
    (label,) = [label for label, (_, count, _) in occupancy["series"].items() if count]
    assert label.startswith("bucket=gp_ucb_pe_sparse/t8/f2x0/m1/q3")


# -- the generator's rules on the new pair of files ---------------------------------------


def test_the_cells_files_keep_the_generators_rules_and_are_tenants16s_in_the_1k_bucket():
    generator.check_data(CONFIG, TRAFFIC)
    assert generator.study_count(TRAFFIC) == 48 and CONFIG["reduced"] == {}
    rounds = studies.rounds_in_bucket(TRAFFIC["start_trials"], TRAFFIC["suggest_count"])
    assert rounds == 16 and TRAFFIC["start_trials"] + 25 * (rounds - 1) == CONFIG["completed_trials"] == 975
    exact = contract.load(ROOT, "chipbench", "traffic", "tenants16.json")
    for key in ("clients", "studies_per_client", "suggest_count", "think_ms", "batched_share_pct"):
        assert TRAFFIC[key] == exact[key], key  # the tenants are default20d.tenants16's
    lone = contract.load(ROOT, "chipbench", "traffic", "lone25-1k.json")
    assert (TRAFFIC["generator"], TRAFFIC["start_trials"]) == (lone["generator"], lone["start_trials"])
    assert set(TRAFFIC) == set(exact) and "trace_seconds" not in TRAFFIC and "max_rounds_per_study" not in TRAFFIC
    # A rehearsal meets in flushes and is judged: nothing of its limits is given away but the share's floor.
    assert REHEARSAL["clients"] >= 4 and REHEARSAL["think_ms"] == 0
    # (A start at 575 breaks no rule: 17 rounds, the last suggest at 975 too.)
    generator.check_data(CONFIG, {**TRAFFIC, "start_trials": 575})


def test_the_shared_deployment_is_default20d_sparse_but_for_its_tenants():
    """The configuration's file is a deployment of its own (a server its
    tenants share); every shape, the reference and every limit are
    ``default20d-sparse``'s, since a slot owes the answers its study would
    get alone. What differs is what states the tenancy."""
    alone = contract.load(ROOT, "chipbench", "configs", "default20d-sparse.json")
    differs = {"name", "source", "deployment", "tenants", "guarantees", "limit_readings", "assumed"}
    assert set(CONFIG) - set(alone) == {"tenants"} and not set(alone) - set(CONFIG)
    assert {key for key in alone if CONFIG[key] != alone[key]} == differs - {"tenants"}
    assert CONFIG["limits"] == alone["limits"] and CONFIG["reference"] == alone["reference"] == "sgpr_ucb_pe"
    assert CONFIG["guarantees"][: len(alone["guarantees"])] == alone["guarantees"]  # none weakened, two more
    assert len(CONFIG["guarantees"]) == len(alone["guarantees"]) + 2
    assert {k: v for k, v in CONFIG["assumed"].items() if k != "tenants"} == alone["assumed"]
    tenants = CONFIG["tenants"]
    assert (tenants["clients"], tenants["studies_per_client"]) == (TRAFFIC["clients"], TRAFFIC["studies_per_client"])
    assert tenants["studies"] == generator.study_count(TRAFFIC) <= tenants["designer_cache_entries"] == contract.MAX_STUDIES
    from vizier_tpu.serving import config as serving_config

    shipped = serving_config.ServingConfig()
    assert (tenants["batch_slots"], tenants["batch_wait_ms"], tenants["designer_cache_entries"]) == (
        shipped.batch_max_size, shipped.batch_max_wait_ms, shipped.cache_max_entries)
    # Each limit lies between this cell's own two readings, where a control moves the number.
    for name, reading in CONFIG["limit_readings"].items():
        if isinstance(reading, dict) and reading.get("control_smallest") is not None and name in CONFIG["limits"]:
            if name == "pick_acquisition_err_label_std":
                continue  # PR 41's note: no limit lies between its readings; the stddev limits catch control 1
            assert reading["sound_largest"] < CONFIG["limits"][name] < reading["control_smallest"], name


@pytest.mark.parametrize(
    "where,key,value,sentence",
    [("traffic", "start_trials", 500, "served by the exact programs until it reaches the sparse switch at 512"),
     ("traffic", "start_trials", 590, "holds 990 completed trials before it would leave the 1024 bucket"),
     ("config", "trial_padding_bucket", 512, "not the configuration's trial_padding_bucket 512")],
    ids=["starts_exact", "ends_before_the_buckets_last_suggest", "another_bucket"],
)
def test_a_planted_breach_of_a_rule_fails_with_its_sentence(where, key, value, sentence):
    files = {"config": copy.deepcopy(CONFIG), "traffic": copy.deepcopy(TRAFFIC)}
    files[where][key] = value
    with pytest.raises(AssertionError, match=sentence):
        generator.check_data(files["config"], files["traffic"])


def test_the_supply_is_closed_rounds_arithmetic_on_this_cells_files():
    # 48 studies x 16 rounds, less a cold round a study, one more on the first,
    # the warm rounds of 16 clients and each first study's turn alone.
    assert [generator.requests_after_setup(CONFIG, TRAFFIC, warm) for warm in (2, 3, 4)] == [671, 655, 639]


# -- the new reader ----------------------------------------------------------------------


def _occupancy(series):
    return {"histograms_window": {"vizier_batch_occupancy": {
        "bounds": [1, 2, 3, 4, 6, 8], "series": {label: ([0] * 7, count, float(total))
                                                  for label, (count, total) in series.items()}}}}


@pytest.mark.parametrize(
    "evidence,want",
    [(_occupancy({"bucket=gp_ucb_pe_sparse/t1024/f20x0/m1/q25": (40, 230)}), 100.0),
     (_occupancy({"bucket=gp_ucb_pe_sparse/t1024/f20x0/m1/q25": (30, 170),
                  "bucket=gp_ucb_pe/t512/f20x0/m1/q25": (10, 40)}), 75.0),
     (_occupancy({"bucket=gp_ucb_pe_sparse/t1024/f20x0/m1/q25,device=d0": (6, 12),
                  "bucket=gp_ucb_pe_sparse/t1024/f20x0/m1/q25,device=d1": (2, 4)}), 100.0),
     (_occupancy({"bucket=gp_ucb_pe/t512/f20x0/m1/q25": (10, 40)}), 0.0),
     (_occupancy({"bucket=gp_ucb_pe_sparse/t1024/f20x0/m1/q25": (0, 0)}), None),
     (_occupancy({"": (40, 230)}), None),
     (_occupancy({}), None),
     ({"histograms_window": {}}, None),
     ({}, None)],
    ids=["every_flush_sparse", "a_mix", "by_device_too", "none_sparse", "no_flush_in_the_window",
         "an_unlabelled_histogram", "an_empty_histogram", "no_histogram", "no_evidence"],
)
def test_sparse_flush_share_reads_the_flushes_by_their_buckets_kind(evidence, want):
    value = run.load_reader("sparse_flush_share").read(evidence)
    assert value == (want if want is None else pytest.approx(want))


# -- the cell in the benchmark -----------------------------------------------------------


def test_the_cell_is_appended_last_with_its_entries_and_nothing_else_changed():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells[-2:] == [LONE, CELL] and len(cells) == 7 and [w["chips"] for w in BENCH["workloads"]].count(4) == 1
    cell = BENCH["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("default20d-sparse-shared", "tenants16-1k", 1)
    configs = [c["name"] for c in BENCH["configs"]]
    assert configs[-2:] == ["default20d-sparse", "default20d-sparse-shared"] and len(configs) == 6
    assert BENCH["configs"][-1]["reduced"] == [] and BENCH["configs"][-1]["source"] != BENCH["configs"][-2]["source"]
    assert [w["name"] for w in BENCH["workloads"] if w["config"] == "default20d-sparse-shared"] == [CELL]
    by_name = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    reported = [m["name"] for m in BENCH["end_to_end"] if contract.reports(m, CELL)]
    assert reported == ["suggest_p50_ms.pool", "suggestions_per_s", "setup_s"]
    for name in reported[:2]:
        assert by_name[name]["workloads"][-1] == CELL and by_name[name]["bound"] == 0.25
    here = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", [])]
    assert here == BENCH["per_layer"][-len(here):] and all(m["workloads"] == [CELL] for m in here)
    names = [m["name"] for m in here]
    assert names[-3:] == ["sparse_suggest_share.pool1k", "nystrom_augments_per_suggest.pool1k", "sparse_flush_share"]
    assert all(n.endswith(".pool1k") for n in names[:-1]) and len(names) == 20
    assert all(m["moves"] in reported[:2] for m in here)
    assert by_name["sparse_flush_share"] == {
        "name": "sparse_flush_share", "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "batch executor", "moves": "suggestions_per_s", "workloads": [CELL]}
    # Each .pool1k entry is its sibling in the exact pool (or in the sparse
    # tier's sequential cell) but for the name, the cell and, for the latter
    # two, the end-to-end metric this cell reports.
    for name in names[:-3]:
        base = name[: -len(".pool1k")]
        sibling = by_name.get(base + ".pool") or by_name[base]
        assert sibling["workloads"] == [POOL], name
        assert {k: v for k, v in by_name[name].items() if k not in ("name", "workloads")} == {
            k: v for k, v in sibling.items() if k not in ("name", "workloads")}, name
    for name in names[-3:-1]:
        sibling = by_name[name[: -len(".pool1k")]]
        assert sibling["workloads"] == [LONE], name
        assert {k: v for k, v in by_name[name].items() if k not in ("name", "workloads", "moves")} == {
            k: v for k, v in sibling.items() if k not in ("name", "workloads", "moves")}, name
    # supply_used_share cannot be entered (test_pace.py holds its entries to three).
    assert not any(n.startswith("supply_used_share") for n in names)


# -- a rehearsal, as a child whose server goes sparse at 32 trials --------------------------

# The child names what it compiles, and when: the duration events of JAX's own
# monitoring carry the function's name.
NAME_COMPILES = SKIP_CHIP + (
    "; import atexit, json, time, jax.monitoring as _m; _seen = []"
    "; _m.register_event_duration_secs_listener(lambda event, seconds, **kw: _seen.append("
    "[kw.get('fun_name'), time.time()]) if event == '/jax/core/compile/backend_compile_duration' else None)"
    "; atexit.register(lambda: print(json.dumps({'phase': 'compiled', 'programs': _seen}), flush=True))"
)
HOST_SIDE_CONVERSIONS = {"jit(convert_element_type)"}  # warm-start parameters a fused flush left as host NumPy


def test_a_rehearsal_meets_in_sparse_flushes_is_correct_and_compiles_no_device_program(cache_dir, monkeypatch):  # noqa: F811
    monkeypatch.setenv("VIZIER_SPARSE_THRESHOLD", str(THRESHOLD))
    monkeypatch.setenv("VIZIER_SPARSE_INDUCING", str(CONFIG["rehearse"]["num_inducing"]))
    done, objs = _run(["--workload", CELL, "--seed", "2147483777", "--seconds", "2", "--rehearse",
                       "--trace", "0"], cache_dir, NAME_COMPILES)
    phase = {o["phase"]: o for o in objs if "phase" in o}
    result = [o for o in objs if "correct" in o and "phase" not in o][-1]
    assert done.returncode == 0, done.stderr[-2000:]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"suggest_p50_ms.pool", "suggestions_per_s", "setup_s"}
    window, layers, setup = phase["window"], phase["layers"], phase["setup"]
    stats = window["stats_window"]
    assert stats["sparse_suggests"] == window["requests"] == result["attempted"]
    assert layers["sparse_suggest_share.pool1k"] == 100.0 and layers["sparse_flush_share"] == 100.0
    assert layers["batched_share.pool1k"] > 0 and stats["batched_suggests"] > 0
    assert stats["batched_suggests"] + stats.get("lone_handbacks", 0) == window["requests"]
    assert layers["batch_occupancy.pool1k"] > 1.0 and layers["flush_host_ms.pool1k"] > 0
    assert layers["nystrom_augments_per_suggest.pool1k"] == pytest.approx(
        stats.get("nystrom_augments", 0) / stats["sparse_suggests"])
    # A flush is one train program, a hand-back one more, and each counted its work.
    assert stats["train_programs"] == stats["batch_flushes"] > 0
    assert layers["train_iterations.pool1k"] == pytest.approx(stats["train_loop_trips"] / stats["train_programs"])
    assert layers["train_evals_per_iteration.pool1k"] >= 1.0
    assert 0.0 <= layers["train_lockstep_idle_share.pool1k"] < 100.0
    for name in ("batch_fallbacks", "batch_slot_errors", "fallbacks"):
        assert result["compared"][f"stats.{name}"] == [0, 0]
    assert result["compared"]["clients_out_of_studies"] == [0, 0]
    # Nothing but the host-side conversions compiles inside the window.
    in_window = [name for name, at in phase["compiled"]["programs"]
                 if setup["wall_time"] < at <= window["wall_time"]]
    assert set(in_window) <= HOST_SIDE_CONVERSIONS, in_window
    assert layers["compiles_in_window.pool1k"] == len(in_window)
    # The supply, from the files alone, is what the run counted.
    config, traffic = run.sized(CONFIG, True), run.sized(TRAFFIC, True)
    assert window["requests_available"] == generator.requests_after_setup(config, traffic, setup["warm_rounds"])
    assert window["requests"] < window["requests_available"]
