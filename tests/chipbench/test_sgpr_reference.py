"""``default20d-sparse.lone25`` from the CPU side: the float64 SGPR reference
(``references/sgpr_ucb_pe.py``) held against the program at a small size —
its predictive against ``SparseGPState.predict``, its k-center against
``select_inducing_kcenter``, its pending conditioning against
``_append_row_sparse`` — the faults ``compare`` must catch planted on a
stand-in batch, the rules of ``generators/closed_rounds_1k.py``, and a
rehearsal of the cell as a child whose server switches to the sparse tier
at a size the CPU can hold (``VIZIER_SPARSE_THRESHOLD`` 32, 8 inducing rows:
the child's environment, not the cell's: on the chip the cell sets nothing).
"""

from __future__ import annotations

import copy
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _path in (ROOT, os.path.dirname(os.path.abspath(__file__))):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import contract_checks as contract  # noqa: E402  (beside this file)
from chipbench import run  # noqa: E402
from chipbench.lib import checks  # noqa: E402
from chipbench.lib import studies  # noqa: E402
from test_harness import SKIP_CHIP, _run, cache_dir  # noqa: E402,F401  (the child-run helpers)

CELL = "default20d-sparse.lone25"
BENCH = contract.load(ROOT, "BENCHMARK.json")
CONFIG = contract.load(ROOT, "chipbench", "configs", "default20d-sparse.json")
TRAFFIC = contract.load(ROOT, "chipbench", "traffic", "lone25-1k.json")
reference = run.load_module("references", "sgpr_ucb_pe")
generator = run.load_module("generators", "closed_rounds_1k")

N, D, M = 64, 4, 16  # the small study: 64 rows of 4 floats, 16 inducing rows
HYPER = {"amplitude": 0.8, "noise_stddev": 0.12, "length_scales": np.asarray([0.6, 0.9, 1.4, 2.0])}


def _small(seed: int = 0):
    """(rows, warped labels) of the small study, float32-representable."""
    rng = np.random.default_rng([seed, N])
    x = rng.uniform(size=(N, D)).astype(np.float32).astype(np.float64)
    y = np.sin(3.0 * x[:, 0]) + x[:, 1:].sum(axis=1) + 0.1 * rng.normal(size=N)
    y = ((y - y.mean()) / y.std()).astype(np.float32).astype(np.float64)
    return x, y


def _program(x, y, m=M, pad=None, spare=0):
    """The program's side of the small study: (model, trained state, the
    model over ``m + spare`` slots, constrained params)."""
    import jax.numpy as jnp

    from vizier_tpu.models import gp as gp_lib
    from vizier_tpu.surrogates import sparse_gp

    n_pad = pad or len(x)
    cont = np.zeros((n_pad, x.shape[1]), np.float32)
    cont[: len(x)] = x
    labels = np.zeros(n_pad, np.float32)
    labels[: len(x)] = y
    data = gp_lib.GPData(
        continuous=jnp.asarray(cont), categorical=jnp.zeros((n_pad, 0), jnp.int32),
        labels=jnp.asarray(labels), row_mask=jnp.asarray(np.arange(n_pad) < len(x)),
        cont_dim_mask=jnp.ones((x.shape[1],), bool), cat_dim_mask=jnp.ones((0,), bool))
    base = gp_lib.VizierGaussianProcess(num_continuous=x.shape[1], num_categorical=0)
    model = sparse_gp.SparseGaussianProcess(base=base, num_inducing=m)
    params = {"amplitude": jnp.asarray(HYPER["amplitude"], jnp.float32),
              "noise_stddev": jnp.asarray(HYPER["noise_stddev"], jnp.float32),
              "continuous_length_scales": jnp.asarray(HYPER["length_scales"], jnp.float32)}
    state = model.precompute_constrained(params, sparse_gp.select_inducing_kcenter(data, m))
    wide = sparse_gp.SparseGaussianProcess(base=base, num_inducing=m + spare)
    return model, state, wide, params


def _features(points):
    import jax.numpy as jnp

    from vizier_tpu.models import kernels

    points = np.atleast_2d(points)
    return kernels.MixedFeatures(jnp.asarray(points, jnp.float32), jnp.zeros((len(points), 0), jnp.int32))


# -- the reference against the program ----------------------------------------------


def test_the_constants_are_the_programs():
    from vizier_tpu.designers import gp_ucb_pe
    from vizier_tpu.surrogates import config as surrogate_config
    from vizier_tpu.surrogates import sparse_gp

    assert reference.NYSTROM_RESIDUAL_FRACTION == gp_ucb_pe._NYSTROM_RESIDUAL_FRACTION
    assert (reference.KMM_JITTER, reference.JITTER) == (sparse_gp._KMM_JITTER, sparse_gp._JITTER)
    shipped = surrogate_config.SurrogateConfig()
    assert generator.SPARSE_SWITCH == shipped.sparse_threshold_trials
    assert CONFIG["num_inducing"] == shipped.num_inducing and shipped.sparse_ucb_pe


def test_the_kcenter_is_the_programs():
    x, y = _small()
    _, state, _, _ = _program(x, y)
    (chosen,) = reference.kcenter(x, y, M)
    assert chosen.tolist() == np.asarray(state.sdata.inducing_indices).tolist()
    assert chosen[0] == int(np.argmax(y)) and len(set(chosen.tolist())) == M


def test_a_tie_for_the_farthest_row_is_walked_both_ways():
    # Rows 1 and 2 lie equally far from the start (row 0, the best label):
    # float32 may take either, so both sets come back, the float64 one first.
    x = np.asarray([[0.5, 0.5], [0.9, 0.5], [0.1, 0.5], [0.5, 0.6]])
    y = np.asarray([1.0, 0.0, 0.1, 0.2])
    sets = reference.kcenter(x, y, 2, max_sets=4)
    assert [s.tolist() for s in sets] == [[0, 1], [0, 2]]
    assert [s.tolist() for s in reference.kcenter(x, y, 2)] == [[0, 1]]  # one set unless asked


def test_the_predictive_is_the_programs_at_float32_tolerance():
    x, y = _small()
    _, state, _, _ = _program(x, y)
    query = np.random.default_rng(7).uniform(size=(48, D)).astype(np.float32).astype(np.float64)
    query = np.concatenate([query, x[:8] + 1e-3])  # beside the trials: near-equal terms
    mean, stddev = (np.asarray(a, np.float64) for a in state.predict(_features(query)))
    z = x[reference.kcenter(x, y, M)[0]]
    want_mean, want_stddev = reference.sgpr_posterior(x, y, z, query, *HYPER.values())
    assert np.max(np.abs(mean - want_mean)) < 2e-5 and np.max(np.abs(stddev - want_stddev)) < 2e-5
    rough_mean, rough_stddev = reference.sgpr_bf16_matmul(x, y, z, query, *HYPER.values())
    assert np.max(np.abs(rough_mean - want_mean)) > 1e-3 and np.max(np.abs(rough_stddev - want_stddev)) > 1e-3


def test_the_bound_is_the_programs_loss_less_its_priors_pull():
    import jax.numpy as jnp

    x, y = _small()
    model, state, _, params = _program(x, y)
    coll = model.param_collection()
    bare = dict(params)  # the loss adds the nugget itself: hand it the noise without
    nugget = 0.05 * HYPER["amplitude"]
    bare["noise_stddev"] = jnp.asarray(np.sqrt(HYPER["noise_stddev"] ** 2 - nugget**2), jnp.float32)
    loss = float(model.neg_log_likelihood(coll.unconstrain(bare), state.sdata))
    pull = float(coll.regularization(coll.constrain(coll.unconstrain(bare))))
    z = x[reference.kcenter(x, y, M)[0]]
    bound = reference.Sgpr(x, y, z, HYPER["amplitude"], HYPER["noise_stddev"] ** 2, HYPER["length_scales"]).neg_bound()
    assert loss - pull == pytest.approx(bound, rel=2e-4)


def test_pending_conditioning_is_append_row_sparse_with_one_pick_that_augments_and_one_that_does_not():
    from vizier_tpu.designers import gp_ucb_pe
    from vizier_tpu.surrogates import sparse_gp

    x, y = _small()
    _, state, wide, params = _program(x, y, pad=80, spare=2)
    (chosen,) = reference.kcenter(x, y, M)
    near = x[chosen[3]] + 1e-3  # beside an inducing row: the trained rows explain it
    far = np.asarray([0.999, 0.001, 0.999, 0.001])  # a corner no row is near
    candidates = np.random.default_rng(11).uniform(size=(40, D))
    points = np.concatenate([[near], [far], candidates])
    batch = reference.SparseBatch(x, y, x[chosen], points, *HYPER.values(), CONFIG["ucb_pe"])
    shares = [batch.residual_share(0), batch.residual_share(1)]
    assert shares[0] < reference.NYSTROM_RESIDUAL_FRACTION - 0.05 < shares[1] - 0.1

    all_data = state.sdata.data.replace(labels=state.sdata.data.labels * 0.0)
    grown = sparse_gp.with_pending_capacity(state.sdata, all_data, 2)
    trained_slots = int(np.sum(np.asarray(grown.inducing_mask)))
    for j, (point, joins) in enumerate(((near, False), (far, True))):
        grown = gp_ucb_pe._append_row_sparse(grown, _features(point), state)
        batch.add_pending(j)  # the published rule, from the reference's own residual
        assert int(np.sum(np.asarray(grown.inducing_mask))) - trained_slots == len(batch.augmented) == int(joins)
        assert int(np.sum(np.asarray(grown.data.row_mask))) == N + j + 1
        everything = wide.precompute_constrained(params, grown)
        stddev = np.asarray(everything.predict(_features(points))[1], np.float64)
        assert np.max(np.abs(stddev - batch.std_all())) < 2e-5
    # The pick that joined deflates its neighbourhood to the noise floor; as a
    # data row alone (the other way) the inducing rows would have swallowed it.
    alone = copy.copy(reference.SparseBatch(x, y, x[chosen], points, *HYPER.values(), CONFIG["ucb_pe"]))
    alone.add_pending(1, augment=False)
    assert batch.std_all()[1] < 0.5 * alone.std_all()[1]


# -- what compare must catch, on a stand-in batch ------------------------------------

COUNT = 6
SEED, TRIALS = 1, 600  # ONE seeded study, just past the switch, and ...
# ... the program's own cold fit of it (PR 41, CPU, `_train_sparse_gp` as
# shipped): made-up hyperparameters of the right order explain LESS than
# nothing here (length scales 4-16 evenly spread gain -0.09 nats a trial).
BIG = {"amplitude": 0.8853, "noise_stddev": 0.1068, "length_scales": np.asarray([
    5.2, 5.01, 5.0, 13.14, 4.67, 12.27, 4.3, 4.67, 13.01, 6.22, 5.38, 4.97, 5.16, 6.03, 4.88, 4.41, 15.19,
    5.49, 5.26, 4.65])}


def _study(pool_seed: int, hyper=None, cast=None):
    """The client's record of the 20-D study and a sound program's answers
    to its last suggest: picked greedily from the reference's own
    candidates (drawn from ``pool_seed``) and stamped with its float64
    predictive (``cast``: with the matmul operands rounded)."""
    hyper = hyper or BIG
    n = TRIALS
    rng = np.random.default_rng([SEED, n])
    _, x, labels = studies.seeded_trials(CONFIG, rng, n)
    y = reference.warp_labels(labels, CONFIG["goal"])
    pool = reference._exact.candidates(
        x, y, x[:1], np.random.default_rng([pool_seed, 7]), CONFIG["check_candidates"])
    z = x[reference.kcenter(x, y, CONFIG["num_inducing"])[0]]
    batch = reference.SparseBatch(x, y, z, pool, *hyper.values(), CONFIG["ucb_pe"],
                                  **({"cast": cast} if cast else {}))
    picks, meta = [], {k: [] for k in ("mean", "stddev", "stddev_from_all", "use_ucb", "acquisition")}
    for j in range(COUNT):
        use_ucb = j == 0
        score = batch.scores(use_ucb, [picks[0]] if j else [])
        best = int(np.argmax(score))
        for key, value in (("mean", batch.mean[best]), ("stddev", batch.std[best]),
                           ("stddev_from_all", batch.std_all()[best]), ("use_ucb", float(use_ucb)),
                           ("acquisition", score[best])):
            meta[key].append(value)
        picks.append(best)
        batch.add_pending(best)
    record = {"rows": x, "labels": labels, "picks": pool[picks],
              "meta": {k: np.asarray(v) for k, v in meta.items()}}
    trained = {"completed": n, "x": x.astype(np.float32).astype(np.float64),
               "y": y.astype(np.float32).astype(np.float64), "surrogate_mode": "sparse", **hyper}
    return record, trained


# (The stand-in picks the best of a pool of its own, not by a 75k sweep:
# the shortfall's limit is a stand-in's here, as in test_control.py.)
LIMITS = {**CONFIG["limits"], "first_pick_shortfall_label_std": 1.0}


def _broken(record, trained):
    result = reference.compare(record, trained, CONFIG, np.random.default_rng(3))
    assert set(result["numbers"]) == set(CONFIG["limits"])  # the names the cell's limits use, all of them
    return sorted(n for n, v in result["numbers"].items() if not checks.judge(v, LIMITS[n])), result


def test_a_sound_batch_keeps_every_limit():
    broken, result = _broken(*_study(1))
    assert broken == []
    assert result["seen"]["inducing_sets_tried"] >= 1 and result["seen"]["walks"] >= 1
    assert 0 <= result["seen"]["nystrom_augments"] <= COUNT


def test_a_planted_noise_only_fit_fails_the_bound_gain():
    record, trained = _study(2)
    y = reference.warp_labels(record["labels"], CONFIG["goal"])
    nothing = {"amplitude": 0.011, "noise_stddev": float(np.std(y)), "length_scales": np.full(20, 0.3)}
    record, trained = _study(2, hyper=nothing)  # its own answers, stamped soundly
    broken, result = _broken(record, trained)
    assert broken == ["train_bound_gain_per_trial"]
    assert abs(result["numbers"]["train_bound_gain_per_trial"]) < 0.01  # it gains ~0 over explaining nothing
    assert result["seen"]["bound_gain_over_prior_centre_per_trial"] > 1000.0  # ... and thousands over the centre
    sound = _broken(*_study(2))[1]["numbers"]["train_bound_gain_per_trial"]
    assert sound > 1.5 * CONFIG["limits"]["train_bound_gain_per_trial"]["min"]


def test_a_bfloat16_operand_posterior_fails_a_limit():
    record, trained = _study(3, cast=reference._exact._bf16)
    broken, _ = _broken(record, trained)
    assert set(broken) & {"pick_mean_err_label_std", "pick_stddev_err_label_std",
                          "pick_stddev_all_err_label_std", "pick_acquisition_err_label_std"}


def test_the_answers_of_the_exact_posterior_fail_the_sparse_reference():
    # A server that never switched answers from all 600 rows, not from 128.
    record, trained = _study(4)
    exact = reference._exact._Batch(
        record["rows"], reference.warp_labels(record["labels"], CONFIG["goal"]), record["picks"],
        *BIG.values(), CONFIG["ucb_pe"])
    record["meta"]["stddev"] = exact.std
    assert "pick_stddev_err_label_std" in _broken(record, trained)[0]
    trained["surrogate_mode"] = "exact"
    assert "surrogate_mismatch" in _broken(record, trained)[0]


# -- the generator's rules -----------------------------------------------------------


def test_the_cells_files_keep_the_generators_rules_and_nothing_is_cut():
    generator.check_data(CONFIG, TRAFFIC)
    assert generator.study_count(TRAFFIC) == 20 and CONFIG["reduced"] == {}
    rounds = studies.rounds_in_bucket(TRAFFIC["start_trials"], TRAFFIC["suggest_count"])
    assert rounds == 16 and TRAFFIC["start_trials"] + 25 * (rounds - 1) == CONFIG["completed_trials"] == 975
    assert studies.bucket(975, 25) == (1024, 1024) and studies.bucket(1000, 25) == (1024, 2048)
    assert "sparse_suggests" not in CONFIG["zero_counters"]
    base = contract.load(ROOT, "chipbench", "configs", "default20d.json")
    for key in ("algorithm", "num_float_parameters", "goal", "objective", "ucb_pe", "check_candidates",
                "acquisition_evaluations", "ard_restarts", "ard_maxiter", "hyperparameter_prior_centre",
                "control_acquisition_evaluations", "check_studies"):
        assert CONFIG[key] == base[key], key  # default20d's shapes, every one
    assert sorted(set(base["zero_counters"]) - set(CONFIG["zero_counters"])) == ["sparse_suggests"]
    # The worker loop is closed_rounds' own: imported, no second copy.
    assert generator.Generator.__module__.endswith("generators.closed_rounds")


@pytest.mark.parametrize(
    "where,key,value,sentence",
    [("traffic", "start_trials", 500, "served by the exact programs until it reaches the sparse switch at 512"),
     ("config", "completed_trials", 1000, "before it would leave the 1024 bucket"),
     ("config", "trial_padding_bucket", 512, "not the configuration's trial_padding_bucket 512"),
     ("config", "surrogate", "exact", "this generator's studies are sparse"),
     ("traffic", "max_rounds_per_study", 3, "makes 3 rounds before it is retired")],
    ids=["starts_exact", "leaves_the_bucket", "another_bucket", "exact_surrogate", "too_few_rounds"],
)
def test_a_planted_breach_of_a_rule_of_closed_rounds_1k_fails_with_its_sentence(where, key, value, sentence):
    files = {"config": copy.deepcopy(CONFIG), "traffic": copy.deepcopy(TRAFFIC)}
    files[where][key] = value
    with pytest.raises(AssertionError, match=sentence):
        generator.check_data(files["config"], files["traffic"])


def test_the_supply_is_closed_rounds_arithmetic_on_this_cells_files():
    # 20 studies x 16 rounds, less a cold round a study, one more on the
    # first, and the warm rounds: what the traffic file's pace is held against.
    assert [generator.requests_after_setup(CONFIG, TRAFFIC, warm) for warm in (1, 2, 3)] == [298, 297, 296]


# -- the new readers ------------------------------------------------------------------


@pytest.mark.parametrize(
    "evidence,share,augments",
    [
        ({"stats_window": {"sparse_suggests": 120, "nystrom_augments": 300}, "attempted": 120}, 100.0, 2.5),
        ({"stats_window": {"sparse_suggests": 30, "nystrom_augments": 0}, "attempted": 120}, 25.0, 0.0),
        ({"stats_window": {"sparse_suggests": 0, "nystrom_augments": 0}, "attempted": 120}, 0.0, None),
        ({"stats_window": {"sparse_suggests": 120}, "attempted": 120}, 100.0, None),  # a parent: no such counter
        ({"stats_window": {"sparse_suggests": 0}, "attempted": 0}, None, None),
        ({}, None, None),
    ],
    ids=["every_suggest", "a_mix", "none_sparse", "a_parent", "no_request", "no_evidence"],
)
def test_the_two_readers_read_planted_counters(evidence, share, augments):
    for name, want in (("sparse_suggest_share", share), ("nystrom_augments_per_suggest", augments)):
        value = run.load_reader(name).read(evidence)
        assert value == (want if want is None else pytest.approx(want)), name


def test_the_cell_is_the_benchmarks_last_and_its_entries_are_appended():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells[-1] == CELL and len(cells) == 6 and [w["chips"] for w in BENCH["workloads"]].count(4) == 1
    assert BENCH["configs"][-1]["name"] == "default20d-sparse" and BENCH["configs"][-1]["reduced"] == []
    p50 = next(m for m in BENCH["end_to_end"] if m["name"] == "suggest_p50_ms")
    assert p50["workloads"][-1] == CELL and p50["bound"] == 0.1
    here = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", [])]
    assert here == BENCH["per_layer"][-len(here):] and all(m["workloads"] == [CELL] for m in here)
    assert all(m["moves"] == "suggest_p50_ms" for m in here)
    names = [m["name"] for m in here]
    assert names[-2:] == ["sparse_suggest_share", "nystrom_augments_per_suggest"]
    assert all(n.endswith(".sparse") for n in names[:-2]) and len(names) == 17
    # Each .sparse entry is its .lone sibling but for the name and the cell.
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in names[:-2]:
        lone = by_name[name.replace(".sparse", ".lone")]
        assert {k: v for k, v in by_name[name].items() if k not in ("name", "workloads")} == {
            k: v for k, v in lone.items() if k not in ("name", "workloads")}, name


# -- a rehearsal, as a child whose server goes sparse at 32 trials ----------------------


def test_a_rehearsal_is_served_sparse_is_correct_and_counts_its_supply(cache_dir, monkeypatch):  # noqa: F811
    monkeypatch.setenv("VIZIER_SPARSE_THRESHOLD", "32")
    monkeypatch.setenv("VIZIER_SPARSE_INDUCING", str(CONFIG["rehearse"]["num_inducing"]))
    done, objs = _run(["--workload", CELL, "--seed", "2147483777", "--seconds", "3", "--rehearse",
                       "--trace", "0"], cache_dir, SKIP_CHIP)
    result = objs[-1]
    assert done.returncode == 0, done.stderr[-2000:]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"suggest_p50_ms", "setup_s"}
    phase = {o["phase"]: o for o in objs if "phase" in o}
    window, layers, setup = phase["window"], phase["layers"], phase["setup"]
    stats = window["stats_window"]
    assert stats["sparse_suggests"] == window["requests"] == result["attempted"]
    assert layers["sparse_suggest_share"] == 100.0 and layers["compiles_in_window.sparse"] == 0
    assert layers["nystrom_augments_per_suggest"] == pytest.approx(
        stats.get("nystrom_augments", 0) / stats["sparse_suggests"])
    # The sparse trainer counts its work as the exact one does, and the two
    # phases of the device wait carry the sparse programs' time.
    assert stats["train_programs"] == stats.get("warm_trains", 0) + stats.get("cold_trains", 0) > 0
    assert layers["train_iterations.sparse"] == pytest.approx(stats["train_loop_trips"] / stats["train_programs"])
    assert layers["train_evals_per_iteration.sparse"] >= 1.0
    both = layers["train_wait_ms.sparse"] + layers["acquire_wait_ms.sparse"]
    assert both == pytest.approx(layers["device_wait_ms.sparse"], rel=1e-6)
    assert result["compared"]["window.batched_share_pct"] == [0.0, {"max": 0}]
    assert all(s["nystrom_augments"] >= 0 for s in phase["fitted"]["studies"])
    # The supply, from the files alone, is what the run counted.
    config, traffic = run.sized(CONFIG, True), run.sized(TRAFFIC, True)
    assert window["requests_available"] == generator.requests_after_setup(config, traffic, setup["warm_rounds"])


def test_without_the_switch_the_rehearsal_is_exact_and_not_correct(cache_dir):  # noqa: F811
    # At 36 trials the shipped default serves the exact posterior: the
    # cell's guard reads 0 and the comparison names the surrogate.
    done, objs = _run(["--workload", CELL, "--seed", "2147483777", "--seconds", "2", "--rehearse",
                       "--trace", "0"], cache_dir, SKIP_CHIP)
    failing = [c["name"] for o in objs if o.get("phase") == "correct" for c in o["compared"] if not c["ok"]]
    assert done.returncode != 0 and any(name.endswith("surrogate_mismatch") for name in failing)
    layers = [o for o in objs if o.get("phase") == "layers"][0]
    assert layers["sparse_suggest_share"] == 0.0
