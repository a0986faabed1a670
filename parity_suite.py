"""Side-by-side parity measurement: this framework vs the reference.

BASELINE.md: the reference publishes no benchmark numbers, so it must be run
(CPU) as its own baseline. This suite runs reference designers and this
repo's designers against the SAME experimenter objects (reference trials are
adapted through a thin parameter-dict bridge, so both sides optimize the
byte-identical objective with the same seeds and budgets), builds
convergence curves, and scores statistical parity with the comparator
machinery (win-rate / log-efficiency bands).

Scope note (documented limitation, not a choice): the reference's GP stack
imports equinox + tensorflow_probability, which are absent from this image
and may not be installed. Its runnable algorithms — random, quasi-random,
eagle (firefly), NSGA2 — are measured; eagle-vs-eagle and random-vs-random
are direct same-algorithm parity checks, and this repo's GP designers are
additionally gated on dominating the reference's runnable baselines.

Usage:
  bash tools/build_reference_copy.sh        # once per machine
  python parity_suite.py [--scale 1.0] [--out regret_report_r5.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REF_PATH = "/tmp/refvizier"


def _progress(msg: str) -> None:
    print(f"[parity] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Reference-designer adapter: drives a reference designer with OUR
# experimenter. Parameters cross the bridge as plain python dicts.
# ---------------------------------------------------------------------------


def _to_ref_problem(problem):
    """Builds a reference ProblemStatement mirroring ours."""
    from vizier import pyvizier as ref_vz

    from vizier_tpu.pyvizier import parameter_config as pc

    ref = ref_vz.ProblemStatement()
    root = ref.search_space.root
    for cfg in problem.search_space.parameters:
        if cfg.type == pc.ParameterType.DOUBLE:
            lo, hi = cfg.bounds
            root.add_float_param(cfg.name, lo, hi)
        elif cfg.type == pc.ParameterType.INTEGER:
            lo, hi = cfg.bounds
            root.add_int_param(cfg.name, int(lo), int(hi))
        elif cfg.type == pc.ParameterType.DISCRETE:
            root.add_discrete_param(cfg.name, list(cfg.feasible_values))
        else:
            root.add_categorical_param(
                cfg.name, [str(v) for v in cfg.feasible_values]
            )
    for m in problem.metric_information:
        goal = (
            ref_vz.ObjectiveMetricGoal.MAXIMIZE
            if m.goal.is_maximize
            else ref_vz.ObjectiveMetricGoal.MINIMIZE
        )
        ref.metric_information.append(
            ref_vz.MetricInformation(name=m.name, goal=goal)
        )
    return ref


def run_reference_designer(designer_factory, experimenter, num_trials, batch):
    """suggest→evaluate→update loop for a REFERENCE designer over OUR
    experimenter; returns our completed Trial objects."""
    from vizier import algorithms as ref_vza
    from vizier import pyvizier as ref_vz

    from vizier_tpu.pyvizier import trial as trial_lib

    problem = experimenter.problem_statement()
    ref_problem = _to_ref_problem(problem)
    designer = designer_factory(ref_problem)
    ours: list = []
    tid = 0
    while tid < num_trials:
        count = min(batch, num_trials - tid)
        suggestions = designer.suggest(count)
        if not suggestions:
            break
        batch_ours, batch_ref = [], []
        for s in suggestions:
            tid += 1
            params = {name: v.value for name, v in s.parameters.items()}
            batch_ours.append(trial_lib.Trial(id=tid, parameters=params))
        experimenter.evaluate(batch_ours)
        for s, t in zip(suggestions, batch_ours):
            rt = s.to_trial(t.id)
            if t.final_measurement is None:
                rt.complete(
                    ref_vz.Measurement(),
                    infeasibility_reason=t.infeasibility_reason or "infeasible",
                )
            else:
                rt.complete(
                    ref_vz.Measurement(
                        metrics={
                            k: m.value
                            for k, m in t.final_measurement.metrics.items()
                        }
                    )
                )
            batch_ref.append(rt)
        designer.update(
            ref_vza.CompletedTrials(batch_ref), ref_vza.ActiveTrials([])
        )
        ours.extend(batch_ours)
    return ours


def run_our_designer(designer_factory, experimenter, num_trials, batch):
    from vizier_tpu.algorithms import core as core_lib

    problem = experimenter.problem_statement()
    designer = designer_factory(problem)
    ours: list = []
    tid = 0
    while tid < num_trials:
        count = min(batch, num_trials - tid)
        batch_trials = []
        for s in designer.suggest(count):
            tid += 1
            batch_trials.append(s.to_trial(tid))
        experimenter.evaluate(batch_trials)
        designer.update(core_lib.CompletedTrials(batch_trials))
        ours.extend(batch_trials)
    return ours


# ---------------------------------------------------------------------------
# Suite.
# ---------------------------------------------------------------------------


def rank_sum_p(a, b) -> float:
    """Two-sided Mann-Whitney p (normal approximation): H0 = same dist."""
    from scipy import stats

    a, b = np.asarray(a, float), np.asarray(b, float)
    n, m = len(a), len(b)
    if n == 0 or m == 0:
        return 1.0
    ranks = stats.rankdata(np.concatenate([a, b]))
    u = ranks[:n].sum() - n * (n + 1) / 2.0
    mu = n * m / 2.0
    sigma = np.sqrt(n * m * (n + m + 1) / 12.0)
    z = (u - mu) / max(sigma, 1e-9)
    return float(2.0 * (1.0 - stats.norm.cdf(abs(z))))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--scale", type=float, default=1.0)
    parser.add_argument("--out", default="regret_report_r3.json")
    parser.add_argument("--platform", default="cpu", choices=["cpu", "tpu"])
    parser.add_argument(
        "--only",
        default=None,
        choices=(
            "branin_2d",
            "mixed_space_default",
            "bbob20d_sphere",
            "bbob20d_rastrigin",
            "zdt1_hypervolume",
            "nasbench201_synthetic",
        ),
        help="Run a single config by report name (e.g. nasbench201_synthetic).",
    )
    args = parser.parse_args()
    s = args.scale

    if not os.path.isdir(REF_PATH):
        raise SystemExit(
            f"{REF_PATH} missing — run tools/build_reference_copy.sh first."
        )
    sys.path.insert(0, REF_PATH)

    if args.platform:  # the variable JAX reads itself, before jax loads
        os.environ["JAX_PLATFORMS"] = args.platform

    from vizier_tpu import benchmarks
    from vizier_tpu import pyvizier as vz
    from vizier_tpu.benchmarks.analyzers import convergence_curve as cc
    from vizier_tpu.benchmarks.analyzers import state_analyzer as sa
    from vizier_tpu.benchmarks.experimenters.synthetic import bbob, multiobjective
    from vizier_tpu.designers import RandomDesigner
    from vizier_tpu.designers.eagle_strategy import EagleStrategyDesigner
    from vizier_tpu.designers.evolution import NSGA2Designer
    from vizier_tpu.designers.gp_bandit import VizierGPBandit
    from vizier_tpu.designers.gp_ucb_pe import VizierGPUCBPEBandit

    # Reference designers (imported from the patched /tmp copy).
    from vizier._src.algorithms.designers import quasi_random as ref_qr
    from vizier._src.algorithms.designers import random as ref_random
    from vizier._src.algorithms.designers.eagle_strategy import (
        eagle_strategy as ref_eagle,
    )
    from vizier._src.algorithms.evolution import nsga2 as ref_nsga2

    report: dict = {
        "note": (
            "Reference GP designers are unmeasurable in this image "
            "(equinox/tensorflow_probability absent; installation banned). "
            "Parity is asserted same-algorithm (random↔random, eagle↔eagle, "
            "nsga2↔nsga2) and by this repo's GP designers dominating the "
            "reference's runnable baselines on identical objectives/seeds."
        ),
        "scale": s,
        "configs": {},
    }
    t_start = time.time()

    def curve_for(trials, metric):
        return cc.ConvergenceCurveConverter(metric, flip_signs_for_min=True).convert(
            trials
        )

    def algorithms_for(config_name):
        """name -> (side, factory(problem, seed)) at this config's budgets."""
        gp_evals = max(int(25_000 * s), 1500)

        def my_gp(p, seed):
            return VizierGPBandit(
                p,
                rng_seed=seed,
                max_acquisition_evaluations=gp_evals,
                num_seed_trials=5,
            )

        def my_ucbpe(p, seed):
            return VizierGPUCBPEBandit(
                p,
                rng_seed=seed,
                max_acquisition_evaluations=gp_evals,
                num_seed_trials=5,
            )

        return {
            "ref-random": ("ref", lambda p, seed: ref_random.RandomDesigner(p.search_space, seed=seed)),
            "ref-quasirandom": ("ref", lambda p, seed: ref_qr.QuasiRandomDesigner(p.search_space, seed=seed)),
            "ref-eagle": ("ref", lambda p, seed: ref_eagle.EagleStrategyDesigner(p, seed=seed)),
            "my-random": ("mine", lambda p, seed: RandomDesigner(p.search_space, seed=seed)),
            "my-eagle": ("mine", lambda p, seed: EagleStrategyDesigner(p, seed=seed)),
            "my-gp-ucb": ("mine", my_gp),
            "my-ucbpe-default": ("mine", my_ucbpe),
        }

    def run_config(name, experimenter, num_trials, batch, seeds, skip=()):
        if args.only and name != args.only:
            return
        # ``experimenter`` may be a factory ``seed -> Experimenter`` so
        # configs can randomize per seed (e.g. shifted BBOB optima).
        if isinstance(experimenter, benchmarks.Experimenter):
            exp_of = lambda _seed, _e=experimenter: _e  # noqa: E731
        else:
            exp_of = experimenter
        metric = next(
            m
            for m in exp_of(0).problem_statement().metric_information
            if not m.is_safety_metric
        )
        records = []
        finals: dict = {}
        cheap = {"ref-random", "ref-quasirandom", "my-random", "ref-eagle", "my-eagle"}
        for algo_name, (side, factory) in algorithms_for(name).items():
            if algo_name in skip:
                continue
            # Cheap algorithms get extra seeds: the parity rank-sum tests
            # need sample size, and these runs cost almost nothing.
            algo_seeds = (
                tuple(seeds) + tuple(100 + i for i in range(len(seeds), 6))
                if algo_name in cheap
                else seeds
            )
            curves = []
            for seed in algo_seeds:
                _progress(f"{name}: {algo_name} seed={seed}")
                np.random.seed(seed)  # some reference paths use np global rng
                runner = run_reference_designer if side == "ref" else run_our_designer
                trials = runner(
                    lambda p, _seed=seed: factory(p, _seed),
                    exp_of(seed),
                    num_trials,
                    batch,
                )
                curves.append(curve_for(trials, metric))
            combined = cc.ConvergenceCurve.align_xs(curves)
            finals[algo_name] = [float(c.ys[0, -1]) for c in curves]
            records.append(
                sa.BenchmarkRecord(
                    algorithm=algo_name,
                    experimenter_metadata={"config": name},
                    plot_elements={"objective": sa.PlotElement(combined)},
                )
            )
        sa.BenchmarkRecordAnalyzer.add_comparison_metrics(records, "ref-random")
        rows = sa.BenchmarkRecordAnalyzer.summarize(records)

        # Parity verdicts.
        def row(algo):
            return next((r for r in rows if r["algorithm"] == algo), None)

        verdicts = {}
        ref_rand, my_rand = row("ref-random"), row("my-random")
        if ref_rand and my_rand:
            # Same algorithm, same objective: per-seed finals must be
            # statistically indistinguishable (two-sided rank-sum).
            p = rank_sum_p(finals["my-random"], finals["ref-random"])
            verdicts["random_parity"] = {
                "rank_sum_p": p,
                "finals_mine": finals["my-random"],
                "finals_ref": finals["ref-random"],
                "pass": bool(p > 0.05),
            }
        ref_e, my_e = row("ref-eagle"), row("my-eagle")
        if ref_e and my_e:
            gap = my_e["objective_final_median"] - ref_e["objective_final_median"]
            spread = abs(
                ref_rand["objective_final_median"] - ref_e["objective_final_median"]
            ) if ref_rand else 1.0
            p = rank_sum_p(finals["my-eagle"], finals["ref-eagle"])
            # Parity: statistically indistinguishable, or mine ahead, or the
            # deficit within half the ref's improvement-over-random (with an
            # absolute floor for configs where eagle ≈ random and the spread
            # is pure noise).
            tolerance = max(
                0.5 * spread, 0.05 * abs(ref_e["objective_final_median"]), 1e-3
            )
            verdicts["eagle_parity"] = {
                "final_median_gap": gap,
                "rank_sum_p": p,
                "tolerance": tolerance,
                "pass": bool(p > 0.05 or gap >= -tolerance),
            }
        for gp_name in ("my-gp-ucb", "my-ucbpe-default"):
            r = row(gp_name)
            if r and ref_rand:
                verdicts[f"{gp_name}_beats_random"] = {
                    "log_efficiency": r.get("log_efficiency_vs_ref-random"),
                    "final_median_vs_random": r["objective_final_median"]
                    - ref_rand["objective_final_median"],
                    "pass": bool(
                        r["objective_final_median"]
                        >= ref_rand["objective_final_median"]
                    ),
                }
        report["configs"][name] = {"rows": rows, "verdicts": verdicts}
        _progress(f"{name}: done ({time.time() - t_start:.0f}s elapsed)")

    # -- Config 1: Branin 2-D (classic GP benchmark) ------------------------
    run_config(
        "branin_2d",
        benchmarks.NumpyExperimenter(
            bbob.Branin, benchmarks.bbob_problem(2, metric_name="bbob_eval")
        ),
        num_trials=max(int(60 * s), 16),
        batch=2,
        seeds=(1, 2, 3),
    )

    # -- Config 2: mixed int/float/categorical (README space), DEFAULT -----
    def mixed_experimenter():
        problem = vz.ProblemStatement()
        root = problem.search_space.root
        root.add_float_param("lr", 1e-4, 1e-1, scale_type=vz.ScaleType.LOG)
        root.add_int_param("layers", 1, 8)
        root.add_categorical_param("opt", ["adam", "sgd", "rmsprop"])
        problem.metric_information.append(
            vz.MetricInformation(name="acc", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
        )
        from vizier_tpu.pyvizier import trial as trial_lib

        class MixedExp(benchmarks.Experimenter):
            def evaluate(self, suggestions):
                for t in suggestions:
                    lr = t.parameters.get_value("lr")
                    layers = t.parameters.get_value("layers")
                    opt = str(t.parameters.get_value("opt"))
                    acc = (
                        1.0
                        - (np.log10(lr) + 2.0) ** 2 * 0.2
                        - 0.03 * abs(int(layers) - 4)
                        + (0.05 if opt == "adam" else 0.0)
                    )
                    t.complete(trial_lib.Measurement(metrics={"acc": acc}))

            def problem_statement(self):
                return problem

        return MixedExp()

    run_config(
        "mixed_space_default",
        mixed_experimenter(),
        num_trials=max(int(45 * s), 15),
        batch=3,
        seeds=(1, 2),
    )

    # -- Config 3: 20-D BBOB (Sphere, Rastrigin) — eagle's home turf -------
    # Shifted per seed (matching the reference factory's shift-application,
    # ``experimenter_factory.py:151-153``) so the optimum never coincides
    # with the search-box center that GP designers default-seed: an
    # unshifted run measures seeding, not optimization. ONE shared instance
    # definition pins this report, the CI gate, and the budget A/B together.
    from vizier_tpu.benchmarks.experimenters import experimenter_factory

    for fn_name in ("Sphere", "Rastrigin"):

        def shifted_bbob(seed, _fn=fn_name):
            return experimenter_factory.shifted_bbob_instance(_fn, seed)

        run_config(
            f"bbob20d_{fn_name.lower()}",
            shifted_bbob,
            num_trials=max(int(150 * s), 30),
            batch=10,
            seeds=(1, 2),
            skip=("my-gp-ucb", "ref-quasirandom"),  # UCB-PE covers the GP side
        )

    # -- Config 4: multi-objective ZDT1 hypervolume ------------------------
    def run_mo():
        if args.only and args.only != "zdt1_hypervolume":
            return
        exp = multiobjective.MultiObjectiveExperimenter.zdt("zdt1", dimension=6)
        metrics = list(exp.problem_statement().metric_information)
        ref_point = np.array([-1.1, -6.0], dtype=np.float32)
        n = max(int(80 * s), 20)
        results = {}

        def hv(trials):
            curve = cc.HypervolumeCurveConverter(
                metrics, reference_point=ref_point
            ).convert(trials)
            return float(curve.ys[0, -1])

        mo_algos = {
            "ref-nsga2": (
                "ref",
                lambda p, seed: ref_nsga2.NSGA2Designer(p, population_size=20, seed=seed),
            ),
            "ref-random": (
                "ref",
                lambda p, seed: ref_random.RandomDesigner(p.search_space, seed=seed),
            ),
            "my-nsga2": (
                "mine",
                lambda p, seed: NSGA2Designer(p, population_size=20, seed=seed),
            ),
            "my-ucbpe-default": (
                "mine",
                lambda p, seed: VizierGPUCBPEBandit(
                    p,
                    rng_seed=seed,
                    max_acquisition_evaluations=max(int(10_000 * s), 1000),
                    num_seed_trials=5,
                ),
            ),
        }
        for algo_name, (side, factory) in mo_algos.items():
            hvs = []
            for seed in (1, 2):
                _progress(f"zdt1: {algo_name} seed={seed}")
                runner = (
                    run_reference_designer if side == "ref" else run_our_designer
                )
                trials = runner(
                    lambda p, _seed=seed: factory(p, _seed), exp, n, 5
                )
                hvs.append(hv(trials))
            results[algo_name] = float(np.median(hvs))
        verdicts = {
            "nsga2_parity": {
                "ref": results["ref-nsga2"],
                "mine": results["my-nsga2"],
                "pass": bool(
                    results["my-nsga2"]
                    >= results["ref-nsga2"]
                    - 0.5 * (results["ref-nsga2"] - results["ref-random"])
                ),
            },
            "ucbpe_beats_random": {
                "pass": bool(results["my-ucbpe-default"] >= results["ref-random"])
            },
        }
        report["configs"]["zdt1_hypervolume"] = {
            "rows": results,
            "verdicts": verdicts,
        }
        _progress("zdt1: done")

    run_mo()

    # -- Config 5: NASBench-201 cell space (BASELINE.md's NAS config) ------
    # The real dataset isn't bundled in this image; the handler's synthetic
    # table preserves the pipeline (6-op categorical cells -> snap-to-table
    # accuracy) so the full tabular NAS benchmark path is measured e2e.
    # (BASELINE names this config "via PyGlove"; pyglove itself is absent,
    # so the same space runs through the designer path instead.)
    from vizier_tpu.benchmarks.experimenters import surrogates

    run_config(
        "nasbench201_synthetic",
        surrogates.NASBench201Handler().make_synthetic_experimenter(seed=0),
        num_trials=max(int(80 * s), 20),
        batch=5,
        seeds=(1, 2),
        skip=("my-gp-ucb", "ref-quasirandom"),  # UCB-PE covers the GP side
    )

    report["elapsed_secs"] = round(time.time() - t_start, 1)
    report["all_pass"] = all(
        v.get("pass", True)
        for cfg in report["configs"].values()
        for v in cfg["verdicts"].values()
    )
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))


if __name__ == "__main__":
    main()
