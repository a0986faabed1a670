"""Flagship benchmark: GP-UCB suggest() latency at 1000 trials / 20-D.

Prints ONE JSON line:
``{"metric": ..., "value": N, "unit": "ms", "vs_baseline": N}``.

The north-star target (BASELINE.md) is suggest() p50 < 1000 ms at 1000
trials, 20-D, on TPU; ``vs_baseline`` is target_ms / measured_p50 (>1 beats
the target). The measured step is the full device-side suggest compute:
output-warped labels → ARD train (multi-restart L-BFGS) → ensemble
posterior → UCB + trust region → vectorized Eagle sweep (75k evaluations)
→ top-k candidates, excluding the first-compile run (jit caches are
reusable across suggests in a real serving process).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _progress(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _arm_watchdog(budget_s: float) -> None:
    """Hard-exits with a stack dump if the benchmark wedges mid-run.

    A device call that never returns would otherwise hang until the
    caller's timeout with zero diagnostics. The watchdog leaves a traceback
    on stderr and a prompt non-zero exit instead.
    """
    import faulthandler
    import threading

    def fire():
        _progress(f"WATCHDOG: no completion after {budget_s:.0f}s; dumping stacks")
        faulthandler.dump_traceback(file=sys.stderr)
        os._exit(3)

    t = threading.Timer(budget_s, fire)
    t.daemon = True
    t.start()


def _static_flop_budget(
    n_pad: int, dim: int, max_evals: int, pool: int, restarts: int, maxiter: int
) -> dict:
    """Static per-suggest flop budget (docs/guides/tpu_architecture.md).

    Upper-bound model of the measured device-side step (ARD train + one
    acquisition sweep) in raw flops:

    - ARD: ``restarts`` L-BFGS runs x (maxiter grad evals + ~1 line-search
      NLL eval per iteration) x per-eval cost, where one NLL+grad eval is
      ~3x the forward Gram + Cholesky (reverse-mode factor ~2):
      fwd = 2*n_pad^2*dim (Gram) + n_pad^3/3 (Cholesky). At 1024x20 this is
      ~1.2 GFLOP/eval — the guide's "~1 GFLOP" line item. The ftol early
      exit makes this an upper bound.
    - Sweep: (max_evals/pool) eagle iterations x 2*(pool*n_pad*dim kernel
      row + n_pad^2*pool ``linv @ k_star^T`` matmul) — ~160 GFLOP at the
      1000x20-D/75k-eval north-star point, matching the guide.
    """
    fwd = 2.0 * n_pad * n_pad * dim + n_pad**3 / 3.0
    ard = restarts * (2.0 * maxiter) * (3.0 * fwd)
    iters = max(max_evals // pool, 1)
    sweep = iters * 2.0 * (pool * n_pad * dim + n_pad * n_pad * pool)
    return {"ard_flops": ard, "sweep_flops": sweep, "total_flops": ard + sweep}


def _surrogate_env_config() -> dict:
    """The process-wide VIZIER_SPARSE* config, for artifact provenance."""
    from vizier_tpu.surrogates import SurrogateConfig

    return SurrogateConfig.from_env().as_dict()


def _speculative_env_config() -> dict:
    """The process-wide VIZIER_SPECULATIVE* config, for provenance."""
    from vizier_tpu.serving.speculative import SpeculativeConfig

    return SpeculativeConfig.from_env().as_dict()


def _registered_programs() -> list:
    """The registered compute-IR program kinds, for provenance."""
    from vizier_tpu.compute import registry as compute_registry

    return list(compute_registry.kinds())


def _loadgen_env_config() -> dict:
    """The process-wide VIZIER_LOADGEN* scenario config, for provenance."""
    from vizier_tpu.loadgen import ScenarioConfig

    config = ScenarioConfig.from_env()
    return {
        "name": config.name,
        "seed": config.seed,
        "scale": config.scale,
        "num_studies": config.num_studies,
        "total_studies": config.total_studies,
        "target": config.target,
        "events": [e.as_dict() for e in config.events],
    }


def _mesh_env_config() -> dict:
    """The process-wide VIZIER_MESH* config, for artifact provenance."""
    import dataclasses

    from vizier_tpu.parallel.mesh import MeshConfig

    return dataclasses.asdict(MeshConfig.from_env())


def _slo_env_config() -> dict:
    """The process-wide VIZIER_SLO* config, for artifact provenance."""
    from vizier_tpu.observability.slo import SloConfig

    return SloConfig.from_env().as_dict()


def main() -> None:
    _arm_watchdog(float(os.environ.get("VIZIER_BENCH_WATCHDOG_S", 540.0)))

    _progress("init: importing jax")
    import jax

    # No fallback: a run that finds no TPU fails, unless the caller asked
    # for the CPU explicitly (a smoke run of the control flow, not a timing).
    backend = jax.default_backend()
    asked_for_cpu = "cpu" in os.environ.get("JAX_PLATFORMS", "").split(",")
    if backend != "tpu" and not asked_for_cpu:
        _progress(f"no TPU found (backend {backend!r}); JAX_PLATFORMS=cpu not given")
        sys.exit(2)

    # Persistent XLA compilation cache: JAX_COMPILATION_CACHE_DIR, else
    # VIZIER_COMPILE_CACHE_DIR, else <checkout>/.jax_cache
    # (serving.compile_cache); its directory is stamped into the JSON so
    # compile-vs-cached runs are distinguishable after the fact.
    from vizier_tpu.serving import compile_cache

    cache_dir = compile_cache.configure_entry_point()

    from vizier_tpu import types
    from vizier_tpu.designers.gp import acquisitions
    from vizier_tpu.models import gp as gp_lib
    from vizier_tpu.models import kernels
    from vizier_tpu.models import output_warpers
    from vizier_tpu.optimizers import eagle as eagle_lib
    from vizier_tpu.optimizers import lbfgs as lbfgs_lib
    from vizier_tpu.optimizers import vectorized as vectorized_lib
    from vizier_tpu.designers.gp_bandit import _maximize_acquisition, _train_gp

    _progress(f"backend: {backend} ({len(jax.devices())} devices)")

    # SCALE < 1 shrinks the problem for smoke-testing on CPU; the
    # full-size benchmark (SCALE unset) runs on the TPU.
    scale = float(os.environ.get("VIZIER_BENCH_SCALE", "1.0"))

    num_trials, dim = max(int(1000 * scale), 16), 20
    n_pad = 1 << (num_trials - 1).bit_length()  # next power-of-2 bucket
    batch_count = 25  # suggestion batch (reference default batch)
    max_evals = max(int(75_000 * scale), 500)
    repeats = 5 if scale >= 1.0 else 2

    rng = np.random.default_rng(0)
    x = rng.uniform(size=(num_trials, dim)).astype(np.float32)
    y_raw = -np.sum((x - 0.5) ** 2, axis=1) + 0.1 * rng.normal(size=num_trials)
    warped = output_warpers.create_default_warper()(y_raw)

    features = types.ContinuousAndCategorical(
        continuous=types.PaddedArray.from_array(x, (n_pad, dim)),
        categorical=types.PaddedArray.from_array(
            np.zeros((num_trials, 0), np.int32), (n_pad, 0), fill_value=0
        ),
    )
    labels = types.PaddedArray.from_array(
        warped[:, None].astype(np.float32), (n_pad, 1), fill_value=np.nan
    )
    data = gp_lib.GPData.from_model_data(types.ModelData(features, labels))

    model = gp_lib.VizierGaussianProcess(num_continuous=dim, num_categorical=0)
    ard = lbfgs_lib.LbfgsOptimizer(maxiter=50)
    strategy = eagle_lib.VectorizedEagleStrategy(num_continuous=dim, category_sizes=())
    vec_opt = vectorized_lib.VectorizedOptimizer(strategy, max_evaluations=max_evals)

    def one_suggest(seed: int):
        key = jax.random.PRNGKey(seed)
        k_train, k_acq = jax.random.split(key)
        # ARD budget matches the reference's published envelope and the
        # designer's production defaults (4 restarts, maxiter 50, single
        # posterior — BASELINE.md / lbfgs_lib.DEFAULT_RANDOM_RESTARTS).
        states = _train_gp(
            model, ard, data, k_train, lbfgs_lib.DEFAULT_RANDOM_RESTARTS, 1
        )
        predictive = gp_lib.EnsemblePredictive(states)
        best_label = jax.numpy.max(
            jax.numpy.where(data.row_mask, data.labels, -jax.numpy.inf)
        )
        scoring = acquisitions.ScoringFunction(
            predictive=predictive,
            acquisition=acquisitions.UCB(1.8),
            best_label=best_label,
            trust_region=acquisitions.TrustRegion.from_data(data),
        )
        result = _maximize_acquisition(
            vec_opt, scoring, k_acq, batch_count,
            kernels.MixedFeatures(data.continuous[:10], data.categorical[:10]),
        )
        jax.block_until_ready(result)
        return result

    _progress(
        f"compile: first suggest at {num_trials}x{dim}d, {max_evals} evals"
    )
    t0 = time.perf_counter()
    one_suggest(0)  # compile
    _progress(f"compile: done in {time.perf_counter() - t0:.1f}s")
    # Latency distribution via the observability histogram (fixed
    # exponential buckets — the same estimator a Prometheus scrape of the
    # serving process would apply), alongside the exact sample percentile
    # that remains the longitudinal headline number: bucket interpolation
    # error must not masquerade as a perf regression across rounds.
    from vizier_tpu.observability import ObservabilityConfig, MetricsRegistry

    obs_config = ObservabilityConfig.from_env()
    bench_metrics = MetricsRegistry()
    latency_hist = bench_metrics.histogram(
        "bench_suggest_latency_seconds", help="bench.py device-side suggest"
    )
    times = []
    for i in range(1, repeats + 1):
        t0 = time.perf_counter()
        one_suggest(i)
        times.append((time.perf_counter() - t0) * 1000.0)
        latency_hist.observe(times[-1] / 1000.0)
        _progress(f"repeat {i}/{repeats}: {times[-1]:.1f} ms")
    p50 = float(np.percentile(times, 50))

    # End-to-end DEFAULT-algorithm check: the full VizierGPUCBPEBandit
    # designer suggest(25) at the same scale, INCLUDING python-side trial
    # conversion, per-metric output warping, ARD training, and the UCB/PE
    # batch loop. One fresh completed trial is folded in before each repeat
    # so the GP-fit cache cannot serve stale states (matches production:
    # every suggest sees new data). Reported as an extra key on the same
    # JSON line.
    _progress("e2e: full DEFAULT designer suggest() at bench scale")
    from vizier_tpu import pyvizier as vz
    from vizier_tpu.algorithms import core as core_lib
    from vizier_tpu.designers.gp_ucb_pe import VizierGPUCBPEBandit

    problem = vz.ProblemStatement()
    for d in range(dim):
        problem.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
    problem.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    designer = VizierGPUCBPEBandit(
        problem, max_acquisition_evaluations=max_evals
    )
    trials = []
    for i in range(num_trials):
        t = vz.Trial(
            id=i + 1,
            parameters={f"x{d}": float(x[i, d]) for d in range(dim)},
        )
        t.complete(vz.Measurement(metrics={"obj": float(y_raw[i])}))
        trials.append(t)
    designer.update(core_lib.CompletedTrials(trials))
    t0 = time.perf_counter()
    designer.suggest(batch_count)  # compile
    _progress(f"e2e compile: done in {time.perf_counter() - t0:.1f}s")
    e2e_times = []
    e2e_hist = bench_metrics.histogram(
        "bench_e2e_suggest_latency_seconds", help="bench.py e2e designer suggest"
    )
    next_id = num_trials + 1
    for i in range(repeats):
        fresh = vz.Trial(
            id=next_id,
            parameters={
                f"x{d}": float(v)
                for d, v in enumerate(rng.uniform(size=dim))
            },
        )
        fresh.complete(vz.Measurement(metrics={"obj": float(-i)}))
        next_id += 1
        t0 = time.perf_counter()
        designer.update(core_lib.CompletedTrials([fresh]))
        designer.suggest(batch_count)
        e2e_times.append((time.perf_counter() - t0) * 1000.0)
        e2e_hist.observe(e2e_times[-1] / 1000.0)
        _progress(f"e2e repeat {i + 1}/{repeats}: {e2e_times[-1]:.1f} ms")
    e2e_p50 = float(np.percentile(e2e_times, 50))

    def _hist_ms(hist, q):
        value = hist.percentile(q)
        return round(value * 1000.0, 1) if value is not None else None

    target_ms = 1000.0
    if scale == 1.0:
        # Stable id for longitudinal tracking across rounds.
        metric = "gp_ucb_suggest_p50@1000x20d_75k_evals"
    else:
        metric = f"gp_ucb_suggest_p50@{num_trials}x{dim}d_{max_evals}evals_scaled"
    # Static flop budget of the measured device-side step (a count from
    # shapes; utilization needs a peaks table keyed by device_kind, which
    # the benchmark of ROADMAP S1 brings).
    budget = _static_flop_budget(
        n_pad, dim, max_evals, strategy.config.pool_size,
        lbfgs_lib.DEFAULT_RANDOM_RESTARTS, ard.maxiter,
    )
    device = jax.devices()[0]
    line = {
        "metric": metric,
        "value": round(p50, 1),
        "unit": "ms",
        "vs_baseline": round(target_ms / p50, 3),
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": jax.device_count(),
        },
        "static_flop_budget_gflop": round(budget["total_flops"] / 1e9, 1),
        # Histogram-derived percentiles (vizier_tpu.observability buckets):
        # the distribution a Prometheus scrape of the serving process would
        # see, reported next to the exact-sample headline p50 above.
        "hist_p50_ms": _hist_ms(latency_hist, 50),
        "hist_p95_ms": _hist_ms(latency_hist, 95),
        "hist_p99_ms": _hist_ms(latency_hist, 99),
        "e2e_default_designer_suggest_p50_ms": round(e2e_p50, 1),
        "e2e_hist_p50_ms": _hist_ms(e2e_hist, 50),
        "e2e_hist_p95_ms": _hist_ms(e2e_hist, 95),
        "e2e_hist_p99_ms": _hist_ms(e2e_hist, 99),
        "observability": obs_config.as_dict(),
        # JAX persistent compilation cache (serving.compile_cache): repeat
        # bench runs against the same directory pay zero XLA compiles —
        # compare first-call latencies across runs.
        "compilation_cache": {"dir": cache_dir, "active": cache_dir is not None},
        # Round-4 semantics (docs/guides/tpu_architecture.md): the default
        # "first_pick_full" spends one full budget on the exploitation pick
        # plus one split across the rest (~2 sweeps per suggest) — r1-r3
        # e2e numbers spent a full budget on EVERY pick (25 sweeps).
        "e2e_budget_policy": designer.acquisition_budget_policy,
        # Which surrogate path produced these numbers: bench drives the
        # exact-GP device programs directly (and the DEFAULT UCB-PE
        # designer for e2e at a trial count below the sparse threshold),
        # so the measured mode is always "exact"; the env config rides
        # along so artifacts that DO auto-switch are distinguishable
        # (tools/surrogate_ab.py measures both sparse paths).
        "surrogates": {
            "active_mode": "exact",
            **_surrogate_env_config(),
        },
        # Speculative pre-compute (serving.speculative): bench drives the
        # designers directly, so no suggest here is ever served from a
        # parked batch — the env config rides along so artifacts from
        # speculative-enabled processes are distinguishable
        # (tools/speculative_ab.py measures the served-hit path).
        "speculative": {
            "active": False,
            **_speculative_env_config(),
        },
        # Mesh execution plane (parallel.mesh / VIZIER_MESH*): bench
        # drives designers directly (no batch executor), so no flush here
        # is mesh-dispatched — the env config plus the visible device
        # count ride along so artifacts from mesh-enabled processes are
        # distinguishable (tools/batching_ab.py --devices measures it).
        "mesh": {
            "active": False,
            "visible_devices": jax.device_count(),
            **_mesh_env_config(),
        },
        # The compute-IR program set this build registers (vizier_tpu.
        # compute.registry): artifacts from trees with more/fewer batched
        # designer programs are distinguishable after the fact.
        "compute_programs": _registered_programs(),
        # Active SLO configuration (observability.slo / VIZIER_SLO*):
        # bench itself serves no SLO traffic, but an artifact produced
        # under armed SLOs (the sampler thread + exemplar capture) must be
        # distinguishable from one produced bare.
        "slo": _slo_env_config(),
        # The loadgen scenario config (vizier_tpu.loadgen / VIZIER_LOADGEN*):
        # bench drives designers directly, not the traffic engine, but a
        # soak-adjacent artifact stamps which scenario the environment was
        # set up for (tools/soak.py produces SOAK_REPORT.json itself).
        "loadgen": _loadgen_env_config(),
    }
    print(json.dumps(line))


if __name__ == "__main__":
    main()
