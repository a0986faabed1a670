#!/usr/bin/env python3
"""Chip smoke: the served suggest path, once, on the TPU.

One process, which holds the chip from its first JAX call to exit, drives
the service DEFAULT (GP-UCB-PE) through the entry points a user calls —
``clients.Study.from_study_config`` with no endpoint (in-process
VizierService → Pythia → serving runtime → designer cache → batch executor
→ designer), and once over real gRPC through ``DefaultVizierServer`` — at
the width its users run: 20 continuous parameters, ``suggest(count=25)``,
75,000 acquisition evaluations, 4 ARD restarts × maxiter 50, every serving
default as shipped. No ``VIZIER_*`` switch and no ``JAX_PLATFORMS`` is set
here, and no child process is started.

- study A: 400 completed trials (exact GP, pad bucket 512);
- study B: 1,000 completed trials (the sparse side of the auto-switch);
- per study one cold ``suggest(25)`` and two more, each from a new worker
  after completing one returned trial; then study A's trials on a gRPC
  server in this process and one ``suggest(25)`` there.

Every check is made here, not read off a log: 25 finite, in-range,
distinct suggestions per call; no reliability fallback (stamp or counter);
the expected program kind on each side of the switch; trained state on a
TPU device; the posterior against NumPy float64 from the same
hyperparameters. ``--chips 4`` runs only the multi-device path (the
designers' automatic whole-host mesh) and its one-device comparison.

Each phase prints one JSON object; the last stdout line is
``{"ok": ..., "device": {"platform", "kind", "count"}}``. Any failed
check, any exception, or a platform other than ``tpu`` exits non-zero.
Smaller ``--trials/--evals`` rehearse the control flow on the CPU (and
still fail there, for the platform); at the default size a run that finds
no TPU stops before it starts.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

DIM = 20
COUNT = 25
DEFAULT_TRIALS = (400, 1000)
NUM_QUERY = 256
POSTERIOR_BOUND = 0.1  # max abs error, in units of the label stddev
ZERO_COUNTERS = (
    "fallbacks",
    "designer_failures",
    "breaker_short_circuits",
    "deadline_exceeded",
)
EXACT_KIND, SPARSE_KIND = "gp_ucb_pe", "gp_ucb_pe_sparse"
# Device-phase families of the sequential suggest on each side of the switch.
PHASE_PREFIX = {EXACT_KIND: "gp_ucb_pe.", SPARSE_KIND: "sparse_gp.ucb_pe_"}


def emit(obj: Dict[str, Any]) -> None:
    print(json.dumps(obj, sort_keys=True, default=str), flush=True)


# -- checks (imported by tests/test_chip_smoke.py) ---------------------------


def check_batch(trials: Sequence[Any], count: int = COUNT, dim: int = DIM) -> List[str]:
    """Failures of one returned batch of ``vz.Trial``s (empty = valid)."""
    from vizier_tpu.reliability import fallback

    failures = []
    if len(trials) != count:
        failures.append(f"returned {len(trials)} suggestions, wanted {count}")
    rows = []
    for t in trials:
        values = [t.parameters[f"x{d}"].value for d in range(dim)]
        if not all(isinstance(v, float) and math.isfinite(v) for v in values):
            failures.append(f"trial {t.id}: non-finite parameter value")
        elif not all(0.0 <= v <= 1.0 for v in values):
            failures.append(f"trial {t.id}: parameter outside [0, 1]")
        if fallback.is_fallback_suggestion(t.metadata):
            failures.append(f"trial {t.id}: carries the reliability fallback stamp")
        rows.append(tuple(values))
    if len(set(rows)) != len(rows):
        failures.append("two suggestions of the batch are identical")
    return failures


def check_stats(stats: Dict[str, int]) -> List[str]:
    """The run must not have been rescued by the reliability layer."""
    return [
        f"serving_stats()[{name!r}] == {stats.get(name)}, wanted 0"
        for name in ZERO_COUNTERS
        if stats.get(name) != 0
    ]


def check_posterior(mean_err: float, stddev_err: float, clamped: int) -> List[str]:
    """``clamped`` counts variances that reached the model's 1e-12 floor:
    at seeded points away from the data that only happens when the
    difference of near-equal terms came out negative before the clamp."""
    failures = []
    if not (math.isfinite(mean_err) and math.isfinite(stddev_err)):
        failures.append("posterior has NaN/inf on the device")
    if clamped:
        failures.append(f"{clamped} posterior variance(s) negative before clamping")
    for name, err in (("mean", mean_err), ("stddev", stddev_err)):
        if err > POSTERIOR_BOUND:
            failures.append(
                f"posterior {name} off by {err} label stddevs (> {POSTERIOR_BOUND})"
            )
    return failures


# -- float64 references ------------------------------------------------------


def matern52_f64(a, b, amplitude, length_scales) -> np.ndarray:
    """ARD Matern-5/2 in NumPy float64, from ``models/kernels.py``'s definition."""
    ls = np.asarray(length_scales, np.float64)
    diff = np.asarray(a, np.float64)[:, None, :] / ls - np.asarray(b, np.float64)[None] / ls
    sq = np.sum(diff * diff, axis=-1)
    d = np.sqrt(np.maximum(sq, 1e-20))
    s5 = math.sqrt(5.0)
    return float(amplitude) ** 2 * (1.0 + s5 * d + 5.0 / 3.0 * sq) * np.exp(-s5 * d)


def reference_posterior(x, y, query, amplitude, noise_stddev, length_scales):
    """The zero-mean GP posterior from the hyperparameters alone, in NumPy
    float64 (``models/gp.py``: K + (σ² + 1e-5)·I): independent of every
    factor the device computed."""
    gram = matern52_f64(x, x, amplitude, length_scales)
    gram += (float(noise_stddev) ** 2 + 1e-5) * np.eye(len(gram))
    chol = np.linalg.cholesky(gram)
    k_star = matern52_f64(query, x, amplitude, length_scales)
    mean = k_star @ np.linalg.solve(gram, np.asarray(y, np.float64))
    v = np.linalg.solve(chol, k_star.T)
    var = float(amplitude) ** 2 - np.sum(v * v, axis=0)
    return mean, np.sqrt(np.maximum(var, 1e-12))


def factor_view(state) -> Dict[str, Any]:
    """What a trained state's ``predict`` multiplies: support points, their
    mask, the mean weights, and the signed L⁻¹-like factors of the variance
    (exact: amp² − ‖L⁻¹k*‖²; sparse SGPR: … + ‖L_B⁻¹L⁻¹k*‖²)."""
    if hasattr(state, "sdata"):  # surrogates.sparse_gp.SparseGPState
        return dict(
            support=state.sdata.z_continuous,
            mask=state.sdata.inducing_mask,
            weights=state.w,
            factors=((-1.0, state.linv), (1.0, state.lb_linv)),
        )
    return dict(
        support=state.data.continuous,
        mask=state.data.row_mask,
        weights=state.alpha,
        factors=((-1.0, state.linv),),
    )


def posterior_from_factors(view, k_star, amplitude, matmul):
    """mean and UNCLAMPED variance from a state's own factors, with the
    caller's matmul (NumPy float64, or jnp at a chosen precision)."""
    mean = matmul(k_star, view["weights"])
    var = amplitude**2
    for sign, factor in view["factors"]:
        t = matmul(factor, k_star.T)
        var = var + sign * (t * t).sum(axis=0)
    return mean, var


# -- instrumentation: XLA compiles, counted from JAX's own events ------------


class CompileCounter:
    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_requests = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += seconds

    def _on_event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1

    def snapshot(self) -> Dict[str, float]:
        return {
            "compiles": self.compiles,
            "compile_seconds": round(self.seconds, 3),
            "persistent_cache_hits": self.cache_hits,
            "persistent_cache_requests": self.cache_requests,
        }

    def since(self, before: Dict[str, float]) -> Dict[str, float]:
        now = self.snapshot()
        return {k: round(now[k] - before[k], 3) for k in now}


# -- the workload ------------------------------------------------------------


def study_config(evals: Optional[int]):
    from vizier_tpu import pyvizier as vz

    config = vz.StudyConfig()  # algorithm DEFAULT = GP-UCB-PE
    for d in range(DIM):
        config.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
    config.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    if evals is not None:  # rehearsal only: the default budget is 75,000
        config.metadata.ns("gp_ucb_pe")["max_acquisition_evaluations"] = str(evals)
    return config


def objective(x: np.ndarray) -> np.ndarray:
    return -np.sum((x - 0.5) ** 2, axis=-1)


def completed_trials(rng: np.random.Generator, n: int):
    """Seeded trials on a shifted quadratic."""
    from vizier_tpu import pyvizier as vz

    x = rng.uniform(size=(n, DIM))
    y = objective(x) + 0.1 * rng.normal(size=n)
    trials = []
    for i in range(n):
        t = vz.Trial(parameters={f"x{d}": float(x[i, d]) for d in range(DIM)})
        t.complete(vz.Measurement(metrics={"obj": float(y[i])}))
        trials.append(t)
    return trials


def load_study(config, label: str, trials, endpoint: Optional[str] = None):
    from vizier_tpu.service import clients

    t0 = time.perf_counter()
    study = clients.Study.from_study_config(
        config,
        owner="chip_smoke",
        study_id=f"study-{label}",
        endpoint=endpoint,
    )
    for t in trials:
        study._client.create_trial(t)
    emit(
        {
            "phase": "load_study",
            "study": label,
            "transport": "grpc" if endpoint else "in_process",
            "trials": len(trials),
            "seconds": round(time.perf_counter() - t0, 3),
        }
    )
    return study


def complete_one(study, trial) -> None:
    from vizier_tpu import pyvizier as vz

    x = np.array([trial.parameters[f"x{d}"].value for d in range(DIM)])
    study.get_trial(trial.id).complete(
        vz.Measurement(metrics={"obj": float(objective(x))})
    )


def timed_suggest(
    failures: List[str], compiles: CompileCounter, study, label: str, call: int,
    transport: str,
):
    before = compiles.snapshot()
    t0 = time.perf_counter()
    suggested = study.suggest(count=COUNT, client_id=f"worker-{call}")  # a new worker
    seconds = time.perf_counter() - t0
    trials = [t.materialize() for t in suggested]  # with metadata, for the stamp
    failed = check_batch(trials)
    failures += [f"study {label} call {call} ({transport}): {f}" for f in failed]
    emit(
        {
            "phase": "suggest",
            "study": label,
            "call": call,
            "cold": call == 1,
            "transport": transport,
            "seconds": round(seconds, 3),
            "returned": len(trials),
            "failed": failed,
            **compiles.since(before),
        }
    )
    return trials


def executor_bucket_kinds(runtime) -> Dict[str, int]:
    """Program kind → flushes, from the batch executor's own histogram
    (its ``bucket`` label leads with the resolved program's kind)."""
    hist = runtime.metrics.get("vizier_batch_occupancy")
    kinds: Dict[str, int] = {}
    if hist is not None:
        for key, (_, count, _) in hist.series_data().items():
            kind = dict(key)["bucket"].split("/")[0]
            kinds[kind] = kinds.get(kind, 0) + count
    return kinds


def device_phases() -> Dict[str, float]:
    """Device phases that ran (the tracer's ``device.wait`` stage spans),
    synced by ``block_until_ready``: "phase/mode" → total seconds so far."""
    from vizier_tpu.observability import tracing as tracing_lib

    out: Dict[str, float] = {}
    for span in tracing_lib.get_tracer().finished_spans():
        if span.name == "device.wait":
            key = f"{span.attributes['phase']}/{span.attributes['mode']}"
            out[key] = out.get(key, 0.0) + span.duration_secs
    return out


def leaf_devices(tree) -> set:
    """The devices a trained state's array leaves live on (None stands for
    a NumPy leaf on the host)."""
    import jax

    found = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        found |= leaf.sharding.device_set if isinstance(leaf, jax.Array) else {None}
    return found


def device_names(devices) -> List[str]:
    return sorted("host" if d is None else str(d) for d in devices)


def off_platform(devices, platform: str) -> List[str]:
    """Names of the devices (or "host") that are not ``platform`` devices."""
    return device_names(d for d in devices if d is None or d.platform != platform)


def cached_designer(runtime, study):
    entry = runtime.designer_cache.peek(study.resource_name, touch=False)
    if entry is None:
        raise RuntimeError(f"no cached designer for {study.resource_name}")
    return entry.designer


def posterior_check(
    failures: List[str], designer, label: str, seed: int, platform: str
) -> None:
    """The trained state's posterior on the device, against float64.

    Checked: the product's own ``predict`` against (exact side) the
    posterior recomputed in NumPy float64 from the hyperparameters alone,
    and (both sides) float64 arithmetic over the state's own factors.
    Reported next to it, not checked: the same matmuls at the TPU's
    default precision — what the product computed before PR 21 pinned them.
    """
    import jax
    import jax.numpy as jnp

    from vizier_tpu.models import kernels

    state = jax.tree_util.tree_map(lambda a: a[0], designer._last_predictive.states)
    view = factor_view(state)
    family = "sparse" if hasattr(state, "sdata") else "exact"
    query_np = np.random.default_rng(seed + 1).uniform(size=(NUM_QUERY, DIM))
    query_np = query_np.astype(np.float32)
    query = kernels.MixedFeatures(
        jnp.asarray(query_np), jnp.zeros((NUM_QUERY, 0), jnp.int32)
    )
    mean_dev, stddev_dev = jax.jit(lambda s, q: s.predict(q))(state, query)
    computed_on = mean_dev.devices()
    mean_dev, stddev_dev = (np.asarray(a, np.float64) for a in (mean_dev, stddev_dev))

    host = jax.device_get(state)
    mask = np.asarray(factor_view(host)["mask"])
    hview = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), factor_view(host))
    amplitude = float(host.params["amplitude"])
    length_scales = host.params["continuous_length_scales"]
    labels = np.asarray(host.data.labels, np.float64)[np.asarray(host.data.row_mask)]
    label_std = float(np.std(labels))
    k_star = matern52_f64(query_np, hview["support"], amplitude, length_scales)
    k_star = np.where(mask[None, :], k_star, 0.0)
    mean_f, var_f = posterior_from_factors(hview, k_star, amplitude, np.matmul)
    stddev_f = np.sqrt(np.maximum(var_f, 1e-12))

    def err(a, b) -> float:
        return float(np.max(np.abs(a - b)) / label_std)

    out = {
        "phase": "posterior_vs_float64",
        "study": label,
        "family": family,
        "queries": NUM_QUERY,
        "support_rows": int(mask.sum()),
        "label_std": label_std,
        "amplitude": amplitude,
        "noise_stddev": float(host.params["noise_stddev"]),
        "computed_on": device_names(computed_on),
        "clamped_variances": int(np.sum(stddev_dev <= 1.0001e-6)),
        "vs_float64_over_same_factors": {
            "mean_max_abs_err_in_label_std": err(mean_dev, mean_f),
            "stddev_max_abs_err_in_label_std": err(stddev_dev, stddev_f),
        },
    }
    mean_err, stddev_err = out["vs_float64_over_same_factors"].values()
    if family == "exact":
        row_mask = np.asarray(host.data.row_mask)
        mean_ref, stddev_ref = reference_posterior(
            np.asarray(host.data.continuous)[row_mask], labels, query_np,
            amplitude, host.params["noise_stddev"], length_scales,
        )
        mean_err, stddev_err = err(mean_dev, mean_ref), err(stddev_dev, stddev_ref)
        out["vs_float64_from_hyperparameters"] = {
            "mean_max_abs_err_in_label_std": mean_err,
            "stddev_max_abs_err_in_label_std": stddev_err,
        }

    # The same matmuls on the device at a named precision, for the record.
    @jax.jit
    def k_star_device(state, query):
        base = state.model.base if family == "sparse" else state.model
        support = kernels.MixedFeatures(
            view["support"], jnp.zeros((view["support"].shape[0], 0), jnp.int32)
        )
        k = base._kernel(state.params, query, support, state.data)
        return jnp.where(view["mask"][None, :], k, 0.0)

    k_dev = k_star_device(state, query)
    for name in ("DEFAULT", "HIGHEST"):
        precision = getattr(jax.lax.Precision, name)
        _, var_p = jax.jit(
            lambda k: posterior_from_factors(
                view, k, state.params["amplitude"],
                lambda a, b: jnp.matmul(a, b, precision=precision),
            )
        )(k_dev)
        var_p = np.asarray(var_p, np.float64)
        out[f"recomputed_at_precision_{name.lower()}"] = {
            "min_variance_before_clamp": float(var_p.min()),
            "negative_variances": int(np.sum(var_p < 0.0)),
            "stddev_max_abs_err_in_label_std": err(
                np.sqrt(np.maximum(var_p, 1e-12)), stddev_f
            ),
        }
    failed = check_posterior(mean_err, stddev_err, out["clamped_variances"])
    if off_platform(computed_on, platform):
        failed.append(f"posterior computed on {off_platform(computed_on, platform)}")
    failures += [f"posterior {label}: {f}" for f in failed]
    emit({**out, "failed": failed})


def study_report(
    failures: List[str], runtime, study, label: str, expected_kind: Optional[str],
    platform: str, kinds_before: Dict[str, int], phases_before: Dict[str, float],
):
    """Which program ran, where its state lives — from stats, not assumed."""
    designer = cached_designer(runtime, study)
    kinds = {
        k: n - kinds_before.get(k, 0)
        for k, n in executor_bucket_kinds(runtime).items()
        if n - kinds_before.get(k, 0)
    }
    phases = {
        k: round(total - phases_before.get(k, 0.0), 3)
        for k, total in device_phases().items()
        if total != phases_before.get(k)
    }
    where = leaf_devices(designer._cached_states[0])
    failed = []
    if expected_kind is not None and set(kinds) != {expected_kind}:
        failed.append(f"executor resolved {kinds}, wanted only {expected_kind!r}")
    family = EXACT_KIND if designer.surrogate_mode == "exact" else SPARSE_KIND
    if expected_kind is not None and family != expected_kind:
        failed.append(f"designer ran {family!r}, wanted {expected_kind!r}")
    if not phases or not all(p.startswith(PHASE_PREFIX[family]) for p in phases):
        failed.append(f"device phases {sorted(phases)} are not all {family!r} phases")
    if off_platform(where, platform):
        failed.append(
            f"trained state lives on {off_platform(where, platform)}, "
            f"not on a {platform} device"
        )
    failures += [f"study {label}: {f}" for f in failed]
    emit(
        {
            "phase": "study_report",
            "study": label,
            "completed_trials": len(designer._trials),
            "surrogate_mode": designer.surrogate_mode,
            "executor_bucket_kinds": kinds,
            "device_phase_seconds": phases,
            "ard_train_counts": dict(designer.ard_train_counts),
            "state_devices": device_names(where),
            "mesh_devices": designer._mesh_size(),
            "failed": failed,
        }
    )
    return designer


def run_study(
    failures, compiles, runtime, config, label, trials, expected_kind, platform
):
    kinds_before, phases_before = executor_bucket_kinds(runtime), device_phases()
    study = load_study(config, label, trials)
    batches = []
    for call in (1, 2, 3):
        batches.append(
            timed_suggest(failures, compiles, study, label, call, "in_process")
        )
        if call < 3:  # new data: the fit cache cannot answer the next call
            complete_one(study, batches[-1][0])
    designer = study_report(
        failures, runtime, study, label, expected_kind, platform,
        kinds_before, phases_before,
    )
    return designer, batches


def check_and_emit_stats(failures: List[str], where: str, stats: Dict[str, int]):
    failed = check_stats(stats)
    failures += [f"{where}: {f}" for f in failed]
    emit({"phase": "serving_stats", "where": where, "stats": stats, "failed": failed})


def one_chip_phases(args, failures, compiles, platform) -> None:
    from vizier_tpu.service import vizier_client
    from vizier_tpu.service import vizier_server
    from vizier_tpu.surrogates import config as surrogate_config_lib

    rng = np.random.default_rng(args.seed)
    config = study_config(args.evals)
    threshold = surrogate_config_lib.SurrogateConfig().sparse_threshold_trials
    servicer = vizier_client._get_local_servicer()
    runtime = servicer._pythia.serving_runtime
    trials_a = completed_trials(rng, args.trials[0])
    for label, trials in (("A", trials_a), ("B", completed_trials(rng, args.trials[1]))):
        kind = SPARSE_KIND if len(trials) >= threshold else EXACT_KIND
        designer, _ = run_study(
            failures, compiles, runtime, config, label, trials, kind, platform
        )
        posterior_check(failures, designer, label, args.seed, platform)
    check_and_emit_stats(failures, "in_process", servicer.serving_stats())

    # Once over real gRPC: a server and its client in this one process.
    server = vizier_server.DefaultVizierServer()
    study = load_study(config, "A", trials_a, endpoint=server.endpoint)
    timed_suggest(failures, compiles, study, "A", 1, "grpc")
    check_and_emit_stats(failures, "grpc_server", server.serving_stats())
    server.stop(0)
    server.pythia_servicer.shutdown()


def first_pick_acquisition(trials) -> float:
    """The UCB pick's acquisition value (the batch's exploitation pick)."""
    for t in trials:
        ns = t.metadata.ns("gp_ucb_pe")
        if ns.get("use_ucb") == "True":
            return float(ns.get("acquisition"))
    raise RuntimeError("batch has no UCB pick")


def four_chip_phases(args, failures, compiles, platform, device_count) -> None:
    """The designers' automatic whole-host mesh, and its one-device twin."""
    import jax

    from vizier_tpu import pyvizier as vz
    from vizier_tpu.algorithms import core as core_lib
    from vizier_tpu.designers import gp_ucb_pe
    from vizier_tpu.service import vizier_client

    if device_count != args.chips:
        failures.append(f"--chips {args.chips} but the process sees {device_count}")
    config = study_config(args.evals)
    trials = completed_trials(np.random.default_rng(args.seed), args.trials[0])
    servicer = vizier_client._get_local_servicer()
    runtime = servicer._pythia.serving_runtime
    # A meshed designer is unbatchable: the executor runs it inline, so no
    # bucket kind is expected — the device phases say what ran.
    designer, batches = run_study(
        failures, compiles, runtime, config, "A", trials, None, platform
    )
    spans = sorted(
        {
            len(leaf.sharding.device_set)
            for leaf in jax.tree_util.tree_leaves(designer._cached_states[0])
        }
    )
    if designer._mesh_size() != device_count or spans != [device_count]:
        failures.append(
            f"mesh of {designer._mesh_size()} devices, state leaves span "
            f"{spans} devices; wanted {device_count} everywhere"
        )
    mesh_value = first_pick_acquisition(batches[0])
    check_and_emit_stats(failures, "in_process", servicer.serving_stats())

    # The same study on one device of this process, three seeds; seed 0 is
    # the service's own.
    for i, t in enumerate(trials):
        t.id = i + 1
    values = []
    kwargs = served_designer_kwargs(runtime, args.evals)
    for seed in (0, 1, 2):
        single = gp_ucb_pe.VizierGPUCBPEBandit(
            config.to_problem(), use_mesh=False, rng_seed=seed, **kwargs
        )
        single.update(core_lib.CompletedTrials(trials))
        before = compiles.snapshot()
        t0 = time.perf_counter()
        batch = [
            vz.Trial(id=i + 1, parameters=s.parameters, metadata=s.metadata)
            for i, s in enumerate(single.suggest(COUNT))
        ]
        seconds = time.perf_counter() - t0
        failed = check_batch(batch)
        failures += [f"one-device seed {seed}: {f}" for f in failed]
        values.append(first_pick_acquisition(batch))
        emit(
            {
                "phase": "one_device_suggest",
                "seed": seed,
                "seconds": round(seconds, 3),
                "first_pick_acquisition": values[-1],
                "state_devices": device_names(leaf_devices(single._cached_states[0])),
                "failed": failed,
                **compiles.since(before),
            }
        )
    spread = max(values) - min(values)
    if not mesh_value >= values[0] - spread:
        failures.append(
            f"mesh first-pick acquisition {mesh_value} is worse than the "
            f"one-device {values[0]} by more than the 3-seed spread {spread}"
        )
    emit(
        {
            "phase": "mesh_vs_one_device",
            "mesh_devices": designer._mesh_size(),
            "state_leaf_device_spans": spans,
            "mesh_first_pick_acquisition": mesh_value,
            "one_device_first_pick_acquisition": values,
            "three_seed_spread": spread,
        }
    )


def served_designer_kwargs(runtime, evals: Optional[int]) -> Dict[str, Any]:
    """What the policy factory hands a served GP designer."""
    from vizier_tpu.service import policy_factory

    kwargs = policy_factory.DefaultPolicyFactory(runtime)._gp_designer_kwargs()
    if evals is not None:
        kwargs["max_acquisition_evaluations"] = evals
    return kwargs


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--trials",
        type=lambda s: tuple(int(v) for v in s.split(",")),
        default=DEFAULT_TRIALS,
        help="completed trials of study A,B (default 400,1000)",
    )
    parser.add_argument(
        "--evals",
        type=int,
        default=None,
        help="acquisition evaluations (default: the shipped 75,000, no override)",
    )
    parser.add_argument(
        "--chips",
        type=int,
        default=1,
        help="4 = only the multi-device mesh path and its one-device comparison",
    )
    args = parser.parse_args(argv)
    rehearsal = (args.trials, args.evals) != (DEFAULT_TRIALS, None)

    import jax

    from vizier_tpu.serving import compile_cache

    cache_dir = compile_cache.configure_entry_point()
    devices = jax.devices()  # from here on this process holds the chip
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    emit(
        {
            "phase": "device",
            **device,
            "jax": jax.__version__,
            "compile_cache_dir": cache_dir,
            "compile_cache_from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "seed": args.seed,
            "trials": list(args.trials),
            "evals": args.evals or 75_000,
            "rehearsal": rehearsal,
        }
    )
    failures: List[str] = []
    if device["platform"] != "tpu":
        failures.append(f"platform is {device['platform']!r}, not 'tpu'")
        if not rehearsal:  # no accelerator: nothing to smoke at full size
            emit({"phase": "failures", "failures": failures})
            emit({"ok": False, "device": device})
            return 2
    compiles = CompileCounter()
    t0 = time.perf_counter()
    try:
        if args.chips > 1:
            four_chip_phases(args, failures, compiles, device["platform"], len(devices))
        else:
            one_chip_phases(args, failures, compiles, device["platform"])
    except BaseException as e:
        emit({"phase": "exception", "type": type(e).__name__, "message": str(e)})
        emit({"ok": False, "device": device})
        raise
    emit(
        {
            "phase": "total",
            "seconds": round(time.perf_counter() - t0, 3),
            **compiles.snapshot(),
        }
    )
    if failures:
        emit({"phase": "failures", "failures": failures})
    emit({"ok": not failures, "device": device})
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
