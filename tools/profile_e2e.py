"""Decomposes the DEFAULT designer's e2e suggest() cost at full scale.

Usage: JAX_PLATFORMS=cpu python tools/profile_e2e.py [--trials 1000] [--evals 75000]

Prints a per-stage wall-clock table for one steady-state suggest(25):
encode/warp (host), ARD train (device), suggest-batch (device), decode.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=1000)
    ap.add_argument("--evals", type=int, default=75_000)
    ap.add_argument("--batch", type=int, default=25)
    ap.add_argument("--repeats", type=int, default=2)
    args = ap.parse_args()

    from vizier_tpu import pyvizier as vz
    from vizier_tpu.algorithms import core as core_lib
    from vizier_tpu.designers.gp_ucb_pe import VizierGPUCBPEBandit

    dim = 20
    rng = np.random.default_rng(0)
    x = rng.uniform(size=(args.trials, dim))
    y = -np.sum((x - 0.5) ** 2, axis=1) + 0.1 * rng.normal(size=args.trials)

    problem = vz.ProblemStatement()
    for d in range(dim):
        problem.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
    problem.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    designer = VizierGPUCBPEBandit(
        problem, max_acquisition_evaluations=args.evals
    )
    trials = []
    for i in range(args.trials):
        t = vz.Trial(
            id=i + 1, parameters={f"x{d}": float(x[i, d]) for d in range(dim)}
        )
        t.complete(vz.Measurement(metrics={"obj": float(y[i])}))
        trials.append(t)

    t0 = time.perf_counter()
    designer.update(core_lib.CompletedTrials(trials))
    print(f"update(all {args.trials}): {time.perf_counter()-t0:.3f}s")

    # Instrument the stages by monkey-timing the designer internals.
    stage: dict = {}

    orig_train = designer._train_states_me

    def timed_train(*args, **kwargs):
        t0 = time.perf_counter()
        # Sub-time the host-side encode inside by instrumenting the converter.
        conv = designer._converter
        orig_enc = conv.metrics.encode
        orig_feat = designer._padded_features

        def enc(trials):
            s = time.perf_counter()
            out = orig_enc(trials)
            stage["metrics.encode"] = stage.get("metrics.encode", 0) + (
                time.perf_counter() - s
            )
            return out

        def feat(*rows, **kw):
            s = time.perf_counter()
            out = orig_feat(*rows, **kw)
            stage["padded_features"] = stage.get("padded_features", 0) + (
                time.perf_counter() - s
            )
            return out

        object.__setattr__(conv.metrics, "encode", enc)
        designer._padded_features = feat
        try:
            out = orig_train(*args, **kwargs)
            jax.block_until_ready(out[0].params if hasattr(out[0], "params") else out[0])
        finally:
            object.__setattr__(conv.metrics, "encode", orig_enc)
            designer._padded_features = orig_feat
        stage["train_states_me(total)"] = stage.get(
            "train_states_me(total)", 0
        ) + (time.perf_counter() - t0)
        return out

    designer._train_states_me = timed_train

    from vizier_tpu.designers import gp_ucb_pe as mod

    orig_suggest_batch = mod._suggest_batch

    def timed_suggest_batch(*a, **kw):
        t0 = time.perf_counter()
        out = orig_suggest_batch(*a, **kw)
        jax.block_until_ready(out[0].scores)
        stage["suggest_batch(jit)"] = stage.get("suggest_batch(jit)", 0) + (
            time.perf_counter() - t0
        )
        return out

    mod._suggest_batch = timed_suggest_batch

    orig_all_points = designer._all_points_data

    def timed_all_points(count):
        t0 = time.perf_counter()
        out = orig_all_points(count)
        stage["all_points_data"] = stage.get("all_points_data", 0) + (
            time.perf_counter() - t0
        )
        return out

    designer._all_points_data = timed_all_points

    orig_decode = designer._decode_ucb_pe

    def timed_decode(*a, **kw):
        t0 = time.perf_counter()
        out = orig_decode(*a, **kw)
        stage["decode"] = stage.get("decode", 0) + (time.perf_counter() - t0)
        return out

    designer._decode_ucb_pe = timed_decode

    print("compile pass (not counted):", flush=True)
    t0 = time.perf_counter()
    designer.suggest(args.batch)
    print(f"  compile suggest: {time.perf_counter()-t0:.1f}s", flush=True)

    next_id = args.trials + 1
    totals = []
    for r in range(args.repeats):
        stage.clear()
        fresh = vz.Trial(
            id=next_id,
            parameters={f"x{d}": float(v) for d, v in enumerate(rng.uniform(size=dim))},
        )
        fresh.complete(vz.Measurement(metrics={"obj": float(-r)}))
        next_id += 1
        t0 = time.perf_counter()
        designer.update(core_lib.CompletedTrials([fresh]))
        designer.suggest(args.batch)
        total = time.perf_counter() - t0
        totals.append(total)
        print(f"repeat {r}: total {total*1000:.0f} ms", flush=True)
        for k, v in sorted(stage.items(), key=lambda kv: -kv[1]):
            print(f"  {k:28s} {v*1000:9.1f} ms ({100*v/total:5.1f}%)")
        # metrics.encode / padded_features are nested inside
        # train_states_me(total); only top-level intervals count here.
        top_level = sum(
            v
            for k, v in stage.items()
            if k not in ("metrics.encode", "padded_features")
        )
        other = total - top_level
        print(f"  {'(other/untimed)':28s} {other*1000:9.1f} ms ({100*other/total:5.1f}%)")
    print(f"p50 total: {np.percentile(totals, 50)*1000:.0f} ms")


if __name__ == "__main__":
    main()
