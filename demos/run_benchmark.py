"""Benchmark demo: compare designers on a BBOB function, save a plot.

Usage:
  python demos/run_benchmark.py --function Sphere --dim 4 --trials 30 \
      --out /tmp/convergence.png [--platform cpu]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--function", default="Sphere")
    parser.add_argument("--dim", type=int, default=4)
    parser.add_argument("--trials", type=int, default=30)
    parser.add_argument("--repeats", type=int, default=2)
    parser.add_argument("--out", default="/tmp/convergence.png")
    parser.add_argument("--platform", default=None, choices=["cpu", "tpu"])
    args = parser.parse_args()

    # The flag only sets the variable JAX reads itself, before jax loads.
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform

    import matplotlib

    matplotlib.use("Agg")

    from vizier_tpu import benchmarks
    from vizier_tpu.benchmarks.analyzers import plot_utils
    from vizier_tpu.benchmarks.experimenters.synthetic import bbob
    from vizier_tpu.designers import QuasiRandomDesigner, RandomDesigner
    from vizier_tpu.designers.gp_bandit import VizierGPBandit

    functions = dict(bbob.BBOB_FUNCTIONS, **bbob.EXTRA_FUNCTIONS)
    fn = functions[args.function]

    factories = {
        "random": lambda p, **kw: RandomDesigner(p.search_space, seed=kw.get("seed", 0)),
        "quasirandom": lambda p, **kw: QuasiRandomDesigner(
            p.search_space, seed=kw.get("seed", 0)
        ),
        "gp_ucb": lambda p, **kw: VizierGPBandit(
            p, rng_seed=kw.get("seed") or 0, max_acquisition_evaluations=5000
        ),
    }
    states, names = [], []
    for name, factory in factories.items():
        for r in range(args.repeats):
            exp = benchmarks.NumpyExperimenter(fn, benchmarks.bbob_problem(args.dim))
            state = benchmarks.BenchmarkState.from_designer_factory(exp, factory, seed=r)
            benchmarks.BenchmarkRunner(
                [benchmarks.GenerateAndEvaluate(2)],
                num_repeats=-(-args.trials // 2),  # ceil: honor odd budgets
            ).run(state)
            states.append(state)
            names.append(name)
            print(f"{name} repeat {r} done", flush=True)
    ax = plot_utils.plot_states(
        states, algorithm_names=names, title=f"{args.function} {args.dim}D"
    )
    ax.get_figure().savefig(args.out, dpi=120)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
