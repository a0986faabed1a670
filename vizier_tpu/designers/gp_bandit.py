"""VizierGPBandit: the flagship TPU-native GP Bayesian-optimization designer.

Parity with ``/root/reference/vizier/_src/algorithms/designers/gp_bandit.py:88``
("The Vizier GP Bandit Algorithm", arXiv:2408.11527), rebuilt TPU-first:

- quasi-random (+default-point) seeding for the first trials;
- output warping (half-rank → z-score → infeasible imputation);
- ARD via multi-restart pure-JAX L-BFGS — one jitted program, restarts
  vmapped (shardable over the mesh);
- hyperparameter *ensembles* (top-k restarts) combined as a uniform mixture;
- UCB/EI acquisition with an L∞ trust region;
- acquisition maximized by the vectorized Eagle strategy inside a jitted
  ``fori_loop`` (75k evaluations per suggest, no host round-trips).

Padding keeps jit caches stable as the study grows (``converters.padding``);
every model-side op is mask-safe.
"""

from __future__ import annotations

import abc
import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from vizier_tpu import types
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.compute import ir as compute_ir
from vizier_tpu.compute import registry as compute_registry
from vizier_tpu.converters import core as converters
from vizier_tpu.converters import padding as padding_lib
from vizier_tpu.designers import quasi_random
from vizier_tpu.designers.gp import acquisitions
from vizier_tpu.models import gp as gp_lib
from vizier_tpu.models import kernels
from vizier_tpu.models import output_warpers
from vizier_tpu.models import params as params_lib
from vizier_tpu.optimizers import eagle as eagle_lib
from vizier_tpu.optimizers import lbfgs as lbfgs_lib
from vizier_tpu.observability import jax_timing
from vizier_tpu.observability import tracing as tracing_lib
from vizier_tpu.optimizers import vectorized as vectorized_lib
from vizier_tpu.surrogates import config as surrogate_config_lib
from vizier_tpu.surrogates import sparse_bandit
from vizier_tpu.surrogates import sparse_gp
from vizier_tpu.pyvizier import base_study_config
from vizier_tpu.pyvizier import trial as trial_
from vizier_tpu.utils import profiler

Array = jax.Array


def _as_prng_key(rng) -> Array:
    """Coerces the Predictor contract's rng (numpy Generator | PRNGKey |
    None) into a jax PRNGKey."""
    if rng is None:
        return jax.random.PRNGKey(0)
    if isinstance(rng, np.random.Generator):
        return jax.random.PRNGKey(int(rng.integers(0, 2**31 - 1)))
    return rng


@functools.partial(
    jax.jit, static_argnames=("model", "optimizer", "num_restarts", "ensemble_size")
)
def _train_gp(
    model: gp_lib.VizierGaussianProcess,
    optimizer: lbfgs_lib.LbfgsOptimizer,
    data: gp_lib.GPData,
    rng: Array,
    num_restarts: int,
    ensemble_size: int,
    warm_start: Optional[gp_lib.Params] = None,
) -> Tuple[gp_lib.GPState, Array]:
    """ARD: restarts → L-BFGS (vmapped) → top-k precomputed posteriors, and
    the optimizer's own count of its work (``OptimizeResult.work``: one small
    integer array, which ``read_train_work`` fetches where a phase is timed).

    ``warm_start`` (previous suggest's best unconstrained params) is
    prepended as an EXTRA restart row — steady-state hyperparameters move
    little between suggests, so that row usually lands at the optimum
    immediately, while the random restarts keep their full exploration
    budget. (It used to *replace* restart 0; losing one random init
    measurably regressed small-budget mixed-space convergence — see
    PARITY.md "Warm-start ARD seeding".)
    """
    coll = model.param_collection()
    inits = coll.batch_random_init_unconstrained(rng, num_restarts)
    if warm_start is not None:
        inits = jax.tree_util.tree_map(
            lambda batch, warm: jnp.concatenate([warm[None], batch], axis=0),
            inits,
            warm_start,
        )
    loss_fn = lambda p: model.neg_log_likelihood(p, data)
    result = optimizer(loss_fn, inits, best_n=ensemble_size)
    states = jax.vmap(lambda p: model.precompute(p, data))(result.params)
    return states, result.work()


# Of ``lbfgs.work_counts``: what a train's ``device.wait`` span says of the
# program(s) it waited for, and what ``ard_train_counts`` adds up under the
# names ``serving_stats()`` has for them (``train_<name>``).
_WORK_SPAN_ATTRIBUTES = ("loop_trips", "rows", "row_iterations", "evaluations")
_WORK_COUNTERS = ("programs", "loop_trips", "row_trips", "row_iterations", "evaluations")


def read_train_work(phase, works: Sequence[Array]) -> Optional[Dict[str, int]]:
    """After ``phase.block``: what the phase's train programs counted of
    their own work (``lbfgs.work_counts``, summed over the programs), ONE
    small read a program, also written on the phase's span. None — nothing
    read, nothing counted — when the phase is inert or trained nothing."""
    total: Optional[Dict[str, int]] = None
    for work in works:
        fetched = phase.read(work)
        if fetched is None:
            return None
        counts = lbfgs_lib.work_counts(fetched)
        total = counts if total is None else {k: total[k] + counts[k] for k in counts}
    if total is not None:
        phase.set_attributes(**{k: total[k] for k in _WORK_SPAN_ATTRIBUTES})
    return total


@functools.partial(jax.jit, static_argnames=("vec_opt", "count"))
def _maximize_acquisition(
    vec_opt: vectorized_lib.VectorizedOptimizer,
    scoring: acquisitions.ScoringFunction,
    rng: Array,
    count: int,
    prior_features: kernels.MixedFeatures,
) -> vectorized_lib.VectorizedOptimizerResult:
    return vec_opt(scoring.score, rng, count=count, prior_features=prior_features)


def _prior_features_from_data(data: gp_lib.GPData) -> kernels.MixedFeatures:
    """Top observed points (by warped label) to seed the eagle pool.

    Traceable (used both eagerly by the sequential path and under vmap by
    the multi-study batched path): k is a function of the *padded* row
    count so shapes stay stable within a padding bucket.
    """
    labels = jnp.where(data.row_mask, data.labels, -jnp.inf)
    k = min(10, data.num_rows)
    _, idx = jax.lax.top_k(labels, k)
    num_valid = jnp.sum(data.row_mask)
    idx = jnp.where(jnp.arange(k) < num_valid, idx, idx[0])
    return kernels.MixedFeatures(data.continuous[idx], data.categorical[idx])


# The sequential path's call: ONE small program a fit, compiled in set-up,
# where the eager form launched one per operation (where, top_k, sum, two
# gathers and their index arithmetic).
_prior_features_jit = jax.jit(_prior_features_from_data)


# -- cross-study batched programs (vizier_tpu.parallel.batch_executor) ------
#
# The padding schedule makes concurrent studies shape-identical by
# construction, so the per-study jitted programs above vmap cleanly over a
# leading study axis: N same-bucket studies per device dispatch instead of
# N dispatches. Inputs are stacked pytrees (``batch_executor.stack_pytrees``)
# with per-study PRNG keys; the inner computation is the SAME program the
# sequential path runs, so slot i of a batch matches study i run alone.


def _sweep_one(vec_opt, acquisition, s, d, k, count, use_trust_region):
    """Per-study scoring + eagle sweep, vmapped by the flush program below
    (identical math to the sequential suggest)."""
    best_label = jnp.max(jnp.where(d.row_mask, d.labels, -jnp.inf))
    trust = acquisitions.TrustRegion.from_data(d) if use_trust_region else None
    scoring = acquisitions.ScoringFunction(
        predictive=gp_lib.EnsemblePredictive(s),
        acquisition=acquisition,
        best_label=best_label,
        trust_region=trust,
    )
    return _maximize_acquisition(
        vec_opt, scoring, k, count, _prior_features_from_data(d)
    )


def _warm_next_batched(model: gp_lib.VizierGaussianProcess, states) -> gp_lib.Params:
    """Per-slot warm seed for the NEXT train: best member's params mapped
    back through the bijectors — the sequential writeback, traced + vmapped."""
    coll = model.param_collection()
    return jax.vmap(
        lambda p: coll.unconstrain(jax.tree_util.tree_map(lambda a: a[0], p))
    )(states.params)


@functools.partial(
    jax.jit,
    static_argnames=(
        "model", "optimizer", "vec_opt", "acquisition",
        "num_restarts", "ensemble_size", "count", "use_trust_region",
    ),
)
def _gp_bandit_flush_program(
    model: gp_lib.VizierGaussianProcess,
    optimizer: lbfgs_lib.LbfgsOptimizer,
    vec_opt: vectorized_lib.VectorizedOptimizer,
    acquisition,
    md: types.ModelData,  # stacked host ModelData, leading study axis
    rng_train: Array,  # [B]
    rng_acq: Array,  # [B]
    warm: gp_lib.Params,  # [B]
    num_restarts: int,
    ensemble_size: int,
    count: int,
    use_trust_region: bool,
):
    """ONE device program per bucket flush: encode→train→sweep→warm seed.

    Fusing the stages keeps the whole flush a single XLA dispatch — the
    per-program launch + host-sync overhead that dominates N-small-program
    serving happens once per BATCH instead of ~3·N times.
    """
    data = jax.vmap(lambda m: gp_lib.GPData.from_model_data(m))(md)
    states, work = jax.vmap(
        lambda d, k, w: _train_gp(
            model, optimizer, d, k, num_restarts, ensemble_size, w
        )
    )(data, rng_train, warm)
    result = jax.vmap(
        lambda s, d, k: _sweep_one(
            vec_opt, acquisition, s, d, k, count, use_trust_region
        )
    )(states, data, rng_acq)
    return states, _warm_next_batched(model, states), result, work


@functools.partial(
    jax.jit, static_argnames=("model", "optimizer", "num_restarts")
)
def _train_gp_per_metric(
    model: gp_lib.VizierGaussianProcess,
    optimizer: lbfgs_lib.Optimizer,
    batched_data: gp_lib.GPData,  # leading axis M on labels/masks/features
    rng: Array,
    num_restarts: int,
) -> gp_lib.GPState:
    """One independently-trained GP per objective metric (vmapped)."""
    coll = model.param_collection()

    def train_one(data: gp_lib.GPData, key: Array) -> gp_lib.GPState:
        inits = coll.batch_random_init_unconstrained(key, num_restarts)
        loss_fn = lambda p: model.neg_log_likelihood(p, data)
        result = optimizer(loss_fn, inits)
        return model.precompute(result.params, data)

    m = batched_data.labels.shape[0]
    keys = jax.random.split(rng, m)
    return jax.vmap(train_one)(batched_data, keys)


@dataclasses.dataclass
class VizierGPBandit(core_lib.Designer, core_lib.Predictor):
    """GP-UCB/EI designer over flat (non-conditional) search spaces."""

    problem: base_study_config.ProblemStatement
    acquisition: str = "ucb"  # 'ucb' | 'ei' | 'pi' | 'pe'
    ucb_coefficient: float = 1.8
    num_seed_trials: int = 2
    ard_restarts: int = lbfgs_lib.DEFAULT_RANDOM_RESTARTS
    ensemble_size: int = 1
    max_acquisition_evaluations: int = 75_000
    use_trust_region: bool = True
    # HEBO-style learnable Kumaraswamy input warping (non-stationary
    # objectives); see models.gp.VizierGaussianProcess.use_input_warping.
    use_input_warping: bool = False
    padding: Optional[padding_lib.PaddingSchedule] = None
    metric_index: int = 0
    rng_seed: int = 0
    # Injectable ARD optimizer (tests swap in a cheaper one; must be hashable).
    ard_optimizer: Optional[lbfgs_lib.Optimizer] = None
    # Carry the previous suggest's trained params into the next train as
    # an extra restart seed. False restores the reference's per-request
    # cold train (trained params are discarded between suggests).
    use_warm_start_ard: bool = True
    # Completed trials required before warm seeding ENGAGES. Early in a
    # study the NLL landscape is nearly flat, and a previously trained seed
    # keeps winning the restart selection — a self-reinforcing mode lock-in
    # that measurably regressed 40-trial mixed-space convergence (see
    # PARITY.md "Warm-start ARD seeding"). Below the floor every train is
    # cold (full random restarts); steady-state serving, where the warm
    # latency win lives, sits far above it.
    warm_start_min_trials: int = 20
    # Restart budget for a WARM train (one with trained seed params). None
    # keeps the full ``ard_restarts`` budget; the serving runtime sets 1 so
    # steady-state suggests pay one early-exiting L-BFGS run instead of
    # ``ard_restarts`` full cold starts (regret parity:
    # tests/serving/test_warm_start_parity.py).
    warm_ard_restarts: Optional[int] = None
    # Multi-chip data plane: None = auto (build a mesh over all devices when
    # more than one exists and route ARD restarts + acquisition pools through
    # vizier_tpu.parallel); True/False force it on/off.
    use_mesh: Optional[bool] = None
    # Scalable-surrogate auto-switch (vizier_tpu.surrogates): above the
    # config's trial threshold the single-objective suggest path trains an
    # SGPR sparse posterior (O(n·m²)) instead of the exact GP (O(n³)), with
    # hysteresis at the boundary. None (and SurrogateConfig(sparse=False))
    # keep the exact path everywhere — bit-identical to the seed. The
    # serving runtime threads its process-wide config in here.
    surrogate: Optional[surrogate_config_lib.SurrogateConfig] = None

    def __post_init__(self):
        if self.problem.search_space.is_conditional:
            raise ValueError("VizierGPBandit requires a flat search space.")
        if self.problem.search_space.is_empty():
            raise ValueError("Empty search space.")
        self._converter = converters.TrialToModelInputConverter.from_problem(
            self.problem, padding=self.padding
        )
        enc = self._converter.encoder
        self._model = gp_lib.VizierGaussianProcess(
            num_continuous=enc.num_continuous,
            num_categorical=enc.num_categorical,
            use_input_warping=self.use_input_warping,
        )
        self._ard = self.ard_optimizer or lbfgs_lib.LbfgsOptimizer()
        # The acquisition optimizer works in the (possibly feature-padded)
        # model space so its candidates match the GP kernel's shapes; padded
        # dims are masked out of the kernel and sliced off at decode time.
        pad = self._converter.padding
        self._cont_width = pad.pad_features(enc.num_continuous)
        self._cat_width = pad.pad_features(enc.num_categorical)
        cat_sizes = tuple(enc.category_sizes) + (1,) * (
            self._cat_width - enc.num_categorical
        )
        strategy = eagle_lib.VectorizedEagleStrategy(
            num_continuous=self._cont_width,
            category_sizes=cat_sizes,
        )
        self._vec_opt = vectorized_lib.VectorizedOptimizer(
            strategy, max_evaluations=self.max_acquisition_evaluations
        )
        self._warper = output_warpers.create_default_warper()
        self._seeder = quasi_random.QuasiRandomDesigner(
            self.problem.search_space, seed=self.rng_seed
        )
        self._trials: List[trial_.Trial] = []
        # The encoded rows of ``_trials``, kept between suggests: ``update``
        # appends the rows of the trials it is handed, and every reader goes
        # through ``_completed_rows``, whose ``sync`` catches a ``_trials``
        # that was rebound or rewritten and encodes it again from scratch.
        self._store = converters.EncodedTrials(enc, self._converter.metrics)
        self._warper_fitted = False
        self._rng = jax.random.PRNGKey(self.rng_seed)
        # What ``_last_predictive`` holds, and a fit that becomes one only
        # when somebody reads it (see the property).
        self._predictive = None
        self._unread_fit = None
        # Multi-chip path (SURVEY §2.10): when more than one device is
        # visible, suggest() shards the ARD restarts over a mesh of all of
        # them and runs one full acquisition sweep a device, unasked. That
        # is more work for the same answer, not a shorter suggest: every
        # device trains its own restarts and runs its own 75,000
        # evaluations, and such a designer is never batched and never
        # sparse. What it costs beside one chip is the benchmark's to say
        # (PERF.md §5, `default20d-host4.lone25` beside `default20d.lone25`).
        self._mesh = None
        self._mesh_suggests = 0  # suggests whose sweeps ran on it (serving stats)
        if self.use_mesh is not None:
            want_mesh = self.use_mesh
        else:
            # VIZIER_DISABLE_MESH opts out of the auto-mesh (the CPU test
            # suite sets it: 8 *virtual* host devices share the same cores,
            # so pool-sharding only multiplies work there). Read through
            # the central switch registry; env_set also fixes the old raw
            # read treating "0" as set-and-therefore-disabled.
            from vizier_tpu.analysis import registry as _registry

            want_mesh = len(jax.devices()) > 1 and not _registry.env_set(
                "VIZIER_DISABLE_MESH"
            )
        if want_mesh:
            from vizier_tpu import parallel

            self._mesh = parallel.create_mesh()
        # Seed the warm start with a random init so _train_gp's pytree
        # structure never changes across suggests (None -> dict would force
        # a full recompile of the ARD program on the second call).
        self._warm_params = self._model.param_collection().random_init_unconstrained(
            jax.random.PRNGKey(self.rng_seed + 1)
        )
        # True once _warm_params holds genuinely TRAINED params (vs the
        # random placeholder above) — gates the reduced warm restart budget
        # and the warm/cold accounting below.
        self._warm_is_trained = False
        # ``sequential_trains``: training suggests that enqueued their sweeps
        # under the train (``gp_ucb_pe.suggest``); ``sweeps_ahead``: those
        # whose train was still running when the last sweep was enqueued.
        self._ard_train_counts = {
            "warm": 0, "cold": 0, "cached": 0,
            "sequential_trains": 0, "sweeps_ahead": 0,
            **{f"train_{name}": 0 for name in _WORK_COUNTERS},
        }
        # Sparse-surrogate auto-switch state (vizier_tpu.surrogates): the
        # mode is sticky (hysteresis) and a crossover drops all warm/
        # posterior state so neither surrogate ever trains from the
        # other's optimum (see _refresh_surrogate_mode).
        self._surrogate_mode = surrogate_config_lib.MODE_EXACT
        self._sparse_model_cache: Optional[sparse_gp.SparseGaussianProcess] = None
        self._last_sparse_state: Optional[sparse_gp.SparseGPState] = None
        # ``fit_reads``: how many times a deferred fit was made a predictive
        # (``_last_predictive``'s getter): 0 for as long as only suggests run.
        self._surrogate_counts = {
            "sparse_suggests": 0, "crossovers": 0, "nystrom_augments": 0,
            "fit_reads": 0,
        }

    # -- Designer ----------------------------------------------------------

    def update(
        self,
        completed: core_lib.CompletedTrials,
        all_active: core_lib.ActiveTrials = core_lib.ActiveTrials(),
    ) -> None:
        del all_active
        self._trials.extend(completed.trials)
        self._store.sync(self._trials)

    @property
    def _last_predictive(self):
        """The last fit's predictive. A fit that needs device work to become
        one (GP-UCB-PE slices metric 0 out of its per-metric state, one
        program) waits in ``_unread_fit`` until somebody reads this: an
        operator or ``sparse_inducing_state()`` does, a suggest and the
        serving policy do not (``surrogate_counts["fit_reads"]`` counts the
        reads; GP-UCB-PE's ``predict``/``sample`` answer from its cached
        per-metric fit and read nothing here).
        Callers hold the designer as they do for a suggest (the serving
        cache entry's lock)."""
        if self._unread_fit is not None:
            fit, self._unread_fit = self._unread_fit, None
            self._predictive = self._predictive_of(fit)
            self._surrogate_counts["fit_reads"] += 1
        return self._predictive

    @_last_predictive.setter
    def _last_predictive(self, predictive) -> None:
        self._predictive = predictive
        self._unread_fit = None

    def _predictive_of(self, fit):
        """The predictive of an ``_unread_fit`` (designers that defer one)."""
        raise NotImplementedError

    def _completed_rows(self) -> tuple:
        """(continuous [N, Dc] float32, categorical [N, Ds] int32, raw
        labels [N, M] float64) of ``_trials``, from the store."""
        self._store.sync(self._trials)
        return (*self._store.features(), self._store.labels())

    @property
    def encoded_row_counts(self) -> dict:
        """Rows the suggests encoded against rows they took from the store
        as they were held (serving stats)."""
        return {
            "encoded": self._store.rows_encoded,
            "reused": self._store.rows_reused,
        }

    # -- mesh-aware compute (the ONE production train/sweep implementation) --

    def _mesh_size(self) -> int:
        return len(self._mesh.devices.flat) if self._mesh is not None else 1

    @property
    def mesh_counts(self) -> dict:
        """Suggests whose sweeps were launched on this designer's mesh (the
        sparse surrogate's are not), and its width (0 without one)."""
        return {
            "suggests": self._mesh_suggests,
            "devices": self._mesh_size() if self._mesh is not None else 0,
        }

    def _train(
        self,
        data: gp_lib.GPData,
        rng: Array,
        ensemble_size: int,
        warm_start: Optional[gp_lib.Params] = None,
        num_restarts: Optional[int] = None,
    ) -> Tuple[gp_lib.GPState, Array]:
        """ARD train; restarts shard over the mesh when one is present.
        Returns the fit and the program's count of its work (``_train_gp``).

        ``num_restarts`` overrides ``self.ard_restarts`` (the warm-started
        steady-state path trains with ``warm_ard_restarts``); it is floored
        at ``ensemble_size`` so the top-k ensemble selection stays valid.
        """
        restarts = max(num_restarts or self.ard_restarts, ensemble_size)
        if self._mesh is None:
            return _train_gp(
                self._model, self._ard, data, rng,
                restarts, ensemble_size, warm_start,
            )
        from vizier_tpu import parallel

        ndev = self._mesh_size()
        restarts = -(-restarts // ndev) * ndev  # ceil to mesh multiple
        return parallel.train_gp_sharded(
            self._model, self._ard, data, rng,
            restarts, ensemble_size, self._mesh, warm_start,
        )

    def _warm_update_allowed(self) -> bool:
        """Whether this train's optimum may seed the next one (floor met)."""
        return (
            self.use_warm_start_ard
            and len(self._trials) >= self.warm_start_min_trials
        )

    def _warm_restart_budget(self) -> Optional[int]:
        """Restart override for the NEXT train: set only when a trained warm
        seed exists and a reduced warm budget is configured."""
        if (
            self.use_warm_start_ard
            and self._warm_is_trained
            and self.warm_ard_restarts is not None
        ):
            return self.warm_ard_restarts
        return None

    def _record_train(self) -> None:
        self._ard_train_counts[
            "warm" if (self.use_warm_start_ard and self._warm_is_trained) else "cold"
        ] += 1

    def _record_train_work(self, counts: Optional[Dict[str, int]]) -> None:
        """``read_train_work``'s counts into ``ard_train_counts`` (a fused
        flush's, once: through the flush's first member)."""
        for name in _WORK_COUNTERS if counts else ():
            self._ard_train_counts[f"train_{name}"] += counts[name]

    # -- serving warm-start surface (vizier_tpu.serving) --------------------

    def warm_start_state(self) -> Optional[gp_lib.Params]:
        """Last trained unconstrained ARD params (None before first train)."""
        return self._warm_params if self._warm_is_trained else None

    def set_warm_start_state(self, params: gp_lib.Params) -> None:
        """Injects trained unconstrained params as the next restart seed 0."""
        self._warm_params = params
        self._warm_is_trained = True

    @property
    def ard_train_counts(self) -> dict:
        """Copies of the warm/cold ARD train counters and of what the timed
        train programs counted of their own work (serving stats)."""
        return dict(self._ard_train_counts)

    # -- scalable-surrogate auto-switch (vizier_tpu.surrogates) -------------

    @property
    def surrogate_mode(self) -> str:
        """The active surrogate mode ("exact" | "sparse")."""
        return self._surrogate_mode

    @property
    def surrogate_counts(self) -> dict:
        """Copies of the sparse-suggest / crossover / fit-read counters
        (serving stats)."""
        return dict(self._surrogate_counts)

    def sparse_inducing_state(self) -> Optional[sparse_gp.SparseGPState]:
        """The last trained sparse posterior (inducing set + factorization);
        None on the exact path or before the first sparse train."""
        return self._last_sparse_state

    def _sparse_model(self) -> sparse_gp.SparseGaussianProcess:
        if self._sparse_model_cache is None:
            # m rides the SAME bucket grid as trial counts so every
            # (n-bucket, m-bucket) pair is one compiled program family.
            m_pad = self._converter.padding.pad_trials(
                self.surrogate.num_inducing
            )
            self._sparse_model_cache = sparse_gp.SparseGaussianProcess(
                base=self._model, num_inducing=m_pad
            )
        return self._sparse_model_cache

    def _refresh_surrogate_mode(self) -> str:
        """Applies the auto-switch for the current trial count.

        A crossover (either direction) drops every piece of cross-surrogate
        state: the warm ARD seed is re-randomized (a fresh placeholder keeps
        the train program's pytree structure stable) and the cached
        posterior cleared, so stale exact-GP params can never seed — or be
        served from — the sparse posterior, and vice versa. The next train
        after a crossover is therefore a full-budget cold train.
        """
        cfg = self.surrogate
        if cfg is None:
            return self._surrogate_mode
        mode = cfg.mode_for(len(self._trials), current=self._surrogate_mode)
        if mode != self._surrogate_mode:
            old_mode = self._surrogate_mode
            self._surrogate_mode = mode
            self._surrogate_counts["crossovers"] += 1
            # Serving-tier observers (speculative pre-compute) invalidate
            # their derived state the moment the flip happens.
            surrogate_config_lib.fire_crossover_hook(self, old_mode, mode)
            self._warm_params = (
                self._model.param_collection().random_init_unconstrained(
                    jax.random.PRNGKey(
                        self.rng_seed + 1 + self._surrogate_counts["crossovers"]
                    )
                )
            )
            self._warm_is_trained = False
            self._last_predictive = None
            self._last_sparse_state = None
        return mode

    def _suggest_sparse(self, count: int) -> List[trial_.TrialSuggestion]:
        """The sparse twin of the single-objective suggest: SGPR collapsed-
        bound train (k-center inducing selection inside the program) + the
        same UCB/EI + trust-region eagle sweep over the sparse posterior.
        Consumes the RNG stream in the exact order of the exact path (train
        key, then acquisition key)."""
        tracer = tracing_lib.get_tracer()
        with profiler.timeit("convert_trials"), tracer.span("designer.prepare"):
            data = gp_lib.GPData.from_model_data(self._warped_model_data())
        model = self._sparse_model()
        restarts = max(
            self._warm_restart_budget() or self.ard_restarts, self.ensemble_size
        )
        with profiler.timeit("train_gp"):
            with jax_timing.device_phase("sparse_gp.train", stage="train") as phase:
                states, work = sparse_bandit._train_sparse_gp(
                    model,
                    self._ard,
                    data,
                    self._next_rng(),
                    restarts,
                    self.ensemble_size,
                    self._warm_params,
                )
                phase.block(states)
                self._record_train_work(read_train_work(phase, (work,)))
        self._record_train()
        if self._warm_update_allowed():
            coll = self._model.param_collection()
            self._warm_params = coll.unconstrain(
                jax.tree_util.tree_map(lambda a: a[0], states.params)
            )
            self._warm_is_trained = True
        predictive = sparse_gp.SparseEnsemblePredictive(states)
        self._last_predictive = predictive
        self._last_sparse_state = states
        best_label = jnp.max(jnp.where(data.row_mask, data.labels, -jnp.inf))
        trust = (
            acquisitions.TrustRegion.from_data(data)
            if self.use_trust_region
            else None
        )
        scoring = acquisitions.ScoringFunction(
            predictive=predictive,
            acquisition=self._make_acquisition(),
            best_label=best_label,
            trust_region=trust,
        )
        prior = self._prior_features(data)
        with profiler.timeit("acquisition_optimizer"):
            with jax_timing.device_phase(
                "sparse_gp.acquisition", stage="acquire"
            ) as phase:
                result = sparse_bandit._maximize_sparse_acquisition(
                    self._vec_opt, scoring, self._next_rng(), count, prior
                )
                jax.block_until_ready(result.scores)
                phase.block(result)
        self._surrogate_counts["sparse_suggests"] += 1
        with profiler.timeit("best_candidates_to_trials"), tracer.span(
            "designer.decode"
        ):
            return self._decode_result(
                result, count, kind=f"{self.acquisition}+sparse"
            )

    # -- read by the registered programs at the bottom of this module ------

    def _batch_restarts(self) -> int:
        """The jit-static restart budget the next train would use (mirrors
        ``_train``'s floor-at-ensemble rule)."""
        return max(
            self._warm_restart_budget() or self.ard_restarts, self.ensemble_size
        )

    def _maximize(
        self,
        scoring,
        rng: Array,
        count: int,
        prior_features: kernels.MixedFeatures,
    ) -> vectorized_lib.VectorizedOptimizerResult:
        """Acquisition sweep; one independent eagle pool per device."""
        if self._mesh is None:
            return _maximize_acquisition(
                self._vec_opt, scoring, rng, count, prior_features
            )
        from vizier_tpu import parallel

        self._mesh_suggests += 1  # a suggest of this class makes one sweep
        return parallel.maximize_acquisition_sharded(
            self._vec_opt, scoring, rng, count,
            self._mesh_size(), self._mesh, prior_features,
        )

    def _next_rng(self) -> Array:
        self._rng, out = jax.random.split(self._rng)
        return out

    def _padded_features(
        self,
        continuous: np.ndarray,
        categorical: np.ndarray,
        extra_rows: int = 0,
    ) -> tuple:
        """(ModelInput, n_pad) of encoded rows: the ONE pad implementation.

        The rows come from the store (``_completed_rows``) or, for trials
        that are not this study's completed ones, from the encoder.
        ``extra_rows`` reserves additional padded capacity (e.g. for batch
        fantasy conditioning in GP-UCB-PE).
        """
        conv = self._converter
        n_pad = conv.padding.pad_trials(continuous.shape[0] + extra_rows)
        features = types.ContinuousAndCategorical(
            continuous=types.PaddedArray.from_array(
                continuous.astype(np.float32, copy=False),
                (n_pad, conv.padding.pad_features(conv.encoder.num_continuous)),
            ),
            categorical=types.PaddedArray.from_array(
                categorical.astype(np.int32, copy=False),
                (n_pad, conv.padding.pad_features(conv.encoder.num_categorical)),
                fill_value=0,
            ),
        )
        return features, n_pad

    @staticmethod
    def _padded_labels(warped: np.ndarray, n_pad: int) -> types.PaddedArray:
        """The ONE warped-label padding implementation."""
        return types.PaddedArray.from_array(
            warped[:, None].astype(np.float32), (n_pad, 1), fill_value=np.nan
        )

    def _warped_model_data(self, extra_rows: int = 0) -> types.ModelData:
        """Encode + warp labels + pad. Labels leave here all-MAXIMIZE ~N(0,1)."""
        cont, cat, raw_labels = self._completed_rows()  # labels NaN infeasible
        self._store.tally()
        # The warp is a whole-study computation (half-rank and the
        # infeasible shift depend on every label): only its input is kept.
        warped = self._warper(raw_labels[:, self.metric_index])
        self._warper_fitted = raw_labels.shape[0] > 0
        features, n_pad = self._padded_features(cont, cat, extra_rows)
        return types.ModelData(
            features=features, labels=self._padded_labels(warped, n_pad)
        )

    def set_priors(self, prior_trials: Sequence[Sequence[trial_.Trial]]) -> None:
        """Registers prior-study trials for stacked-residual transfer learning.

        Parity with ``gp_bandit.py:289`` (``set_priors``): each sequence is
        one prior study (oldest first); priors must share the search space.
        """
        self._priors = [list(p) for p in prior_trials]

    def _num_objectives(self) -> int:
        return sum(
            1 for m in self.problem.metric_information if not m.is_safety_metric
        )

    def suggest(self, count: Optional[int] = None) -> List[trial_.TrialSuggestion]:
        count = count or 1
        n = len(self._trials)
        if n < self.num_seed_trials:
            return self._seed_suggestions(count)
        if self._num_objectives() > 1:
            return self._suggest_multiobjective(count)
        if getattr(self, "_priors", None):
            return self._suggest_with_priors(count)
        if (
            self._refresh_surrogate_mode() == surrogate_config_lib.MODE_SPARSE
            # Joint qEI optimizes the whole batch through predict_joint,
            # which the collapsed sparse posterior does not expose — q-batch
            # qEI studies stay exact rather than silently degrading to
            # independent EI picks.
            and not (self.acquisition == "qei" and count > 1)
        ):
            return self._suggest_sparse(count)

        tracer = tracing_lib.get_tracer()
        with profiler.timeit("convert_trials"), tracer.span("designer.prepare"):
            data = gp_lib.GPData.from_model_data(self._warped_model_data())
        with profiler.timeit("train_gp"):
            # Device-phase timing: block the trained states INSIDE the span
            # so async dispatch cannot shift ARD device time onto whatever
            # later op first synchronizes; the first call per process is
            # recorded as compile, the rest as steady-state execute.
            with jax_timing.device_phase(
                "gp_bandit.train_gp", stage="train", devices=self._mesh_size()
            ) as phase:
                states, work = self._train(
                    data,
                    self._next_rng(),
                    self.ensemble_size,
                    self._warm_params,
                    num_restarts=self._warm_restart_budget(),
                )
                phase.block(states)
                self._record_train_work(read_train_work(phase, (work,)))
        self._record_train()
        if self._warm_update_allowed():
            # Warm-start the next suggest from this one's best member
            # (states.params are constrained; map back through the bijectors).
            coll = self._model.param_collection()
            self._warm_params = coll.unconstrain(
                jax.tree_util.tree_map(lambda a: a[0], states.params)
            )
            self._warm_is_trained = True
        predictive = gp_lib.EnsemblePredictive(states)
        self._last_predictive = predictive

        best_label = jnp.max(jnp.where(data.row_mask, data.labels, -jnp.inf))
        trust = (
            acquisitions.TrustRegion.from_data(data) if self.use_trust_region else None
        )
        if self.acquisition == "qei" and count > 1:
            if self._converter.encoder.num_categorical:
                raise ValueError(
                    "acquisition='qei' joint batches support continuous spaces "
                    "only; use VizierGPUCBPEBandit for batch suggestions on "
                    "mixed continuous/categorical spaces."
                )
            # Joint q-batch: optimize the whole batch as one point in
            # (q*Dc)-space under Monte-Carlo qEI.
            strategy = eagle_lib.VectorizedEagleStrategy(
                num_continuous=self._cont_width * count, category_sizes=()
            )
            vec = vectorized_lib.VectorizedOptimizer(
                strategy, max_evaluations=self.max_acquisition_evaluations
            )
            self._mesh_suggests += self._mesh is not None
            result = _maximize_q_batch(
                vec,
                states,
                best_label,
                trust,
                self._next_rng(),
                count,
                16,
                self._prior_features(data),
                mesh=self._mesh,
            )
            rows = jnp.asarray(result.features.continuous[0]).reshape(
                count, self._cont_width
            )
            unrolled = vectorized_lib.VectorizedOptimizerResult(
                kernels.MixedFeatures(rows, jnp.zeros((count, 0), jnp.int32)),
                jnp.full((count,), result.scores[0]),
            )
            return self._decode_result(unrolled, count, kind="qei_joint")
        acq = self._make_acquisition()
        scoring = acquisitions.ScoringFunction(
            predictive=predictive,
            acquisition=acq,
            best_label=best_label,
            trust_region=trust,
        )
        prior = self._prior_features(data)
        with profiler.timeit("acquisition_optimizer"):
            with jax_timing.device_phase(
                "gp_bandit.acquisition", stage="acquire", devices=self._mesh_size()
            ) as phase:
                result = self._maximize(scoring, self._next_rng(), count, prior)
                jax.block_until_ready(result.scores)
                phase.block(result)
        with profiler.timeit("best_candidates_to_trials"), tracer.span(
            "designer.decode"
        ):
            return self._decode_result(result, count, kind=self.acquisition)

    def _decode_result(
        self, result: vectorized_lib.VectorizedOptimizerResult, count: int, *, kind: str
    ) -> List[trial_.TrialSuggestion]:
        # One batched device->host fetch (separate np.asarray calls are one
        # blocking round trip each).
        cont, cat, scores = jax.device_get(
            (result.features.continuous, result.features.categorical, result.scores)
        )
        enc = self._converter.encoder
        # ONE decode of the batch's rows, as of its fetch.
        parameters = self._converter.to_parameters(
            cont[:count, : enc.num_continuous], cat[:count, : enc.num_categorical]
        )
        suggestions = []
        for params, score in zip(parameters, scores[:count]):
            s = trial_.TrialSuggestion(parameters=params)
            s.metadata.ns("gp_bandit")["acquisition"] = float(score)
            s.metadata.ns("gp_bandit")["acquisition_kind"] = kind
            suggestions.append(s)
        return suggestions

    # -- transfer learning -------------------------------------------------

    def _data_for_trials(self, trials: Sequence[trial_.Trial]) -> gp_lib.GPData:
        """Encodes an arbitrary trial set with this designer's converter."""
        conv = self._converter
        raw = conv.metrics.encode(trials)
        warped = self._warper(raw[:, self.metric_index])
        self._warper_fitted = raw.shape[0] > 0
        features, n_pad = self._padded_features(*conv.encoder.encode(trials))
        return gp_lib.GPData.from_model_data(
            types.ModelData(features, self._padded_labels(warped, n_pad))
        )

    def _suggest_with_priors(self, count: int) -> List[trial_.TrialSuggestion]:
        from vizier_tpu.models import stacked_residual

        with profiler.timeit("convert_trials"):
            datasets = [self._data_for_trials(p) for p in self._priors]
            data = gp_lib.GPData.from_model_data(self._warped_model_data())
            datasets.append(data)
        with profiler.timeit("train_gp"):
            stack = stacked_residual.train_stacked_residual_gp(
                self._model,
                self._ard,
                datasets,
                self._next_rng(),
                num_restarts=self.ard_restarts,
            )
        # Stacked-residual training has no warm-start path (priors retrain
        # the whole stack); it always counts as a cold train.
        self._ard_train_counts["cold"] += 1
        self._last_predictive = stack  # duck-typed .predict
        best_label = jnp.max(jnp.where(data.row_mask, data.labels, -jnp.inf))
        scoring = acquisitions.ScoringFunction(
            predictive=stack,
            acquisition=self._make_acquisition(),
            best_label=best_label,
            trust_region=(
                acquisitions.TrustRegion.from_data(data)
                if self.use_trust_region
                else None
            ),
        )
        with profiler.timeit("acquisition_optimizer"):
            result = self._maximize(
                scoring, self._next_rng(), count, self._prior_features(data)
            )
            jax.block_until_ready(result.scores)
        with profiler.timeit("best_candidates_to_trials"):
            return self._decode_result(
                result, count, kind=f"{self.acquisition}+priors"
            )

    # -- multi-objective ---------------------------------------------------

    def _suggest_multiobjective(self, count: int) -> List[trial_.TrialSuggestion]:
        """Random-hypervolume scalarized UCB over per-metric GPs."""
        cont, cat, raw = self._completed_rows()  # raw: [N, M] all-MAXIMIZE
        self._store.tally()
        objective_idx = [
            j
            for j, m in enumerate(self.problem.metric_information)
            if not m.is_safety_metric
        ]
        features, n_pad = self._padded_features(cont, cat)
        datas = []
        refs = []
        for j in objective_idx:
            warped = self._warper(raw[:, j])
            datas.append(
                gp_lib.GPData.from_model_data(
                    types.ModelData(features, self._padded_labels(warped, n_pad))
                )
            )
            refs.append(
                float(
                    acquisitions.get_reference_point(
                        jnp.asarray(warped, jnp.float32),
                        jnp.ones(len(warped), bool),
                    )
                )
            )
        batched = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *datas)
        with profiler.timeit("train_gp"):
            states = _train_gp_per_metric(
                self._model, self._ard, batched, self._next_rng(), self.ard_restarts
            )
        # Per-metric vmapped training is not warm-started (GP-UCB-PE owns
        # the warm multimetric path); cold by definition.
        self._ard_train_counts["cold"] += 1
        m = len(objective_idx)
        directions = jnp.abs(
            jax.random.normal(self._next_rng(), (64, m), dtype=jnp.float32)
        )
        directions = directions / jnp.linalg.norm(directions, axis=-1, keepdims=True)
        scoring = acquisitions.HVScalarizedScoring(
            metric_states=states,
            directions=directions,
            reference_point=jnp.asarray(refs, jnp.float32),
            ucb_coefficient=self.ucb_coefficient,
            trust_region=(
                acquisitions.TrustRegion.from_data(datas[0])
                if self.use_trust_region
                else None
            ),
        )
        with profiler.timeit("acquisition_optimizer"):
            result = self._maximize(
                scoring, self._next_rng(), count, self._prior_features(datas[0])
            )
            jax.block_until_ready(result.scores)
        with profiler.timeit("best_candidates_to_trials"):
            return self._decode_result(result, count, kind="hv_scalarized_ucb")

    # -- pieces ------------------------------------------------------------

    def _make_acquisition(self):
        if self.acquisition == "ucb":
            return acquisitions.UCB(self.ucb_coefficient)
        if self.acquisition in ("ei", "qei"):  # qei degenerates to EI at q=1
            return acquisitions.EI()
        if self.acquisition == "pi":
            return acquisitions.PI()
        if self.acquisition == "pe":
            return acquisitions.PE()
        raise ValueError(f"Unknown acquisition {self.acquisition!r}.")

    def _seed_suggestions(self, count: int) -> List[trial_.TrialSuggestion]:
        out: List[trial_.TrialSuggestion] = []
        if not self._trials:
            from vizier_tpu.algorithms import designer_policy

            out.append(designer_policy.default_suggestion(self.problem))
        while len(out) < count:
            out.extend(self._seeder.suggest(count - len(out)))
        return out[:count]

    def _prior_features(self, data: gp_lib.GPData) -> kernels.MixedFeatures:
        """Top observed points (by warped label) to seed the eagle pool.

        Slots past the valid rows would be all-zero padding rows, so
        :func:`_prior_features_from_data` redirects them to the best row.
        """
        return _prior_features_jit(data)

    # -- Predictor ---------------------------------------------------------

    def sample(
        self,
        suggestions: Sequence[trial_.TrialSuggestion],
        rng=None,
        num_samples: int = 1000,
    ) -> np.ndarray:
        """UNWARPED posterior samples [S, T] (original metric scale).

        Reference ``VizierGPBandit.sample``: draw in the warped space the GP
        was trained in, then invert the output-warper pipeline. ``rng`` may
        be a jax PRNGKey OR a numpy Generator (the Predictor base contract).
        """
        rng = _as_prng_key(rng)
        if not suggestions:
            return np.zeros((num_samples, 0))
        predictive = self._require_predictive()
        feats = self._encode_suggestions(suggestions)
        mean, stddev = predictive.predict(feats)
        eps = jax.random.normal(rng, (num_samples,) + mean.shape, mean.dtype)
        warped = np.asarray(mean[None] + stddev[None] * eps)  # [S, T]
        if not self._warper_fitted:
            # Predict before any training labels: the warped space IS the
            # native space (prior samples on a fresh study).
            return warped
        out = self._warper.unwarp(warped.reshape(-1, 1)).reshape(warped.shape)
        # The model trains on sign-flipped (all-MAXIMIZE) labels; the
        # converter owns the flip rule, so route back through it for
        # genuine user-scale samples on MINIMIZE objectives.
        return self._converter.metrics.decode_column(out, self.metric_index)

    def predict(
        self,
        suggestions: Sequence[trial_.TrialSuggestion],
        rng: Optional[np.random.Generator] = None,
        num_samples: Optional[int] = None,
    ) -> core_lib.Prediction:
        """Empirical mean/stddev of UNWARPED posterior samples.

        Parity with the reference predict contract (``gp_bandit.py`` predict
        → sample → unwarp): values come back in the original metric scale.
        """
        samples = self.sample(suggestions, rng=rng, num_samples=num_samples or 1000)
        return core_lib.Prediction(
            mean=np.mean(samples, axis=0), stddev=np.std(samples, axis=0)
        )

    def _require_predictive(self) -> gp_lib.EnsemblePredictive:
        if self._last_predictive is None:
            if len(self._trials) < max(self.num_seed_trials, 1):
                raise ValueError("Not enough completed trials to predict.")
            data = gp_lib.GPData.from_model_data(self._warped_model_data())
            states, _ = self._train(data, self._next_rng(), self.ensemble_size)
            self._last_predictive = gp_lib.EnsemblePredictive(states)
        return self._last_predictive

    def _encode_suggestions(
        self, suggestions: Sequence[trial_.TrialSuggestion]
    ) -> kernels.MixedFeatures:
        trials = [s.to_trial(i + 1) for i, s in enumerate(suggestions)]
        cont, cat = self._converter.encoder.encode(trials)
        n = len(trials)
        cont_p = np.zeros((n, self._cont_width), dtype=np.float32)
        cont_p[:, : cont.shape[1]] = cont
        cat_p = np.zeros((n, self._cat_width), dtype=np.int32)
        cat_p[:, : cat.shape[1]] = cat
        return kernels.MixedFeatures(jnp.asarray(cont_p), jnp.asarray(cat_p))


def default_factory(
    problem: base_study_config.ProblemStatement, seed: Optional[int] = None, **kwargs
) -> VizierGPBandit:
    return VizierGPBandit(problem, rng_seed=seed or 0, **kwargs)


@functools.partial(
    jax.jit, static_argnames=("vec_opt", "q", "num_samples", "mesh")
)
def _maximize_q_batch(
    vec_opt: vectorized_lib.VectorizedOptimizer,
    states: gp_lib.GPState,  # leading ensemble axis
    best_label: Array,
    trust: Optional[acquisitions.TrustRegion],
    rng: Array,
    q: int,
    num_samples: int,
    prior_features: Optional[kernels.MixedFeatures] = None,
    mesh=None,
) -> vectorized_lib.VectorizedOptimizerResult:
    """Joint q-batch qEI: each candidate is a whole batch in q*Dc space.

    Parity with the reference's ``n_parallel`` q-group mode
    (``vectorized_base.py:364-372``): the strategy explores flattened
    [q * Dc] points; the score of a candidate is the Monte-Carlo qEI of its
    q constituent points under the *joint* ensemble posterior (full q×q
    covariance per candidate — duplicated members are perfectly correlated,
    so collapsing the batch onto one point earns no extra credit).

    With a ``mesh``, the (q·Dc)-space search runs one independent eagle
    pool per device with a single top-k merge
    (``parallel.maximize_score_fn_sharded``) — the same pool-sharding the
    single-point acquisitions use.
    """
    dc = states.data.continuous.shape[-1]
    ds = states.data.categorical.shape[-1]
    mc_rng = jax.random.fold_in(rng, 7)

    # Every array the score reads: the mesh sweep's replicated operands.
    operands = (states, best_label, trust, mc_rng)

    def score(operands, flat: kernels.MixedFeatures) -> Array:
        states, best_label, trust, mc_rng = operands
        b = flat.continuous.shape[0]
        pts = flat.continuous.reshape(b, q, dc)

        def per_candidate(batch_pts: Array) -> Array:
            query = kernels.MixedFeatures(
                batch_pts, jnp.zeros((q, ds), jnp.int32)
            )
            means, covs = jax.vmap(lambda s: s.predict_joint(query))(states)
            chols = jnp.linalg.cholesky(covs)  # [E, q, q]
            eps = jax.random.normal(
                mc_rng, (num_samples,) + means.shape, dtype=means.dtype
            )  # [S, E, q]
            draws = means[None] + jnp.einsum("eqr,ser->seq", chols, eps)
            batch_max = jnp.max(draws, axis=-1)  # [S, E]
            qei = jnp.mean(jnp.maximum(batch_max - best_label, 0.0))
            if trust is not None:
                # Sum (not mean): each member pays the single-point penalty.
                qei = qei - jnp.sum(trust.penalty(query))
            return qei

        return jax.vmap(per_candidate)(pts)

    prior = None
    if prior_features is not None:
        # Tile the top observed points across the q slots so the joint
        # search starts anchored at the incumbent region.
        k = prior_features.continuous.shape[0]
        tiled = jnp.tile(prior_features.continuous, (1, q)).reshape(k, q * dc)
        prior = kernels.MixedFeatures(tiled, jnp.zeros((k, 0), jnp.int32))
    if mesh is not None:
        from vizier_tpu import parallel

        return parallel.maximize_score_fn_sharded(
            vec_opt,
            score,
            operands,
            rng,
            count=1,
            num_pools=len(mesh.devices.flat),
            mesh=mesh,
            prior_features=prior,
        )
    return vec_opt(
        functools.partial(score, operands), rng, count=1, prior_features=prior
    )


# -- compute-IR programs (vizier_tpu.compute) --------------------------------
#
# The batched designer-compute contract for the GP-bandit family: one
# program per compiled-flush family (exact | sparse), registered so the
# batch executor, prewarm walker, chaos wrappers, device-phase tracing and
# the speculative lane consume them generically. The two families share one
# body (``_GPBanditFlush``); ``prepare``/``finalize`` run the state
# transitions of the sequential ``suggest``, so slot i of a batch stays
# bit-identical to study i run alone.


def _gp_bandit_unbatchable(designer: "VizierGPBandit", count: int) -> bool:
    """Paths the batched flush programs do not cover (seeding, multi-
    objective, transfer priors, joint qEI, mesh-sharded): those run the
    ordinary sequential suggest."""
    return bool(
        designer._mesh is not None
        or len(designer._trials) < designer.num_seed_trials
        or designer._num_objectives() > 1
        or getattr(designer, "_priors", None)
        or (designer.acquisition == "qei" and count > 1)
    )


def _gp_bandit_demux(items, states, warm_next, result, train_work):
    """ONE device->host fetch for the whole batch; per-slot demux is then
    free numpy views (per-slot device slices would be ~20 dispatches per
    slot and dominated the executor's wall time). ``train_work`` (the
    flush's one train program: ``read_train_work``) goes to the first
    member alone, so that it is counted once a flush."""
    from vizier_tpu.parallel import batch_executor

    # The per-flush half of the fused path's designer.decode stage
    # (finalize is the per-slot half).
    with tracing_lib.get_tracer().span(
        "designer.decode", **tracing_lib.FUSED_FLUSH
    ):
        states, warm_next, result = jax.device_get((states, warm_next, result))
        return [
            dict(
                states=batch_executor.slice_pytree(states, i),
                warm_next=batch_executor.slice_pytree(warm_next, i),
                result=batch_executor.slice_pytree(result, i),
                train_work=train_work if i == 0 else None,
            )
            for i in range(len(items))
        ]


class _GPBanditFlush(compute_ir.DesignerProgram):
    """The flush both GP-bandit families run: encode→multi-restart ARD→
    UCB/EI sweep, one fused vmapped dispatch per bucket.

    A subclass states its registry literals and the three things that
    differ: the surrogate mode it owns, its model and flush program, and
    what ``finalize`` keeps of the fit."""

    #: The ``surrogate_config_lib.MODE_*`` whose studies this program owns.
    surrogate_mode = ""
    #: Appended to the acquisition name in the suggestions' metadata.
    decode_suffix = ""

    @abc.abstractmethod
    def _model(self, designer: "VizierGPBandit"):
        """The model the flush trains: first bucket static, first flush
        argument."""

    @abc.abstractmethod
    def _flush(self, *args):
        """The jitted flush program of this family, looked up in its
        module when called (``tests/compute/test_tpu_compile.py`` swaps
        it there): its outputs, the train's count of its work last."""

    @abc.abstractmethod
    def _keep_fit(self, designer: "VizierGPBandit", states) -> None:
        """The sequential suggest's bookkeeping of a trained fit."""

    def bucket_key(self, designer, count):
        if _gp_bandit_unbatchable(designer, count):
            return None
        if designer._refresh_surrogate_mode() != self.surrogate_mode:
            return None  # the other family's program owns this study
        # The model rides in the statics (the sparse one with its padded
        # inducing-slot count, the m-bucket), so equal keys ⇒ one compiled
        # flush program per bucket — per (n-bucket, m-bucket) pair when
        # sparse.
        return compute_ir.BucketKey(
            kind=self.kind,
            pad_trials=designer._converter.padding.pad_trials(
                len(designer._trials)
            ),
            cont_width=designer._cont_width,
            cat_width=designer._cat_width,
            metric_count=1,
            count=count,
            statics=(
                self._model(designer),
                designer._ard,
                designer._vec_opt,
                designer._batch_restarts(),
                designer.ensemble_size,
                designer._make_acquisition(),
                designer.use_trust_region,
            ),
        )

    def prepare(self, designer, count):
        """Host-side half of a batched suggest: encode + warp + RNG draws.

        Consumes the designer's RNG stream in exactly the order the
        sequential ``suggest`` would (train key, then acquisition key), so
        batched and sequential runs of the same study are key-for-key
        identical. Host-only: the ModelData leaves stay numpy; the GPData
        conversion happens inside the batched program, so prepare issues
        zero device dispatches.
        """
        return dict(
            designer=designer,
            count=count,
            md=designer._warped_model_data(),
            rng_train=designer._next_rng(),
            rng_acq=designer._next_rng(),
            warm=designer._warm_params,
            restarts=designer._batch_restarts(),
        )

    def device_program(self, items, pad_to=None, placement=None):
        """ONE vmapped train + ONE vmapped sweep for the whole bucket
        (slot 0's jit statics stand in for everyone's — the bucket key
        guarantees they are equal). With a mesh ``placement`` the stacked
        study axis is committed onto its submesh, so the fused dispatch
        spans the placement's devices."""
        from vizier_tpu.parallel import batch_executor

        d0: "VizierGPBandit" = items[0]["designer"]
        stacked = batch_executor.stack_members(
            items, ("md", "rng_train", "rng_acq", "warm"), pad_to, placement
        )
        with jax_timing.device_phase(
            self.device_phase, **tracing_lib.FUSED_FLUSH
        ) as phase:
            states, warm_next, result, work = self._flush(
                self._model(d0), d0._ard, d0._vec_opt, d0._make_acquisition(),
                stacked["md"], stacked["rng_train"], stacked["rng_acq"],
                stacked["warm"],
                items[0]["restarts"], d0.ensemble_size,
                items[0]["count"], d0.use_trust_region,
            )
            phase.block(result)
            train_work = read_train_work(phase, (work,))
        return _gp_bandit_demux(items, states, warm_next, result, train_work)

    def finalize(self, designer, item, output):
        """Host-side demux: per-study warm-param writeback + decode — the
        same state transitions the sequential suggest performs."""
        designer._record_train()
        designer._record_train_work(output["train_work"])
        if designer._warm_update_allowed():
            # The unconstrain already ran (vmapped) inside the flush program.
            designer._warm_params = output["warm_next"]
            designer._warm_is_trained = True
        self._keep_fit(designer, output["states"])
        return designer._decode_result(
            output["result"],
            item["count"],
            kind=f"{designer.acquisition}{self.decode_suffix}",
        )

    def prewarm_factory(self, problem, **kwargs):
        # The walker's synthetic studies engage the sparse program exactly
        # when the factory's surrogate config flips them sparse (threshold
        # vs the walked trial bucket) — the same auto-switch live studies
        # use.
        return VizierGPBandit(problem, **kwargs)


class GPBanditProgram(_GPBanditFlush):
    """Exact-GP single-objective flush."""

    kind = "gp_bandit"
    device_phase = "gp_bandit.suggest_batched"
    surrogate_family = "exact"
    shardable_batch_axis = "study"
    algorithms = ("GAUSSIAN_PROCESS_BANDIT",)
    surrogate_mode = surrogate_config_lib.MODE_EXACT

    def _model(self, designer):
        return designer._model

    def _flush(self, *args):
        return _gp_bandit_flush_program(*args)

    def _keep_fit(self, designer, states):
        designer._last_predictive = gp_lib.EnsemblePredictive(states)


class GPBanditSparseProgram(_GPBanditFlush):
    """Sparse (SGPR) flush twin: same stages over the collapsed-bound
    posterior, one compiled program per (n-bucket, m-bucket) pair, its own
    device phase so the ``device.wait`` spans' ``phase`` separates sparse
    from exact time."""

    kind = "gp_bandit_sparse"
    device_phase = "sparse_gp.suggest_batched"
    surrogate_family = "sparse"
    shardable_batch_axis = "study"
    algorithms = ("GAUSSIAN_PROCESS_BANDIT",)
    surrogate_mode = surrogate_config_lib.MODE_SPARSE
    decode_suffix = "+sparse"

    def _model(self, designer):
        return designer._sparse_model()

    def _flush(self, *args):
        return sparse_bandit._sparse_flush_program(*args)

    def _keep_fit(self, designer, states):
        designer._last_predictive = sparse_gp.SparseEnsemblePredictive(states)
        designer._last_sparse_state = states
        designer._surrogate_counts["sparse_suggests"] += 1


compute_registry.register(VizierGPBandit, GPBanditProgram())
compute_registry.register(VizierGPBandit, GPBanditSparseProgram())
