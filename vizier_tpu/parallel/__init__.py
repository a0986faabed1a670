"""Device-mesh sharding: the ICI data plane the reference never had.

The reference is CPU-single-host inside each Pythia call (SURVEY.md §2.10,
§5.8); here the three embarrassingly-parallel axes of the GP-bandit suggest
path shard across a ``jax.sharding.Mesh``:

- **restarts** — ARD L-BFGS random restarts (data-parallel over devices);
- **ensemble** — GP hyperparameter ensemble members;
- **pools** — independent Eagle pools of the acquisition sweep (each device
  runs its own ask-evaluate-tell loop; results merge with one final top-k).

Restarts and ensemble members are batch axes of already-vmapped jitted
programs, so sharding them is pure ``NamedSharding`` annotation — XLA
partitions the train and inserts any collectives over ICI. Gradients/Cholesky
stay device-local. What the partitioner puts in, compiled for a 4-chip v5e at
512 rows (PERF.md §5, PR 35): one scalar all-reduce in the *condition* of the
L-BFGS ``while`` and one in its line search's (the vmapped loops go on while
any restart on any device does, so every iteration of both is a rendezvous of
the mesh and the train lasts as long as its slowest restart), then one gather
of the losses and of the best member.

The pools are a *manual* axis instead (``jax.shard_map`` in
:func:`maximize_score_fn_sharded`): a device's program is the plain
``VectorizedOptimizer`` sweep on its own key, what one chip runs — compiled
for the same 4-chip v5e, 27 launched operations an eagle iteration, none with
a pool axis, no collective inside the loop — and each pick's global top-k
merge is two small all-reduces (the scores, the winner's row). As a ``vmap``
axis that the partitioner split, every device kept a pool axis of one, which
the compiler laid second-minor in the body's reduce fusions and tiled
``T(1,128)``, one sublane of eight: a device's sweep took 2.4 × one chip's
(PERF.md §6, PR 40; ``tests/compute/test_tpu_compile.py`` holds the body).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from vizier_tpu.designers.gp import acquisitions
from vizier_tpu.models import gp as gp_lib
from vizier_tpu.models import kernels
from vizier_tpu.optimizers import lbfgs as lbfgs_lib
from vizier_tpu.optimizers import vectorized as vectorized_lib

# Cross-study continuous batching (the intra-host sibling of the mesh data
# plane below): N same-shape-bucket studies per device dispatch.
from vizier_tpu.parallel.batch_executor import BatchExecutor
from vizier_tpu.parallel.batch_executor import BatchSlotError
from vizier_tpu.parallel.batch_executor import BucketKey

# Mesh execution plane for the batch executor (VIZIER_MESH*): device
# placements, shard-granularity padding, and the multi-host coordinator
# seam.
from vizier_tpu.parallel.mesh import DevicePlacement
from vizier_tpu.parallel.mesh import MeshConfig
from vizier_tpu.parallel.mesh import build_placements
from vizier_tpu.parallel.mesh import multihost_mesh

Array = jax.Array

DEVICE_AXIS = "devices"


def create_mesh(
    n_devices: Optional[int] = None, axis_name: str = DEVICE_AXIS
) -> Mesh:
    """A 1-D mesh over the first ``n_devices`` (default: all) devices."""
    devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"Requested {n_devices} devices but only {len(devices)} exist."
            )
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis_name,))


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> Mesh:
    """Joins a multi-host JAX cluster and returns the global device mesh.

    The reference's distributed story is gRPC-only (one CPU host per
    Pythia call); this is the scale-out path it never had: each host runs
    one process, ``jax.distributed.initialize`` wires the cluster over
    DCN, and the returned 1-D mesh spans every chip of every host. All
    sharded entry points in this module take that mesh unchanged — the
    parallel axes (restarts / ensemble / pools) are communication-free,
    so cross-host traffic is one final top-k gather; everything else
    rides ICI within each host's slice.

    Without a ``coordinator_address`` this is one host: the distributed
    runtime is not touched at all and the mesh is the local devices. (A
    pod that relies on JAX's own cluster detection calls
    ``jax.distributed.initialize()`` itself first, and sees its failure
    itself.) With one, the cluster is joined and a failure propagates — a
    silently absent cluster would shard per-host and corrupt results.

    MUST run before any JAX call that initializes the XLA backend
    (including ``jax.devices()``): ``jax.distributed.initialize`` refuses
    to run afterwards.
    """
    if coordinator_address is not None and not jax.distributed.is_initialized():
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    return create_mesh()


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def batch_sharded(mesh: Mesh):
    """Leading-axis sharding over the device axis."""
    return NamedSharding(mesh, P(mesh.axis_names[0]))


# ---------------------------------------------------------------------------
# Sharded ARD training: restarts across devices.
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("model", "optimizer", "num_restarts", "ensemble_size", "mesh"),
)
def train_gp_sharded(
    model: gp_lib.VizierGaussianProcess,
    optimizer: lbfgs_lib.Optimizer,
    data: gp_lib.GPData,
    rng: Array,
    num_restarts: int,
    ensemble_size: int,
    mesh: Mesh,
    warm_start: Optional[dict] = None,
) -> Tuple[gp_lib.GPState, Array]:
    """Multi-restart ARD with the restart axis sharded over the mesh: the
    fit and, as ``gp_bandit._train_gp``, the optimizer's count of its work
    (every device's rows: the loop's condition is an all-reduce).

    ``num_restarts`` should be a multiple of the mesh size. Data is
    replicated (it is small); each device runs its restarts locally; the
    loops' conditions and the final top-k selection are the cross-device
    reductions (module docstring). ``warm_start``
    replaces the first restart here — unlike ``gp_bandit._train_gp``, which
    prepends it as an extra row — because appending would break the
    restarts-divisible-by-mesh sharding; at mesh-scale restart budgets the
    one lost random init is immaterial.
    """
    coll = model.param_collection()
    inits = coll.batch_random_init_unconstrained(rng, num_restarts)
    if warm_start is not None:
        inits = jax.tree_util.tree_map(
            lambda batch, warm: batch.at[0].set(warm), inits, warm_start
        )
    inits = jax.lax.with_sharding_constraint(
        inits, batch_sharded(mesh)
    )
    data = jax.lax.with_sharding_constraint(data, replicated(mesh))
    loss_fn = lambda p: model.neg_log_likelihood(p, data)
    result = optimizer(loss_fn, inits, best_n=ensemble_size)
    states = jax.vmap(lambda p: model.precompute(p, data))(result.params)
    return states, result.work()


# ---------------------------------------------------------------------------
# Sharded acquisition sweep: independent eagle pools per device.
# ---------------------------------------------------------------------------


def maximize_score_fn_sharded(
    vec_opt: vectorized_lib.VectorizedOptimizer,
    score_fn,
    operands,
    rng: Array,
    count: int,
    num_pools: int,
    mesh: Mesh,
    prior_features: Optional[kernels.MixedFeatures] = None,
) -> vectorized_lib.VectorizedOptimizerResult:
    """Runs ``num_pools`` independent vectorized sweeps, pools sharded.

    Each pool consumes ``vec_opt.max_evaluations`` scores; total work is
    ``num_pools ×`` that, wall-clock ≈ one pool when num_pools == mesh size.
    The merge is a single global top-k. Traceable (callable from inside
    larger jitted programs, e.g. the UCB-PE batch loop).

    The pools are a *manual* axis (``jax.shard_map``), not a ``vmap`` axis
    for the partitioner to split: a device's program is ``vec_opt`` itself on
    that device's key — the sweep a one-chip caller runs, with no pool axis
    in it (module docstring). ``score_fn(operands, query) -> [Q]`` reads
    every array it needs from ``operands``, a pytree that reaches each device
    replicated: the values a closure would capture are laid out on the
    caller's automatic mesh, and the manual one refuses them. ``num_pools``
    is a multiple of the mesh size; a device with more than one pool runs
    them one after another.
    """
    axis = mesh.axis_names[0]
    per_device, remainder = divmod(num_pools, mesh.shape[axis])
    if remainder or not per_device:
        raise ValueError(
            f"num_pools={num_pools} must be a positive multiple of the "
            f"mesh's {mesh.shape[axis]} devices."
        )
    keys = jax.random.split(rng, num_pools)  # pool i on device i // per_device

    def run_pools(keys, operands, prior_features):
        # This device's [per_device] keys -> its [per_device, count, ...] best.
        def run_pool(key: Array) -> vectorized_lib.VectorizedOptimizerResult:
            return vec_opt(
                functools.partial(score_fn, operands),
                key,
                count=count,
                prior_features=prior_features,
            )

        if per_device == 1:
            return jax.tree_util.tree_map(lambda a: a[None], run_pool(keys[0]))
        return jax.lax.map(run_pool, keys)

    sweep = jax.shard_map(
        run_pools,
        mesh=mesh,
        in_specs=(P(axis), P(), P()),
        out_specs=P(axis),
        # The sweep's loop starts from constants (zeros, -inf) and carries
        # per-device values: typed, every such carry would need a cast.
        check_vma=False,
    )
    # [pools, count, ...] over the devices. The ``jit`` is for a caller
    # outside one (a map run eagerly refuses its own output's sharding).
    results = jax.jit(sweep)(keys, operands, prior_features)
    flat = num_pools * count  # explicit: -1 breaks on zero-width categorical
    flat_scores = results.scores.reshape(flat)
    flat_cont = results.features.continuous.reshape(
        (flat,) + results.features.continuous.shape[2:]
    )
    flat_cat = results.features.categorical.reshape(
        (flat,) + results.features.categorical.shape[2:]
    )
    top_scores, idx = jax.lax.top_k(flat_scores, count)
    return vectorized_lib.VectorizedOptimizerResult(
        kernels.MixedFeatures(flat_cont[idx], flat_cat[idx]), top_scores
    )


@functools.partial(
    jax.jit, static_argnames=("vec_opt", "count", "num_pools", "mesh")
)
def maximize_acquisition_sharded(
    vec_opt: vectorized_lib.VectorizedOptimizer,
    scoring: acquisitions.ScoringFunction,
    rng: Array,
    count: int,
    num_pools: int,
    mesh: Mesh,
    prior_features: Optional[kernels.MixedFeatures] = None,
) -> vectorized_lib.VectorizedOptimizerResult:
    """Pool-sharded sweep of a ScoringFunction pytree (jitted entry point)."""
    return maximize_score_fn_sharded(
        vec_opt,
        lambda scoring, query: scoring.score(query),
        scoring,
        rng,
        count,
        num_pools,
        mesh,
        prior_features,
    )


# ---------------------------------------------------------------------------
# One fused multi-chip "suggest step" (ARD train + acquisition sweep).
# ---------------------------------------------------------------------------


def suggest_step_sharded(
    model: gp_lib.VizierGaussianProcess,
    optimizer: lbfgs_lib.Optimizer,
    vec_opt: vectorized_lib.VectorizedOptimizer,
    data: gp_lib.GPData,
    rng: Array,
    *,
    count: int,
    num_restarts: int,
    ensemble_size: int,
    mesh: Mesh,
    ucb_coefficient: float = 1.8,
) -> vectorized_lib.VectorizedOptimizerResult:
    """Full GP-bandit compute step over the mesh: train → score → sweep."""
    train_rng, acq_rng = jax.random.split(rng)
    states, _ = train_gp_sharded(
        model, optimizer, data, train_rng, num_restarts, ensemble_size, mesh
    )
    predictive = gp_lib.EnsemblePredictive(states)
    best_label = jnp.max(jnp.where(data.row_mask, data.labels, -jnp.inf))
    scoring = acquisitions.ScoringFunction(
        predictive=predictive,
        acquisition=acquisitions.UCB(ucb_coefficient),
        best_label=best_label,
        trust_region=acquisitions.TrustRegion.from_data(data),
    )
    return maximize_acquisition_sharded(
        vec_opt, scoring, acq_rng, count, len(mesh.devices.flat), mesh
    )
