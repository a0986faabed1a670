"""The main path's device programs compile for a TPU v5e, without the chip.

The TPU compiler is installed in the sandbox and compiles for a chip that
is described, not attached (`on-chip-measurement` guide, section 2.3). The
programs are obtained the way the batch executor obtains them —
``compute.registry.resolve`` → ``prepare`` → ``device_program`` — with the
jitted flush program intercepted at its call, so the lowered arguments are
exactly the executor's, at the reference's default widths: 20-D,
``suggest(25)``, 75,000 acquisition evaluations, 4 ARD restarts × maxiter
50, padded to the executor's batch of 8.

A compile that passes is not a chip run: nothing executes, so this says
nothing about results or times. The benchmark's cells (``chipbench/run.py``)
are the chip run.

The topology is described inside a module-scoped fixture and nowhere else:
only one process may hold the TPU library, so nothing here touches
``topologies`` at import, in a ``skipif`` or in ``parametrize``; every such
test lives in this one file (about two minutes of compiling in all). Left
out for that budget: the exact flush program at pad 1024 (~100 s), which
the service does not reach with the sparse switch at 512.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from vizier_tpu import parallel
from vizier_tpu import pyvizier as vz
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.compute import registry as compute_registry
from vizier_tpu.designers import gp_bandit
from vizier_tpu.designers import gp_ucb_pe
from vizier_tpu.designers.gp import acquisitions
from vizier_tpu.models import gp as gp_lib
from vizier_tpu.models import kernels
from vizier_tpu.surrogates import config as surrogate_config_lib
from vizier_tpu.surrogates import sparse_gp

DIM = 20
COUNT = 25
BATCH = 8  # ServingConfig.batch_max_size with batch_pad_partial
HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to assert
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def host4(topo):
    """The described host's four chips as the designers' 1-D mesh."""
    return Mesh(np.asarray(topo.devices), (parallel.DEVICE_AXIS,))


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off around these."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _designer(num_trials: int, surrogate=None) -> gp_ucb_pe.VizierGPUCBPEBandit:
    """The service DEFAULT at its shipped budget over a 20-D study."""
    problem = vz.ProblemStatement()
    for d in range(DIM):
        problem.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
    problem.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    designer = gp_ucb_pe.VizierGPUCBPEBandit(
        problem, use_mesh=False, surrogate=surrogate
    )
    rng = np.random.default_rng(0)
    x = rng.uniform(size=(num_trials, DIM))
    y = -np.sum((x - 0.5) ** 2, axis=1)
    trials = []
    for i in range(num_trials):
        t = vz.Trial(
            id=i + 1, parameters={f"x{d}": float(x[i, d]) for d in range(DIM)}
        )
        t.complete(vz.Measurement(metrics={"obj": float(y[i])}))
        trials.append(t)
    designer.update(core_lib.CompletedTrials(trials))
    return designer


def _as_shapes(tree, sharding):
    """Array leaves become shapes on ``sharding``; anything else — a model,
    an optimizer, a Python scalar: the jit statics — passes through."""

    def leaf(a):
        if isinstance(a, (np.ndarray, np.generic, jax.Array, jax.ShapeDtypeStruct)):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
        return a

    return jax.tree_util.tree_map(leaf, tree)


class _Captured(Exception):
    pass


def _lower_flush(monkeypatch, designer, flush_name: str, sharding):
    """Lowers the flush program ``device_program`` would have dispatched."""
    program, key = compute_registry.resolve(designer, COUNT)
    item = program.prepare(designer, COUNT)
    jitted = getattr(gp_ucb_pe, flush_name)
    seen = {}

    def capture(*args):
        seen["args"] = args
        raise _Captured

    monkeypatch.setattr(gp_ucb_pe, flush_name, capture)
    with pytest.raises(_Captured):
        program.device_program([item], pad_to=BATCH)
    monkeypatch.undo()
    return key, jitted.lower(*_as_shapes(seen["args"], sharding))


def _fits(compiled):
    """The program's temporaries, arguments and outputs fit one v5e's HBM.
    Returns the compiler's memory analysis."""
    mem = compiled.memory_analysis()
    total = (
        mem.temp_size_in_bytes
        + mem.argument_size_in_bytes
        + mem.output_size_in_bytes
    )
    print(
        f"temp {mem.temp_size_in_bytes / 1e6:.1f} MB, arguments "
        f"{mem.argument_size_in_bytes / 1e6:.1f} MB, outputs "
        f"{mem.output_size_in_bytes / 1e6:.1f} MB"
    )
    assert total < HBM_BYTES, mem
    return mem


def _batch_minor_copies(hlo_text: str, shape: str) -> list[str]:
    """The ``copy`` instructions of ``shape`` (``f32[2,512,512]``) whose
    result or operand is laid out with the leading axis minor — ``{0,...}``,
    which on the TPU pads that axis to 128 lanes."""
    typed = re.compile(r"(%[\w.\-]+) = " + re.escape(shape) + r"\{(\d)")
    minor = dict(m.groups() for m in typed.finditer(hlo_text))
    copy = re.compile(
        r"(%[\w.\-]+) = " + re.escape(shape) + r"\{(\d)[^}]*\} copy\((%[\w.\-]+)\)"
    )
    return [
        m.group(0)
        for m in copy.finditer(hlo_text)
        if m.group(2) == "0" or minor.get(m.group(3)) == "0"
    ]


def _computations(hlo_text: str) -> dict[str, list[str]]:
    """The compiled text's computations by name: each one's instruction
    lines."""
    computations: dict[str, list[str]] = {}
    current = None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) \(.*\{\s*$", line)
        if head:
            current = computations.setdefault(head.group(1), [])
        elif line.startswith("}"):
            current = None
        elif current is not None:
            current.append(line)
    return computations


_CALLED = re.compile(
    r"(?:body|condition|calls|to_apply|branch_computations)=\{?(%[\w.\-]+)"
)


def _custom_calls_by_while(hlo_text: str, target: str) -> dict[str, int]:
    """For every ``while`` of the compiled text whose body holds (itself or
    in a computation it calls) a custom-call to ``target``: how many."""
    computations = _computations(hlo_text)
    totals: dict[str, int] = {}

    def total(name: str) -> int:
        if name not in totals:
            lines = computations.get(name, [])
            totals[name] = sum(
                f'custom_call_target="{target}"' in line for line in lines
            ) + sum(total(callee) for callee in set(_CALLED.findall("\n".join(lines))))
        return totals[name]

    whiles = re.compile(r"(%[\w.\-]+) = .* while\(.*body=(%[\w.\-]+)")
    found = {
        m.group(1): total(m.group(2))
        for lines in computations.values()
        for m in map(whiles.search, lines)
        if m
    }
    return {name: count for name, count in found.items() if count}


_LAUNCHED = ("fusion", "copy", "sort", "custom-call")


def _launched_by_while(hlo_text: str) -> dict[str, list[str]]:
    """For every ``while`` of the compiled text: the instructions its body
    launches itself, one device operation each — fusions, copies, sorts and
    custom-calls. What a fusion holds is inside its one launch, and what a
    nested ``while`` launches is counted under that ``while``."""
    computations = _computations(hlo_text)
    launched = re.compile(
        r"^\s*(?:ROOT )?%[\w.\-]+ = .*? (" + "|".join(_LAUNCHED) + r")\("
    )
    whiles = re.compile(r"(%[\w.\-]+) = .* while\(.*body=(%[\w.\-]+)")
    return {
        m.group(1): [
            line.strip()
            for line in computations.get(m.group(2), [])
            if launched.match(line)
        ]
        for lines in computations.values()
        for m in map(whiles.search, lines)
        if m
    }


def test_custom_calls_are_counted_by_while():
    text = (
        "%inner (p: f32[4]) -> f32[4] {\n"
        '  %c.1 = f32[4] custom-call(%p), custom_call_target="Cholesky"\n'
        "}\n"
        "%outer (p: f32[4]) -> f32[4] {\n"
        '  %c.2 = f32[4] custom-call(%p), custom_call_target="Cholesky"\n'
        "  %while.2 = f32[4] while(%c.2), condition=%cond, body=%inner\n"
        "}\n"
        "ENTRY %main (p: f32[4]) -> f32[4] {\n"
        '  %c.3 = f32[4] custom-call(%p), custom_call_target="Cholesky"\n'
        "  %while.1 = f32[4] while(%c.3), condition=%cond, body=%outer\n"
        "}\n"
    )
    assert _custom_calls_by_while(text, "Cholesky") == {"%while.2": 1, "%while.1": 2}


def test_launched_operations_are_counted_by_while():
    """A body's own fusions, copies, sorts and custom-calls, one each; what
    is not launched (a bitcast, a get-tuple-element, the nested ``while``
    itself) is not counted, and a nested body counts under its own name."""
    text = (
        "%fused_computation.1 (p: f32[4]) -> f32[4] {\n"
        "  %copy.9 = f32[4] copy(%p)\n"
        "}\n"
        "%inner (p: f32[4]) -> f32[4] {\n"
        "  %gte.1 = f32[4] get-tuple-element(%p), index=0\n"
        "  %sort.1 = (f32[4], s32[4]) sort(%gte.1, %iota), dimensions={0}\n"
        "  ROOT %fusion.2 = f32[4] fusion(%sort.1), kind=kLoop, calls=%fused_computation.1\n"
        "}\n"
        "%outer (p: f32[4]) -> f32[4] {\n"
        "  %bitcast.1 = f32[4] bitcast(%p)\n"
        "  %copy.1 = f32[4] copy(%bitcast.1)\n"
        '  %c.2 = f32[4] custom-call(%copy.1), custom_call_target="Cholesky"\n'
        "  %while.2 = f32[4] while(%c.2), condition=%cond, body=%inner\n"
        "}\n"
        "ENTRY %main (p: f32[4]) -> f32[4] {\n"
        "  %fusion.1 = f32[4] fusion(%p), kind=kLoop, calls=%fused_computation.1\n"
        "  %while.1 = f32[4] while(%fusion.1), condition=%cond, body=%outer\n"
        "}\n"
    )
    found = {
        name: [re.match(r"(?:ROOT )?(%[\w.\-]+)", line).group(1) for line in lines]
        for name, lines in _launched_by_while(text).items()
    }
    assert found == {"%while.2": ["%sort.1", "%fusion.2"], "%while.1": ["%copy.1", "%c.2"]}


def test_batch_minor_copies_are_recognised():
    """The guard's reader on the two lines it has to tell apart: PR 33's
    ``%copy.1090`` (restart axis made minor) and a plain transposing copy."""
    text = (
        "%gte.1 = f32[2,512,512]{2,1,0:T(8,128)} get-tuple-element(%p), index=3\n"
        "%copy.1090 = f32[2,512,512]{0,2,1:T(8,128)} copy(%gte.1), metadata={}\n"
        "%copy.1076 = f32[2,512,512]{2,1,0:T(8,128)S(1)} copy(%copy.1090)\n"
        "%copy.1323 = f32[2,512,512]{1,2,0:T(8,128)S(1)} copy(%gte.1)\n"
    )
    found = _batch_minor_copies(text, "f32[2,512,512]")
    assert [line.split(" ")[0] for line in found] == ["%copy.1090", "%copy.1076"]


def test_exact_flush_pad512_compiles(monkeypatch, one_chip):
    designer = _designer(400)
    key, lowered = _lower_flush(
        monkeypatch, designer, "_ucb_pe_flush_program", one_chip
    )
    assert key.kind == "gp_ucb_pe" and key.pad_trials == 512
    assert designer._vec_opt.max_evaluations == 75_000
    compiled = lowered.compile()
    mem = _fits(compiled)
    # 1,299 MB while the NLL read diag(L) with ``jnp.diagonal`` (a batch-minor
    # relayout of the [8, 5, 512, 512] factors), 255 MB since (PR 34), 111 MB
    # since the L-BFGS step takes the accepted point's gradient from the
    # search's evaluation (PR 36: the factors it carries, 1.1 MB a row, are
    # less than the second forward pass's temporaries were).
    assert mem.temp_size_in_bytes < 512 * 1024**2, mem
    # The sweeps' kernel passes never had the sequential program's disease
    # (``test_eagle_loop_body_launches_what_it_needs``): under the ``vmap``
    # over slots the slot axis is the second-minor one, eight full sublanes,
    # with the unit metric and member axes there (PR 41 and before) or not.
    passes = set(
        re.findall(r" (f32\[8,(?:1,1,)?50,512,20\]\{[^}]*\})", compiled.as_text())
    )
    assert passes
    thin = [s for s in passes if not re.search(r"\]\{\d,0,[\d,]+:T\(8,128\)", s)]
    assert not thin, thin


def test_sparse_flush_pad1024_compiles(monkeypatch, one_chip):
    # A bare designer has surrogate=None and would resolve 1,000 trials to
    # the exact pad-1024 program; serving threads its SurrogateConfig in.
    designer = _designer(1000, surrogate=surrogate_config_lib.SurrogateConfig())
    key, lowered = _lower_flush(
        monkeypatch, designer, "_sparse_ucb_pe_flush_program", one_chip
    )
    assert key.kind == "gp_ucb_pe_sparse" and key.pad_trials == 1024
    assert designer._sparse_model().num_inducing == 128
    _fits(lowered.compile())


def _param_shapes(model):
    """Shapes of one member's unconstrained hyperparameters."""
    return jax.eval_shape(
        lambda: model.param_collection().random_init_unconstrained(
            jax.random.PRNGKey(0)
        )
    )


def _gp_state_shapes(designer, n_pad: int, sharding):
    """Shapes of an [E=1] trained ensemble at ``n_pad`` rows, no training."""
    model = designer._model
    data = jax.eval_shape(
        lambda: gp_lib.GPData(
            continuous=jnp.zeros((n_pad, DIM), jnp.float32),
            categorical=jnp.zeros((n_pad, 0), jnp.int32),
            labels=jnp.zeros((n_pad,), jnp.float32),
            row_mask=jnp.ones((n_pad,), bool),
            cont_dim_mask=jnp.ones((DIM,), bool),
            cat_dim_mask=jnp.ones((0,), bool),
        )
    )
    params = _param_shapes(model)
    states = jax.eval_shape(
        lambda p, d: jax.vmap(lambda q: model.precompute(q, d))(
            jax.tree_util.tree_map(lambda a: a[None], p)
        ),
        params,
        data,
    )
    return _as_shapes(data, sharding), _as_shapes(states, sharding)


def _lower_train(designer, n_pad: int, restarts: int, sharding):
    """``_train_gp`` at ``n_pad`` rows: ``restarts`` random rows + the warm one."""
    model = designer._model
    data, _ = _gp_state_shapes(designer, n_pad, sharding)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sharding)
    warm = _as_shapes(_param_shapes(model), sharding)
    return gp_bandit._train_gp.lower(
        model, designer._ard, data, key, restarts, 1, warm
    )


def test_ard_train_1024_compiles(one_chip):
    """``_train_gp``: 4 restarts × L-BFGS maxiter 50 at 1024×20, warm row."""
    designer = _designer(1)
    assert (designer.ard_restarts, designer._ard.maxiter) == (4, 50)
    _fits(_lower_train(designer, 1024, designer.ard_restarts, one_chip).compile())


def test_warm_train_pad512_never_lays_its_factor_batch_minor(one_chip):
    """``_train_gp`` as ``default20d.lone25`` runs it in every request —
    pad 512, one restart + the warm row — reads ``diag(L)`` without copying
    the ``[2, 512, 512]`` factor into a layout whose minor axis is the
    restarts (``models.gp.cholesky_diagonal``; eight such 134 MB copies and
    138.95 MB of temporaries with ``jnp.diagonal``, 2.74 MB without, 2.65 MB
    with the factor in the line search's carry, PR 36).

    And an L-BFGS iteration factorises once outside its line search: the
    evaluation at ``t0``. A factorisation at pad 512 is four ``Cholesky``
    custom-calls (128-wide blocks), so the line search's ``while`` holds
    four and the L-BFGS ``while`` around it eight; the parent's held twelve,
    four of them ``value_and_grad`` at the point the search had just
    evaluated."""
    compiled = _lower_train(_designer(1), 512, 1, one_chip).compile()
    text = compiled.as_text()
    assert _batch_minor_copies(text, "f32[2,512,512]") == []
    assert _fits(compiled).temp_size_in_bytes < 16 * 1024**2
    assert sorted(_custom_calls_by_while(text, "Cholesky").values()) == [4, 8]


def test_sweep_75k_evaluations_compiles(one_chip):
    """``_maximize_acquisition``: the 75,000-evaluation Eagle sweep over a
    pad-1024 posterior, UCB + trust region, 25 candidates."""
    designer = _designer(1)
    data, states = _gp_state_shapes(designer, 1024, one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    prior = kernels.MixedFeatures(
        jax.ShapeDtypeStruct((10, DIM), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((10, 0), jnp.int32, sharding=one_chip),
    )

    def sweep(states, data, key, prior):
        scoring = acquisitions.ScoringFunction(
            predictive=gp_lib.EnsemblePredictive(states),
            acquisition=acquisitions.UCB(1.8),
            best_label=jnp.max(data.labels),
            trust_region=acquisitions.TrustRegion.from_data(data),
        )
        return gp_bandit._maximize_acquisition(
            designer._vec_opt, scoring, key, COUNT, prior
        )

    _fits(jax.jit(sweep).lower(states, data, key, prior).compile())


def test_posterior_ucb_forward_compiles(one_chip):
    """Precompute (Cholesky + L⁻¹) → posterior → UCB at 1024×20, 256 queries
    — the forward step the benchmark's ``correct`` checks against float64."""
    designer = _designer(1)
    model = designer._model
    data, _ = _gp_state_shapes(designer, 1024, one_chip)
    params = _as_shapes(_param_shapes(model), one_chip)
    query = kernels.MixedFeatures(
        jax.ShapeDtypeStruct((256, DIM), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((256, 0), jnp.int32, sharding=one_chip),
    )

    def forward(params, data, query):
        mean, stddev = model.precompute(params, data).predict(query)
        return acquisitions.UCB(1.8)(mean, stddev, jnp.max(data.labels))

    _fits(jax.jit(forward).lower(params, data, query).compile())


def test_sequential_crossings_compile(one_chip):
    """The sequential path's three small programs between its big ones
    (``_sweep_inputs``, ``_stack_fits``, ``_append_first_pick``) at pad 512,
    one metric: the ``top_k`` of the prior features is the only operation of
    theirs a chip's compiler could refuse."""
    designer = _designer(1)
    data, states = _gp_state_shapes(designer, 512, one_chip)
    picked = kernels.MixedFeatures(
        jax.ShapeDtypeStruct((1, DIM), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((1, 0), jnp.int32, sharding=one_chip),
    )
    _fits(gp_ucb_pe._sweep_inputs.lower((data,)).compile())
    _fits(gp_ucb_pe._stack_fits.lower((states,)).compile())
    _fits(gp_ucb_pe._append_first_pick.lower(data, picked).compile())


def _sparse_state_shapes(designer, n_pad: int, count: int, sharding):
    """Shapes of an [M=1, E=1] trained SGPR ensemble at ``n_pad`` rows over
    the model's inducing slots, and of its all-points twin with ``count``
    spare slots (``_sparse_all_points``), no training."""
    model = designer._sparse_model()
    data, _ = _gp_state_shapes(designer, n_pad, sharding)
    params = _param_shapes(model)
    states_me = jax.eval_shape(
        lambda p, d: jax.vmap(
            jax.vmap(
                lambda q: model.precompute(
                    q, sparse_gp.select_inducing_kcenter(d, model.num_inducing)
                )
            )
        )(jax.tree_util.tree_map(lambda a: a[None, None], p)),
        params,
        data,
    )
    all_data = jax.eval_shape(
        lambda s, d: gp_ucb_pe._sparse_all_points(s, d, count), states_me, data
    )
    return _as_shapes(states_me, sharding), _as_shapes(all_data, sharding)


def _lower_suggest_batch(
    designer, n_pad: int, count: int, sharding, mesh=None, sparse=False
):
    """``_suggest_batch`` as the sequential path calls it: one metric, one
    member, the trained and the all-points data at ``n_pad`` rows, trust
    region on; with a ``mesh``, the pools of each pick's sweep over its
    devices. On the exact GP, or (``sparse``) on the SGPR tier of a
    ``suggest(COUNT)``: the all-points twin holds ``COUNT`` spare slots for
    both of its sweeps, the first pick's and the other ``COUNT - 1``'s."""
    if sparse:
        model = designer._sparse_all_model(COUNT)
        states_me, all_data = _sparse_state_shapes(designer, n_pad, COUNT, sharding)
    else:
        model = designer._model
        all_data, states = _gp_state_shapes(designer, n_pad, sharding)
        states_me = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct((1,) + a.shape, a.dtype, sharding=sharding),
            states,
        )

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    prior = kernels.MixedFeatures(
        shape((10, DIM), jnp.float32), shape((10, 0), jnp.int32)
    )
    return gp_ucb_pe._suggest_batch.lower(
        model, designer._vec_opt, states_me, all_data,
        shape((1, n_pad), jnp.float32), shape((n_pad,), bool),
        shape((1,), jnp.float32), prior, shape((2,), jnp.uint32),
        shape((), bool), shape((), bool), count, designer.config, True, mesh, None,
    )


def _eagle_body(hlo_text: str, scores: str = "f32[50,512]") -> list[str]:
    """The launched operations of the eagle loop: the one ``while`` whose
    body evaluates the candidates against the data — a ``scores``-shaped
    cross-covariance (the loop over picks holds it and evaluates one
    point)."""
    eagle = [
        ops
        for ops in _launched_by_while(hlo_text).values()
        if any(f" {scores}" in op for op in ops)
    ]
    assert len(eagle) == 1, [len(ops) for ops in eagle]
    return eagle[0]


def _assert_launches_what_it_needs(
    hlo_text: str, ops: list[str], limit: int, rows: tuple[int, ...]
):
    """At most ``limit`` launches an iteration, no sort, one key derivation;
    and every kernel pass fills its vector registers: an operand
    ``[..., 50, n, 20]`` of the body (candidates x ``n`` of ``rows`` x
    features) has no axis in front — a unit metric or member axis that
    ``vmap`` put there is laid second-minor and tiled ``T(1,128)``, one
    sublane of a register's eight (PERF.md, PR 42) — and is tiled
    ``T(8,128)``."""
    assert len(ops) <= limit, "\n".join(ops)
    assert not [op for op in ops if " sort(" in op]
    assert len([op for op in ops if "_threefry_split" in op]) <= 1
    inside = "\n".join(ops + _called_by(hlo_text, ops))
    for n in rows:
        passes = set(re.findall(rf" (f32\[(?:\d+,)*50,{n},20\]\{{[^}}]*\}})", inside))
        assert passes, n
        thin = [
            s for s in passes if not re.match(rf"f32\[50,{n},20\]\{{[\d,]+:T\(8,128\)", s)
        ]
        assert not thin, thin


def _assert_one_cross_covariance(ops: list[str]):
    passes = [
        op for op in ops if " f32[50,512]" in op and re.search(r'op_name="[^"]*reduce_sum"', op)
    ]
    assert len(passes) == 1, "\n".join(passes)


@pytest.mark.parametrize("count", [1, 24])
def test_eagle_loop_body_launches_what_it_needs(one_chip, count):
    """An iteration of the 75,000-evaluation sweep is bound on the chip by
    how many operations its body launches (32 us for a floor of ~3; PERF.md,
    PR 38), so the count is held: ``_suggest_batch`` at pad 512 x 20-D as
    ``default20d.lone25`` runs it (``count`` 1: the first pick's sweep;
    ``count`` 24: the loop over the other picks, the eagle loop inside it).

    PR 37's body launched 47: 14 of them key derivations
    (``_threefry_split``), a sort for the best of 51, and the candidates'
    ``[50, 512]`` cross-covariance twice, once a posterior. PR 38's launched
    27, its one cross-covariance pass fused over ``f32[1,1,50,512,20]``
    tiled ``T(1,128)`` behind a copy of its own: 26 since the unit metric
    and member axes stay out of it (``gp_ucb_pe._per_member``)."""
    designer = _designer(1)
    assert designer._vec_opt.strategy.batch_size == 50
    assert designer._vec_opt.max_evaluations == 75_000
    text = _lower_suggest_batch(designer, 512, count, one_chip).compile().as_text()
    ops = _eagle_body(text)
    print(f"count {count}: {len(ops)} launched operations an iteration")
    _assert_launches_what_it_needs(text, ops, limit=26, rows=(512,))
    _assert_one_cross_covariance(ops)


@pytest.mark.parametrize("count", [1, 24])
def test_sparse_eagle_loop_body_launches_what_it_needs(one_chip, count):
    """The sequential sweep past the sparse switch, as
    ``default20d-sparse.lone25`` runs it: pad 1,024 over 128 inducing rows,
    153 with the spare slots of a ``suggest(25)``. Its score predicts each
    posterior apart (``_mixture_predict``), so an iteration makes two kernel
    passes, ``[50,128,20]`` and ``[50,153,20]``: both were tiled
    ``T(1,128)`` under the unit metric and member axes, in a body of 36."""
    designer = _designer(1, surrogate=surrogate_config_lib.SurrogateConfig())
    assert designer._sparse_model().num_inducing == 128
    text = (
        _lower_suggest_batch(designer, 1024, count, one_chip, sparse=True)
        .compile()
        .as_text()
    )
    ops = _eagle_body(text, scores="f32[50,153]")
    print(f"sparse, count {count}: {len(ops)} launched operations an iteration")
    _assert_launches_what_it_needs(text, ops, limit=34, rows=(128, 153))


_COLLECTIVE = re.compile(
    r" (?:all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter"
    r"|collective-broadcast)(?:-start)?\("
)


def _called_by(hlo_text: str, lines: list[str]) -> list[str]:
    """The instruction lines of every computation ``lines`` call, and of
    those they call."""
    computations = _computations(hlo_text)
    seen: set[str] = set()
    todo = list(lines)
    found = []
    while todo:
        for name in set(_CALLED.findall(todo.pop())) - seen:
            seen.add(name)
            found += computations.get(name, [])
            todo += computations.get(name, [])
    return found


@pytest.mark.parametrize("count", [1, 24])
def test_eagle_loop_body_on_a_mesh_is_the_one_chip_body(host4, count):
    """On a four-chip host a device's sweep is the sweep one chip runs:
    ``_suggest_batch`` with the designers' mesh, every operand replicated.
    The pools are a manual axis (``parallel.maximize_score_fn_sharded``), so
    no operation of the eagle loop carries a pool axis. As a ``vmap`` axis
    that the partitioner split, each device's unit pool axis sat second-minor
    in the body's broadcast-difference-reduce fusions, tiled ``T(1,128)`` —
    one sublane of a register's eight: the pull was fused over
    ``f32[1,50,20,50]{3,0,2,1:T(1,128)}`` at ~15 us for one chip's 0.65, the
    trust region's pass over ``f32[1,50,512,20]`` at ~4.4 for 1.0, and the
    body launched 33 operations for one chip's 27 (PERF.md, PR 40; one
    chip's own unit axes, the metric's and the member's, left with PR 42).

    What the compiler puts in for the mesh: nothing inside the eagle loop,
    and two all-reduces a pick — the scores' and the winner's row's."""
    designer = _designer(1)
    replicated = NamedSharding(host4, PartitionSpec())
    text = (
        _lower_suggest_batch(designer, 512, count, replicated, host4)
        .compile()
        .as_text()
    )
    ops = _eagle_body(text)
    print(f"count {count}, four chips: {len(ops)} launched operations an iteration")
    _assert_launches_what_it_needs(text, ops, limit=26, rows=(512,))
    _assert_one_cross_covariance(ops)
    inside = _called_by(text, ops)
    fused = set(re.findall(r" (f32\[[\d,]+\]\{[^}]*\})", "\n".join(inside)))
    pooled = sorted(
        shape
        for shape in fused
        if re.match(r"f32\[(?:1,)+(?:50,20,50|50,512,20|50,50,20)\]", shape)
    )
    assert not pooled, pooled
    assert [s for s in fused if re.match(r"f32\[50,20,50\]\{[\d,]+:T\(8,128\)", s)], fused
    assert [s for s in fused if re.match(r"f32\[50,512,20\]\{[\d,]+:T\(8,128\)", s)], fused
    assert not [line for line in ops + inside if _COLLECTIVE.search(line)]
    # One pick's merge, whether the loop over picks is there (24) or
    # unrolled away (1).
    collectives = [line.strip() for line in text.splitlines() if _COLLECTIVE.search(line)]
    assert len(collectives) <= 2, "\n".join(collectives)
