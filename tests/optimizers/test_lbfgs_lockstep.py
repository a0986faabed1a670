"""An L-BFGS iteration makes only the evaluations whose results it keeps.

``LbfgsOptimizer`` runs ``lbfgs_minimize`` under a ``vmap`` over restarts. A
batched ``while_loop`` runs its body on every row until the last row is done,
so (a) a finished row must not go on searching — it would replay its last
line search in every later iteration, the live rows waiting for it — and (b)
the accepted point's gradient comes from the evaluation the search made, not
from a second one. Neither may move an iterate: the parent's body (PR 35's
``lbfgs_minimize``, below as ``_parent_loop``) is the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import pytest

from vizier_tpu.models import gp as gp_lib
from vizier_tpu.optimizers import lbfgs

OPTIONS = dict(
    maxiter=50,
    memory=10,
    max_linesearch_steps=20,
    gtol=1e-5,
    ftol=1e-6,
    ftol_patience=2,
    armijo_c1=1e-4,
)


def _parent_loop(loss_fn, x0, *, maxiter, memory, max_linesearch_steps, gtol, ftol,
                 ftol_patience, armijo_c1, finished_rows_search=True):
    """``lbfgs_minimize`` as it was before PR 36, statement for statement, in
    the ``(init, cond, step)`` form of ``lbfgs._lbfgs_loop``. With
    ``finished_rows_search=False`` it is the parent plus (a) alone: the one
    change is ``live`` in ``ls_cond``."""
    value_and_grad = jax.value_and_grad(loss_fn)
    f0, g0 = value_and_grad(x0)
    n = x0.shape[0]
    init = lbfgs._LbfgsState(
        x=x0,
        f=f0,
        g=g0,
        s_hist=jnp.zeros((memory, n), x0.dtype),
        y_hist=jnp.zeros((memory, n), x0.dtype),
        rho=jnp.zeros((memory,), x0.dtype),
        k=jnp.asarray(0, jnp.int32),
        done=jnp.asarray(False),
        t_init=jnp.asarray(1.0, x0.dtype),
        small_count=jnp.asarray(0, jnp.int32),
    )

    def cond(state):
        return (state.k < maxiter) & ~state.done

    def step(state):
        d = -lbfgs._two_loop_direction(state, memory)
        gd = jnp.dot(state.g, d)
        bad = (gd >= 0.0) | ~jnp.isfinite(gd)
        d = jnp.where(bad, -state.g, d)
        gd = jnp.where(bad, -jnp.dot(state.g, state.g), gd)

        live = finished_rows_search | cond(state)

        def ls_cond(carry):
            t, f_new, i = carry
            insufficient = f_new > state.f + armijo_c1 * t * gd
            return (
                live
                & (insufficient | ~jnp.isfinite(f_new))
                & (i < max_linesearch_steps)
            )

        def ls_body(carry):
            t, _, i = carry
            t = t * 0.5
            return t, loss_fn(state.x + t * d), i + 1

        t0 = jnp.minimum(
            state.t_init, lbfgs.MAX_STEP / jnp.maximum(jnp.max(jnp.abs(d)), 1e-30)
        )
        t, f_new, num_halvings = jax.lax.while_loop(
            ls_cond, ls_body, (t0, loss_fn(state.x + t0 * d), jnp.asarray(0))
        )
        accepted = jnp.isfinite(f_new) & (f_new <= state.f)
        x_new = jnp.where(accepted, state.x + t * d, state.x)
        f_new = jnp.where(accepted, f_new, state.f)
        g_new = jnp.where(accepted, value_and_grad(x_new)[1], state.g)

        s = x_new - state.x
        y = g_new - state.g
        sy = jnp.dot(s, y)
        slot = jnp.mod(state.k, memory)
        update_hist = accepted & (sy > 1e-10)
        s_hist = jnp.where(update_hist, state.s_hist.at[slot].set(s), state.s_hist)
        y_hist = jnp.where(update_hist, state.y_hist.at[slot].set(y), state.y_hist)
        rho = jnp.where(
            update_hist, state.rho.at[slot].set(1.0 / jnp.maximum(sy, 1e-20)), state.rho
        )
        small_grad = jnp.max(jnp.abs(g_new)) < gtol
        small_decrease = (
            accepted
            & (ftol > 0.0)
            & ((state.f - f_new) <= ftol * jnp.maximum(jnp.abs(f_new), 1.0))
        )
        small_count = jnp.where(small_decrease, state.small_count + 1, 0)
        converged = small_grad | (small_count >= ftol_patience)
        unhalved = accepted & (num_halvings == 0)
        t_init_next = jnp.where(
            unhalved | ~accepted,
            jnp.asarray(1.0, state.x.dtype),
            jnp.minimum(jnp.asarray(1.0, state.x.dtype), t * 4.0),
        )
        new_state = lbfgs._LbfgsState(
            x=x_new,
            f=f_new,
            g=g_new,
            s_hist=s_hist,
            y_hist=y_hist,
            rho=rho,
            k=state.k + 1,
            done=converged | ~accepted,
            t_init=t_init_next,
            small_count=small_count,
        )
        return new_state, num_halvings

    return init, cond, step


_parent_plus_a = functools.partial(_parent_loop, finished_rows_search=False)


def _minimize(loop, loss_fn, x0):
    init, cond, step = loop(loss_fn, x0, **OPTIONS)
    final = jax.lax.while_loop(cond, lambda state: step(state)[0], init)
    return final.x, final.f


def _drive(loop, loss_fn, x0s):
    """The vmapped loop driven from Python, rows masked as the batched
    ``while_loop`` masks them. Yields, for every iteration the program makes,
    ``(state before, state after, halvings, live)``; ``halvings`` is each
    row's own count, and the batched search makes the largest of them."""

    def init_row(x0):
        return loop(loss_fn, x0, **OPTIONS)[0]

    def step_row(state):
        _, cond, step = loop(loss_fn, state.x, **OPTIONS)
        live = cond(state)
        new, halvings = step(state)
        kept = jax.tree_util.tree_map(lambda a, b: jnp.where(live, a, b), new, state)
        return kept, halvings, live

    step_rows = jax.jit(jax.vmap(step_row))
    state = jax.jit(jax.vmap(init_row))(x0s)
    while True:
        new, halvings, live = step_rows(state)
        if not np.any(live):
            return
        yield state, new, np.asarray(halvings), np.asarray(live)
        state = new


def _exact_gp_nll(pad: int, trials: int, dim: int, seed: int):
    """The exact GP's ARD loss on a noisy quadratic (``default20d``'s
    objective) — over the flat unconstrained vector and over the parameter
    tree — and ``starts(rows)``, that many random starts."""
    model = gp_lib.VizierGaussianProcess(num_continuous=dim, num_categorical=0)
    rng = np.random.default_rng(seed)
    x = rng.uniform(size=(pad, dim)).astype(np.float32)
    y = -np.sum((x - 0.5) ** 2, axis=1) + 0.1 * rng.normal(size=pad)
    y = ((y - y.mean()) / y.std()).astype(np.float32)
    mask = np.arange(pad) < trials
    data = gp_lib.GPData(
        continuous=jnp.asarray(x),
        categorical=jnp.zeros((pad, 0), jnp.int32),
        labels=jnp.asarray(np.where(mask, y, 0.0), jnp.float32),
        row_mask=jnp.asarray(mask),
        cont_dim_mask=jnp.ones((dim,), bool),
        cat_dim_mask=jnp.ones((0,), bool),
    )
    coll = model.param_collection()
    template = coll.random_init_unconstrained(jax.random.PRNGKey(0))
    _, unravel = jax.flatten_util.ravel_pytree(template)

    def loss(flat):
        return model.neg_log_likelihood(unravel(flat), data)

    def tree_loss(params):
        return model.neg_log_likelihood(params, data)

    def starts(rows: int):
        keys = jax.random.split(jax.random.PRNGKey(seed), rows)
        return jax.vmap(coll.random_init_unconstrained)(keys)

    return loss, tree_loss, starts


def _flat(inits):
    return jax.vmap(lambda p: jax.flatten_util.ravel_pytree(p)[0])(inits)


# Two rows of which row 0 is done after 14 of the program's 26 iterations and
# would halve twice in each of the other 12: found by a scan over seeds
# (pad 64 at 20-D rarely halves at all).
LOCKSTEP_PROBLEM = dict(pad=64, trials=60, dim=20, seed=15)


def _evaluations(minimize, loss_fn, x0s) -> int:
    """How often the vmapped program evaluates the loss: a callback without
    arguments is not batched, so it fires once a trip whatever the rows."""
    calls = []

    def counted(x):
        jax.debug.callback(lambda: calls.append(1))
        return loss_fn(x)

    jax.block_until_ready(jax.jit(jax.vmap(lambda x0: minimize(counted, x0)))(x0s))
    jax.effects_barrier()
    return len(calls)


def test_a_finished_row_does_not_search():
    loss, _, starts = _exact_gp_nll(**LOCKSTEP_PROBLEM)
    x0s = _flat(starts(2))

    parent = list(_drive(_parent_loop, loss, x0s))
    replayed = [int(h[~live].max()) for _, _, h, live in parent if not live.all()]
    assert len(replayed) >= 10 and min(replayed) >= 2, replayed
    parent_trips = sum(int(h.max()) for _, _, h, _ in parent)
    live_rows_own = sum(int(h[live].max()) for _, _, h, live in parent)
    assert (parent_trips, live_rows_own) == (27, 3)

    # (a) alone: the live rows' arithmetic is the parent's, so the search
    # makes the live rows' own halvings, and no other.
    alone = list(_drive(_parent_plus_a, loss, x0s))
    assert len(alone) == len(parent)
    assert sum(int(h.max()) for _, _, h, _ in alone) == live_rows_own

    ours = list(_drive(lbfgs._lbfgs_loop, loss, x0s))
    for _, _, h, live in ours:
        assert not h[~live].any(), (h, live)
    our_trips = sum(int(h.max()) for _, _, h, _ in ours)
    assert our_trips == live_rows_own

    # The same, counted in the vmapped program itself: one evaluation at the
    # start, then in every iteration the one at t0, the halvings, and one
    # more forward pass (the parent's value_and_grad; ours replays the
    # callback, not the factorisation, in the checkpointed backward pass).
    def parent_minimize(loss_fn, x0):
        return _minimize(_parent_loop, loss_fn, x0)

    def our_minimize(loss_fn, x0):
        return lbfgs.lbfgs_minimize(loss_fn, x0, **OPTIONS)

    assert _evaluations(parent_minimize, loss, x0s) == 1 + 2 * len(parent) + parent_trips
    assert _evaluations(our_minimize, loss, x0s) == 1 + 2 * len(ours) + our_trips


@functools.lru_cache(maxsize=None)
def _parent_run(rows: int):
    """The exact GP's loss at pad 64, ``rows`` random starts (0: one start,
    un-vmapped) and what the parent's body returns from them."""
    loss, tree_loss, starts = _exact_gp_nll(pad=64, trials=60, dim=20, seed=3)
    inits = starts(max(rows, 1))
    x, f = _run(_parent_loop, loss, _flat(inits), rows)
    return loss, tree_loss, inits, np.asarray(x), np.asarray(f)


def _run(loop, loss, x0s, rows: int):
    if rows == 0:
        return jax.jit(lambda x0: _minimize(loop, loss, x0))(x0s[0])
    return jax.jit(jax.vmap(lambda x0: _minimize(loop, loss, x0)))(x0s)


ROWS = pytest.mark.parametrize(
    "rows", [0, 2, 5, 40], ids=lambda r: f"rows{r}" if r else "no_vmap"
)


@ROWS
def test_stopping_finished_rows_moves_no_bit(rows):
    """(a) touches no live row's arithmetic: every row's ``(x, f)`` is the
    parent's to the bit, un-vmapped and 2, 5 and 40 rows wide."""
    loss, _, inits, x_parent, f_parent = _parent_run(rows)
    x_alone, f_alone = _run(_parent_plus_a, loss, _flat(inits), rows)
    np.testing.assert_array_equal(np.asarray(x_alone), x_parent)
    np.testing.assert_array_equal(np.asarray(f_alone), f_parent)


@ROWS
def test_optimizer_returns_the_parents_losses(rows):
    """(a) + (b) against the parent. Not to the bit: the carried gradient is
    the same backward pass compiled in another place, and XLA orders its sums
    differently there (first seen as half an ulp of one gradient entry in
    iteration 6 of a run, which L-BFGS then amplifies), so the losses are held
    to 1e-5 x max(|f|, 1) of the parent's. Largest difference seen on these
    cases (CPU): 3.1e-5 at |f| = 86, 3.5e-7 of it."""
    loss, tree_loss, inits, _, f_parent = _parent_run(rows)
    if rows == 0:
        x0 = _flat(inits)[0]
        _, ours = jax.jit(lambda x: lbfgs.lbfgs_minimize(loss, x, **OPTIONS))(x0)
    else:
        ours = jax.jit(lambda i: lbfgs.LbfgsOptimizer()(tree_loss, i))(inits).losses
    difference = np.abs(np.asarray(ours) - f_parent)
    assert np.all(difference <= 1e-5 * np.maximum(np.abs(f_parent), 1.0)), difference


@pytest.mark.parametrize("rows", [2, 5])
def test_carried_gradient_is_the_accepted_points(rows):
    """(b): at every accepted step of every live row the state's gradient,
    taken from the search's own evaluation, is ``jax.grad(loss)`` there
    (equal to the bit in all 44 and 109 accepted steps where this was
    written; held to 1e-6 of the gradient's largest entry)."""
    loss, _, starts = _exact_gp_nll(pad=64, trials=60, dim=20, seed=5)
    grad = jax.jit(jax.vmap(jax.grad(loss)))
    accepted_steps = 0
    for before, after, _, live in _drive(lbfgs._lbfgs_loop, loss, _flat(starts(rows))):
        moved = live & np.any(np.asarray(after.x) != np.asarray(before.x), axis=1)
        expected = np.asarray(grad(after.x))
        scale = np.max(np.abs(expected), axis=1, keepdims=True)
        err = np.abs(np.asarray(after.g) - expected) / scale
        assert np.all(err[moved] <= 1e-6), err[moved].max()
        # A rejected search keeps the gradient it had.
        stuck = live & ~moved
        np.testing.assert_array_equal(np.asarray(after.g)[stuck], np.asarray(before.g)[stuck])
        accepted_steps += int(moved.sum())
    assert accepted_steps >= 20 * rows


# -- the train returns its own work (PR 39) -----------------------------------
# ``lbfgs_minimize_counted`` hands out, beside (x, f), the iterations a row ran
# and the loss evaluations it made; ``_drive`` above makes the same counts by
# hand, one program iteration at a time.

COUNTED_PROBLEMS = pytest.mark.parametrize(
    "problem, rows",
    [(LOCKSTEP_PROBLEM, 2), (dict(pad=64, trials=60, dim=20, seed=3), 5),
     (dict(pad=64, trials=60, dim=20, seed=5), 2)],
    ids=["lockstep_seed15_rows2", "seed3_rows5", "seed5_rows2"],
)


def _counted(loss, x0s):
    return jax.jit(jax.vmap(lambda x0: lbfgs.lbfgs_minimize_counted(loss, x0, **OPTIONS)))(x0s)


@COUNTED_PROBLEMS
def test_returned_counts_are_the_hand_driven_loops(problem, rows):
    """Row for row under the ``vmap``: iterations = the program iterations the
    row was live in, evaluations = the one at its start, then the one at
    ``t0`` and one a halving in each of them. A finished row's counts stop
    while the loop goes on for the others."""
    loss, _, starts = _exact_gp_nll(**problem)
    x0s = _flat(starts(rows))
    iterations = np.zeros(rows, np.int64)
    evaluations = np.ones(rows, np.int64)
    trips = 0
    for _, _, halvings, live in _drive(lbfgs._lbfgs_loop, loss, x0s):
        iterations += live
        evaluations += np.where(live, 1 + halvings, 0)
        trips += 1
    _, _, got_iterations, got_evaluations = _counted(loss, x0s)
    assert got_iterations.dtype == got_evaluations.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got_iterations), iterations)
    np.testing.assert_array_equal(np.asarray(got_evaluations), evaluations)
    # The loop ran for its slowest row, and some row stopped before it.
    assert iterations.max() == trips and iterations.min() < trips, iterations
    assert np.all(evaluations >= 1 + iterations)


@COUNTED_PROBLEMS
def test_counting_moves_no_bit(problem, rows):
    """``x`` and ``f`` are those of a run that carries no counter: the file's
    own ``_minimize`` over ``_lbfgs_loop``, and ``lbfgs_minimize``."""
    loss, _, starts = _exact_gp_nll(**problem)
    x0s = _flat(starts(rows))
    x, f, _, _ = _counted(loss, x0s)
    x_plain, f_plain = _run(lbfgs._lbfgs_loop, loss, x0s, rows)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(x_plain))
    np.testing.assert_array_equal(np.asarray(f), np.asarray(f_plain))
    x_two, f_two = jax.jit(jax.vmap(lambda x0: lbfgs.lbfgs_minimize(loss, x0, **OPTIONS)))(x0s)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(x_two))
    np.testing.assert_array_equal(np.asarray(f), np.asarray(f_two))


@pytest.mark.parametrize("best_n", [None, 2], ids=["best", "top2"])
def test_optimizer_result_carries_every_rows_counts(best_n):
    """``OptimizeResult.iterations`` / ``.evaluations`` are ``[num_restarts]``
    whatever ``best_n`` keeps, and ``work()`` stacks them for ONE read."""
    loss, tree_loss, starts = _exact_gp_nll(**LOCKSTEP_PROBLEM)
    inits = starts(3)
    result = jax.jit(lambda i: lbfgs.LbfgsOptimizer()(tree_loss, i, best_n=best_n))(inits)
    _, _, iterations, evaluations = _counted(loss, _flat(inits))
    np.testing.assert_array_equal(np.asarray(result.iterations), np.asarray(iterations))
    np.testing.assert_array_equal(np.asarray(result.evaluations), np.asarray(evaluations))
    work = np.asarray(result.work())
    assert work.shape == (2, 3) and work.dtype == np.int32
    np.testing.assert_array_equal(work, np.stack([iterations, evaluations]))


def test_adam_counts_its_scan():
    _, tree_loss, starts = _exact_gp_nll(**LOCKSTEP_PROBLEM)
    result = jax.jit(lambda i: lbfgs.AdamOptimizer(maxiter=7)(tree_loss, i))(starts(2))
    np.testing.assert_array_equal(np.asarray(result.work()), [[7, 7], [8, 8]])


@pytest.mark.parametrize(
    "work, expected",
    [
        # One sequential train: the warm seed's row done after 3 of 5 trips.
        ([[3, 5], [7, 12]],
         dict(programs=1, loop_trips=5, rows=2, row_trips=10, row_iterations=8, evaluations=19)),
        # A fused flush of two slots (the second a padded copy): ONE loop.
        ([[[3, 5], [7, 12]], [[2, 9], [4, 20]]],
         dict(programs=1, loop_trips=9, rows=4, row_trips=36, row_iterations=19, evaluations=43)),
        # Nothing iterated (every row converged at its start).
        ([[0, 0], [1, 1]],
         dict(programs=1, loop_trips=0, rows=2, row_trips=0, row_iterations=0, evaluations=2)),
    ],
    ids=["sequential", "flush_slots", "no_iteration"],
)
def test_work_counts_of_one_program(work, expected):
    assert lbfgs.work_counts(np.asarray(work, np.int32)) == expected
