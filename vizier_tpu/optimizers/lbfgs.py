"""ARD hyperparameter optimizers: pure-JAX L-BFGS with vmapped restarts.

TPU-first replacement for the reference's scipy-driven L-BFGS-B
(``/root/reference/vizier/_src/jax/optimizers/jaxopt_wrappers.py:113,234`` and
``optax_wrappers.py:38``): bounds are handled by the soft-clip
reparameterization (``models.params``), so plain L-BFGS suffices — the whole
multi-restart train is ONE jitted XLA program: ``vmap`` over restarts, no
host round-trips, shardable over the ``restarts`` mesh axis
(``vizier_tpu.parallel``).

The L-BFGS here is a compact hand-rolled implementation (two-loop recursion
over fixed-size history buffers + Armijo backtracking line search in a
bounded ``while_loop``). Library zoom line searches produce enormous XLA
graphs under vmap; this one keeps compile times in seconds and contains only
fixed-shape ops.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, NamedTuple, Optional, Protocol, Tuple

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import optax

from vizier_tpu.models import params as params_lib

Array = jax.Array
Params = params_lib.Params
LossFn = Callable[[Params], Array]

# Matches the reference's published ARD budget (vizier/jax/optimizers.py:30).
DEFAULT_RANDOM_RESTARTS = 4


# The longest step of one iteration, per coordinate of the unconstrained
# vector. The hyperparameters are soft-clipped (``models.params.SoftClip``: a
# sigmoid, flat beyond |x| ~ 10), and an ARD loss on near noise-free labels
# has gradients of 1e3-1e4 where the noise is set too small: a first step
# ``x - t * g`` then lands every parameter on a saturated bound, where the loss
# can still be far below the start's, so Armijo accepts it, and where the
# gradient vanishes, so the run ends "converged" on a junk fit (amplitude 100,
# length scales 0.005; or amplitude 0.01, noise 1 — PERF.md, PR 29). Held to
# one unit a step, a run crosses the whole useful range in a few iterations
# and cannot jump onto the flat part.
MAX_STEP = 1.0


class OptimizeResult(NamedTuple):
    params: Params  # best (or top-k stacked) unconstrained params
    losses: Array  # [num_restarts] final losses
    best_loss: Array
    # What each restart row cost (int32): under the ``vmap`` over restarts
    # the rows share one ``while_loop``, which runs ``max(iterations)`` trips.
    iterations: Array  # [num_restarts] iterations the row ran
    evaluations: Array  # [num_restarts] loss evaluations the row made

    def work(self) -> Array:
        """``[2, num_restarts]`` int32, iterations over evaluations: the one
        small array a train program hands out beside its fit
        (:func:`work_counts` reads it on the host)."""
        return jnp.stack([self.iterations, self.evaluations])


def work_counts(work) -> Dict[str, int]:
    """What ONE train program's batched L-BFGS loop did, from the fetched
    :meth:`OptimizeResult.work` of every row it ran in lockstep (any leading
    axes: a fused flush's slots, padded ones included).

    ``loop_trips`` is the loop's own count, the largest row's; ``row_trips``
    what lockstep ran (rows x trips) against ``row_iterations``, what the
    rows needed; ``evaluations`` the rows' loss evaluations (for an ARD loss
    a Cholesky each).
    """
    work = np.asarray(work)
    iterations, evaluations = work[..., 0, :], work[..., 1, :]
    trips = int(iterations.max())
    return {
        "programs": 1,
        "loop_trips": trips,
        "rows": int(iterations.size),
        "row_trips": trips * int(iterations.size),
        "row_iterations": int(iterations.sum()),
        "evaluations": int(evaluations.sum()),
    }


class Optimizer(Protocol):
    """(loss_fn, batched inits) -> best unconstrained params + diagnostics."""

    def __call__(
        self, loss_fn: LossFn, init_batch: Params, *, best_n: Optional[int] = None
    ) -> OptimizeResult:
        ...


def _keep_factorisations(prim, *_, **__) -> bool:
    """The ``jax.checkpoint`` policy of a line-search evaluation: of a forward
    pass keep the factors and the triangular solves, recompute the rest.

    The search evaluates the loss at every trial point and the accepted
    one's gradient is then taken from that evaluation (``lbfgs_minimize``),
    so what the backward pass needs rides the search's loop carry. All of
    it would be every intermediate of the loss (an exact GP's NLL at
    512 x 20-D: 38.6 MB a row, a ``[512, 512, 20]`` tensor the fused Gram
    never writes among them); the factor and its two solves are 1.1 MB, and
    what the backward pass recomputes from them is the Gram, one fused pass.
    A loss without a factorisation keeps nothing and its backward pass
    recomputes the forward one, which is what ``value_and_grad`` cost.
    """
    return prim.name in ("cholesky", "triangular_solve")


class _LbfgsState(NamedTuple):
    x: Array  # [n] current point
    f: Array  # scalar loss
    g: Array  # [n] gradient
    s_hist: Array  # [m, n] position diffs
    y_hist: Array  # [m, n] gradient diffs
    rho: Array  # [m] 1 / (s·y)
    k: Array  # iteration counter (int32)
    done: Array  # bool convergence flag
    t_init: Array  # initial line-search step for the next iteration
    small_count: Array  # consecutive iterations with sub-ftol decrease


def _two_loop_direction(state: _LbfgsState, memory: int) -> Array:
    """H·g via the standard two-loop recursion over the circular history."""
    q = state.g
    k = state.k
    valid_count = jnp.minimum(k, memory)

    def bwd(i, carry):
        q, alphas = carry
        # i = 0 is the newest pair.
        idx = jnp.mod(k - 1 - i, memory)
        valid = i < valid_count
        alpha = jnp.where(valid, state.rho[idx] * jnp.dot(state.s_hist[idx], q), 0.0)
        q = q - jnp.where(valid, alpha, 0.0) * state.y_hist[idx]
        alphas = alphas.at[i].set(alpha)
        return q, alphas

    q, alphas = jax.lax.fori_loop(0, memory, bwd, (q, jnp.zeros(memory, q.dtype)))

    # Initial Hessian scaling gamma = s·y / y·y of the newest pair.
    newest = jnp.mod(k - 1, memory)
    sy = jnp.dot(state.s_hist[newest], state.y_hist[newest])
    yy = jnp.dot(state.y_hist[newest], state.y_hist[newest])
    gamma = jnp.where((k > 0) & (yy > 1e-20), sy / yy, 1.0)
    r = gamma * q

    def fwd(i, r):
        # Reverse order: oldest first = i counts from the back.
        j = memory - 1 - i
        idx = jnp.mod(k - 1 - j, memory)
        valid = j < valid_count
        beta = jnp.where(valid, state.rho[idx] * jnp.dot(state.y_hist[idx], r), 0.0)
        return r + jnp.where(valid, alphas[j] - beta, 0.0) * state.s_hist[idx]

    return jax.lax.fori_loop(0, memory, fwd, r)


def _lbfgs_loop(
    loss_fn: Callable[[Array], Array],
    x0: Array,
    *,
    maxiter: int,
    memory: int,
    max_linesearch_steps: int,
    gtol: float,
    ftol: float,
    ftol_patience: int,
    armijo_c1: float,
):
    """``(init, cond, step)`` of ``lbfgs_minimize``'s ``while_loop``.

    ``step`` returns the next state and the halvings its line search made.
    """
    f0, g0 = jax.value_and_grad(loss_fn)(x0)
    n = x0.shape[0]
    init = _LbfgsState(
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
    kept_loss = jax.checkpoint(loss_fn, policy=_keep_factorisations, prevent_cse=False)

    def evaluate(x: Array):
        """``loss_fn(x)`` and its pullback, flattened: the residual arrays (a
        valid ``while_loop`` carry, which the pullback itself is not: its
        treedef holds a jaxpr, and two traces' compare unequal) and the
        treedef that makes any such list a pullback again."""
        f, pullback = jax.vjp(kept_loss, x)
        residuals, treedef = jax.tree_util.tree_flatten(pullback)
        return f, residuals, treedef

    def cond(state: _LbfgsState) -> Array:
        return (state.k < maxiter) & ~state.done

    def step(state: _LbfgsState) -> Tuple[_LbfgsState, Array]:
        d = -_two_loop_direction(state, memory)
        # Fall back to steepest descent if d is not a descent direction.
        gd = jnp.dot(state.g, d)
        bad = (gd >= 0.0) | ~jnp.isfinite(gd)
        d = jnp.where(bad, -state.g, d)
        gd = jnp.where(bad, -jnp.dot(state.g, state.g), gd)

        # Armijo backtracking: t <- t/2 until sufficient decrease. Each
        # trial is a full evaluation (for an ARD loss a Cholesky), so an
        # iteration makes only those whose results it keeps.
        # Under a ``vmap`` over restarts the outer ``while_loop`` runs this
        # step on EVERY row until the last row's ``cond`` is false and
        # selects the old state for the rows that are done, and the search
        # is a second batched ``while_loop`` that runs until every row's
        # ``ls_cond`` is false. A finished row's state is frozen: it would
        # take the same direction from the same point and fail Armijo the
        # same number of times in every later iteration, with the live rows
        # waiting for it (44-71 % of a warm train's halvings were such
        # replays; PERF.md, PR 36). So ``ls_cond`` reads ``live``: a
        # finished row's evaluation at ``t0`` still runs in lockstep, as it
        # must, and its halvings do not.
        live = cond(state)

        def ls_cond(carry):
            t, f_new, _, i = carry
            insufficient = f_new > state.f + armijo_c1 * t * gd
            return (
                live
                & (insufficient | ~jnp.isfinite(f_new))
                & (i < max_linesearch_steps)
            )

        def ls_body(carry):
            t, _, _, i = carry
            t = t * 0.5
            f_new, residuals, _ = evaluate(state.x + t * d)
            return t, f_new, residuals, i + 1

        # Warm-started line search: restarting at t=1 every iteration costs
        # ~6-8 halvings per iteration on ill-scaled ARD losses (measured
        # 291-386 line-search evals per restart on the 1000x20d bench
        # problem); started at 4x the last accepted step, the live rows of a
        # warm train make 0.7-1.6 an iteration (counted on the CPU at
        # 512 x 20-D; PERF.md, PR 36).
        # When the warm-started t0 is accepted WITHOUT halving, larger steps
        # may have been available, so the next iteration resets to a full
        # step — otherwise a capped step cascade can stall ill-conditioned
        # runs far from the optimum.
        # No trial point lies further than MAX_STEP from the current one in
        # any coordinate.
        t0 = jnp.minimum(state.t_init, MAX_STEP / jnp.maximum(jnp.max(jnp.abs(d)), 1e-30))
        f_t0, residuals_t0, pullback_tree = evaluate(state.x + t0 * d)
        t, f_new, residuals, num_halvings = jax.lax.while_loop(
            ls_cond, ls_body, (t0, f_t0, residuals_t0, jnp.asarray(0))
        )
        accepted = jnp.isfinite(f_new) & (f_new <= state.f)
        x_new = jnp.where(accepted, state.x + t * d, state.x)
        # The accepted point was evaluated in the search: its gradient is
        # that evaluation's backward pass, not a second forward one.
        (g_trial,) = jax.tree_util.tree_unflatten(pullback_tree, residuals)(
            jnp.ones_like(f_new)
        )
        f_new = jnp.where(accepted, f_new, state.f)
        g_new = jnp.where(accepted, g_trial, state.g)

        s = x_new - state.x
        y = g_new - state.g
        sy = jnp.dot(s, y)
        slot = jnp.mod(state.k, memory)
        update_hist = accepted & (sy > 1e-10)
        s_hist = jnp.where(
            update_hist, state.s_hist.at[slot].set(s), state.s_hist
        )
        y_hist = jnp.where(
            update_hist, state.y_hist.at[slot].set(y), state.y_hist
        )
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
        new_state = _LbfgsState(
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


def lbfgs_minimize_counted(
    loss_fn: Callable[[Array], Array],
    x0: Array,
    *,
    maxiter: int = 50,
    memory: int = 10,
    max_linesearch_steps: int = 20,
    gtol: float = 1e-5,
    ftol: float = 1e-6,
    ftol_patience: int = 2,
    armijo_c1: float = 1e-4,
) -> Tuple[Array, Array, Array, Array]:
    """Minimizes a flat-vector loss; returns (x, f(x)) and the run's own
    work: the iterations it made and its loss evaluations (the one at ``x0``,
    then the one at ``t0`` and one a halving in every iteration), both
    int32. jit/vmap-safe; under a ``vmap`` a finished row's counts stop
    while the loop goes on for the others.

    ``ftol`` is a scipy-style relative-decrease stop: once ``ftol_patience``
    CONSECUTIVE accepted steps each improve the loss by less than
    ``ftol * max(|f|, 1)`` the run is converged (``ftol <= 0`` disables).
    The patience matters: a single small decrease can come from a step
    capped by the line-search warm start rather than a true plateau, and
    stopping there returns a bad optimum on ill-scaled problems. Without
    any ftol stop every restart burns the full ``maxiter`` budget — at
    1000 trials each iteration is a padded-1024 Cholesky, and the ARD loss
    plateaus ~25-40% before the budget (measured on the bench problem).
    """
    init, cond, step = _lbfgs_loop(
        loss_fn,
        x0,
        maxiter=maxiter,
        memory=memory,
        max_linesearch_steps=max_linesearch_steps,
        gtol=gtol,
        ftol=ftol,
        ftol_patience=ftol_patience,
        armijo_c1=armijo_c1,
    )

    def body(carry):
        state, evaluations = carry
        state, num_halvings = step(state)
        return state, evaluations + 1 + num_halvings

    final, evaluations = jax.lax.while_loop(
        lambda carry: cond(carry[0]), body, (init, jnp.asarray(1, jnp.int32))
    )
    return final.x, final.f, final.k, evaluations


def lbfgs_minimize(
    loss_fn: Callable[[Array], Array], x0: Array, **options
) -> Tuple[Array, Array]:
    """:func:`lbfgs_minimize_counted` (its options) without the counts."""
    x, f, _, _ = lbfgs_minimize_counted(loss_fn, x0, **options)
    return x, f


def _select_best(
    finals: Params,
    losses: Array,
    best_n: Optional[int],
    iterations: Array,
    evaluations: Array,
) -> OptimizeResult:
    losses = jnp.where(jnp.isfinite(losses), losses, jnp.inf)
    if best_n is None:
        best = jnp.argmin(losses)
        best_params = jax.tree_util.tree_map(lambda a: a[best], finals)
        return OptimizeResult(
            best_params, losses, losses[best], iterations, evaluations
        )
    _, top_idx = jax.lax.top_k(-losses, best_n)
    top_params = jax.tree_util.tree_map(lambda a: a[top_idx], finals)
    return OptimizeResult(
        top_params, losses, losses[top_idx[0]], iterations, evaluations
    )


@dataclasses.dataclass(frozen=True)
class LbfgsOptimizer:
    """Multi-restart L-BFGS, fully jitted; ``best_n`` keeps an ensemble."""

    maxiter: int = 50
    memory_size: int = 10
    max_linesearch_steps: int = 20
    gtol: float = 1e-5
    ftol: float = 1e-6  # <= 0 disables the relative-decrease stop
    ftol_patience: int = 2

    def __call__(
        self, loss_fn: LossFn, init_batch: Params, *, best_n: Optional[int] = None
    ) -> OptimizeResult:
        template = jax.tree_util.tree_map(lambda a: a[0], init_batch)
        _, unravel = jax.flatten_util.ravel_pytree(template)

        def flat_loss(x: Array) -> Array:
            return loss_fn(unravel(x))

        def run_one(init: Params) -> Tuple[Params, Array, Array, Array]:
            x0, _ = jax.flatten_util.ravel_pytree(init)
            x, f, iterations, evaluations = lbfgs_minimize_counted(
                flat_loss,
                x0,
                maxiter=self.maxiter,
                memory=self.memory_size,
                max_linesearch_steps=self.max_linesearch_steps,
                gtol=self.gtol,
                ftol=self.ftol,
                ftol_patience=self.ftol_patience,
            )
            return unravel(x), f, iterations, evaluations

        finals, losses, iterations, evaluations = jax.vmap(run_one)(init_batch)
        return _select_best(finals, losses, best_n, iterations, evaluations)


@dataclasses.dataclass(frozen=True)
class AdamOptimizer:
    """Adam fallback (parity with the reference's OptaxTrain wrapper)."""

    learning_rate: float = 5e-2
    maxiter: int = 200

    def __call__(
        self, loss_fn: LossFn, init_batch: Params, *, best_n: Optional[int] = None
    ) -> OptimizeResult:
        opt = optax.adam(self.learning_rate)

        def run_single(init: Params) -> Tuple[Params, Array]:
            def step(carry, _):
                prms, state = carry
                value, grad = jax.value_and_grad(loss_fn)(prms)
                updates, state = opt.update(grad, state, prms)
                prms = optax.apply_updates(prms, updates)
                return (prms, state), value

            (final, _), _ = jax.lax.scan(
                step, (init, opt.init(init)), None, length=self.maxiter
            )
            return final, loss_fn(final)

        finals, losses = jax.vmap(run_single)(init_batch)
        # A scan: every row makes every step, and one evaluation more.
        steps = jnp.full(losses.shape, self.maxiter, jnp.int32)
        return _select_best(finals, losses, best_n, steps, steps + 1)


def default_optimizer() -> Optimizer:
    return LbfgsOptimizer()
