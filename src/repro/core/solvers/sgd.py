"""Stochastic-gradient linear solver with Polyak iterate averaging.

The follow-up paper "Scalable Gaussian Processes with Latent Kronecker
Structure" (arXiv 2506.06895) replaces CG with SGD-style solves of
``A x = b`` when the config axis n grows 10-100x: each sweep is one operator
application (the same cost as a CG sweep) but the iteration is a plain
heavy-ball step, so it tolerates low precision and never breaks down on an
indefinite ``p^T A p``. Solving the quadratic

    f(x) = 0.5 x^T A x - b^T x        (grad f = A x - b = -r)

by gradient descent with momentum gives the update

    v <- momentum * v + r
    x <- x + lr * v

with ``lr ~ 1 / lambda_max(A)`` estimated by power iteration when not given.
Polyak (tail) averaging smooths the last-iterate oscillation: the running
mean of the iterates past a burn-in is tracked alongside the running mean of
their residuals (free, by linearity of ``r = b - A x``), and the averaged
iterate is returned per system whenever its residual beats the last
iterate's.

Diagnostics mirror :class:`repro.core.solvers.cg.CGResult` exactly —
per-column convergence freezing, ``col_iters``, active-column ``matvecs``,
TRUE final residual — so engines and posteriors consume SGD solves
unchanged. ``breakdown`` flags non-finite iterates (divergence), the SGD
analogue of CG's indefinite-operator breakdown.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from .. import telemetry
from .cg import CGResult, _dot, eager

__all__ = ["sgd_solve", "estimate_lmax"]


def estimate_lmax(A: Callable, b: jnp.ndarray, iters: int = 8) -> jnp.ndarray:
    """Largest eigenvalue of SPD ``A`` by power iteration started at ``b``.

    ``b`` may carry leading system dims; every system runs its own power
    iteration (sharing the batched operator sweeps) and the max over
    systems is returned — one scalar, since all systems share the same
    operator. All-zero systems contribute 0 and are ignored by the max.
    """
    nrm = jnp.sqrt(_dot(b, b))
    v0 = b / jnp.where(nrm == 0, 1.0, nrm)[..., None, None]

    def body(_, carry):
        v, lam = carry
        w = A(v)
        lam = jnp.sqrt(_dot(w, w))
        v = w / jnp.where(lam == 0, 1.0, lam)[..., None, None]
        return v, lam

    _, lam = jax.lax.fori_loop(0, iters, body,
                               (v0, jnp.zeros(b.shape[:-2], b.dtype)))
    return jnp.max(lam)


def sgd_solve(A: Callable[[jnp.ndarray], jnp.ndarray], b: jnp.ndarray,
              tol: float = 0.01, max_iters: int = 500,
              x0: jnp.ndarray | None = None, momentum: float = 0.9,
              lr: float = 0.0, lr_iters: int = 8,
              avg_frac: float = 0.5) -> CGResult:
    """Solve SPD ``A x = b`` by heavy-ball gradient descent + Polyak tail
    averaging, on grid-form (..., n, m) right-hand-side stacks.

    ``lr <= 0`` auto-tunes the step size to ``1 / lambda_max(A)`` via
    ``lr_iters`` power-iteration sweeps (stable for any momentum in
    [0, 1)). Averaging starts after ``avg_frac * max_iters`` sweeps; the
    averaged iterate is used per system only where its (exactly tracked)
    residual beats the last iterate's. Semantics otherwise match
    :func:`repro.core.solvers.cg.cg_solve`: converged columns freeze and
    stop counting toward ``matvecs``, and the reported ``rel_residual`` is
    the true final ``||b - A x|| / ||b||``.
    """
    if eager(A, b, x0):
        telemetry.count("solver.eager")
    if x0 is None:
        x0 = jnp.zeros_like(b)
    b_norm = jnp.sqrt(_dot(b, b))
    safe_b_norm = jnp.where(b_norm == 0, 1.0, b_norm)
    sys_shape = b.shape[:-2]

    if lr and lr > 0:
        step_size = jnp.asarray(lr, b.dtype)
    else:
        lam = estimate_lmax(A, b, iters=lr_iters)
        step_size = 1.0 / jnp.where(lam == 0, 1.0, lam)

    avg_start = int(max_iters * avg_frac)
    r0 = b - A(x0)
    state0 = dict(
        x=x0, v=jnp.zeros_like(b), r=r0, it=jnp.int32(0),
        breakdown=jnp.zeros(sys_shape, bool),
        col_iters=jnp.zeros(sys_shape, jnp.int32), matvecs=jnp.int32(0),
        x_sum=jnp.zeros_like(b), r_sum=jnp.zeros_like(b),
        avg_cnt=jnp.zeros(sys_shape, jnp.int32),
    )

    def active_mask(state):
        rel = jnp.sqrt(_dot(state["r"], state["r"])) / safe_b_norm
        return jnp.logical_and(rel > tol, ~state["breakdown"])

    def cond(state):
        return jnp.logical_and(jnp.any(active_mask(state)),
                               state["it"] < max_iters)

    def body(state):
        it = state["it"]
        active = active_mask(state)
        am = active[..., None, None]
        v = jnp.where(am, momentum * state["v"] + state["r"], state["v"])
        x = jnp.where(am, state["x"] + step_size * v, state["x"])
        r = jnp.where(am, b - A(x), state["r"])
        # Divergence shows up as inf/nan in the residual: flag it as
        # breakdown (freezing the column) rather than looping to max_iters.
        blew_up = jnp.logical_and(active, ~jnp.all(jnp.isfinite(r),
                                                   axis=(-2, -1)))
        do_avg = jnp.logical_and(active, it + 1 > avg_start)
        davg = do_avg[..., None, None]
        return dict(
            x=x, v=v, r=r, it=it + 1,
            breakdown=jnp.logical_or(state["breakdown"], blew_up),
            col_iters=jnp.where(active, it + 1, state["col_iters"]),
            matvecs=state["matvecs"] + jnp.sum(active, dtype=jnp.int32),
            x_sum=jnp.where(davg, state["x_sum"] + x, state["x_sum"]),
            r_sum=jnp.where(davg, state["r_sum"] + r, state["r_sum"]),
            avg_cnt=state["avg_cnt"] + do_avg.astype(jnp.int32),
        )

    state = jax.lax.while_loop(cond, body, state0)
    # Polyak average: mean of the tail iterates; by linearity of
    # r = b - A(x) its residual is the mean of the tail residuals, so the
    # averaged-vs-last choice costs no extra operator sweep.
    cnt = jnp.maximum(state["avg_cnt"], 1)[..., None, None].astype(b.dtype)
    x_avg = state["x_sum"] / cnt
    r_avg = state["r_sum"] / cnt
    use_avg = jnp.logical_and(
        state["avg_cnt"] > 0,
        _dot(r_avg, r_avg) < _dot(state["r"], state["r"]))
    x = jnp.where(use_avg[..., None, None], x_avg, state["x"])
    r_true = b - A(x)
    return CGResult(
        x=x, iters=state["it"],
        rel_residual=jnp.sqrt(_dot(r_true, r_true)) / safe_b_norm,
        breakdown=state["breakdown"], col_iters=state["col_iters"],
        matvecs=state["matvecs"])
