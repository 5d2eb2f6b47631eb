"""Preconditioned block CG on packed vectors.

The preconditioned variant of :mod:`repro.core.solvers.cg`: same per-column
freezing, breakdown flags, warm starts and TRUE-final-residual reporting,
but iterating on *packed* (..., N) vectors with an ``M_inv`` approximate
inverse applied to the whole RHS stack once per sweep (see
:mod:`repro.core.precond` for the pivoted-Cholesky/Woodbury construction).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from .. import telemetry
from .cg import CGResult, eager

__all__ = ["pcg_solve"]


def pcg_solve(A: Callable, b: jnp.ndarray, M_inv: Callable,
              tol: float = 0.01, max_iters: int = 10_000,
              x0: jnp.ndarray | None = None) -> CGResult:
    """Preconditioned block CG on packed vectors (..., N).

    ``M_inv`` approximates A^{-1} (see core.precond for the pivoted-Cholesky
    preconditioner) and is applied to the whole RHS stack in one batched
    sweep per iteration. The stopping rule monitors the unpreconditioned
    (recursively updated) residual, matching cg_solve; the *reported*
    ``rel_residual`` is the true final residual ``||b - Ax|| / ||b||``.
    Like :func:`repro.core.solvers.cg.cg_solve` it freezes converged
    columns, flags breakdown (``pAp <= 0``) per system, and warm-starts
    from ``x0``. An eager call runs eagerly (counted ``solver.eager``):
    compiled as one program like CG's loop, the preconditioner that the
    operator caches would be built again inside it on every call.
    """
    if eager(A, b, x0):
        telemetry.count("solver.eager")
    if x0 is None:
        x0 = jnp.zeros_like(b)
    b_norm = jnp.sqrt(jnp.sum(b * b, axis=-1))
    safe = jnp.where(b_norm == 0, 1.0, b_norm)
    sys_shape = b.shape[:-1]
    r0 = b - A(x0)
    z0 = M_inv(r0)
    rz0 = jnp.sum(r0 * z0, axis=-1)
    state0 = dict(x=x0, r=r0, z=z0, p=z0, rz=rz0, it=jnp.int32(0),
                  breakdown=jnp.zeros(sys_shape, bool),
                  col_iters=jnp.zeros(sys_shape, jnp.int32),
                  matvecs=jnp.int32(0))

    def active_mask(state):
        rel = jnp.sqrt(jnp.sum(state["r"] * state["r"], axis=-1)) / safe
        return jnp.logical_and(rel > tol, ~state["breakdown"])

    def cond(state):
        return jnp.logical_and(jnp.any(active_mask(state)),
                               state["it"] < max_iters)

    def body(state):
        x, r, z, p, rz = (state["x"], state["r"], state["z"], state["p"],
                          state["rz"])
        it = state["it"]
        active = active_mask(state)
        Ap = A(p)
        pAp = jnp.sum(p * Ap, axis=-1)
        broke = jnp.logical_and(active, pAp <= 0)
        step = jnp.logical_and(active, pAp > 0)
        alpha = jnp.where(step, rz / jnp.where(pAp == 0, 1.0, pAp), 0.0)
        x = x + alpha[..., None] * p
        r = r - alpha[..., None] * Ap
        z = M_inv(r)
        rz_new = jnp.where(step, jnp.sum(r * z, axis=-1), rz)
        beta = jnp.where(step, rz_new / jnp.where(rz == 0, 1.0, rz), 0.0)
        p = jnp.where(step[..., None], z + beta[..., None] * p, p)
        return dict(
            x=x, r=r, z=z, p=p, rz=rz_new, it=it + 1,
            breakdown=jnp.logical_or(state["breakdown"], broke),
            col_iters=jnp.where(step, it + 1, state["col_iters"]),
            matvecs=state["matvecs"] + jnp.sum(active, dtype=jnp.int32))

    state = jax.lax.while_loop(cond, body, state0)
    x = state["x"]
    r_true = b - A(x)
    rel = jnp.sqrt(jnp.sum(r_true * r_true, axis=-1)) / safe
    return CGResult(x=x, iters=state["it"], rel_residual=rel,
                    breakdown=state["breakdown"],
                    col_iters=state["col_iters"], matvecs=state["matvecs"])
