"""Batched multi-RHS block conjugate gradients on grid-form vectors.

Matches the paper's App. B settings: relative residual-norm tolerance 0.01,
max 10 000 iterations. The operator is a callable u -> A(u) acting on
(..., n, m) grid vectors; multiple right-hand sides batch over leading dims
and every iteration applies the operator to the WHOLE stack in one batched
sweep (same semantics as GPyTorch's mBCG). On top of the classic batched
loop the solver adds:

* **per-column convergence freezing** — a system that has reached ``tol``
  stops updating (``alpha = 0``, its direction is held fixed) instead of
  riding along to the slowest system's iteration count. Frozen columns no
  longer drift numerically and no longer count as useful operator work:
  ``CGResult.matvecs`` accumulates only the *active* columns per sweep, and
  ``CGResult.col_iters`` records the per-system iteration of convergence.
* **breakdown detection** — on an indefinite or numerically broken operator
  ``p^T A p <= 0`` for a still-active column. Previously the column was
  silently frozen with ``alpha = 0`` and could be reported as a success;
  now it raises the per-system ``CGResult.breakdown`` flag (and is frozen,
  so the remaining healthy columns still converge).
* **warm starts** — :func:`cg_solve` accepts ``x0``; scheduler-style warm
  refits restart from the previous solution instead of zero.
* **CG-Lanczos tridiagonals** — :func:`cg_solve_tridiag` additionally
  returns the Lanczos tridiagonal coefficients of each system's Krylov
  space, recovered from the CG step sizes (Saad; Gardner et al., 2018's
  mBCG). This is what lets one stacked solve of ``K^{-1}[y | probes]``
  also produce the SLQ log-determinant with zero extra operator sweeps
  (see :func:`repro.core.slq.slq_logdet_from_tridiag`).
* **one program per shape** — an eager solve over an operator that is a
  registered pytree (``LatentKroneckerOperator``) runs the whole loop as
  one jitted program, the operator's arrays its arguments, so a new
  operator of the same shapes reuses the executable. An eager
  ``lax.while_loop`` over a closure would trace, lower and compile again
  for every operator. Bare-closure operators stay eager; inside an outer
  trace the loop is inlined as before. Counters ``solver.compiled`` and
  ``solver.eager`` (:mod:`repro.core.telemetry`) count eager solves by the
  path they took.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from .. import telemetry

__all__ = ["cg_solve", "cg_solve_tridiag", "CGResult", "CGTridiag"]


class CGResult(NamedTuple):
    x: jnp.ndarray
    iters: jnp.ndarray          # scalar int32: total operator sweeps
    rel_residual: jnp.ndarray   # (...,) per-system final relative residual
    breakdown: jnp.ndarray | None = None   # (...,) bool: pAp <= 0 observed
    col_iters: jnp.ndarray | None = None   # (...,) int32 per-system iters
    matvecs: jnp.ndarray | None = None     # scalar int32: active-column MVMs
    # Escalation trace attached by repro.core.solvers.guarded on EAGER
    # solves: a tuple of EscalationStep records (None for raw solver calls
    # and for solves inside traced programs, where the guard passes
    # through). Lives on the diagnostics path only — never a traced value.
    trace: Any = None


class CGTridiag(NamedTuple):
    """CG-Lanczos tridiagonal coefficients per system (see cg_solve_tridiag).

    ``alphas``/``betas`` are the raw CG step/update coefficients of the
    first ``max_rank`` iterations; ``steps`` is how many were recorded per
    system (recording stops when a column converges or breaks down).
    """
    alphas: jnp.ndarray   # (..., max_rank)
    betas: jnp.ndarray    # (..., max_rank)
    steps: jnp.ndarray    # (...,) int32


def _dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Per-system inner product over the trailing (n, m) grid axes."""
    return jnp.sum(a * b, axis=(-2, -1))


def _cg_loop(A: Callable, b: jnp.ndarray, tol: float, max_iters: int,
             x0: jnp.ndarray | None, record: int):
    """Shared block-CG loop; ``record > 0`` also carries tridiag arrays."""
    if x0 is None:
        x0 = jnp.zeros_like(b)
    b_norm = jnp.sqrt(_dot(b, b))
    # Guard all-zero RHS (can occur for fully-unobserved batches).
    safe_b_norm = jnp.where(b_norm == 0, 1.0, b_norm)
    sys_shape = b.shape[:-2]

    r0 = b - A(x0)
    zero_i = jnp.zeros(sys_shape, jnp.int32)
    state0 = dict(
        x=x0, r=r0, p=r0, rs=_dot(r0, r0), it=jnp.int32(0),
        breakdown=jnp.zeros(sys_shape, bool), col_iters=zero_i,
        matvecs=jnp.int32(0),
    )
    if record:
        state0["ta"] = jnp.zeros((*sys_shape, record), b.dtype)
        state0["tb"] = jnp.zeros((*sys_shape, record), b.dtype)
        state0["tsteps"] = zero_i

    def active_mask(state):
        rel = jnp.sqrt(state["rs"]) / safe_b_norm
        return jnp.logical_and(rel > tol, ~state["breakdown"])

    def cond(state):
        return jnp.logical_and(jnp.any(active_mask(state)),
                               state["it"] < max_iters)

    def body(state):
        x, r, p, rs = state["x"], state["r"], state["p"], state["rs"]
        it = state["it"]
        active = active_mask(state)
        Ap = A(p)
        pAp = _dot(p, Ap)
        # Indefinite / numerically broken column: freeze it and flag it
        # instead of silently reporting success on a stalled system.
        broke = jnp.logical_and(active, pAp <= 0)
        breakdown = jnp.logical_or(state["breakdown"], broke)
        step = jnp.logical_and(active, pAp > 0)
        alpha = jnp.where(step, rs / jnp.where(pAp == 0, 1.0, pAp), 0.0)
        x = x + alpha[..., None, None] * p
        r = r - alpha[..., None, None] * Ap
        rs_new = jnp.where(step, _dot(r, r), rs)
        beta = jnp.where(step, rs_new / jnp.where(rs == 0, 1.0, rs), 0.0)
        # Frozen columns keep their direction fixed (alpha = 0 above makes
        # them no-ops); stepping columns do the standard update.
        p = jnp.where(step[..., None, None], r + beta[..., None, None] * p, p)

        out = dict(state)
        out.update(
            x=x, r=r, p=p, rs=rs_new, it=it + 1,
            breakdown=breakdown,
            col_iters=jnp.where(step, it + 1, state["col_iters"]),
            matvecs=state["matvecs"] + jnp.sum(active, dtype=jnp.int32),
        )
        if record:
            # Record the CG (alpha, beta) pair of this iteration for the
            # first `record` steps of each still-stepping column; the
            # Lanczos T is rebuilt from these in slq_logdet_from_tridiag.
            slot = jnp.minimum(it, record - 1)
            write = jnp.logical_and(step, it < record)
            ta, tb = state["ta"], state["tb"]
            out["ta"] = ta.at[..., slot].set(
                jnp.where(write, alpha, ta[..., slot]))
            out["tb"] = tb.at[..., slot].set(
                jnp.where(write, beta, tb[..., slot]))
            out["tsteps"] = jnp.where(write, it + 1, state["tsteps"])
        return out

    state = jax.lax.while_loop(cond, body, state0)
    # Report the TRUE final residual ||b - Ax|| / ||b||, not the recursively
    # updated one: on ill-conditioned systems the recursion drifts (it can
    # report convergence the solution never reached).
    x = state["x"]
    r_true = b - A(x)
    res = CGResult(
        x=x, iters=state["it"],
        rel_residual=jnp.sqrt(_dot(r_true, r_true)) / safe_b_norm,
        breakdown=state["breakdown"], col_iters=state["col_iters"],
        matvecs=state["matvecs"])
    tri = None
    if record:
        tri = CGTridiag(alphas=state["ta"], betas=state["tb"],
                        steps=state["tsteps"])
    return res, tri


_cg_loop_compiled = jax.jit(_cg_loop,
                            static_argnames=("tol", "max_iters", "record"))


def eager(*values: Any) -> bool:
    """No tracer among the leaves of ``values``: a solve over them runs
    outside any jit trace."""
    return not any(isinstance(v, jax.core.Tracer)
                   for v in jax.tree_util.tree_leaves(values))


def _run(A: Callable, b: jnp.ndarray, tol: float, max_iters: int,
         x0: jnp.ndarray | None, record: int):
    """``_cg_loop``, compiled once per shape where the solve is eager and
    the operator a pytree; inlined under an outer trace."""
    if not eager(A, b, x0, tol):
        return _cg_loop(A, b, tol, max_iters, x0, record)
    if jax.tree_util.treedef_is_leaf(jax.tree_util.tree_structure(A)):
        telemetry.count("solver.eager")
        return _cg_loop(A, b, tol, max_iters, x0, record)
    telemetry.count("solver.compiled")
    return _cg_loop_compiled(A, b, tol=float(tol), max_iters=int(max_iters),
                             x0=x0, record=record)


def cg_solve(A: Callable[[jnp.ndarray], jnp.ndarray], b: jnp.ndarray,
             tol: float = 0.01, max_iters: int = 10_000,
             x0: jnp.ndarray | None = None) -> CGResult:
    """Solve A x = b for SPD A with batched block conjugate gradients.

    b: (..., n, m) grid-form right-hand sides (zeros at unobserved cells);
    all systems share each operator sweep. Returns grid-form solutions of
    the same shape, with per-system convergence/breakdown diagnostics.
    """
    res, _ = _run(A, b, tol, max_iters, x0, record=0)
    return res


def cg_solve_tridiag(A: Callable, b: jnp.ndarray, max_rank: int,
                     tol: float = 0.01, max_iters: int = 10_000,
                     x0: jnp.ndarray | None = None
                     ) -> tuple[CGResult, CGTridiag]:
    """Block CG that also returns per-system CG-Lanczos tridiagonals.

    The Lanczos tridiagonal of the Krylov space started at ``b`` falls out
    of the CG coefficients (T_jj = 1/a_j + b_{j-1}/a_{j-1}, T_{j,j+1} =
    sqrt(b_j)/a_j), so a single stacked solve doubles as the SLQ probe
    sweep — no separate Lanczos recursion, no extra operator applications.
    Only the first ``max_rank`` iterations are recorded (the Gauss
    quadrature converges long before CG does). Warm starts are
    intentionally NOT applied to tridiag solves by callers that need the
    Krylov space of ``b`` itself; ``x0`` is still accepted for the solve.
    """
    if max_rank <= 0:
        raise ValueError("max_rank must be positive for cg_solve_tridiag")
    return _run(A, b, tol, max_iters, x0, record=int(max_rank))
