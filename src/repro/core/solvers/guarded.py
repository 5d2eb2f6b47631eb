"""Guarded solves: a deterministic escalation ladder over the solver stack.

The block solvers (:mod:`repro.core.solvers`) *report* degradation — per
column ``breakdown`` flags on ``p^T A p <= 0`` and TRUE final residuals —
but never act on it. This module consumes those diagnostics and escalates
deterministically when a solve degrades:

1. **retry with jitter escalation** — re-solve against ``A + eps*I`` with
   ``eps`` starting at ``10 * config.jitter`` and growing x10 per retry up
   to ``config.guard_jitter_max`` (at most ``config.guard_retries``
   retries). A jittered operator is strictly better conditioned; for
   near-singular gram factors this is usually enough.
2. **switch solver** — walk the registry ladder ``sgd -> cg -> pcg``
   (solvers strictly after the failing one; unregistered/custom solvers
   escalate to ``cg`` then ``pcg``), each on the ORIGINAL operator.
3. **dense Cholesky fallback** — when the operator exposes its Kronecker
   factors (``K1`` / ``K2`` / ``mask`` / ``noise``) and the grid is small
   (``mask.size <= config.guard_dense_max``), assemble the masked dense
   matrix and solve exactly.

``LKGPConfig.solve_policy`` selects what happens around the ladder:

* ``"strict"``      — no escalation; a degraded solve raises
                      :class:`GuardedSolveError` immediately.
* ``"escalate"``    — walk the ladder, return the first healthy result;
                      raise :class:`GuardedSolveError` if it is exhausted.
* ``"best_effort"`` — walk the ladder, never raise: if nothing is healthy,
                      return the attempt with the smallest worst-column
                      residual (breakdown flags intact).

A solve is *degraded* iff any column flags ``breakdown`` or any final
residual is non-finite. A residual merely above ``tol`` (a max-iters stop)
is NOT degraded — that is ordinary iterative-solver behaviour the callers
already tolerate.

Every guarded result carries its escalation ``trace`` (a tuple of
:class:`EscalationStep`) on ``CGResult.trace``, which flows through
``_stash_diagnostics`` into ``Posterior.solve_info``. Ladder activity is
counted per stage (:func:`escalation_tally`, the ``solver.<stage>``
counters of :mod:`repro.core.telemetry`); the engines additionally count
each extra attempt in :func:`repro.core.engines.solve_tally`. Eager solves
are spanned: ``solver.dispatch`` (the base solver's call, up to its
unfinished loop: the host's trace, lowering, cache lookup and launch),
``solver.guard`` (the health verdict, whose host reads wait for the loop)
and ``solver.escalate`` (each rung after the first).

**Tracing:** the guards are host-side control flow. Inside a traced
program (jit/vmap — e.g. the fit objective) the diagnostics are tracers,
so the guard detects that and passes the base solver's result through
untouched: the traced program is bit-identical to an unguarded one (the
``audit_guarded_solves`` jaxpr auditor pins this — no host callbacks, no
f64). Guards therefore act on the eager paths: posterior solves, serving,
and any direct ``engine.solve*`` call.
"""
from __future__ import annotations

from contextlib import nullcontext
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from .base import StackedSolveResult, Solver, get_solver, resolve_solver
from .cg import CGResult, eager

__all__ = [
    "EscalationStep", "GuardedSolveError", "GuardedSolver", "SOLVE_POLICIES",
    "guarded_solve", "guarded_solve_stacked", "escalation_tally",
]

SOLVE_POLICIES = ("strict", "escalate", "best_effort")

# Escalation order: stochastic SGD solves are the flakiest, plain CG is the
# workhorse, preconditioned CG is the most robust iterative option. A
# failing solver escalates to the ones AFTER it; unknown (custom) solvers
# escalate to cg then pcg.
_LADDER = ("sgd", "cg", "pcg")
_FACTOR_ATTRS = ("K1", "K2", "mask", "noise")


class EscalationStep(NamedTuple):
    """One rung of the escalation ladder, as executed."""
    stage: str            # "attempt" | "retry_jitter" | "switch_solver"
    #                     # | "dense_fallback"
    solver: str           # solver name the attempt ran with
    jitter: float         # extra diagonal jitter applied (0.0 = none)
    ok: bool              # attempt came back healthy
    worst_residual: float  # max per-column relative residual (nan -> inf)


class GuardedSolveError(RuntimeError):
    """Every rung of the escalation ladder degraded (or policy="strict"
    forbade escalation). Carries the executed ``trace``."""

    def __init__(self, message: str, trace: tuple = ()) -> None:
        super().__init__(message)
        self.trace = trace


# -- ladder activity counters --------------------------------------------
_STAGES = ("retry_jitter", "switch_solver", "dense_fallback",
           "degraded_returns", "strict_failures")


def escalation_tally() -> dict[str, int]:
    """Counts of escalation-ladder activity in this process, by stage."""
    return {k: int(telemetry.total(f"solver.{k}")) for k in _STAGES}


def _bump(stage: str) -> None:
    telemetry.count(f"solver.{stage}")


# -- health ---------------------------------------------------------------
def _is_traced(value: Any) -> bool:
    return isinstance(value, jax.core.Tracer)


def _eager(A: Any, b: Any, x0: Any) -> bool:
    """No tracer among the right-hand side, the warm start and the
    operator's factors: the solve runs eagerly, outside any jit trace."""
    return eager(b, x0, *(getattr(A, a, None) for a in _FACTOR_ATTRS))


def _spanned(name: str, on: bool):
    return telemetry.span(name) if on else nullcontext()


def _worst_residual(res: CGResult) -> float:
    rel = telemetry.host_read(res.rel_residual, "solver.worst_residual")
    if rel.size == 0:
        return 0.0
    return float(np.max(np.nan_to_num(rel, nan=np.inf, posinf=np.inf,
                                      neginf=np.inf)))


def _degraded(res: CGResult) -> bool:
    """Breakdown flagged or non-finite final residual.

    The final residual is the TRUE ``||b - Ax|| / ||b||`` (the solvers
    recompute it), so a non-finite solution always shows up here — no need
    to sync ``x`` separately. Residuals above tolerance do NOT count:
    hitting ``max_iters`` on a hard system is expected behaviour.
    """
    if res.breakdown is not None and bool(np.any(
            telemetry.host_read(res.breakdown, "solver.breakdown"))):
        return True
    return not bool(np.all(np.isfinite(
        telemetry.host_read(res.rel_residual, "solver.residual"))))


class _JitteredOperator:
    """``u -> A(u) + eps * u``: the base operator with extra diagonal jitter.

    Attribute access (``mask``, ``preconditioner``, Kronecker factors)
    delegates to the base operator so solver routing (e.g. PCG's
    preconditionable check) is unchanged — the base preconditioner remains
    a valid preconditioner for the jittered system.
    """

    def __init__(self, base: Callable, eps: float) -> None:
        self._base = base
        self.eps = eps

    def __call__(self, u: jnp.ndarray) -> jnp.ndarray:
        return self._base(u) + self.eps * u

    def __getattr__(self, name: str) -> Any:
        return getattr(self._base, name)


def _jitter_ladder(config: Any) -> list[float]:
    eps = 10.0 * max(float(getattr(config, "jitter", 1e-6)), 1e-12)
    cap = float(getattr(config, "guard_jitter_max", 1e-2))
    retries = int(getattr(config, "guard_retries", 3))
    out: list[float] = []
    while eps <= cap * (1.0 + 1e-9) and len(out) < retries:
        out.append(eps)
        eps *= 10.0
    return out


def _switch_candidates(base_name: str) -> list[str]:
    if base_name in _LADDER:
        return list(_LADDER[_LADDER.index(base_name) + 1:])
    return ["cg", "pcg"]


def _dense_eligible(A: Any, config: Any) -> bool:
    if not all(hasattr(A, a) for a in _FACTOR_ATTRS):
        return False
    return int(np.prod(A.mask.shape)) <= int(
        getattr(config, "guard_dense_max", 4096))


def _dense_solve(A: Any, b: jnp.ndarray, config: Any) -> CGResult:
    """Exact masked-grid Cholesky solve from the operator's factors.

    Residuals are measured against the assembled dense matrix (the model's
    intended SPD system): the fallback exists precisely for operators whose
    *realisation* broke (bad kernel MVM, indefinite wrapper), so measuring
    against the broken realisation would mark a correct solve degraded.
    """
    from ..mvm import kron_dense

    mv = A.mask.reshape(-1)
    K = kron_dense(A.K1, A.K2) * (mv[:, None] * mv[None, :])
    K = K + jnp.diag(A.noise * mv + (1.0 - mv))
    L = jnp.linalg.cholesky(K)
    if not bool(np.all(np.isfinite(telemetry.host_read(L, "solver.dense_chol")))):
        cap = float(getattr(config, "guard_jitter_max", 1e-2))
        L = jnp.linalg.cholesky(K + cap * jnp.eye(K.shape[0], dtype=K.dtype))
    N = mv.shape[0]
    sys_shape = b.shape[:-2]
    bb = (b * A.mask).reshape(-1, N)
    x = jax.scipy.linalg.cho_solve((L, True), bb.T).T
    x = x * mv
    r = bb - x @ K.T
    norm = jnp.sqrt(jnp.sum(bb * bb, axis=-1))
    rel = (jnp.sqrt(jnp.sum(r * r, axis=-1))
           / jnp.where(norm == 0, 1.0, norm)).reshape(sys_shape)
    return CGResult(
        x=(x.reshape(b.shape)), iters=jnp.int32(0), rel_residual=rel,
        breakdown=jnp.zeros(sys_shape, bool),
        col_iters=jnp.zeros(sys_shape, jnp.int32), matvecs=jnp.int32(0))


def _dense_logdet(A: Any, config: Any) -> jnp.ndarray:
    from ..mvm import kron_dense

    mv = A.mask.reshape(-1)
    K = kron_dense(A.K1, A.K2) * (mv[:, None] * mv[None, :])
    K = K + jnp.diag(A.noise * mv + (1.0 - mv))
    L = jnp.linalg.cholesky(K)
    return 2.0 * jnp.sum(jnp.log(jnp.diag(L)))   # unobserved diag=1 -> log 0


# -- the ladder -----------------------------------------------------------
def _policy(config: Any) -> str:
    policy = getattr(config, "solve_policy", "escalate") or "escalate"
    if policy not in SOLVE_POLICIES:
        raise ValueError(f"unknown solve_policy {policy!r}; "
                         f"expected one of {SOLVE_POLICIES}")
    return policy


def _run_ladder(attempt: Callable[[Solver, Callable], CGResult],
                dense_attempt: Callable[[], CGResult] | None,
                A: Callable, base: Solver, config: Any, what: str,
                first: CGResult | None = None) -> tuple[CGResult, tuple]:
    """Shared ladder driver; returns (result, trace) or raises.

    ``first`` is the base attempt the caller already ran for its health
    pre-check — reused as the ladder's first rung rather than paying the
    base solve twice.
    """
    policy = _policy(config)
    trace: list[EscalationStep] = []
    best: CGResult | None = None
    best_score = np.inf

    def run(stage: str, solver: Solver, op: Callable, eps: float,
            pre: CGResult | None = None) -> tuple[CGResult, bool]:
        nonlocal best, best_score
        with telemetry.span("solver.guard" if pre is not None
                            else "solver.escalate"):
            res = pre if pre is not None else attempt(solver, op)
            ok = not _degraded(res)
            score = _worst_residual(res)
        trace.append(EscalationStep(stage=stage, solver=solver.name,
                                    jitter=eps, ok=ok, worst_residual=score))
        if best is None or score < best_score:
            best, best_score = res, score
        return res, ok

    res, ok = run("attempt", base, A, 0.0, pre=first)
    if ok:
        return res, tuple(trace)
    if policy == "strict":
        _bump("strict_failures")
        raise GuardedSolveError(
            f"{what}: solver {base.name!r} degraded "
            f"(worst residual {trace[0].worst_residual:.3g}) and "
            "solve_policy='strict' forbids escalation", tuple(trace))

    for eps in _jitter_ladder(config):
        _bump("retry_jitter")
        res, ok = run("retry_jitter", base, _JitteredOperator(A, eps), eps)
        if ok:
            return res, tuple(trace)
    for name in _switch_candidates(base.name):
        _bump("switch_solver")
        res, ok = run("switch_solver", get_solver(name), A, 0.0)
        if ok:
            return res, tuple(trace)
    if dense_attempt is not None and _dense_eligible(A, config):
        _bump("dense_fallback")
        with telemetry.span("solver.escalate"):
            res = dense_attempt()
            ok = not _degraded(res)
            score = _worst_residual(res)
        trace.append(EscalationStep(stage="dense_fallback", solver="dense",
                                    jitter=0.0, ok=ok, worst_residual=score))
        if ok:
            return res, tuple(trace)
        if score < best_score:
            best, best_score = res, score

    if policy == "best_effort":
        _bump("degraded_returns")
        assert best is not None
        return best, tuple(trace)
    raise GuardedSolveError(
        f"{what}: escalation ladder exhausted after {len(trace)} attempts "
        f"(best worst-column residual {best_score:.3g}); trace: "
        + " -> ".join(f"{s.stage}[{s.solver}]" for s in trace), tuple(trace))


def guarded_solve(A: Callable, b: jnp.ndarray, config: Any,
                  x0: jnp.ndarray | None = None,
                  solver: Solver | None = None) -> CGResult:
    """Solve ``A x = b`` under the configured escalation policy.

    Drop-in for ``resolve_solver(config, A).solve(...)`` with health
    checking and the escalation ladder on top; the returned
    :class:`CGResult` carries the executed :class:`EscalationStep` tuple as
    ``trace``. Inside traced programs the base result passes through
    unchanged (``trace=None``).
    """
    base = solver if solver is not None else resolve_solver(config, A)
    with _spanned("solver.dispatch", _eager(A, b, x0)):
        res = base.solve(A, b, config, x0=x0)
    if _is_traced(res.rel_residual):
        return res
    with telemetry.span("solver.guard"):
        if _policy(config) != "strict" and not _degraded(res):
            # Fast path: healthy first attempt, record a one-step trace.
            return res._replace(trace=(EscalationStep(
                "attempt", base.name, 0.0, True, _worst_residual(res)),))

    def attempt(slv: Solver, op: Callable) -> CGResult:
        return slv.solve(op, b, config, x0=x0)

    final, trace = _run_ladder(
        attempt, lambda: _dense_solve(A, b, config), A, base, config,
        what="guarded_solve", first=res)
    return final._replace(trace=trace)


def guarded_solve_stacked(A: Callable, rhs: jnp.ndarray, config: Any, *,
                          probe_cols: int = 0, subspace_dim: Any = None,
                          x0: jnp.ndarray | None = None,
                          solver: Solver | None = None) -> StackedSolveResult:
    """Stacked multi-RHS solve under the escalation policy.

    Escalated attempts keep per-column diagnostics intact. A solver switch
    or dense fallback may change ``logdet`` availability: switched solvers
    report ``logdet=None`` exactly as if selected directly (callers already
    handle the separate-SLQ fallback); the dense fallback reports the exact
    observed-subspace log-determinant, which is strictly better than the
    probe estimate it replaces.
    """
    base = solver if solver is not None else resolve_solver(config, A)
    with _spanned("solver.dispatch", _eager(A, rhs, x0)):
        st = base.solve_stacked(A, rhs, config, probe_cols=probe_cols,
                                subspace_dim=subspace_dim, x0=x0)
    if _is_traced(st.result.rel_residual):
        return st
    with telemetry.span("solver.guard"):
        if _policy(config) != "strict" and not _degraded(st.result):
            res = st.result._replace(trace=(EscalationStep(
                "attempt", base.name, 0.0, True, _worst_residual(st.result)),))
            return st._replace(result=res)

    results: dict[int, StackedSolveResult] = {id(st.result): st}

    def attempt(slv: Solver, op: Callable) -> CGResult:
        out = slv.solve_stacked(op, rhs, config, probe_cols=probe_cols,
                                subspace_dim=subspace_dim, x0=x0)
        results[id(out.result)] = out
        return out.result

    def dense_attempt() -> CGResult:
        res = _dense_solve(A, rhs, config)
        logdet = _dense_logdet(A, config) if probe_cols else None
        results[id(res)] = StackedSolveResult(x=res.x, logdet=logdet,
                                              result=res)
        return res

    final, trace = _run_ladder(attempt, dense_attempt, A, base, config,
                               what="guarded_solve_stacked", first=st.result)
    st_final = results[id(final)]
    return st_final._replace(result=final._replace(trace=trace))


class GuardedSolver:
    """Solver-protocol wrapper running a base solver under the ladder.

    Useful for driving an explicit solver (rather than the config-resolved
    one) through the guards; the engines call the module-level functions
    directly.
    """

    def __init__(self, base: Solver) -> None:
        self._base = base
        self.name = f"guarded[{base.name}]"

    def solve(self, A: Callable, b: jnp.ndarray, config: Any,
              x0: jnp.ndarray | None = None) -> CGResult:
        return guarded_solve(A, b, config, x0=x0, solver=self._base)

    def solve_stacked(self, A: Callable, rhs: jnp.ndarray, config: Any, *,
                      probe_cols: int = 0, subspace_dim: Any = None,
                      x0: jnp.ndarray | None = None) -> StackedSolveResult:
        return guarded_solve_stacked(
            A, rhs, config, probe_cols=probe_cols,
            subspace_dim=subspace_dim, x0=x0, solver=self._base)
