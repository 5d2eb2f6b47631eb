"""Pluggable linear-solver stack for the latent-Kronecker engines.

Public surface: the low-level solver functions (:func:`cg_solve`,
:func:`cg_solve_tridiag`, :func:`pcg_solve`, :func:`sgd_solve`), their
shared diagnostics types (:class:`CGResult`, :class:`CGTridiag`,
:class:`StackedSolveResult`), and the strategy registry
(:class:`Solver` protocol, :func:`get_solver` / :func:`resolve_solver` /
:func:`register_solver` / :func:`list_solvers`).

``repro.core.cg`` remains as a deprecation shim re-exporting the moved
functions; new code should import from this package.
"""
from .base import (CGSolver, PCGSolver, SGDSolver, Solver, SOLVERS,
                   StackedSolveResult, get_solver, list_solvers,
                   register_solver, resolve_solver)
from .cg import CGResult, CGTridiag, cg_solve, cg_solve_tridiag
from .guarded import (SOLVE_POLICIES, EscalationStep, GuardedSolveError,
                      GuardedSolver, escalation_tally, guarded_solve,
                      guarded_solve_stacked)
from .pcg import pcg_solve
from .sgd import estimate_lmax, sgd_solve

__all__ = [
    "CGResult", "CGTridiag", "cg_solve", "cg_solve_tridiag", "pcg_solve",
    "sgd_solve", "estimate_lmax",
    "Solver", "SOLVERS", "register_solver", "get_solver", "list_solvers",
    "resolve_solver", "StackedSolveResult",
    "CGSolver", "PCGSolver", "SGDSolver",
    "GuardedSolver", "GuardedSolveError", "EscalationStep", "SOLVE_POLICIES",
    "guarded_solve", "guarded_solve_stacked", "escalation_tally",
]
