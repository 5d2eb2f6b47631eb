"""Core library: the paper's contribution (Latent Kronecker GP).

Layered as state -> engines -> posterior:

* :mod:`~repro.core.state` — immutable :class:`LKGPState` pytree and the
  functional API (``fit``, ``fit_batch``, ``extend``, ``refit``);
* :mod:`~repro.core.engines` — the :class:`InferenceEngine` protocol and
  registry of backends (``dense`` / ``iterative`` / ``pallas`` /
  ``distributed``) selected by ``LKGPConfig.backend``;
* :mod:`~repro.core.posterior` — lazy :class:`Posterior` with a cached
  ``K^{-1} y`` shared between the exact mean and Matheron samples;
* :mod:`~repro.core.lkgp` — the legacy :class:`LKGP` facade;
* :mod:`~repro.core.telemetry` — the program's spans and counters (host
  steps, host reads, solves, compiles), on the profiler trace's clock.

Supporting numerics: the pluggable solver stack — grid-form CG/PCG/SGD
(:mod:`~repro.core.solvers`, with ``LKGPConfig.solver`` selecting the
strategy; :mod:`~repro.core.cg` remains as a deprecation shim), stochastic
Lanczos quadrature (:mod:`~repro.core.slq`), the latent-Kronecker MVM
(:mod:`~repro.core.mvm`), Matheron sampling, transforms, and priors.
"""
from .caching import LRUCache
from .engines import (ENGINES, CustomMVMEngine, DenseEngine,
                      DistributedEngine, InferenceEngine, IterativeEngine,
                      LatentKroneckerOperator, PallasEngine,
                      StackedSolveResult, engine_cache_stats, get_engine,
                      list_backends, make_mll, make_mll_iterative,
                      mll_cholesky, register_engine)
from .gp_kernels import KERNELS_1D, matern12, matern32, matern52, rbf_ard
from .lbfgs import LBFGSResult, lbfgs_minimize
from .lkgp import LKGP
from .matheron import (kronecker_correction, prior_residual_draws,
                       sample_posterior_grid)
from .mvm import (grid_to_packed, joint_cov_packed, kron_dense, lk_mvm,
                  lk_operator, packed_to_grid)
from .posterior import (BatchedPosterior, Posterior, PosteriorLike,
                        joint_grams, posterior, posterior_batch)
from .precond import (pivoted_cholesky_grid, pivoted_cholesky_latent,
                      woodbury_preconditioner)
from .priors import noise_prior_logpdf, x_lengthscale_prior_logpdf
from .slq import (lanczos, rademacher_probes, slq_logdet,
                  slq_logdet_from_tridiag, tridiag_from_cg)
from .errors import ObservationError, check_grid_columns, check_observed_finite
from .solvers import (SOLVE_POLICIES, SOLVERS, CGResult, CGTridiag,
                      EscalationStep, GuardedSolveError, GuardedSolver,
                      Solver, cg_solve, cg_solve_tridiag, escalation_tally,
                      get_solver, guarded_solve, guarded_solve_stacked,
                      list_solvers, pcg_solve, register_solver,
                      resolve_solver, sgd_solve)
from .polish import PolishResult, make_polish
from .state import (FitResult, GPData, LKGPConfig, LKGPParams, LKGPState,
                    compiled_cache_stats, extend, fit, fit_batch,
                    gram_matrices, init_params, log_prior, refit,
                    resolve_backend, stack_states, unstack)
from .transforms import TTransform, XTransform, YTransform

__all__ = [
    # solvers / numerics
    "CGResult", "CGTridiag", "cg_solve", "cg_solve_tridiag", "pcg_solve",
    "sgd_solve", "Solver", "SOLVERS", "get_solver", "register_solver",
    "list_solvers", "resolve_solver",
    # reliability: guarded solves + typed input errors
    "GuardedSolver", "GuardedSolveError", "EscalationStep", "SOLVE_POLICIES",
    "guarded_solve", "guarded_solve_stacked", "escalation_tally",
    "ObservationError", "check_observed_finite", "check_grid_columns",
    "KERNELS_1D", "matern12", "matern32",
    "matern52", "rbf_ard", "LBFGSResult", "lbfgs_minimize",
    "sample_posterior_grid", "prior_residual_draws", "kronecker_correction",
    "grid_to_packed", "joint_cov_packed",
    "kron_dense", "lk_mvm", "lk_operator", "packed_to_grid",
    "noise_prior_logpdf", "x_lengthscale_prior_logpdf", "lanczos",
    "rademacher_probes", "slq_logdet", "slq_logdet_from_tridiag",
    "tridiag_from_cg", "TTransform", "XTransform",
    "YTransform", "pivoted_cholesky_grid", "pivoted_cholesky_latent",
    "woodbury_preconditioner",
    # state + functional API
    "LKGPState", "GPData", "LKGPConfig", "LKGPParams", "FitResult", "fit",
    "fit_batch", "extend", "refit", "unstack", "stack_states",
    "resolve_backend", "gram_matrices", "init_params", "log_prior",
    # fixed-budget polish + cache instrumentation
    "PolishResult", "make_polish", "LRUCache", "compiled_cache_stats",
    "engine_cache_stats",
    # engines
    "InferenceEngine", "ENGINES", "get_engine", "register_engine",
    "list_backends", "DenseEngine", "IterativeEngine", "PallasEngine",
    "DistributedEngine", "CustomMVMEngine", "LatentKroneckerOperator",
    "StackedSolveResult", "make_mll", "make_mll_iterative", "mll_cholesky",
    # posterior + facade
    "PosteriorLike", "Posterior", "posterior", "joint_grams", "LKGP",
    "BatchedPosterior", "posterior_batch",
]
