"""Pluggable inference engines behind one front door.

An :class:`InferenceEngine` realises the projected latent Kronecker operator

    A(u) = mask * (K1 @ (mask * u) @ K2) + sigma^2 * (mask * u)

and the three linear-algebra primitives the model needs: the operator
itself, solves against it, and its (observed-subspace) log-determinant.
Four implementations are registered:

* ``dense``       — exact Cholesky of the masked joint matrix, O(N^3);
                    the paper's naive baseline and the small-N fast path.
* ``iterative``   — batched CG + stochastic Lanczos quadrature (the paper's
                    method), O(n^2 m + n m^2) per MVM.
* ``pallas``      — the iterative engine with every MVM routed through the
                    Pallas TPU kernel (:mod:`repro.kernels.ops`); runs in
                    interpret mode on the CPU so it is testable there.
* ``distributed`` — the iterative engine over the shard_map row-sharded
                    operator (:mod:`repro.distributed.lkgp_dist`), reachable
                    from the top-level API via ``LKGPConfig(backend=...)``.

``make_mll(config, engine)`` assembles the marginal likelihood for any
engine: exact engines differentiate through the Cholesky; iterative-family
engines use the custom-VJP quadratic-form gradient trick (Gardner et al.,
2018) with fixed Rademacher probes.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from . import telemetry
from .caching import LRUCache
from .gp_kernels import HIGHEST
from .mvm import kron_dense, lk_mvm
from .precond import pivoted_cholesky_grid, woodbury_preconditioner
from .slq import slq_logdet
from .solvers import (CGResult, StackedSolveResult, escalation_tally,
                      guarded_solve, guarded_solve_stacked)
from .state import GPData, LKGPConfig, LKGPParams, gram_matrices

__all__ = [
    "InferenceEngine", "ENGINES", "register_engine", "get_engine",
    "engine_cache_stats", "list_backends", "DenseEngine", "IterativeEngine", "PallasEngine",
    "DistributedEngine", "CustomMVMEngine", "LatentKroneckerOperator",
    "StackedSolveResult", "make_mll", "mll_cholesky", "make_mll_iterative",
    "solve_tally", "escalation_tally",
]

_LOG_2PI = math.log(2.0 * math.pi)

def solve_tally() -> int:
    """Engine solve entries in this process (the counter ``solver.entries``
    of :mod:`repro.core.telemetry`): one per eager solve, plus one per extra
    escalation-ladder attempt. Solves inside a jit trace are not counted.
    A cache-verification aid ("did that posterior() call re-solve?"): the
    serving benchmark asserts it stays flat across a warm posterior()
    re-read."""
    return int(telemetry.total("solver.entries"))


def _count_entries(res) -> None:
    """Count an eager solve, and each escalation attempt beyond its first
    (guarded solves attach one trace entry per attempt)."""
    if isinstance(res.x, jax.core.Tracer):
        return
    trace = getattr(res, "trace", None)
    telemetry.count("solver.entries", len(trace) if trace else 1)


@runtime_checkable
class InferenceEngine(Protocol):
    """Linear-algebra backend: operator construction, solves, log-dets."""

    name: str
    exact: bool   # True -> logdet/solve are exact, probes unused

    def operator(self, params: LKGPParams, data: GPData,
                 config: LKGPConfig) -> Callable[[jnp.ndarray], jnp.ndarray]:
        """Build A(u) on grid-form vectors from raw parameters."""
        ...

    def operator_from_grams(self, K1, K2, mask, noise):
        """Build A(u) from precomputed Gram matrices (posterior hot path)."""
        ...

    def solve(self, A, b, config: LKGPConfig, x0=None) -> jnp.ndarray:
        """Solve A x = b; b may carry leading batch dimensions.

        ``x0`` optionally warm-starts iterative solves (scheduler refits).
        """
        ...

    def logdet(self, A, data: GPData, config: LKGPConfig,
               probes: jnp.ndarray | None) -> jnp.ndarray:
        """log det of A restricted to the observed subspace."""
        ...


ENGINES: dict[str, type] = {}


def register_engine(name: str):
    def deco(cls):
        cls.name = name
        ENGINES[name] = cls
        return cls
    return deco


# Bounded + instrumented like the compiled-objective caches it keys (see
# core.state): the cap is far above the four registered engines, so in
# practice nothing is ever evicted — an eviction here would mint a new
# engine identity and silently retrace every cached objective keyed on the
# old one, which is exactly the pathology the hit/miss counters make
# visible.
_ENGINE_SINGLETONS: LRUCache = LRUCache(16)


def engine_cache_stats() -> dict:
    """Hit/miss/eviction counters of the engine singleton map."""
    return _ENGINE_SINGLETONS.stats()


def get_engine(name: str, **kwargs) -> "InferenceEngine":
    """Engine by backend name; kwargs-free lookups return a singleton.

    The singleton matters beyond saving an allocation: the jitted fit
    objective is cached keyed on engine *identity* (see
    ``core.state._cached_fit_vg``), so config-resolved engines must be
    the same object across ``fit``/``refit`` rounds or every refit would
    retrace and recompile. Engines are stateless, so sharing is safe.
    Custom-configured engines (``kwargs`` given) are built fresh.
    """
    try:
        cls = ENGINES[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r}; "
                         f"available: {sorted(ENGINES)}") from None
    if kwargs:
        return cls(**kwargs)
    engine = _ENGINE_SINGLETONS.get(name)
    if engine is None:
        engine = _ENGINE_SINGLETONS[name] = cls()
    return engine


def list_backends() -> list[str]:
    return sorted(ENGINES)


# --------------------------------------------------------------------------
# dense (exact Cholesky)
# --------------------------------------------------------------------------
class _DenseOperator:
    """Callable A(u) that can also materialise / factorise the dense matrix.

    The dynamic-mask construction zeroes unobserved rows/cols and puts a
    unit diagonal on unobserved cells, so the full-grid Cholesky reproduces
    the observed-block solve and log-det exactly while staying jittable.
    The factorisation is cached per instance (one trace/evaluation).
    """

    def __init__(self, K1, K2, mask, noise):
        self.K1, self.K2, self.mask, self.noise = K1, K2, mask, noise
        self._chol: jnp.ndarray | None = None

    def __call__(self, u):
        return lk_mvm(self.K1, self.K2, self.mask, u, self.noise)

    def chol(self):
        if self._chol is None:
            mv = self.mask.reshape(-1)
            K = kron_dense(self.K1, self.K2) * (mv[:, None] * mv[None, :])
            K = K + jnp.diag(self.noise * mv + (1.0 - mv))
            self._chol = jnp.linalg.cholesky(K)
        return self._chol


@register_engine("dense")
class DenseEngine:
    exact = True

    def operator(self, params, data, config):
        K1, K2 = gram_matrices(params, data.X, data.t, config.t_kernel,
                               config.jitter)
        return self.operator_from_grams(K1, K2, data.mask,
                                        jnp.exp(params.raw_noise))

    def operator_from_grams(self, K1, K2, mask, noise):
        return _DenseOperator(K1, K2, mask, noise)

    def solve(self, A, b, config, x0=None):
        # x0 is accepted for interface uniformity; the exact solve ignores it.
        if not isinstance(A, _DenseOperator):
            # Non-dense operator handed to the dense engine: route through
            # the guarded iterative solve and keep the diagnostics (this
            # path used to drop them entirely).
            res = guarded_solve(A, b, config, x0=x0)
            _count_entries(res)
            _stash_diagnostics(A, res)
            return res.x
        L = A.chol()
        N = A.mask.size
        bb = (b * A.mask).reshape(-1, N)          # (batch, N)
        x = jax.scipy.linalg.cho_solve((L, True), bb.T).T
        x = (x * A.mask.reshape(-1)).reshape(b.shape)
        if not isinstance(x, jax.core.Tracer):
            telemetry.count("solver.entries")
        return x

    def logdet(self, A, data, config, probes=None):
        L = A.chol()
        return 2.0 * jnp.sum(jnp.log(jnp.diag(L)))  # unobserved diag = 1 -> log 0


# --------------------------------------------------------------------------
# iterative (CG + SLQ)
# --------------------------------------------------------------------------
class LatentKroneckerOperator:
    """Callable A(u) that remembers its Kronecker factors.

    The iterative-family engines return this instead of a bare closure so
    that ``solve`` can build the pivoted-Cholesky preconditioner from the
    factors when ``LKGPConfig.precond_rank > 0`` — the factorisation only
    needs K1 / K2 / mask, never the assembled operator.
    """

    def __init__(self, K1, K2, mask, noise, mvm=lk_mvm):
        self.K1, self.K2, self.mask, self.noise = K1, K2, mask, noise
        self._mvm = mvm
        self._precond = None    # (rank, M_inv) cache

    def __call__(self, u):
        return self._mvm(self.K1, self.K2, self.mask, u, noise=self.noise)

    def preconditioner(self, rank: int):
        """Woodbury M^{-1} from the rank-``rank`` pivoted Cholesky, cached.

        The factorisation only depends on (K1, K2, mask, noise), all fixed
        for this operator, so repeated solves (posterior alpha + Matheron
        samples, CG inside one MLL evaluation) share one factor.
        """
        if self._precond is None or self._precond[0] != rank:
            L = pivoted_cholesky_grid(self.K1, self.K2, self.mask, rank)
            self._precond = (rank, woodbury_preconditioner(L, self.noise))
        return self._precond[1]


def _stash_diagnostics(A, res: CGResult) -> None:
    """Best-effort: hang the solve diagnostics on the operator object.

    Operators are created per evaluation (and per trace), so the attribute
    has the same lifetime as the solve it describes; eager callers
    (:class:`repro.core.posterior.Posterior`) read it back as
    ``A.last_result``. Plain-callable operators that reject attributes are
    skipped silently.
    """
    try:
        A.last_result = res
    except AttributeError:
        pass


@register_engine("iterative")
class IterativeEngine:
    exact = False

    def operator(self, params, data, config):
        K1, K2 = gram_matrices(params, data.X, data.t, config.t_kernel,
                               config.jitter)
        return self.operator_from_grams(K1, K2, data.mask,
                                        jnp.exp(params.raw_noise))

    def operator_from_grams(self, K1, K2, mask, noise):
        return LatentKroneckerOperator(K1, K2, mask, noise)

    def solve(self, A, b, config, x0=None):
        return self.solve_result(A, b, config, x0=x0).x

    def solve_result(self, A, b, config, x0=None) -> CGResult:
        """Like :meth:`solve` but returning the full per-column diagnostics
        (iterations, true residuals, breakdown flags, MVM counts).

        The solve strategy comes from the registry (``config.solver``:
        cg / pcg / sgd; "auto" keeps the historic PCG-iff-precond_rank
        routing) — see :mod:`repro.core.solvers`. Eager solves run under
        the ``config.solve_policy`` escalation guard
        (:mod:`repro.core.solvers.guarded`); traced solves pass through
        unguarded.
        """
        res = guarded_solve(A, b, config, x0=x0)
        _count_entries(res)
        _stash_diagnostics(A, res)
        return res

    def solve_stacked(self, A, rhs, config, *, probe_cols: int = 0,
                      subspace_dim=None, x0=None) -> StackedSolveResult:
        """ONE batched operator sweep for a whole stack of right-hand sides.

        ``rhs``: (s, n, m) stack (e.g. ``[y | probes | Matheron
        residuals]``); every solver iteration applies the operator to the
        full stack at once, converged columns freeze. When the trailing
        ``probe_cols`` rows are SLQ probes and the CG solver runs, their
        CG-Lanczos tridiagonals are recorded during the SAME solve and
        turned into the log-determinant estimate — no separate Lanczos
        sweep. PCG/SGD solves report ``logdet=None`` and the caller falls
        back to the separate SLQ pass. Eager solves run under the
        ``config.solve_policy`` escalation guard; traced solves pass
        through unguarded.
        """
        st = guarded_solve_stacked(A, rhs, config, probe_cols=probe_cols,
                                   subspace_dim=subspace_dim, x0=x0)
        _count_entries(st.result)
        _stash_diagnostics(A, st.result)
        return st

    def logdet(self, A, data, config, probes):
        return slq_logdet(A, probes, config.slq_iters, jnp.sum(data.mask))


class CustomMVMEngine(IterativeEngine):
    """Iterative engine over a user-supplied ``mvm(K1, K2, mask, u, noise=...)``."""

    name = "custom"

    def __init__(self, mvm: Callable):
        self._mvm = mvm

    def operator_from_grams(self, K1, K2, mask, noise):
        return LatentKroneckerOperator(K1, K2, mask, noise, mvm=self._mvm)


# --------------------------------------------------------------------------
# pallas (iterative, MVMs through the TPU kernel)
# --------------------------------------------------------------------------
def _pallas_mvm_raw(K1, K2, mask, u, noise):
    # Import at call time: repro.kernels imports repro.core.gp_kernels, so a
    # module-level import here would be circular. force_pallas=True runs the
    # kernel on every backend: compiled by Mosaic on TPU, in interpret mode
    # on the CPU (so tests exercise the same code path). f64 operands are
    # cast to f32 at the kernel boundary and the result cast back.
    from ..kernels import ops
    return ops.lk_mvm_op(K1, K2, mask, u, noise, force_pallas=True)


def _with_analytic_vjp(mvm_raw: Callable) -> Callable:
    """Make a kernel MVM ``mvm_raw(K1, K2, mask, u, noise)`` differentiable.

    pallas_call has no autodiff rule, but the MVM is bilinear in (K1, K2, u),
    so the VJPs are closed-form jnp einsums; the ``u`` cotangent is A(g)
    itself (A is symmetric) and is routed back through ``mvm_raw``. Returns
    ``mvm(K1, K2, mask, u, noise=0.0)``.
    """
    @jax.custom_vjp
    def mvm(K1, K2, mask, u, noise):
        return mvm_raw(K1, K2, mask, u, noise)

    def fwd(K1, K2, mask, u, noise):
        return mvm_raw(K1, K2, mask, u, noise), (K1, K2, mask, u, noise)

    def bwd(res, g):
        K1, K2, mask, u, noise = res
        n, m = mask.shape
        gm = (g * mask).reshape(-1, n, m)   # flatten leading batch dims
        um = (u * mask).reshape(-1, n, m)
        umK2 = jnp.einsum("bnm,mk->bnk", um, K2, precision=HIGHEST)
        dK1 = jnp.einsum("bik,bjk->ij", gm, umK2, precision=HIGHEST)
        K1um = jnp.einsum("ij,bjm->bim", K1, um, precision=HIGHEST)
        dK2 = jnp.einsum("bij,bik->jk", K1um, gm, precision=HIGHEST)
        du = mvm_raw(K1, K2, mask, g, noise)          # A(g), A symmetric
        dnoise = jnp.sum(gm * um).astype(jnp.asarray(noise).dtype)
        return dK1, dK2, jnp.zeros_like(mask), du, dnoise

    mvm.defvjp(fwd, bwd)

    def mvm_kw(K1, K2, mask, u, noise=0.0):
        # custom_vjp functions only take positional args; adapt to the
        # ``mvm(K1, K2, mask, u, noise=...)`` calling convention.
        return mvm(K1, K2, mask, u, noise)

    return mvm_kw


_pallas_mvm_kw = _with_analytic_vjp(_pallas_mvm_raw)


@register_engine("pallas")
class PallasEngine(IterativeEngine):
    def operator_from_grams(self, K1, K2, mask, noise):
        return LatentKroneckerOperator(K1, K2, mask, noise, mvm=_pallas_mvm_kw)


# --------------------------------------------------------------------------
# distributed (shard_map row sharding)
# --------------------------------------------------------------------------
@register_engine("distributed")
class DistributedEngine(IterativeEngine):
    """Row-shards the grid over a mesh 'data' axis (one all-gather per MVM).

    Pass a mesh for multi-device runs (n must divide the 'data' axis size);
    the default is a 1-axis mesh over all local devices. K1 is built
    replicated here; the fully row-sharded K1 build used at pod scale lives
    in :func:`repro.distributed.lkgp_dist.dist_mll_value`.

    ``fused`` routes each shard's row-block MVM through the fused Pallas
    kernel (:func:`repro.kernels.lk_mvm.lk_mvm_fused_rows`) instead of the
    two-stage einsum reference. The kernel accumulates in f32, so
    ``"auto"`` only takes it for f32 operands with a block size that passes
    the per-shard VMEM budget check; f64 operands (e.g. the x64 parity
    tests) keep the exact reference body. ``True`` forces it (raising if no
    block configuration fits VMEM), ``False`` disables it.

    Solves route through the solver registry like every iterative engine
    (``config.solver``); the global reductions CG/SGD perform are plain
    ``jnp.sum`` over the sharded rows, which XLA lowers to psums.
    """

    def __init__(self, mesh=None, fused="auto"):
        if mesh is None:
            import numpy as np
            from jax.sharding import Mesh
            mesh = Mesh(np.array(jax.devices()), ("data",))
        self.mesh = mesh
        self.fused = fused

    def _fused_blocks(self, K1, K2, mask):
        """Per-shard (block_n, block_m) for the fused kernel, or None."""
        if self.fused is False:
            return None
        K1 = jnp.asarray(K1)
        if K1.dtype != jnp.float32:
            if self.fused is True:
                raise ValueError(
                    "DistributedEngine(fused=True) needs float32 operands: "
                    f"the fused Pallas kernel accumulates in f32, got "
                    f"{K1.dtype}")
            return None
        from ..analysis.vmem import best_fitting_blocks
        # The per-shard kernel sweeps all n rows of K1, so its blocks are
        # judged at the global n (see lk_mvm_fused_rows).
        n = K1.shape[1]
        m = jnp.asarray(K2).shape[0]
        blocks = best_fitting_blocks(n, m, precision="f32")
        if blocks is None and self.fused is True:
            raise ValueError(
                "DistributedEngine(fused=True): no fused block size fits "
                f"the per-shard VMEM budget for n={n}, m={m}")
        return blocks

    def operator_from_grams(self, K1, K2, mask, noise):
        from ..distributed.lkgp_dist import dist_lk_mvm_fused, dist_lk_operator
        blocks = self._fused_blocks(K1, K2, mask)
        if blocks is not None:
            def fused_raw(K1, K2, mask, u, noise):
                return dist_lk_mvm_fused(
                    self.mesh, K1, K2, mask, noise, block_n=blocks[0],
                    block_m=blocks[1])(u)

            # Differentiable like the pallas engine's MVM: the fit's MLL
            # gradient flows through the operator to K1, K2 and noise.
            mvm = _with_analytic_vjp(fused_raw)
            base = functools.partial(mvm, K1, K2, mask, noise=noise)
        else:
            base = dist_lk_operator(self.mesh, K1, K2, mask, noise)

        def A(u):
            # The shard_map body is rank-2; map leading batch dims (CG rhs
            # stacks, SLQ probes) sequentially over it.
            if u.ndim == 2:
                return base(u)
            flat = u.reshape((-1, *u.shape[-2:]))
            return jax.lax.map(base, flat).reshape(u.shape)

        # Introspection hook: tests and audits assert which body was traced.
        setattr(A, "fused", blocks is not None)
        return A


# --------------------------------------------------------------------------
# marginal likelihood
# --------------------------------------------------------------------------
def mll_cholesky(params: LKGPParams, X, t, Y, mask, t_kernel: str = "matern12",
                 jitter: float = 1e-6) -> jnp.ndarray:
    """Exact MLL of the observed block — the paper's NAIVE baseline.

    O(n^3 m^3) time / O(n^2 m^2) space, via the dynamic-mask construction
    (see :class:`_DenseOperator`). Fully differentiable through the
    Cholesky; also the objective of the ``dense`` engine.
    """
    K1, K2 = gram_matrices(params, X, t, t_kernel, jitter)
    noise = jnp.exp(params.raw_noise)
    mv = mask.reshape(-1)
    y = (Y * mask).reshape(-1)
    K = kron_dense(K1, K2) * (mv[:, None] * mv[None, :])
    K = K + jnp.diag(noise * mv + (1.0 - mv))
    L = jnp.linalg.cholesky(K)
    alpha = jax.scipy.linalg.cho_solve((L, True), y)
    N = jnp.sum(mask)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diag(L)))  # unobserved diag = 1 -> log 0
    return (-0.5 * jnp.dot(y, alpha, precision=HIGHEST) - 0.5 * logdet
            - 0.5 * N * _LOG_2PI)


def make_mll(config: LKGPConfig, engine: "InferenceEngine") -> Callable:
    """MLL as ``mll(params, X, t, Y, mask, probes)`` for any engine.

    Exact engines ignore ``probes`` and differentiate through the Cholesky.
    Iterative-family engines share fixed Rademacher probes between the SLQ
    log-det estimate and the stochastic trace gradients; fixing them makes
    the objective deterministic, which the L-BFGS line search requires.
    """
    if engine.exact:
        # Exact engines differentiate straight through their solve/logdet
        # (no probes, no custom VJP). For DenseEngine this is exactly
        # mll_cholesky: one cached Cholesky shared by solve and log-det.
        def mll_exact(params, X, t, Y, mask, probes=None):
            data = GPData(X, t, None, mask)
            A = engine.operator(params, data, config)
            Ym = Y * mask
            alpha = engine.solve(A, Ym, config)
            N = jnp.sum(mask)
            logdet = engine.logdet(A, data, config, probes)
            return (-0.5 * jnp.sum(Ym * alpha) - 0.5 * logdet
                    - 0.5 * N * _LOG_2PI)
        return mll_exact

    def _operator(params, X, t, mask):
        return engine.operator(params, GPData(X, t, None, mask), config)

    @jax.custom_vjp
    def mll(params, X, t, Y, mask, probes):
        value, _ = _fwd(params, X, t, Y, mask, probes)
        return value

    def _fwd(params, X, t, Y, mask, probes):
        A = _operator(params, X, t, mask)
        Ym = Y * mask
        rhs = jnp.concatenate([Ym[None], probes], axis=0)
        N = jnp.sum(mask)
        # Consolidated path: ONE stacked block solve covers the mean solve,
        # the trace-gradient probe solves, AND (via the probes' CG-Lanczos
        # tridiagonals) the SLQ log-det — no separate Lanczos sweep. The
        # fallback (slq_via_cg=False, engines without solve_stacked, or
        # preconditioned solves whose Krylov space is M^{-1}A's) runs the
        # classic stacked solve + reorthogonalised-Lanczos SLQ.
        stacked = getattr(engine, "solve_stacked", None)
        logdet = None
        if stacked is not None and getattr(config, "slq_via_cg", True):
            st = stacked(A, rhs, config, probe_cols=probes.shape[0],
                         subspace_dim=N)
            sol, logdet = st.x, st.logdet
        else:
            sol = engine.solve(A, rhs, config)
        if logdet is None:
            logdet = engine.logdet(A, GPData(X, t, None, mask), config,
                                   probes)
        alpha, W = sol[0], sol[1:]
        value = -0.5 * jnp.sum(Ym * alpha) - 0.5 * logdet - 0.5 * N * _LOG_2PI
        return value, (params, X, t, Y, mask, alpha, W, probes)

    def _bwd(res, gbar):
        params, X, t, Y, mask, alpha, W, probes = res
        p = probes.shape[0]

        def h(pp):
            A = _operator(pp, X, t, mask)
            quad_alpha = jnp.sum(alpha * A(alpha))
            quad_tr = jnp.sum(W * A(probes)) / p
            return 0.5 * quad_alpha - 0.5 * quad_tr

        gparams = jax.grad(h)(params)
        gparams = jax.tree_util.tree_map(lambda g: gbar * g, gparams)
        zeros = lambda a: jnp.zeros_like(a)
        return (gparams, zeros(X), zeros(t), zeros(Y), zeros(mask),
                zeros(probes))

    mll.defvjp(_fwd, _bwd)
    return mll


def make_mll_iterative(cfg: LKGPConfig, mvm_impl=None):
    """Iterative MLL with custom VJP (backward-compatible entry point).

    Returns ``mll(params, X, t, Y, mask, probes)``. With ``mvm_impl`` given
    (signature ``mvm(K1, K2, mask, u, noise=...)``), every MVM — CG, SLQ,
    and the quadratic-form gradients — routes through it; this is how
    ``LKGPConfig.use_pallas`` threads the Pallas kernel into the objective.
    """
    engine = IterativeEngine() if mvm_impl is None else CustomMVMEngine(mvm_impl)
    return make_mll(cfg, engine)
