"""Immutable model state and the functional fitting API.

The model layer is organised around three abstractions:

* :class:`LKGPState` — an immutable pytree holding fitted parameters,
  input/output transforms, and the *raw* training data. Produced by
  :func:`fit`; consumed by every inference engine and by
  :class:`~repro.core.posterior.Posterior`.
* :class:`~repro.core.engines.InferenceEngine` — pluggable linear-algebra
  backends (``dense`` / ``iterative`` / ``pallas`` / ``distributed``)
  selected via ``LKGPConfig.backend``.
* :class:`~repro.core.posterior.Posterior` — a lazy posterior that caches
  the CG solve of ``K^{-1} y`` and shares it between the exact mean and
  Matheron samples.

State transitions are functional: ``fit(...) -> LKGPState``,
``extend(state, ...) -> LKGPState`` (incremental conditioning with
warm-started hyper-parameters), ``refit(state) -> LKGPState``. A batched
``fit_batch`` vmaps the whole objective over independent tasks.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Any, ClassVar, NamedTuple

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np

from . import gp_kernels as gk
from . import telemetry
from .caching import LRUCache
from .errors import ObservationError, check_grid_columns, check_observed_finite
from .lbfgs import lbfgs_minimize
from .polish import make_polish
from .priors import noise_prior_logpdf, x_lengthscale_prior_logpdf
from .slq import rademacher_probes
from .transforms import TTransform, XTransform, YTransform

__all__ = [
    "LKGPParams", "LKGPConfig", "GPData", "LKGPState", "FitResult",
    "init_params", "gram_matrices", "log_prior", "resolve_backend", "fit",
    "fit_batch", "extend", "refit", "unstack", "stack_states",
    "compiled_cache_stats",
]

_LOG_2PI = math.log(2.0 * math.pi)

BACKENDS = ("dense", "iterative", "pallas", "distributed")


class LKGPParams(NamedTuple):
    """Raw (log-space) parameters; positive values are exp(raw)."""
    raw_x_lengthscale: jnp.ndarray  # (d,)
    raw_t_lengthscale: jnp.ndarray  # ()
    raw_outputscale: jnp.ndarray    # ()
    raw_noise: jnp.ndarray          # ()


@dataclass(frozen=True)
class LKGPConfig:
    """Model + inference configuration.

    ``backend`` selects the inference engine (one front door for all four
    code paths): ``"dense"`` (exact Cholesky), ``"iterative"`` (CG + SLQ),
    ``"pallas"`` (CG + SLQ with every MVM routed through the Pallas TPU
    kernel in :mod:`repro.kernels.ops`), ``"distributed"`` (shard_map row
    sharding over a device mesh). ``"auto"`` resolves from the legacy
    ``mll_method`` / ``use_pallas`` fields and the observation count.
    """
    t_kernel: str = "matern12"
    backend: str = "auto"           # "auto" | dense | iterative | pallas | distributed
    mll_method: str = "auto"        # legacy: "cholesky" | "iterative" | "auto"
    auto_cholesky_max: int = 800    # N_obs threshold for "auto"
    cg_tol: float = 0.01            # paper App. B
    cg_max_iters: int = 10_000      # paper App. B
    precond_rank: int = 0           # >0: rank-r pivoted-Cholesky PCG (iterative/pallas)
    # Linear-solver strategy for the iterative-family engines (see
    # repro.core.solvers): "cg" | "pcg" | "sgd". "auto" keeps the historic
    # routing — PCG iff precond_rank > 0, plain CG otherwise.
    solver: str = "auto"
    sgd_iters: int = 500            # SGD sweep budget (one MVM per sweep)
    sgd_momentum: float = 0.9       # heavy-ball momentum
    sgd_lr: float = 0.0             # 0.0: auto 1/lambda_max via power iteration
    slq_probes: int = 16
    slq_iters: int = 25
    # True: the MLL's log-det comes from the probe columns' CG-Lanczos
    # tridiagonals of the ONE stacked solve K^{-1}[y | probes] (mBCG,
    # Gardner et al. 2018) — no separate Lanczos operator sweeps. False
    # restores the separate reorthogonalised-Lanczos SLQ pass.
    slq_via_cg: bool = True
    jitter: float = 1e-6
    lbfgs_iters: int = 100
    # Hyper-parameter initialisation + optimisation budget policy.
    # ``hyper_init``: "default" starts from the prior-mean init (refits
    # still warm-start from the previous optimum); "amortized" asks the
    # registered :mod:`repro.amortize` encoder for a data-conditioned
    # starting point on every fit AND every refit. ``polish_steps`` picks
    # the optimiser: -1 (default) runs the host-driven L-BFGS for up to
    # ``lbfgs_iters`` iterations; 0 skips optimisation entirely (the init
    # IS the fit — params round-trip bitwise); k > 0 runs the fixed-budget
    # pure-JAX polish (:mod:`repro.core.polish`) for exactly k L-BFGS steps
    # in ONE jitted call. Neither field enters the traced objective, so
    # flipping them never retraces (_objective_cache_key excludes both).
    hyper_init: str = "default"     # "default" | "amortized"
    polish_steps: int = -1          # -1 host L-BFGS | 0 no-op | k device steps
    posterior_samples: int = 64
    # Default cache policy for posterior(state): True lets repeated
    # posterior() calls on an UNCHANGED state share one lazy Posterior (and
    # therefore its cached K^{-1}[y|residuals] solves). Per-call override:
    # posterior(state, cache=...). extend/refit return new state objects,
    # which is what invalidates the cache.
    posterior_cache: bool = True
    seed: int = 0
    use_pallas: bool = False        # legacy alias for backend="pallas"
    # Reliability policy for eager engine solves (repro.core.solvers.guarded):
    # "strict" raises GuardedSolveError on any degraded solve; "escalate"
    # (default) walks the jitter -> solver-switch -> dense-fallback ladder
    # and raises only if it is exhausted; "best_effort" never raises and
    # returns the least-degraded attempt. Solves inside jitted programs
    # (the fit objective) bypass the guard entirely, so none of these
    # fields affect traced computations or the jit cache
    # (_objective_cache_key deliberately excludes them).
    solve_policy: str = "escalate"  # "strict" | "escalate" | "best_effort"
    guard_retries: int = 3          # max jitter-escalation retries
    guard_jitter_max: float = 1e-2  # jitter ladder cap (starts at 10*jitter)
    guard_dense_max: int = 4096     # max mask.size for dense Cholesky fallback


def init_params(d: int, dtype=jnp.float64) -> LKGPParams:
    """Initialise at prior means / paper defaults."""
    return LKGPParams(
        raw_x_lengthscale=jnp.full((d,), math.sqrt(2.0) + 0.5 * math.log(d), dtype),
        raw_t_lengthscale=jnp.asarray(math.log(0.25), dtype),
        raw_outputscale=jnp.asarray(0.0, dtype),
        raw_noise=jnp.asarray(-4.0, dtype),
    )


def gram_matrices(params: LKGPParams, X: jnp.ndarray, t: jnp.ndarray,
                  t_kernel: str = "matern12", jitter: float = 1e-6):
    """K1 (n, n) over configs and K2 (m, m) over progressions (jittered)."""
    k2fn = gk.KERNELS_1D[t_kernel]
    K1 = gk.rbf_ard(X, X, jnp.exp(params.raw_x_lengthscale))
    K2 = k2fn(t, t, jnp.exp(params.raw_t_lengthscale),
              jnp.exp(params.raw_outputscale))
    K1 = K1 + jitter * jnp.eye(X.shape[0], dtype=K1.dtype)
    K2 = K2 + jitter * jnp.eye(t.shape[0], dtype=K2.dtype)
    return K1, K2


def log_prior(params: LKGPParams, d: int) -> jnp.ndarray:
    return (x_lengthscale_prior_logpdf(params.raw_x_lengthscale, d)
            + noise_prior_logpdf(params.raw_noise))


class GPData(NamedTuple):
    """Transformed-space training data handed to an inference engine."""
    X: jnp.ndarray       # (n, d) in the unit hypercube
    t: jnp.ndarray       # (m,) log-scaled to [0, 1]
    Y: jnp.ndarray | None  # (n, m) normalised curves (None when not needed)
    mask: jnp.ndarray    # (n, m) 1.0 where observed


@dataclass(frozen=True)
class LKGPState:
    """Immutable fitted model state (a jax pytree).

    Data fields hold *raw* (untransformed) training data plus the fitted
    transforms and raw GP parameters; ``config`` is static metadata. The
    transformed view engines consume is exposed via :attr:`data`.

    ``fit`` attaches two non-pytree diagnostics with ``object.__setattr__``:
    ``fit_result`` (the L-BFGS result) and ``backend_used``. They describe
    the *fit call that produced this exact state* and never carry over to
    derived states: ``extend`` explicitly clears them (the carried-over
    warm-start parameters are no longer the result of any optimisation of
    the extended data) and ``refit`` re-derives them from its own fit.
    They do not survive ``tree_map`` either — read them with
    ``getattr(state, ..., None)``.

    :func:`repro.core.posterior.posterior` may attach ``_posterior_cache``
    the same way (the state-keyed solve cache): because every state
    transition builds a fresh object, a cached posterior can never outlive
    the state whose solves it holds.
    """
    params: LKGPParams
    X: jnp.ndarray       # (n, d) raw hyper-parameters
    t: jnp.ndarray       # (m,) raw progressions (e.g. epochs, 1-indexed)
    Y: jnp.ndarray       # (n, m) raw metric values
    mask: jnp.ndarray    # (n, m) 1.0 where observed
    x_tf: XTransform
    t_tf: TTransform
    y_tf: YTransform
    config: LKGPConfig = field(default_factory=LKGPConfig)

    # Attached by fit() via object.__setattr__ (see docstring): declared
    # as ClassVar so dataclass/pytree registration ignores them while
    # type checkers still know they exist on instances.
    fit_result: ClassVar[Any]
    backend_used: ClassVar[str]
    engine: ClassVar[Any]

    @property
    def n(self) -> int:
        return self.X.shape[-2]

    @property
    def m(self) -> int:
        return self.t.shape[-1]

    @property
    def d(self) -> int:
        return self.X.shape[-1]

    @property
    def data(self) -> GPData:
        """Transformed-space view of the training data (paper App. B)."""
        return GPData(self.x_tf(self.X), self.t_tf(self.t),
                      self.y_tf(self.Y), self.mask)

    def with_params(self, params: LKGPParams) -> "LKGPState":
        return dataclasses.replace(self, params=params)


jax.tree_util.register_dataclass(
    LKGPState,
    data_fields=["params", "X", "t", "Y", "mask", "x_tf", "t_tf", "y_tf"],
    meta_fields=["config"],
)


def resolve_backend(config: LKGPConfig, n_obs: int) -> str:
    """Map config (including legacy fields) to a concrete backend name."""
    if config.backend != "auto":
        if config.backend not in BACKENDS:
            raise ValueError(f"unknown backend {config.backend!r}; "
                             f"expected one of {BACKENDS}")
        return config.backend
    if config.use_pallas:
        return "pallas"
    if config.mll_method == "cholesky":
        return "dense"
    if config.mll_method == "iterative":
        return "iterative"
    return "dense" if n_obs <= config.auto_cholesky_max else "iterative"


def _fit_transforms(X, t, Y, mask):
    x_tf = XTransform.fit(X)
    t_tf = TTransform.fit(t)
    y_tf = YTransform.fit(Y, mask)
    return x_tf, t_tf, y_tf


class FitResult(NamedTuple):
    """Diagnostics of the optimisation that produced a state's params.

    Superset of the legacy ``LBFGSResult`` fields (``x`` / ``fun`` /
    ``n_iters`` / ``n_evals`` / ``converged``), plus honest budget
    accounting: ``budget`` is the iteration cap the optimiser ran under,
    ``init_source`` records where the starting point came from
    (``"default"`` | ``"amortized"`` | ``"params"``), and ``optimizer``
    names the path taken (``"lbfgs"`` host loop, ``"polish"`` fixed-budget
    device L-BFGS, ``"none"`` for ``polish_steps=0``). A capped run is now
    distinguishable from a converged one: ``converged`` reflects the
    gradient tolerance at the final iterate, while ``n_iters == budget``
    with ``converged=False`` means the budget bound first.
    """
    x: np.ndarray
    fun: float
    n_iters: int
    n_evals: int
    converged: bool
    budget: int
    init_source: str
    optimizer: str


def _flatten_params(p: LKGPParams) -> jnp.ndarray:
    """(d + 3,) flat raw-parameter vector (ravel_pytree field order)."""
    return jnp.concatenate([
        p.raw_x_lengthscale,
        jnp.reshape(p.raw_t_lengthscale, (1,)),
        jnp.reshape(p.raw_outputscale, (1,)),
        jnp.reshape(p.raw_noise, (1,)),
    ])


def _unflatten_params(x: jnp.ndarray, d: int) -> LKGPParams:
    return LKGPParams(raw_x_lengthscale=x[:d], raw_t_lengthscale=x[d],
                      raw_outputscale=x[d + 1], raw_noise=x[d + 2])


# Jitted fit objectives, cached across fit/refit rounds. Key = the
# objective-relevant config fields + engine identity + parameter dim: a
# refit that only bumps lbfgs_iters (or changes seed / posterior_samples,
# which enter through runtime arguments, not the traced program) reuses
# the compiled objective instead of retracing. The engine is part of the
# key *by object* — get_engine returns singletons precisely so this hits.
# Both caches are LRU-bounded with hit/miss/eviction counters (a
# long-lived PredictionService cycling tenant configs must not grow them
# without bound); see :func:`compiled_cache_stats`.
_VG_CACHE: LRUCache = LRUCache(64)
_POLISH_CACHE: LRUCache = LRUCache(64)
# Armijo ladder width. The fixed-budget design evaluates EVERY rung each
# step (deterministic cost), so unused rungs are pure waste: measured on
# prior-sampled tasks, rungs past 1/8 are never accepted from amortized or
# warm inits — width 4 leaves the optimized objective bitwise unchanged
# while cutting the per-step eval count from 7 to 5.
_POLISH_BACKTRACKS = 4
_POLISH_GTOL = 1e-6


def compiled_cache_stats() -> dict:
    """Hit/miss/eviction counters of the compiled-objective caches."""
    return {"fit_vg": _VG_CACHE.stats(), "polish": _POLISH_CACHE.stats()}


def _objective_cache_key(cfg: LKGPConfig) -> tuple:
    return (cfg.t_kernel, cfg.backend, cfg.mll_method, cfg.auto_cholesky_max,
            cfg.cg_tol, cfg.cg_max_iters, cfg.precond_rank, cfg.solver,
            cfg.sgd_iters, cfg.sgd_momentum, cfg.sgd_lr, cfg.slq_probes,
            cfg.slq_iters, cfg.slq_via_cg, cfg.jitter, cfg.use_pallas)


def _cached_fit_vg(cfg: LKGPConfig, engine, d: int):
    """value_and_grad of the fit objective as a pure jitted function.

    The returned function has signature ``vg(params, Xn, tn, Yn, mask,
    probes)`` — all data enters as arguments (``n_obs`` is computed on
    device), so same-shaped refits hit jit's own cache rather than
    re-tracing a fresh closure. The jaxpr auditor's retrace check
    (``repro.analysis.jaxpr_audit``) pins this behaviour.
    """
    from .engines import make_mll

    key = (_objective_cache_key(cfg), engine, d)
    vg = _VG_CACHE.get(key)
    if vg is None:
        mll_fn = make_mll(cfg, engine)

        def objective(p, Xn, tn, Yn, mask, probes):
            n_obs = jnp.sum(mask)
            mll = mll_fn(p, Xn, tn, Yn, mask, probes)
            return -(mll + log_prior(p, d)) / n_obs

        vg = jax.jit(jax.value_and_grad(objective))
        _VG_CACHE[key] = vg
    return vg


def _cached_polish(cfg: LKGPConfig, engine, d: int, steps: int):
    """The fixed-budget polish as ONE cached jitted program.

    Wraps the same compiled objective ``_cached_fit_vg`` hands the host
    L-BFGS (so polish and host paths optimise the identical function) in
    :func:`repro.core.polish.make_polish`. There is deliberately no
    batched variant: :func:`fit_batch` dispatches this exact program once
    per task, which is the only lowering that keeps per-task results
    bitwise identical to a single-task :func:`fit` at every batch size
    (``vmap`` re-associates the Cholesky VJP; ``lax.map`` compiles the
    loop body differently from the straight-line single-task program —
    both measured to drift in the last ulp; see the polish module
    docstring).
    """
    key = (_objective_cache_key(cfg), engine, d, steps)
    fn = _POLISH_CACHE.get(key)
    if fn is None:
        vg = _cached_fit_vg(cfg, engine, d)

        def vg_flat(xf, Xn, tn, Yn, mask, probes):
            f, g = vg(_unflatten_params(xf, d), Xn, tn, Yn, mask, probes)
            return f, _flatten_params(g)

        fn = jax.jit(make_polish(vg_flat, steps=steps,
                                 n_backtracks=_POLISH_BACKTRACKS))
        _POLISH_CACHE[key] = fn
    return fn


def _resolve_init(cfg: LKGPConfig, init, params0, amortizer, d: int, dtype,
                  Xn, tn, Yn, mask, batch: int | None = None):
    """Resolve the starting parameters and their provenance tag.

    Precedence: explicit ``init`` argument > legacy ``params0`` > an
    explicitly passed ``amortizer`` object > ``cfg.hyper_init``. String
    inits are ``"default"`` (prior-mean :func:`init_params`) and
    ``"amortized"`` (the passed or registered :mod:`repro.amortize`
    encoder applied to the transformed data); anything else must be an
    :class:`LKGPParams` (or 4-tuple), returned bitwise-untouched when its
    dtype already matches. With ``batch`` set the data carries a leading
    task axis and the resolved params do too.
    """
    if init is None:
        if params0 is not None:
            init = params0
        elif amortizer is not None:
            init = "amortized"
        else:
            init = cfg.hyper_init
    cast = lambda p: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jnp.asarray(a, dtype), p)
    if isinstance(init, str):
        if init == "default":
            p = init_params(d, dtype)
            if batch is not None:
                p = jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a, (batch, *a.shape)), p)
            return p, "default"
        if init == "amortized":
            if amortizer is None:
                from ..amortize import get_amortizer
                amortizer = get_amortizer(d)
            if batch is not None:
                p = amortizer.init_batch(Xn, tn, Yn, mask)
            else:
                p = amortizer.init_for(Xn, tn, Yn, mask)
            return cast(p), "amortized"
        raise ValueError(f"unknown init {init!r}; expected 'default', "
                         "'amortized', or explicit LKGPParams")
    p = cast(LKGPParams(*init))
    want = 1 if batch is None else 2
    if p.raw_x_lengthscale.ndim != want:
        raise ValueError(
            f"explicit init params have x-lengthscale ndim "
            f"{p.raw_x_lengthscale.ndim}; expected {want} for this "
            f"{'batched ' if batch else ''}fit")
    return p, "params"


def _polish_fit(cfg: LKGPConfig, engine, d: int, dtype, budget: int,
                init_source: str, p0: LKGPParams, Xn, tn, Yn, mask, probes):
    """Fixed-budget polish (or the ``budget == 0`` no-op) for ``fit``."""
    flat0 = _flatten_params(p0).astype(dtype)
    if budget == 0:
        f0, _ = _cached_fit_vg(cfg, engine, d)(p0, Xn, tn, Yn, mask, probes)
        res = FitResult(x=np.asarray(flat0), fun=float(f0), n_iters=0,
                        n_evals=1, converged=False, budget=0,
                        init_source=init_source, optimizer="none")
        return p0, res
    pol = _cached_polish(cfg, engine, d, budget)
    pr = pol(flat0, Xn, tn, Yn, mask, probes)
    params = _unflatten_params(jnp.asarray(pr.x), d)
    res = FitResult(x=np.asarray(pr.x), fun=float(pr.fun), n_iters=budget,
                    n_evals=1 + budget * _POLISH_BACKTRACKS,
                    converged=bool(pr.grad_inf < _POLISH_GTOL),
                    budget=budget, init_source=init_source,
                    optimizer="polish")
    return params, res


@telemetry.span("state.fit")
def fit(X, t, Y, mask, config: LKGPConfig | None = None,
        params0: LKGPParams | None = None, engine=None, *,
        init=None, polish_steps: int | None = None,
        amortizer=None) -> LKGPState:
    """Fit the LKGP and return an immutable :class:`LKGPState`.

    Maximises (MLL + log prior) / N with L-BFGS on log-space parameters,
    through the engine selected by ``config.backend`` (or an explicitly
    provided ``engine``, e.g. a :class:`DistributedEngine` bound to a mesh).

    ``init`` selects the starting point: ``"default"`` (prior-mean init),
    ``"amortized"`` (the passed/registered :mod:`repro.amortize` encoder),
    or explicit :class:`LKGPParams`; unset, it falls back to ``params0``
    (legacy spelling of explicit params) and then ``config.hyper_init``.
    ``polish_steps`` is a one-call override of ``config.polish_steps``:
    ``-1`` runs the host L-BFGS for up to ``config.lbfgs_iters``
    iterations, ``0`` skips optimisation (the init is the fit, bitwise),
    ``k > 0`` runs exactly ``k`` device-side L-BFGS steps in one jitted
    call. ``state.fit_result`` (a :class:`FitResult`) records the budget,
    iterations used, convergence, and init provenance either way.
    """
    from .engines import get_engine

    cfg = config if config is not None else LKGPConfig()
    X = jnp.asarray(X)
    dtype = X.dtype
    t = jnp.asarray(t, dtype)
    Y = jnp.asarray(Y, dtype)
    mask = jnp.asarray(mask, dtype)
    if Y.shape != mask.shape:
        raise ObservationError(
            f"Y shape {Y.shape} does not match mask shape {mask.shape}")
    check_grid_columns(mask, t.shape[-1])
    check_observed_finite(Y, mask)
    # Zero unobserved cells: every downstream use is masked, so this is a
    # no-op for finite payloads, and it makes the documented contract
    # ("unobserved cells may hold anything") true even for NaN/inf there
    # (IEEE NaN*0 = NaN would otherwise poison Y*mask reductions).
    Y = jnp.where(mask > 0, Y, jnp.zeros_like(Y))

    x_tf, t_tf, y_tf = _fit_transforms(X, t, Y, mask)
    Xn, tn, Yn = x_tf(X), t_tf(t), y_tf(Y)

    d = X.shape[1]
    n_obs = int(np.sum(telemetry.host_read(mask, "fit.n_obs")))
    explicit_engine = engine is not None
    backend = engine.name if explicit_engine else resolve_backend(cfg, n_obs)
    if engine is None:
        engine = get_engine(backend)

    if engine.exact:
        probes = None
    else:
        key = jax.random.PRNGKey(cfg.seed)
        probes = rademacher_probes(key, cfg.slq_probes, mask, dtype)

    p0, init_source = _resolve_init(cfg, init, params0, amortizer, d, dtype,
                                    Xn, tn, Yn, mask)
    budget = cfg.polish_steps if polish_steps is None else polish_steps

    if budget >= 0:
        params, res = _polish_fit(cfg, engine, d, dtype, budget, init_source,
                                  p0, Xn, tn, Yn, mask, probes)
    else:
        vg = _cached_fit_vg(cfg, engine, d)
        flat0, unravel = jax.flatten_util.ravel_pytree(p0)

        def value_and_grad(x):
            f, g = vg(unravel(x.astype(dtype)), Xn, tn, Yn, mask, probes)
            return f, jax.flatten_util.ravel_pytree(g)[0]

        lb = lbfgs_minimize(value_and_grad, np.asarray(flat0, np.float64),
                            max_iters=cfg.lbfgs_iters)
        params = unravel(jnp.asarray(lb.x, dtype))
        res = FitResult(x=lb.x, fun=lb.fun, n_iters=lb.n_iters,
                        n_evals=lb.n_evals, converged=lb.converged,
                        budget=cfg.lbfgs_iters, init_source=init_source,
                        optimizer="lbfgs")
    state = LKGPState(params=params, X=X, t=t, Y=Y, mask=mask,
                      x_tf=x_tf, t_tf=t_tf, y_tf=y_tf, config=cfg)
    object.__setattr__(state, "fit_result", res)
    object.__setattr__(state, "backend_used", backend)
    if explicit_engine:
        # Pin an explicitly injected engine (e.g. a DistributedEngine bound
        # to a specific mesh) so posterior()/refit()/extend() keep using it;
        # config-resolved engines stay dynamic ("auto" re-resolves as data
        # grows).
        object.__setattr__(state, "engine", engine)
    return state


def fit_batch(X, t, Y, mask, config: LKGPConfig | None = None,
              params0: LKGPParams | None = None, *,
              init=None, polish_steps: int | None = None,
              amortizer=None) -> LKGPState:
    """Fit B independent tasks jointly via one batched objective.

    X: (B, n, d); t: (m,) or (B, m); Y, mask: (B, n, m). All tasks must
    share shapes. Returns an :class:`LKGPState` whose data leaves carry a
    leading batch dimension; :func:`unstack` splits it into per-task states.

    The batched objective uses the dense (exact Cholesky) marginal
    likelihood — it is fully vmappable (no data-dependent CG trip counts)
    and the per-task problems this path targets are small. With the
    default ``polish_steps=-1`` the B parameter pytrees are optimised
    jointly with one host L-BFGS on the concatenated vector (gradients are
    block-separable across tasks, so each task's optimum coincides with
    its individual fit). With ``polish_steps=k >= 0`` each task instead
    runs the fixed-budget device polish from its resolved init (see
    :func:`fit`): the polish program compiles once and each task is one
    dispatch of that same executable, so per-task results are bitwise
    identical to a single-task ``fit`` with the same init and budget —
    which is what lets the serving layer coalesce cold fits without
    changing any tenant's numbers.
    """
    from .engines import get_engine, mll_cholesky

    cfg = config if config is not None else LKGPConfig()
    X = jnp.asarray(X)
    dtype = X.dtype
    B, n, d = X.shape
    t = jnp.asarray(t, dtype)
    if t.ndim == 1:
        t = jnp.broadcast_to(t, (B, t.shape[0]))
    Y = jnp.asarray(Y, dtype)
    mask = jnp.asarray(mask, dtype)
    if Y.shape != mask.shape:
        raise ObservationError(
            f"Y shape {Y.shape} does not match mask shape {mask.shape}")
    check_grid_columns(mask, t.shape[-1])
    check_observed_finite(Y, mask)
    Y = jnp.where(mask > 0, Y, jnp.zeros_like(Y))   # see fit()

    # Transforms are fitted and applied PER TASK (not vmapped): the batched
    # lowering of even these small reductions differs from the single-task
    # one in the last ulp on CPU, which would break the bitwise
    # fit == fit_batch polish parity before the optimiser ever ran. B is
    # small on this path (coalesced cold fits), so the host loop is free.
    x_tfs = [XTransform.fit(X[i]) for i in range(B)]
    t_tfs = [TTransform.fit(t[i]) for i in range(B)]
    y_tfs = [YTransform.fit(Y[i], mask[i]) for i in range(B)]

    def _stack_trees(objs):
        return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *objs)

    x_tf, t_tf, y_tf = (_stack_trees(x_tfs), _stack_trees(t_tfs),
                        _stack_trees(y_tfs))
    Xn = jnp.stack([x_tfs[i](X[i]) for i in range(B)])
    tn = jnp.stack([t_tfs[i](t[i]) for i in range(B)])
    Yn = jnp.stack([y_tfs[i](Y[i]) for i in range(B)])

    p0, init_source = _resolve_init(cfg, init, params0, amortizer, d, dtype,
                                    Xn, tn, Yn, mask, batch=B)
    budget = cfg.polish_steps if polish_steps is None else polish_steps

    if budget >= 0:
        # The polish reuses fit()'s compiled single-task program through
        # the dense engine (fit_batch is exact/dense by construction),
        # dispatched once per task: the program compiles ONCE (shared
        # _POLISH_CACHE entry with fit) and every task steps through the
        # identical executable, so per-task results are bitwise identical
        # to a single-task fit. vmap/lax.map lowerings were both measured
        # to break that parity in the last ulp (see _cached_polish).
        engine = get_engine("dense")
        flat0 = jax.vmap(_flatten_params)(p0).astype(dtype)
        if budget == 0:
            vg = _cached_fit_vg(cfg, engine, d)
            fs = [vg(_unflatten_params(flat0[i], d), Xn[i], tn[i], Yn[i],
                     mask[i], None)[0] for i in range(B)]
            params = p0
            res = FitResult(x=np.asarray(flat0),
                            fun=float(sum(float(f) for f in fs)),
                            n_iters=0, n_evals=B, converged=False, budget=0,
                            init_source=init_source, optimizer="none")
        else:
            pol = _cached_polish(cfg, engine, d, budget)
            prs = [pol(flat0[i], Xn[i], tn[i], Yn[i], mask[i], None)
                   for i in range(B)]
            xs = jnp.stack([pr.x for pr in prs])
            params = jax.vmap(lambda xf: _unflatten_params(xf, d))(xs)
            res = FitResult(
                x=np.asarray(xs),
                fun=float(sum(float(pr.fun) for pr in prs)),
                n_iters=budget,
                n_evals=B * (1 + budget * _POLISH_BACKTRACKS),
                converged=all(float(pr.grad_inf) < _POLISH_GTOL
                              for pr in prs),
                budget=budget, init_source=init_source, optimizer="polish")
    else:
        def obj_one(p, Xi, ti, Yi, mi):
            n_obs = jnp.sum(mi)
            mll = mll_cholesky(p, Xi, ti, Yi, mi, cfg.t_kernel, cfg.jitter)
            return -(mll + log_prior(p, d)) / n_obs

        def objective(pb):
            return jnp.sum(jax.vmap(obj_one)(pb, Xn, tn, Yn, mask))

        flat0, unravel = jax.flatten_util.ravel_pytree(p0)
        vg = jax.jit(jax.value_and_grad(objective))

        def value_and_grad(x):
            f, g = vg(unravel(x.astype(dtype)))
            return f, jax.flatten_util.ravel_pytree(g)[0]

        lb = lbfgs_minimize(value_and_grad, np.asarray(flat0, np.float64),
                            max_iters=cfg.lbfgs_iters)
        params = unravel(jnp.asarray(lb.x, dtype))
        res = FitResult(x=lb.x, fun=lb.fun, n_iters=lb.n_iters,
                        n_evals=lb.n_evals, converged=lb.converged,
                        budget=cfg.lbfgs_iters, init_source=init_source,
                        optimizer="lbfgs")
    state = LKGPState(params=params, X=X, t=t, Y=Y, mask=mask,
                      x_tf=x_tf, t_tf=t_tf, y_tf=y_tf, config=cfg)
    object.__setattr__(state, "fit_result", res)
    object.__setattr__(state, "backend_used", "dense")
    return state


def unstack(state: LKGPState) -> list[LKGPState]:
    """Split a batched state from :func:`fit_batch` into per-task states."""
    B = state.X.shape[0]
    return [jax.tree_util.tree_map(lambda a: a[i], state) for i in range(B)]


def stack_states(states: list[LKGPState]) -> LKGPState:
    """Stack same-shaped per-task states into one batched state.

    The inverse of :func:`unstack`: every data leaf (params, data,
    transforms) gains a leading batch dimension, yielding a state that
    :func:`~repro.core.posterior.posterior_batch` accepts. This is how the
    serving layer coalesces posterior requests from independent tenants
    into ONE vmapped evaluation. All states must share shapes and an
    identical ``config`` (the pytree treedef carries it as metadata).
    """
    if not states:
        raise ValueError("stack_states needs at least one state")
    first = states[0]
    for i, st in enumerate(states):
        if st.config != first.config:
            raise ValueError(f"state {i} has a different config than state 0"
                             " — coalesced states must share one config")
        if st.X.ndim != 2:
            raise ValueError(f"state {i} is already batched "
                             f"(X ndim {st.X.ndim}); stack unbatched states")
        if (st.X.shape != first.X.shape or st.t.shape != first.t.shape
                or st.Y.shape != first.Y.shape):
            raise ValueError(
                f"state {i} shapes (X {st.X.shape}, t {st.t.shape}, "
                f"Y {st.Y.shape}) do not match state 0 "
                f"(X {first.X.shape}, t {first.t.shape}, Y {first.Y.shape})")
    return jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *states)


@telemetry.span("state.extend")
def extend(state: LKGPState, new_Y, new_mask, new_X=None) -> LKGPState:
    """Incremental conditioning: fold new observations into the state.

    Two modes:

    * ``new_X is None`` — ``new_Y`` / ``new_mask`` are the *full updated*
      (n, m) grids over the existing configs (e.g. a freeze-thaw scheduler
      observed more epochs). ``new_mask`` must be a superset of
      ``state.mask``.
    * ``new_X`` given — k new configs are appended; ``new_Y`` / ``new_mask``
      are their (k, m) rows.

    Output transforms are refit on the union of observed data (the Y shift
    tracks the running max); the fitted hyper-parameters are carried over
    unchanged as a warm start — follow with :func:`refit` to re-optimise
    them from that warm state.
    """
    dtype = state.Y.dtype
    new_Y = jnp.asarray(new_Y, dtype)
    new_mask = jnp.asarray(new_mask, dtype)
    if new_Y.shape != new_mask.shape:
        raise ObservationError(
            f"new_Y shape {new_Y.shape} does not match new_mask shape "
            f"{new_mask.shape}")
    # Reject masks marking cells outside the budget grid t (and budget-axis
    # shape mismatches generally) with a typed error naming the offending
    # columns, instead of an opaque broadcast/concatenate failure below.
    check_grid_columns(new_mask, state.m, what="new_mask")
    check_observed_finite(new_Y, new_mask, what="new_Y")
    new_Y = jnp.where(new_mask > 0, new_Y, jnp.zeros_like(new_Y))  # see fit()

    if new_X is None:
        if new_Y.shape != state.Y.shape:
            raise ValueError(f"full-grid update expects shape {state.Y.shape}, "
                             f"got {new_Y.shape}")
        old_m = telemetry.host_read(state.mask, "extend.mask")
        upd_m = telemetry.host_read(new_mask, "extend.mask")
        if np.any(upd_m < old_m):
            raise ValueError("new_mask must be a superset of the current mask")
        X, Y, mask = state.X, new_Y, new_mask
    else:
        new_X = jnp.asarray(new_X, state.X.dtype)
        X = jnp.concatenate([state.X, new_X], axis=0)
        Y = jnp.concatenate([state.Y, new_Y], axis=0)
        mask = jnp.concatenate([state.mask, new_mask], axis=0)

    x_tf, _, y_tf = _fit_transforms(X, state.t, Y, mask)
    out = dataclasses.replace(state, X=X, Y=Y, mask=mask,
                              x_tf=x_tf, y_tf=y_tf)
    # dataclasses.replace drops every attached attribute. The bound engine
    # is deliberately carried forward (posterior()/refit() keep using the
    # same backend); fit_result / backend_used are deliberately NOT — they
    # described the fit of the *pre-extend* data and would be stale against
    # the extended grid (the carried-over params are a warm start, not an
    # optimum). Clearing them explicitly pins that contract even if the
    # construction above ever changes to one that copies attributes.
    eng = getattr(state, "engine", None)
    if eng is not None:
        object.__setattr__(out, "engine", eng)
    object.__setattr__(out, "fit_result", None)
    object.__setattr__(out, "backend_used", None)
    return out


@telemetry.span("state.refit")
def refit(state: LKGPState, config: LKGPConfig | None = None,
          lbfgs_iters: int | None = None, engine=None, *,
          init=None, polish_steps: int | None = None,
          amortizer=None) -> LKGPState:
    """Re-optimise hyper-parameters warm-started from ``state.params``.

    ``lbfgs_iters`` and ``polish_steps`` are one-call budget overrides:
    they do NOT persist into the returned state's config. An engine bound
    by the original ``fit`` call is reused unless a new one is given.

    The starting point defaults to ``state.params`` (classic warm start)
    — unless the config says ``hyper_init="amortized"`` (or ``init`` /
    ``amortizer`` is given explicitly), in which case every refit
    re-amortizes from the *current* observed data, which tracks the data
    distribution better than dragging yesterday's optimum along. With
    ``init=<params>`` and ``polish_steps=0`` the given params round-trip
    bitwise into the returned state.
    """
    base_cfg = config if config is not None else state.config
    cfg = base_cfg
    if lbfgs_iters is not None:
        cfg = dataclasses.replace(cfg, lbfgs_iters=lbfgs_iters)
    if engine is None:
        engine = getattr(state, "engine", None)
    if init is None and amortizer is None and cfg.hyper_init != "amortized":
        init = state.params
    out = fit(state.X, state.t, state.Y, state.mask, cfg,
              engine=engine, init=init, polish_steps=polish_steps,
              amortizer=amortizer)
    if cfg is not base_cfg:
        diag = {k: getattr(out, k, None)
                for k in ("fit_result", "backend_used", "engine")}
        out = dataclasses.replace(out, config=base_cfg)
        for k, v in diag.items():
            if v is not None:
                object.__setattr__(out, k, v)
    return out
