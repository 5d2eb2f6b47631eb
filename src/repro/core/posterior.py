"""Lazy posterior over the latent grid, behind one ``PosteriorLike`` API.

A :class:`Posterior` is cheap to construct: nothing is computed until a
property is read. The expensive CG solve of ``alpha = K^{-1} (Y * mask)``
is computed once and cached, then shared between

* the exact posterior mean  ``K1[:, :n] @ alpha @ K2``  and
* Matheron-rule samples: by linearity,
  ``K^{-1}(Y - F - eps) = alpha - K^{-1}(F + eps)``, so each sampling call
  only solves for the (F + eps) part and reuses the cached ``alpha`` — the
  sample mean is exactly consistent with the exact mean.

Solves are consolidated: if samples are requested before ``alpha`` exists,
the posterior stacks ``[Y * mask | Matheron residuals]`` into ONE multi-RHS
block solve, so a full posterior evaluation (``final()``: exact mean +
Matheron variance) costs a single batched operator sweep instead of two.
The block solver's per-column diagnostics (iterations, true residuals,
breakdown flags) from the most recent solve are exposed as
:attr:`Posterior.solve_info`; :attr:`Posterior.solve_count` counts the
engine solves this posterior has performed.

Caching is *state-keyed*: :func:`posterior` attaches the lazy posterior to
the state instance itself, so repeated ``posterior(state)`` calls on an
unchanged state return the SAME object and reuse its resident
``K^{-1}[y | residuals]`` instead of re-running the stacked solve. Because
``extend`` / ``refit`` are functional (they return fresh state objects),
derived states never see a stale cache — invalidation is construction.
Per-call control via ``posterior(state, cache=...)``; the default policy
is ``LKGPConfig.posterior_cache``.

:class:`Posterior` (lazy, engine-backed, Matheron MC variance) and
:class:`BatchedPosterior` (vmapped exact dense, one task per batch row)
both conform to the :class:`PosteriorLike` protocol — ``mean`` /
``variance`` / ``samples(key, n_samples)`` / ``final(key, n_samples)`` /
``solve_info`` — so callers (schedulers, the serving layer) swap them
without isinstance checks.

All solves go through the inference engine resolved from the state's
config (or an explicitly provided engine), so the posterior path uses the
same backend — dense, iterative, pallas, or distributed — as fitting.
"""
from __future__ import annotations

import threading
from functools import cached_property
from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from . import gp_kernels as gk
from .engines import get_engine
from .matheron import kronecker_correction, prior_residual_draws
from .mvm import kron_dense
from .state import LKGPState, resolve_backend

__all__ = ["PosteriorLike", "Posterior", "posterior", "joint_grams",
           "BatchedPosterior", "posterior_batch"]


@runtime_checkable
class PosteriorLike(Protocol):
    """One posterior interface for lazy and batched implementations.

    ``mean`` / ``variance`` cover the full grid (original y units);
    ``samples`` draws posterior functions; ``final`` returns the
    final-progression (mean, var) per config; ``solve_info`` surfaces the
    most recent solver diagnostics (None for exact paths that have none).
    """

    @property
    def mean(self) -> jnp.ndarray: ...

    @property
    def variance(self) -> jnp.ndarray: ...

    @property
    def solve_info(self) -> Any: ...

    def samples(self, key, n_samples: int | None = None) -> jnp.ndarray: ...

    def final(self, key=None, n_samples: int | None = None): ...


def joint_grams(state: LKGPState, Xs=None):
    """K1 over [X_train; X_test] (transformed) and K2 over t (jittered).

    Matches the training-time Gram construction: K2 carries the jitter, the
    joint K1 does not (its train block is only used inside the noisy
    operator; Cholesky call sites add jitter themselves).
    """
    cfg = state.config
    p = state.params
    Xn = state.x_tf(state.X)
    tn = state.t_tf(state.t)
    K2 = gk.KERNELS_1D[cfg.t_kernel](
        tn, tn, jnp.exp(p.raw_t_lengthscale), jnp.exp(p.raw_outputscale))
    K2 = K2 + cfg.jitter * jnp.eye(tn.shape[0], dtype=K2.dtype)
    if Xs is None:
        Xa = Xn
    else:
        Xa = jnp.concatenate([Xn, state.x_tf(jnp.asarray(Xs, Xn.dtype))], 0)
    K1a = gk.rbf_ard(Xa, Xa, jnp.exp(p.raw_x_lengthscale))
    return K1a, K2


class Posterior:
    """Lazy LKGP posterior over the full (train [+ test]) x t grid.

    Rows ``[:n]`` of every product are curve continuations for the training
    configs; if ``Xs`` was given, rows ``[n:]`` are predictions for the new
    configs. All outputs are in original y units.
    """

    def __init__(self, state: LKGPState, Xs=None, engine=None):
        self._state = state
        self._Xs = Xs
        if engine is None:
            # An engine explicitly injected at fit() time (e.g. bound to a
            # specific mesh) is pinned on the state; otherwise resolve from
            # config and observation count.
            engine = getattr(state, "engine", None)
        if engine is None:
            n_obs = int(np.sum(np.asarray(state.mask)))
            engine = get_engine(resolve_backend(state.config, n_obs))
        self._engine = engine
        self._alpha: jnp.ndarray | None = None   # cached K^{-1}(Y*mask)
        self._solve_info: Any = None  # CGResult of most recent engine solve
        self._n_solves = 0            # engine solves performed (sweeps run)

    # -- cached pieces -----------------------------------------------------
    @cached_property
    def _grams(self):
        return joint_grams(self._state, self._Xs)

    @cached_property
    def _operator(self):
        """A = P (K1 (x) K2) P^T + sigma^2 I over the training block."""
        K1a, K2 = self._grams
        n = self._state.n
        noise = jnp.exp(self._state.params.raw_noise)
        return self._engine.operator_from_grams(
            K1a[:n, :n], K2, self._state.mask, noise)

    def _solve(self, rhs):
        """Engine solve capturing the block solver's diagnostics."""
        x = self._engine.solve(self._operator, rhs, self._state.config)
        self._solve_info = getattr(self._operator, "last_result", None)
        self._n_solves += 1
        return x

    @property
    def alpha(self):
        """Cached K^{-1} (Y * mask) in transformed space (grid form)."""
        if self._alpha is None:
            st = self._state
            Ym = st.y_tf(st.Y) * st.mask
            self._alpha = self._solve(Ym)
        return self._alpha

    @property
    def solve_info(self):
        """Diagnostics (:class:`repro.core.solvers.CGResult`) of the most recent
        solve through this posterior — per-column iterations, true
        residuals, and breakdown flags — or None before any solve (or for
        engines that do not report them, e.g. the exact dense solve)."""
        return self._solve_info

    @property
    def solve_count(self) -> int:
        """Number of engine solves (batched operator sweeps) this posterior
        has run. A state-cache hit returns the same posterior object, so a
        repeated evaluation leaves this counter unchanged — the handle the
        serving benchmark uses to verify the solve cache."""
        return self._n_solves

    # -- products ----------------------------------------------------------
    @property
    def mean(self) -> jnp.ndarray:
        """Exact posterior mean over the grid: (n(+n*), m), y units."""
        K1a, K2 = self._grams
        n = self._state.n
        mean_t = jnp.einsum("aj,jm,mk->ak", K1a[:, :n], self.alpha, K2,
                            precision=gk.HIGHEST)
        return self._state.y_tf.inverse(mean_t)

    def samples(self, key, n_samples: int | None = None) -> jnp.ndarray:
        """Matheron-rule posterior samples: (s, n(+n*), m), y units.

        If ``alpha`` is not cached yet, ``[Y * mask | residuals]`` are
        stacked into ONE multi-RHS block solve (a single batched operator
        sweep yields the exact mean's alpha AND every sample); afterwards
        samples reuse the cached alpha and only solve the residual part.
        """
        st = self._state
        cfg = st.config
        n_samples = n_samples or cfg.posterior_samples
        K1a, K2 = self._grams
        n = st.n
        noise = jnp.exp(st.params.raw_noise)
        F, eps = prior_residual_draws(key, K1a, K2, n, noise, n_samples,
                                      jitter=cfg.jitter)
        resid = st.mask * (F[:, :n, :] + eps)
        if self._alpha is None:
            Ym = st.y_tf(st.Y) * st.mask
            sol = self._solve(jnp.concatenate([Ym[None], resid], axis=0))
            self._alpha = sol[0]
            u = sol[0][None] - sol[1:]
        else:
            # Linearity: K^{-1}(Y - F - eps) = alpha - K^{-1}(F + eps).
            u = self._alpha[None] - self._solve(resid)
        raw = F + kronecker_correction(K1a, u, K2, n)
        return st.y_tf.inverse(raw)

    @cached_property
    def _default_samples(self):
        cfg = self._state.config
        # fold_in tag 1: the cached default-sample stream. final()'s
        # explicit-key fallback uses tag 2 so the two paths never share
        # randomness (they used to both build PRNGKey(seed + 1)).
        key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), 1)
        return self.samples(key)

    @property
    def variance(self) -> jnp.ndarray:
        """Predictive variance (Matheron MC estimate + observation noise)."""
        st = self._state
        var_f = jnp.var(self._default_samples, axis=0)
        return var_f + st.y_tf.inverse_var(jnp.exp(st.params.raw_noise))

    def final(self, key=None, n_samples: int | None = None):
        """(mean, var) of the final-progression value per config.

        Mean is exact (cached CG solve); variance is estimated from Matheron
        samples plus observation noise — the Fig. 4 protocol.
        """
        st = self._state
        # Samples first: on a fresh posterior this folds the alpha solve and
        # the Matheron residual solves into ONE stacked operator sweep; the
        # mean below then reads the alpha cached by that same solve.
        if key is None and n_samples is None:
            s = self._default_samples[:, :, -1]   # cached; same default key
        else:
            if key is None:
                # tag 2: distinct from the _default_samples stream (tag 1).
                key = jax.random.fold_in(
                    jax.random.PRNGKey(st.config.seed), 2)
            s = self.samples(key, n_samples)[:, :, -1]
        mean = self.mean[:, -1]
        var_f = jnp.var(s, axis=0)
        var_y = var_f + st.y_tf.inverse_var(jnp.exp(st.params.raw_noise))
        return mean, var_y


# -- state-keyed solve cache -----------------------------------------------
# The cached posterior lives ON the state instance (attached the same way
# fit() attaches its diagnostics), so its lifetime is exactly the state's:
# extend/refit build new objects and therefore start cold, evicting a
# session's state drops its solves with it. The lock only guards the
# get-or-create so concurrent serving threads share one posterior.
_CACHE_ATTR = "_posterior_cache"
_BATCH_CACHE_ATTR = "_posterior_batch_cache"
_CACHE_LOCK = threading.Lock()


def _state_cached(state, attr: str, build):
    with _CACHE_LOCK:
        post = getattr(state, attr, None)
        if post is None:
            post = build()
            object.__setattr__(state, attr, post)
        return post


def posterior(state: LKGPState, Xs=None, engine=None,
              cache: bool | None = None) -> Posterior:
    """Lazy posterior for a fitted state (optionally at new configs Xs).

    ``cache=None`` (default) consults ``state.config.posterior_cache``:
    when on, repeated calls on the same state object return ONE shared
    :class:`Posterior` whose solves are resident — the second call performs
    zero additional operator sweeps. Explicit ``Xs`` / ``engine`` arguments
    always bypass the cache (their results are not state-determined);
    ``cache=False`` forces a fresh posterior; ``cache=True`` demands the
    cached one and raises if the call is not cacheable.
    """
    cacheable = Xs is None and engine is None
    if cache is None:
        cache = cacheable and state.config.posterior_cache
    elif cache and not cacheable:
        raise ValueError("cache=True requires the state-determined "
                         "posterior: no explicit Xs or engine")
    if not cache:
        return Posterior(state, Xs=Xs, engine=engine)
    return _state_cached(state, _CACHE_ATTR, lambda: Posterior(state))


# -- batched exact posterior (one vmapped call over fit_batch states) ------
# The jitted+vmapped product functions are cached per (t_kernel, jitter) at
# module level: a fresh closure per BatchedPosterior would make every
# serving request retrace, turning the coalesced hot path into a compile
# benchmark. Same-shape requests now hit jit's own executable cache.
_BATCHED_FN_CACHE: dict = {}


def _batched_products_fn(t_kernel: str, jitter: float):
    key = ("products", t_kernel, jitter)
    fn = _BATCHED_FN_CACHE.get(key)
    if fn is not None:
        return fn
    k2fn = gk.KERNELS_1D[t_kernel]

    def one(params, X, t, Y, mask, x_tf, t_tf, y_tf):
        Xn, tn, Yn = x_tf(X), t_tf(t), y_tf(Y)
        n, m = mask.shape
        K2 = k2fn(tn, tn, jnp.exp(params.raw_t_lengthscale),
                  jnp.exp(params.raw_outputscale))
        K2 = K2 + jitter * jnp.eye(m, dtype=K2.dtype)
        K1 = gk.rbf_ard(Xn, Xn, jnp.exp(params.raw_x_lengthscale))
        noise = jnp.exp(params.raw_noise)

        mv = mask.reshape(-1)
        Kd = kron_dense(K1, K2) * (mv[:, None] * mv[None, :])
        Kd = Kd + jnp.diag(noise * mv + (1.0 - mv))
        L = jnp.linalg.cholesky(Kd)
        ym = (Yn * mask).reshape(-1)
        # Joint-covariance rows at the final-epoch cells, used both for the
        # exact final variance and (below) stacked with ym into ONE
        # multi-RHS solve.
        Krhs = (K1[:, :, None] * K2[:, -1][None, None, :]) * mask[None]
        Krhs = Krhs.reshape(n, n * m)
        # Bitwise per-request == coalesced (the serving guarantee) bans two
        # constructs whose lowering changes with batch size: single-column
        # triangular solves (XLA vectorizes trsv across the batch) and
        # gemm-based means (per-B tiling). So ym rides along the multi-RHS
        # solve, and the mean contraction is broadcast-multiply + reduce.
        sol = jax.scipy.linalg.cho_solve(
            (L, True), jnp.concatenate([ym[:, None], Krhs.T], axis=1))
        alpha = sol[:, 0] * mv
        S = sol[:, 1:]                                      # (N, n)
        ag = alpha.reshape(n, m)
        tmp = jnp.sum(ag[:, :, None] * K2[None, :, :], axis=1)     # (n, m)
        mean_t = jnp.sum(K1[:, :, None] * tmp[None, :, :], axis=1)

        # Exact latent variance of each config's final-epoch value:
        # var_i = K1[ii] K2[mm] - k_i^T A^{-1} k_i with k_i the masked
        # joint-covariance row at cell (i, m-1).
        quad = jnp.sum(Krhs.T * S, axis=0)
        var_f = jnp.diag(K1) * K2[-1, -1] - quad
        var_f = jnp.maximum(var_f, 0.0)
        return (y_tf.inverse(mean_t),
                y_tf.inverse_var(var_f + noise))

    fn = _BATCHED_FN_CACHE[key] = jax.jit(jax.vmap(one))
    return fn


def _batched_cov_fn(t_kernel: str, jitter: float):
    """Full-grid exact posterior: mean (transformed), per-cell variance in
    y units (incl. noise), and the Cholesky of the latent grid covariance
    (for joint sampling) — per task, vmapped over the batch."""
    key = ("cov", t_kernel, jitter)
    fn = _BATCHED_FN_CACHE.get(key)
    if fn is not None:
        return fn
    k2fn = gk.KERNELS_1D[t_kernel]

    def one(params, X, t, Y, mask, x_tf, t_tf, y_tf):
        Xn, tn, Yn = x_tf(X), t_tf(t), y_tf(Y)
        n, m = mask.shape
        N = n * m
        K2 = k2fn(tn, tn, jnp.exp(params.raw_t_lengthscale),
                  jnp.exp(params.raw_outputscale))
        K2 = K2 + jitter * jnp.eye(m, dtype=K2.dtype)
        K1 = gk.rbf_ard(Xn, Xn, jnp.exp(params.raw_x_lengthscale))
        noise = jnp.exp(params.raw_noise)

        mv = mask.reshape(-1)
        Kfull = kron_dense(K1, K2)
        Kd = Kfull * (mv[:, None] * mv[None, :])
        Kd = Kd + jnp.diag(noise * mv + (1.0 - mv))
        L = jnp.linalg.cholesky(Kd)
        ym = (Yn * mask).reshape(-1)
        # Latent covariance of f on EVERY grid cell given the observed
        # cells: C = K - Kx A^{-1} Kx^T with Kx the cross-covariance whose
        # unobserved columns are zeroed (those rows/cols of A are identity,
        # so they contribute nothing to the solve). ym rides along as one
        # more RHS column and the mean uses reduce-style contractions —
        # batch-size-stable bits, see _batched_products_fn.
        Kx = Kfull * mv[None, :]
        sol = jax.scipy.linalg.cho_solve(
            (L, True), jnp.concatenate([ym[:, None], Kx.T], axis=1))
        alpha = sol[:, 0] * mv
        S = sol[:, 1:]                                       # (N, N)
        ag = alpha.reshape(n, m)
        tmp = jnp.sum(ag[:, :, None] * K2[None, :, :], axis=1)
        mean_t = jnp.sum(K1[:, :, None] * tmp[None, :, :], axis=1)
        C = Kfull - jnp.matmul(Kx, S, precision=gk.HIGHEST)
        var_grid = jnp.maximum(jnp.diag(C), 0.0).reshape(n, m)
        Lc = jnp.linalg.cholesky(
            C + 10.0 * jitter * jnp.eye(N, dtype=C.dtype))
        scale = y_tf.scale
        var_y = y_tf.inverse_var(var_grid + noise)
        return mean_t, var_y, Lc, y_tf.shift, scale

    fn = _BATCHED_FN_CACHE[key] = jax.jit(jax.vmap(one))
    return fn


class BatchedPosterior:
    """Vmapped exact posterior over a batch of tasks from :func:`fit_batch`.

    All B tasks are processed in ONE jitted+vmapped call: exact dense
    posterior mean over each task's grid plus the exact final-progression
    mean/variance (no Matheron MC — the per-task problems this path targets
    are small, so the dense O(N^3) route is both exact and fast). The
    Gram construction matches :func:`joint_grams` (jitter on K2 only), so
    per-task results agree with :class:`Posterior` on the same state slice.

    Conforms to :class:`PosteriorLike`: ``variance`` is the exact per-cell
    predictive variance (B, n, m), ``samples(key, n_samples)`` draws exact
    joint posterior functions (s, B, n, m) from the dense grid covariance,
    and ``final(key, n_samples)`` accepts the same signature as
    :meth:`Posterior.final` — with a key it estimates the final variance
    from samples (behavioural parity with the Matheron protocol), without
    one it returns the exact variance. ``solve_info`` is None: the exact
    vmapped Cholesky path has no iterative diagnostics to report.
    """

    def __init__(self, state: LKGPState):
        if state.X.ndim != 3:
            raise ValueError("BatchedPosterior expects a batched state from "
                             f"fit_batch; got X of shape {state.X.shape}")
        self._state = state

    @property
    def solve_info(self):
        """None — the exact dense path reports no iterative diagnostics."""
        return None

    @cached_property
    def _products(self):
        st = self._state
        fn = _batched_products_fn(st.config.t_kernel, st.config.jitter)
        return fn(st.params, st.X, st.t, st.Y, st.mask,
                  st.x_tf, st.t_tf, st.y_tf)

    @cached_property
    def _cov_products(self):
        st = self._state
        fn = _batched_cov_fn(st.config.t_kernel, st.config.jitter)
        return fn(st.params, st.X, st.t, st.Y, st.mask,
                  st.x_tf, st.t_tf, st.y_tf)

    @cached_property
    def _final_exact(self):
        # Resident default-final: the slice is dispatched once, so a warm
        # serving request re-reads arrays instead of re-running eager ops.
        mean, var = self._products
        return mean[:, :, -1], var

    @property
    def mean(self) -> jnp.ndarray:
        """Exact posterior means, (B, n, m), y units."""
        return self._products[0]

    @property
    def variance(self) -> jnp.ndarray:
        """Exact per-cell predictive variance (+ noise), (B, n, m), y units."""
        return self._cov_products[1]

    def samples(self, key, n_samples: int | None = None) -> jnp.ndarray:
        """Exact joint posterior samples, (s, B, n, m), y units.

        Drawn from the dense latent grid covariance per task (no
        observation noise — same convention as :meth:`Posterior.samples`).
        """
        st = self._state
        n_samples = n_samples or st.config.posterior_samples
        mean_t, _, Lc, shift, scale = self._cov_products
        B, n, m = st.Y.shape
        z = jax.random.normal(key, (B, n_samples, n * m), mean_t.dtype)
        draws = mean_t.reshape(B, 1, n * m) + jnp.einsum(
            "bij,bsj->bsi", Lc, z, precision=gk.HIGHEST)
        raw = draws.reshape(B, n_samples, n, m).transpose(1, 0, 2, 3)
        return raw * scale[None, :, None, None] \
            + shift[None, :, None, None]

    def final(self, key=None, n_samples: int | None = None):
        """(mean, var) of the final-progression value, each (B, n).

        Signature-compatible with :meth:`Posterior.final`. The default
        (no key) returns the exact final variance; with an explicit key the
        variance is estimated from ``n_samples`` joint samples plus noise,
        mirroring the Matheron MC protocol of the lazy posterior.
        """
        if key is None and n_samples is None:
            return self._final_exact
        mean, _ = self._products
        if key is None:
            key = jax.random.fold_in(
                jax.random.PRNGKey(self._state.config.seed), 2)
        s = self.samples(key, n_samples)[:, :, :, -1]        # (s, B, n)
        noise = jnp.exp(self._state.params.raw_noise)        # (B,)
        scale = jnp.asarray(self._state.y_tf.scale)          # (B,)
        var_mc = jnp.var(s, axis=0) + (noise * scale**2)[:, None]
        return mean[:, :, -1], var_mc


def posterior_batch(state: LKGPState,
                    cache: bool | None = None) -> BatchedPosterior:
    """Batched exact posterior for a :func:`fit_batch` state.

    Same state-keyed cache semantics as :func:`posterior`: by default the
    batched posterior (and its resident vmapped solve products) is shared
    across calls on the same state object.
    """
    if cache is None:
        cache = state.config.posterior_cache
    if not cache:
        return BatchedPosterior(state)
    return _state_cached(state, _BATCH_CACHE_ATTR,
                         lambda: BatchedPosterior(state))
