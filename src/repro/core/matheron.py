"""Posterior sampling via Matheron's rule with latent Kronecker structure.

    (f | Y)(.) = f(.) + k(., train) P^T (P (K1 (x) K2) P^T + s^2 I)^{-1}
                                        (vec(Y) - f(X x t) - eps)

* Prior samples on the joint grid use the Kronecker factorisation
  (L1 (x) L2) Z  ==  L1 @ Z @ L2^T  at O((n+n*)^3 + m^3) cost.
* The inverse-matrix-vector product is a batched solve against the masked
  latent-Kronecker operator (grid form, zero-padded residuals) — CG by
  default, or any engine solve via the ``solve`` hook.
* The correction is zero-padding -> Kronecker MVM -> evaluation at test rows:
  K1[joint, train] @ u @ K2.

The pieces are exposed separately (:func:`prior_residual_draws`,
:func:`kronecker_correction`) so that :class:`repro.core.posterior.Posterior`
can stack the Matheron residuals together with ``Y * mask`` into ONE
multi-RHS block solve ``K^{-1}[y | residuals]`` — the cached
``alpha = K^{-1}(Y * mask)`` and all samples then cost a single batched
operator sweep, and by linearity (``K^{-1}(Y - F - eps) = alpha -
K^{-1}(F + eps)``) the sample mean stays exactly consistent with the exact
mean.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from . import telemetry
from .gp_kernels import HIGHEST
from .mvm import lk_operator
from .solvers import get_solver

__all__ = ["sample_posterior_grid", "prior_residual_draws",
           "kronecker_correction"]


#: Tenfold jitter escalations tried when a Cholesky factor is not finite.
CHOLESKY_RETRIES = 3

_all_finite = jax.jit(lambda a: jnp.all(jnp.isfinite(a)))


def _psd_cholesky(K: jnp.ndarray, jitter,
                  retries: int = CHOLESKY_RETRIES) -> jnp.ndarray:
    """Cholesky of a Gram matrix that is PSD in exact arithmetic.

    The diagonal jitter is at least the rounding noise of K's dtype, n * eps
    * max(diag K): smooth RBF Grams over thousands of configs have
    eigenvalues far below f32 rounding, and an f32 Cholesky at the f64-sized
    default jitter returns NaN. In f64 the floor is orders of magnitude
    below the default, so f64 draws are unchanged.

    That floor is about eps * ||K||, where a factorisation's own rounding
    lies: after refits LCBench's K1 (lengthscales ~10, least f32
    eigenvalue below -floor) factored to NaN on a TPU. So an eager factor
    is read on the host once, and one that is not finite is taken again at
    ten times the jitter, up to ``retries`` times (as GPyTorch's
    ``psd_safe_cholesky``). A finite factor is returned as it was; under a
    trace the factor is returned unread.
    """
    n = K.shape[0]
    floor = n * jnp.finfo(K.dtype).eps * jnp.max(jnp.diag(K))
    eps = jnp.maximum(jnp.asarray(jitter, K.dtype), floor)
    L = jnp.linalg.cholesky(K + eps * jnp.eye(n, dtype=K.dtype))
    if (retries == 0 or isinstance(L, jax.core.Tracer)
            or telemetry.host_read(_all_finite(L), "matheron.factor")):
        return L
    return _psd_cholesky(K, 10.0 * eps, retries - 1)


def prior_residual_draws(key, K1_joint: jnp.ndarray, K2: jnp.ndarray,
                         n_train: int, noise, n_samples: int,
                         jitter: float = 1e-6):
    """Draw the Matheron prior part: joint-grid prior samples + noise.

    Returns ``(F, eps)`` with ``F`` of shape (s, n+n*, m) — prior samples
    over the full joint grid via the Kronecker factorisation — and ``eps``
    of shape (s, n, m), the observation-noise draws on the training block.
    The solve RHS is then ``mask * (F[:, :n] + eps)``.
    """
    dtype = K1_joint.dtype
    na = K1_joint.shape[0]
    m = K2.shape[0]
    L1 = _psd_cholesky(K1_joint, jitter)
    L2 = _psd_cholesky(K2, jitter)

    kz, ke = jax.random.split(key)
    Z = jax.random.normal(kz, (n_samples, na, m), dtype)
    # Prior samples on the joint grid: vec(F) ~ N(0, K1_joint (x) K2).
    F = jnp.einsum("ij,sjm,km->sik", L1, Z, L2, precision=HIGHEST)
    eps = jnp.sqrt(noise) * jax.random.normal(ke, (n_samples, n_train, m),
                                              dtype)
    return F, eps


def kronecker_correction(K1_joint: jnp.ndarray, u: jnp.ndarray,
                         K2: jnp.ndarray, n_train: int) -> jnp.ndarray:
    """Matheron correction (k1(., X) (x) k2(., t)) P^T u == K1[:, :n] @ u @ K2."""
    return jnp.einsum("aj,sjm,mk->sak", K1_joint[:, :n_train], u, K2,
                      precision=HIGHEST)


def sample_posterior_grid(key, K1_joint: jnp.ndarray, K2: jnp.ndarray,
                          n_train: int, Y: jnp.ndarray, mask: jnp.ndarray,
                          noise, n_samples: int, cg_tol: float = 0.01,
                          cg_max_iters: int = 10_000, jitter: float = 1e-6,
                          mvm: Callable | None = None,
                          solve: Callable | None = None,
                          alpha: jnp.ndarray | None = None,
                          solver: str | None = None,
                          config=None) -> jnp.ndarray:
    """Draw posterior samples over the full (train + test configs) x t grid.

    K1_joint: ((n+n*), (n+n*)) config kernel over [X_train; X_test].
    K2: (m, m) progression kernel on the shared t grid.
    Y, mask: (n, m) observed learning curves (grid form).
    mvm: optional raw MVM ``mvm(K1, K2, mask, u, noise=...)`` for the CG
      operator; solve: optional batched solver ``solve(rhs) -> K^{-1} rhs``
      overriding the solver entirely; alpha: optional cached
      ``K^{-1}(Y * mask)``; solver: registry name (``"cg"``/``"sgd"``/...)
      for the pathwise residual solves — SGD is the arXiv 2506.06895
      pathwise-conditioning regime, where every sample draw is an SGD solve
      against the same operator; config: optional LKGPConfig supplying the
      solver hyper-parameters (tolerances default to ``cg_tol`` /
      ``cg_max_iters`` otherwise).
    Returns samples of shape (n_samples, n+n*, m); rows [:n] are posterior
    curves for the training configs (continuations), rows [n:] for test.
    """
    F, eps = prior_residual_draws(key, K1_joint, K2, n_train, noise,
                                  n_samples, jitter)

    if solve is None:
        K1_tt = K1_joint[:n_train, :n_train]
        if mvm is None:
            A = lk_operator(K1_tt, K2, mask, noise)
        else:
            A = lambda u: mvm(K1_tt, K2, mask, u, noise=noise)
        if config is None:
            # Duck-config carrying just what the solver strategies read.
            from .state import LKGPConfig
            config = LKGPConfig(cg_tol=cg_tol, cg_max_iters=cg_max_iters,
                                solver=solver or "auto")
        elif solver is not None and getattr(config, "solver", None) != solver:
            import dataclasses
            config = dataclasses.replace(config, solver=solver)
        strategy = get_solver(config.solver if config.solver != "auto"
                              else "cg")
        solve = lambda rhs: strategy.solve(A, rhs, config).x

    if alpha is None:
        u = solve(mask * (Y[None] - F[:, :n_train, :] - eps))  # (s, n, m)
    else:
        # Reuse the cached K^{-1}(Y*mask): solve only for the (F + eps) part.
        u = alpha[None] - solve(mask * (F[:, :n_train, :] + eps))

    return F + kronecker_correction(K1_joint, u, K2, n_train)
