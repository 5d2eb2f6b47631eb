"""Stochastic Lanczos quadrature (SLQ) for log-determinants.

Estimates log det(A|_S) of the masked joint operator restricted to the
observed subspace S, using Rademacher probes drawn inside S (probes stay in S
because the operator maps S to itself). This is the standard machinery behind
GPyTorch's iterative marginal likelihood [Gardner et al., 2018], adapted to
the grid-form representation of the latent Kronecker operator.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from .gp_kernels import HIGHEST

__all__ = ["lanczos", "slq_logdet", "slq_logdet_from_tridiag",
           "tridiag_from_cg", "rademacher_probes"]


def rademacher_probes(key, n_probes: int, mask: jnp.ndarray, dtype) -> jnp.ndarray:
    """(p, n, m) +-1 probes restricted to the observed subspace."""
    z = jax.random.rademacher(key, (n_probes, *mask.shape), dtype=dtype)
    return z * mask


def lanczos(A: Callable, v0: jnp.ndarray, num_iters: int):
    """Batched Lanczos tridiagonalisation with full reorthogonalisation.

    v0: (p, n, m) initial probes (not necessarily normalised).
    Returns (alphas (p,k), betas (p,k-1)) of the tridiagonal T per probe.
    """
    p = v0.shape[0]
    norm0 = jnp.sqrt(jnp.sum(v0 * v0, axis=(-2, -1), keepdims=True))
    v = v0 / jnp.maximum(norm0, 1e-30)

    k = num_iters
    V = jnp.zeros((k, *v.shape), v.dtype)  # Lanczos basis for reorthogonalisation
    alphas = jnp.zeros((p, k), v.dtype)
    betas = jnp.zeros((p, k), v.dtype)

    def dot(a, b):
        return jnp.sum(a * b, axis=(-2, -1))

    def body(j, carry):
        V, alphas, betas, v, v_prev, beta_prev = carry
        V = V.at[j].set(v)
        w = A(v) - beta_prev[..., None, None] * v_prev
        alpha = dot(w, v)
        w = w - alpha[..., None, None] * v
        # Full reorthogonalisation: w -= V V^T w (masked basis, so stays in S).
        coeffs = jnp.einsum("kpnm,pnm->kp", V, w, precision=HIGHEST)
        w = w - jnp.einsum("kp,kpnm->pnm", coeffs, V, precision=HIGHEST)
        beta = jnp.sqrt(jnp.maximum(dot(w, w), 0.0))
        v_next = jnp.where(beta[..., None, None] > 1e-12,
                           w / jnp.maximum(beta[..., None, None], 1e-30), 0.0)
        alphas = alphas.at[:, j].set(alpha)
        betas = betas.at[:, j].set(beta)
        return (V, alphas, betas, v_next, v, beta)

    init = (V, alphas, betas, v, jnp.zeros_like(v), jnp.zeros((p,), v.dtype))
    V, alphas, betas, _, _, _ = jax.lax.fori_loop(0, k, body, init)
    return alphas, betas[:, : k - 1]


def slq_logdet(A: Callable, probes: jnp.ndarray, num_iters: int,
               subspace_dim) -> jnp.ndarray:
    """log det estimate of A restricted to the probe subspace.

    probes: (p, n, m) Rademacher probes already masked; every probe has
    squared norm == subspace_dim.
    """
    alphas, betas = lanczos(A, probes, num_iters)

    def per_probe(alpha, beta):
        T = jnp.diag(alpha) + jnp.diag(beta, 1) + jnp.diag(beta, -1)
        lam, U = jnp.linalg.eigh(T)
        lam = jnp.maximum(lam, 1e-30)  # guard Lanczos breakdown zeros
        w0 = U[0, :] ** 2
        return jnp.sum(w0 * jnp.log(lam))

    quad = jax.vmap(per_probe)(alphas, betas)  # (p,)
    return subspace_dim * jnp.mean(quad)


def tridiag_from_cg(cg_alphas: jnp.ndarray, cg_betas: jnp.ndarray,
                    steps: jnp.ndarray):
    """Lanczos tridiagonal (diag, offdiag) from CG step coefficients.

    The Krylov space CG explores from ``b`` is the Lanczos space of
    ``v0 = b/||b||``, and the tridiagonal falls out of the CG (alpha, beta)
    sequences (Saad 2003 §6.7; the mBCG trick of Gardner et al., 2018):

        T[j, j]   = 1/alpha_j + beta_{j-1}/alpha_{j-1}        (beta_{-1}=0)
        T[j, j+1] = sqrt(beta_j) / alpha_j

    ``cg_alphas``/``cg_betas``: (..., k) per-system coefficient arrays;
    ``steps``: (...,) number of valid entries per system. Entries at or
    beyond ``steps`` are padded to an identity block (diag 1, offdiag 0):
    the padding decouples from e1, so it contributes exactly log(1) = 0 to
    the quadrature below.
    """
    k = cg_alphas.shape[-1]
    idx = jnp.arange(k)
    valid = idx < steps[..., None]
    safe_a = jnp.where(valid & (cg_alphas > 0), cg_alphas, 1.0)
    inv_a = 1.0 / safe_a
    prev_ratio = jnp.zeros_like(cg_alphas).at[..., 1:].set(
        cg_betas[..., :-1] / safe_a[..., :-1])
    diag = jnp.where(valid, inv_a + prev_ratio, 1.0)
    # offdiag j couples steps j and j+1; valid only when step j+1 exists.
    off_valid = idx[:-1] < (steps[..., None] - 1)
    off = jnp.where(off_valid,
                    jnp.sqrt(jnp.maximum(cg_betas[..., :-1], 0.0))
                    * inv_a[..., :-1], 0.0)
    return diag, off


def slq_logdet_from_tridiag(diag: jnp.ndarray, off: jnp.ndarray,
                            subspace_dim) -> jnp.ndarray:
    """log det estimate from per-probe Lanczos tridiagonals (p, k)/(p, k-1).

    Same Gauss quadrature as :func:`slq_logdet`, but starting from
    tridiagonal coefficients recovered from a (stacked) CG solve — the
    probes' solves and the log-det then share ONE set of operator sweeps.
    Assumes probes with squared norm == subspace_dim (masked Rademacher).
    """
    def per_probe(d, e):
        T = jnp.diag(d) + jnp.diag(e, 1) + jnp.diag(e, -1)
        lam, U = jnp.linalg.eigh(T)
        lam = jnp.maximum(lam, 1e-30)  # guard breakdown zeros
        w0 = U[0, :] ** 2
        return jnp.sum(w0 * jnp.log(lam))

    quad = jax.vmap(per_probe)(diag, off)  # (p,)
    return subspace_dim * jnp.mean(quad)
