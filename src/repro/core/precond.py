"""Partial pivoted-Cholesky preconditioner for the latent-Kronecker CG.

Beyond-paper extension (the paper's App. B notes CG convergence depends on
conditioning; Lin et al. 2024b — cited therein — study solver improvements).
We build a rank-r pivoted Cholesky approximation L_r of the *latent* joint
covariance using the separable structure: entries of K1 (x) K2 are computed
lazily as K1[i1,j1]*K2[i2,j2] on observed cells only, so the factorisation
costs O(N r^2) time and O(N r) memory for N observed values, never
materialising the joint matrix. The preconditioner is the standard
woodbury-inverted (L_r L_r^T + sigma^2 I)^{-1} applied in O(N r) per CG
iteration — provably reducing the condition number to that of the residual
spectrum (Gardner et al. 2018).

Two factorisation entry points:

* :func:`pivoted_cholesky_latent` — host-side numpy over *packed* observed
  entries (needs a concrete mask; reference / offline use).
* :func:`pivoted_cholesky_grid` — pure-jax over flattened *grid* cells
  (unobserved cells carry a zero diagonal and are never pivoted), jittable
  with a traced mask; this is what the iterative/pallas engines use when
  ``LKGPConfig.precond_rank > 0``.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .gp_kernels import HIGHEST

__all__ = ["pivoted_cholesky_latent", "pivoted_cholesky_grid",
           "woodbury_preconditioner"]


def pivoted_cholesky_latent(K1, K2, mask, rank: int, jitter: float = 1e-12):
    """Rank-``rank`` pivoted Cholesky of (P (K1xK2) P^T) via lazy entries.

    Returns L (N, rank) over the packed observed entries (numpy, float64 —
    this is a host-side setup cost, not a jitted inner loop).
    """
    K1 = np.asarray(K1, np.float64)
    K2 = np.asarray(K2, np.float64)
    mask_np = np.asarray(mask)
    rows, cols = np.nonzero(mask_np)
    N = len(rows)
    rank = min(rank, N)

    diag = K1[rows, rows] * K2[cols, cols]
    L = np.zeros((N, rank))
    perm = np.arange(N)
    d = diag.copy()

    for k in range(rank):
        # pivot: largest remaining diagonal
        # Pivoted-Cholesky setup runs once on host numpy inputs; the
        # pivot index must be a Python int to permute in place.
        j = k + int(np.argmax(d[perm[k:]]))  # lint: disable=RA103
        perm[[k, j]] = perm[[j, k]]
        p = perm[k]
        pivot = d[p]
        if pivot <= jitter:
            L = L[:, :k]
            break
        lkk = np.sqrt(pivot)
        L[p, k] = lkk
        rest = perm[k + 1:]
        # lazy row of the joint covariance at the pivot
        row = K1[rows[rest], rows[p]] * K2[cols[rest], cols[p]]
        if k > 0:
            row = row - L[rest, :k] @ L[p, :k]
        L[rest, k] = row / lkk
        d[rest] = d[rest] - L[rest, k] ** 2
    return jnp.asarray(L)


def pivoted_cholesky_grid(K1, K2, mask, rank: int, jitter: float = 1e-12):
    """Rank-``rank`` pivoted Cholesky of the masked latent covariance, jittable.

    Works on the flattened (n*m,) grid: the diagonal of the masked joint
    covariance is ``mask * diag(K1) ⊗ diag(K2)``, so unobserved cells carry a
    zero diagonal, are never selected as pivots, and end up with all-zero rows
    in L — exactly the projected operator the CG solve sees. Each pivot's
    covariance row is formed lazily from the Kronecker factors
    (``mask ⊙ K1[:, j1] K2[:, j2]^T``), O(nm) per step, O(nm r^2) total.

    Returns L of shape (n*m, rank). Pure jax (lax.fori_loop + dynamic
    argmax pivoting), so it can live inside the jitted MLL objective where
    the mask is a tracer. If the residual diagonal is exhausted before
    ``rank`` steps the remaining columns are zero (harmless in Woodbury).
    """
    K1 = jnp.asarray(K1)
    K2 = jnp.asarray(K2)
    mask = jnp.asarray(mask, K1.dtype)
    n, m = mask.shape
    N = n * m
    diag = (mask * (jnp.diag(K1)[:, None] * jnp.diag(K2)[None, :])).reshape(N)
    mask_flat = mask.reshape(N)

    def body(k, carry):
        L, d, done = carry
        dm = jnp.where(done, -jnp.inf, d)
        j = jnp.argmax(dm)
        pivot = dm[j]
        valid = pivot > jitter
        lkk = jnp.sqrt(jnp.maximum(pivot, jitter))
        j1, j2 = j // m, j % m
        row = (mask * (K1[:, j1][:, None] * K2[:, j2][None, :])).reshape(N)
        row = row - L @ L[j]
        col = jnp.where(done, 0.0, row / lkk).at[j].set(lkk)
        col = jnp.where(valid, col * mask_flat, jnp.zeros_like(col))
        L = L.at[:, k].set(col)
        d = jnp.maximum(d - col * col, 0.0)
        done = done.at[j].set(True)
        return L, d, done

    L0 = jnp.zeros((N, rank), K1.dtype)
    done0 = jnp.zeros((N,), bool)
    L, _, _ = jax.lax.fori_loop(0, rank, body, (L0, diag, done0))
    return L


def woodbury_preconditioner(L, noise):
    """M^{-1} v for M = L L^T + noise I, via Woodbury in O(N r).

    Returns a function on packed vectors (..., N):
    M^{-1} = I/s - L (s I_r + L^T L)^{-1} L^T / s^2,  s = noise.
    """
    import jax

    N, r = L.shape
    eye = jnp.eye(r, dtype=L.dtype)
    inner = noise * eye + jnp.matmul(L.T, L, precision=HIGHEST)  # SPD
    chol = jnp.linalg.cholesky(inner)

    def apply(v):
        w = jnp.einsum("nr,...n->...r", L, v, precision=HIGHEST)
        # cho_solve wants matching batch dims; fold leading dims into the
        # column axis instead so one (r, r) factor serves every RHS.
        wf = w.reshape(-1, r)
        z = jax.scipy.linalg.cho_solve((chol, True), wf.T).T.reshape(w.shape)
        return v / noise - jnp.einsum("nr,...r->...n", L, z,
                                      precision=HIGHEST) / noise

    return apply
