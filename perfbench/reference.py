"""Plain reference of the latent-Kronecker GP posterior, in ``jax.numpy``.

It follows Lin et al. 2024 (arXiv 2410.09239), App. B, and imports nothing
of the program under test:

* inputs: x min-max scaled to the unit cube (a constant dimension maps to
  0), t log-scaled so that [t_1, t_m] maps to [0, 1], y shifted by the
  largest observed value and divided by the observed values' standard
  deviation;
* kernel: RBF with one lengthscale per dimension over x (unit variance)
  times a Matern-1/2 kernel over t with an outputscale; the t factor
  carries a diagonal jitter of 1e-6;
* operator: A = P (K1 (x) K2) P^T + noise I over the observed cells,
  applied in grid form as ``mask * (K1 @ (mask * U) @ K2) + noise * mask * U``;
* solves: batched conjugate gradients, each column stopping at its own
  relative residual;
* the final-epoch posterior mean K1 alpha K2[:, -1], and the exact
  final-epoch variance K1_ii K2_mm - k_i^T A^{-1} k_i, in y units plus the
  observation noise.

Every contraction takes a ``precision``: ``"highest"`` is f32 at
``lax.Precision.HIGHEST``; ``"high"`` is three bf16 passes with f32
accumulation (hi*hi + hi*lo + lo*hi), the arithmetic of
``lax.Precision.HIGH``, written out so that it is the same on every
backend (the CPU ignores the precision argument). The split rounds with
``lax.reduce_precision``, which the compiler keeps: a plain
f32 -> bf16 -> f32 round trip may be elided on TPU, leaving lo = 0 and one
pass. ``"high"`` is the control: the next precision below the one the
configuration states.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "high")
JITTER = 1e-6


def contract(spec: str, a, b, precision: str):
    """``einsum(spec, a, b)`` in f32 at the named precision."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    if precision != "high":
        raise ValueError(f"precision must be one of {PRECISIONS}")

    def split(x):
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)
    dot = partial(jnp.einsum, spec, preferred_element_type=jnp.float32)
    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def transforms(X, t, Y, mask):
    """Scaled (Xn, tn), standardised Yn, and the y shift and scale."""
    lo, hi = X.min(axis=0), X.max(axis=0)
    Xn = jnp.where(hi > lo, (X - lo) / jnp.where(hi > lo, hi - lo, 1.0), 0.0)
    lt = jnp.log(t)
    tn = (lt - lt[0]) / (lt[-1] - lt[0])
    obs = mask > 0
    shift = jnp.max(jnp.where(obs, Y, -jnp.inf))
    cnt = jnp.sum(mask)
    mean = jnp.sum(Y * mask) / cnt
    var = jnp.sum(mask * (Y - mean) ** 2) / cnt
    scale = jnp.sqrt(jnp.maximum(var, 1e-12))
    return Xn, tn, jnp.where(obs, (Y - shift) / scale, 0.0), shift, scale


def grams(x_lengthscale, t_lengthscale, outputscale, Xn, tn):
    """K1 (n, n) over configurations and K2 (m, m) over epochs."""
    Z = Xn / x_lengthscale
    d2 = jnp.sum((Z[:, None, :] - Z[None, :, :]) ** 2, axis=-1)
    K1 = jnp.exp(-0.5 * d2)
    K2 = outputscale * jnp.exp(-jnp.abs(tn[:, None] - tn[None, :])
                               / t_lengthscale)
    return K1, K2 + JITTER * jnp.eye(tn.shape[0], dtype=K2.dtype)


@partial(jax.jit, static_argnames="precision")
def mvm(K1, K2, mask, U, noise, precision: str = "highest"):
    """A U for a (B, n, m) stack U."""
    um = mask * U
    T = contract("bnm,mk->bnk", um, K2, precision)
    return mask * contract("in,bnk->bik", K1, T, precision) + noise * um


@partial(jax.jit, static_argnames=("precision", "max_iters"))
def cg(K1, K2, mask, noise, B, tol, precision: str = "highest",
       max_iters: int = 5000):
    """Solve A X = B column by column: (X, sweeps)."""
    def dot(a, b):
        return jnp.sum(a * b, axis=(-2, -1))

    bn = jnp.sqrt(dot(B, B))
    bn = jnp.where(bn == 0, 1.0, bn)

    def active(s):
        return jnp.sqrt(s[3]) / bn > tol

    def body(s):
        x, r, p, rs, it = s
        on = active(s)
        Ap = mvm(K1, K2, mask, p, noise, precision)
        pAp = dot(p, Ap)
        a = jnp.where(on & (pAp > 0), rs / jnp.where(pAp > 0, pAp, 1.0), 0.0)
        x = x + a[:, None, None] * p
        r = r - a[:, None, None] * Ap
        rs_new = jnp.where(on, dot(r, r), rs)
        beta = rs_new / jnp.where(rs > 0, rs, 1.0)
        p = jnp.where(on[:, None, None], r + beta[:, None, None] * p, p)
        return x, r, p, rs_new, it + 1

    def cond(s):
        return jnp.any(active(s)) & (s[4] < max_iters)

    x0 = jnp.zeros_like(B)
    s = jax.lax.while_loop(cond, body, (x0, B, B, dot(B, B), jnp.int32(0)))
    return s[0], s[4]


def final_mean(K1, K2, alpha, shift, scale, precision: str = "highest"):
    """Final-epoch posterior mean in y units from alpha = A^{-1} y."""
    col = contract("nm,m->n", alpha, K2[:, -1], precision)
    return contract("in,n->i", K1, col, precision) * scale + shift


def final_variance(K1, K2, mask, noise, scale, rows, tol=1e-4,
                   precision: str = "highest"):
    """Exact final-epoch predictive variance (y units) of configs ``rows``."""
    rhs = mask[None] * (K1[:, rows].T[:, :, None] * K2[None, None, :, -1])
    sol, _ = cg(K1, K2, mask, noise, rhs, tol, precision)
    quad = jnp.sum(rhs * sol, axis=(-2, -1))
    var_f = jnp.diag(K1)[rows] * K2[-1, -1] - quad
    return (var_f + noise) * scale ** 2
