"""Stationary GP kernel functions (pure jnp, dtype-polymorphic).

The paper's model (App. B) uses an RBF-ARD kernel over hyper-parameters x
(one lengthscale per dimension, unit variance) and a Matern-1/2 kernel over
the learning-curve progression t (scalar lengthscale, scalar outputscale).
We additionally provide Matern-3/2 and Matern-5/2 for ablations.

All functions take raw (unconstrained, log-space) parameters already
transformed to their positive values by the caller.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "HIGHEST",
    "sq_dist",
    "abs_dist",
    "rbf_ard",
    "matern12",
    "matern32",
    "matern52",
    "KERNELS_1D",
]

# Every f32 contraction in the GP numerics (Gram build, MVM, posterior
# products, L-BFGS inner products) asks for full f32 precision. XLA's
# default on TPU rounds f32 matmul operands to bf16 for one MXU pass, which
# wrecks the distance expansion below (cancellation at ~3 digits) and
# floors CG residuals near 1e-3. On the CPU the setting changes nothing.
HIGHEST = jax.lax.Precision.HIGHEST


def sq_dist(x1: jnp.ndarray, x2: jnp.ndarray) -> jnp.ndarray:
    """Pairwise squared Euclidean distance.

    x1: (n, d), x2: (p, d) -> (n, p). Uses the matmul expansion so the
    contraction runs on the MXU; clamps tiny negatives from cancellation.
    """
    n1 = jnp.sum(x1 * x1, axis=-1)[:, None]
    n2 = jnp.sum(x2 * x2, axis=-1)[None, :]
    d2 = n1 + n2 - 2.0 * jnp.matmul(x1, x2.T, precision=HIGHEST)
    return jnp.maximum(d2, 0.0)


def abs_dist(t1: jnp.ndarray, t2: jnp.ndarray) -> jnp.ndarray:
    """Pairwise absolute distance for 1-D inputs. t1: (n,), t2: (p,) -> (n, p)."""
    return jnp.abs(t1[:, None] - t2[None, :])


def rbf_ard(x1: jnp.ndarray, x2: jnp.ndarray, lengthscale: jnp.ndarray,
            outputscale=1.0) -> jnp.ndarray:
    """RBF kernel with per-dimension lengthscales.

    k(x, x') = outputscale * exp(-0.5 * sum_d ((x_d - x'_d) / l_d)^2)
    """
    z1 = x1 / lengthscale
    z2 = x2 / lengthscale
    return outputscale * jnp.exp(-0.5 * sq_dist(z1, z2))


def matern12(t1: jnp.ndarray, t2: jnp.ndarray, lengthscale, outputscale=1.0) -> jnp.ndarray:
    """Matern-1/2 (exponential / Ornstein-Uhlenbeck) kernel on 1-D inputs."""
    r = abs_dist(t1, t2) / lengthscale
    return outputscale * jnp.exp(-r)


def matern32(t1: jnp.ndarray, t2: jnp.ndarray, lengthscale, outputscale=1.0) -> jnp.ndarray:
    r = abs_dist(t1, t2) * (jnp.sqrt(3.0) / lengthscale)
    return outputscale * (1.0 + r) * jnp.exp(-r)


def matern52(t1: jnp.ndarray, t2: jnp.ndarray, lengthscale, outputscale=1.0) -> jnp.ndarray:
    r = abs_dist(t1, t2) * (jnp.sqrt(5.0) / lengthscale)
    return outputscale * (1.0 + r + r * r / 3.0) * jnp.exp(-r)


KERNELS_1D = {
    "matern12": matern12,
    "matern32": matern32,
    "matern52": matern52,
}
