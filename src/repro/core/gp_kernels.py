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

# Every f32 contraction in the GP numerics (MVM, posterior products, the
# Gram's derivative, L-BFGS inner products) asks for full f32 precision.
# XLA's default on TPU rounds f32 matmul operands to bf16 for one MXU pass,
# which floors CG residuals near 1e-3. On the CPU the setting changes
# nothing.
HIGHEST = jax.lax.Precision.HIGHEST


@jax.custom_jvp
def sq_dist(x1: jnp.ndarray, x2: jnp.ndarray) -> jnp.ndarray:
    """Pairwise squared Euclidean distance by direct differences.

    x1: (n, d), x2: (p, d) -> (n, p) = sum_k (x1_ik - x2_jk)^2, accumulated
    one feature at a time into one (n, p) buffer. The sum is unrolled over
    d inside one jit, so XLA fuses it into a single elementwise pass, also
    when called eagerly: no (n, p, d) array exists. The matmul expansion
    |x|^2 + |x'|^2 - 2 x.x' cancels in f32 once the scaled inputs are large
    (a small lengthscale), leaving an indefinite Gram; differences keep
    every entry exact to rounding and >= 0.

    The derivative is analytic (below), so autodiff saves only x1 and x2,
    never a per-feature residual.
    """
    return _sq_dist_sum(x1, x2)


@jax.jit
def _sq_dist_sum(x1, x2):
    d2 = jnp.zeros((x1.shape[0], x2.shape[0]),
                   jnp.result_type(x1.dtype, x2.dtype))
    for k in range(x1.shape[1]):
        d2 = d2 + jnp.square(x1[:, k, None] - x2[None, :, k])
    return d2


@sq_dist.defjvp
def _sq_dist_jvp(primals, tangents):
    """d(d2)_ij = 2 sum_k (x1_ik - x2_jk)(dx1_ik - dx2_jk), as contractions
    (linear in the tangents, so its transpose is the VJP)."""
    x1, x2 = primals
    dx1, dx2 = tangents
    mm = lambda a, b: jnp.matmul(a, b.T, precision=HIGHEST)  # noqa: E731
    dd = (jnp.sum(x1 * dx1, axis=-1)[:, None]
          + jnp.sum(x2 * dx2, axis=-1)[None, :] - mm(dx1, x2) - mm(x1, dx2))
    return _sq_dist_sum(x1, x2), 2.0 * dd


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
