"""Latent-Kronecker matrix-vector multiplication (the paper's core primitive).

Representation
--------------
The latent grid is (n configs) x (m progressions). A vector v in the observed
subspace is stored in *grid* form: an (n, m) array that is zero at unobserved
cells (``mask`` is 1.0 where observed). The projection P of the paper is then
slice indexing (grid -> packed) and P^T is zero padding (packed -> grid);
neither is ever materialised.

With vec-row-major convention and U = unvec(v) of shape (n, m):

    (K1 (x) K2) vec(U) = vec(K1 @ U @ K2^T)

so the masked joint operator (K_joint + sigma^2 I) applied to a subspace
vector u is

    A(u) = mask * (K1 @ u @ K2) + sigma^2 * u          (K2 symmetric)

which maps the observed subspace to itself; CG run on grid-form vectors with
a masked RHS therefore never leaves the subspace.

Complexities: the MVM is O(n^2 m + n m^2) time and O(nm) space, matching
Section 2 of the paper.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .gp_kernels import HIGHEST

__all__ = [
    "lk_mvm",
    "lk_operator",
    "packed_to_grid",
    "grid_to_packed",
    "kron_dense",
    "joint_cov_packed",
]


def lk_mvm(K1: jnp.ndarray, K2: jnp.ndarray, mask: jnp.ndarray,
           u: jnp.ndarray, noise: jnp.ndarray | float = 0.0) -> jnp.ndarray:
    """Apply A(u) = mask * (K1 @ (mask*u) @ K2) + noise * (mask*u).

    u may have leading batch dimensions: (..., n, m). The inner ``mask*u`` is
    a no-op for vectors already in the subspace but keeps the operator
    symmetric-PSD on the full grid space, which the iterative solvers rely on.
    """
    um = u * mask
    t = jnp.einsum("...nm,mk->...nk", um, K2, precision=HIGHEST)
    s = jnp.einsum("ij,...jm->...im", K1, t, precision=HIGHEST)
    return mask * s + noise * um


def lk_operator(K1, K2, mask, noise):
    """Partial application returning ``A(u)`` for the CG solver; a pytree
    over the factors, so an eager CG solve compiles once per shape."""
    return jax.tree_util.Partial(lk_mvm, K1, K2, mask, noise=noise)


def grid_to_packed(grid: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """P: select observed entries (static mask -> concrete indexing).

    Only used by the O(N^3) reference/naive paths; requires a concrete mask.
    """
    import numpy as np

    idx = np.flatnonzero(np.asarray(mask).ravel())
    return grid.reshape(*grid.shape[:-2], -1)[..., idx]


def packed_to_grid(packed: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """P^T: zero padding back onto the latent grid."""
    import numpy as np

    mask_np = np.asarray(mask)
    idx = np.flatnonzero(mask_np.ravel())
    flat = jnp.zeros((*packed.shape[:-1], mask_np.size), packed.dtype)
    flat = flat.at[..., idx].set(packed)
    return flat.reshape(*packed.shape[:-1], *mask_np.shape)


def kron_dense(K1: jnp.ndarray, K2: jnp.ndarray) -> jnp.ndarray:
    """Dense Kronecker product (naive baseline only; O(n^2 m^2) memory)."""
    n, m = K1.shape[0], K2.shape[0]
    return (K1[:, None, :, None] * K2[None, :, None, :]).reshape(n * m, n * m)


def joint_cov_packed(K1: jnp.ndarray, K2: jnp.ndarray, mask) -> jnp.ndarray:
    """K_joint = P (K1 (x) K2) P^T for the naive Cholesky baseline."""
    import numpy as np

    idx = np.flatnonzero(np.asarray(mask).ravel())
    full = kron_dense(K1, K2)
    return full[jnp.ix_(idx, idx)]
