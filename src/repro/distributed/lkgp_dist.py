"""Distributed latent-Kronecker MVM and CG via shard_map.

TPU-native distribution of the paper's primitive (DESIGN.md §3): rows of the
latent grid (hyper-parameter configs) shard over the 'data' mesh axis; K2
(m x m) is replicated. One MVM is then

    T_loc = (mask_loc * U_loc) @ K2          local    O(n/p * m^2)
    S_loc = K1[rows_loc, :] @ all_gather(T)  1 gather O(n^2/p * m)
    out   = mask_loc * S_loc + noise * U_loc

i.e. a single all-gather of the (n, m) intermediate per CG iteration —
communication O(nm) vs compute O(n^2 m / p + n m^2 / p).

K1 itself is built distributed: each shard evaluates its row block
k1(X_loc, X_full) after one all-gather of X (n x d, tiny). Memory per device
is O(n^2/p + m^2), so a 100k-config sweep fits a pod.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.gp_kernels import KERNELS_1D, rbf_ard

__all__ = ["dist_lk_operator", "dist_lk_mvm_fused", "dist_cg_solve",
           "dist_mll_value"]


def _row_sharded(mesh, *trailing):
    return P("data", *trailing)


def dist_lk_operator(mesh: Mesh, K1_rows, K2, mask, noise):
    """Returns a jit-ready distributed operator u -> A(u).

    K1_rows: (n, n) sharded P('data', None) — row block per device.
    mask, u: (n, m) sharded P('data', None). K2: (m, m) replicated.
    """

    def body(k1r, k2, msk, u):
        t_loc = (msk * u) @ k2                       # (n/p, m)
        t_full = jax.lax.all_gather(t_loc, "data", axis=0, tiled=True)
        s_loc = k1r @ t_full                          # (n/p, m)
        return msk * s_loc + noise * (msk * u)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("data", None), P(None, None), P("data", None),
                  P("data", None)),
        out_specs=P("data", None),
        check_vma=False,
    )
    return functools.partial(fn, K1_rows, K2, mask)


def dist_lk_mvm_fused(mesh: Mesh, K1_rows, K2, mask, noise, *,
                      block_n: int = 128, block_m: int = 128,
                      precision: str = "f32",
                      interpret: bool | None = None):
    """Distributed operator u -> A(u) running the FUSED Pallas kernel per shard.

    Same sharding contract as :func:`dist_lk_operator` (K1_rows / mask / u
    row-sharded P('data', None), K2 replicated), but each shard's row-block
    MVM is one :func:`repro.kernels.lk_mvm.lk_mvm_fused_rows` pallas_call
    instead of the two-stage einsum reference: the (n/p, m) stage-R
    intermediate lives only in VMEM. Communication is unchanged — one
    all-gather of the pre-masked (n, m) input per MVM; the gathered operand
    feeds the kernel's global k sweep while the local mask/u rows feed its
    epilogue.

    The kernel accumulates in f32 (or bf16-compute with ``precision=
    "bf16"``), so callers wanting f64-exact semantics (e.g. x64 parity
    tests) should use :func:`dist_lk_operator`. Block sizes should come
    from :func:`repro.analysis.vmem.best_fitting_blocks` evaluated at the
    PER-SHARD shape (n/p, m) — :class:`repro.core.engines.DistributedEngine`
    does exactly that.
    """
    from ..kernels.lk_mvm import lk_mvm_fused_rows

    def body(k1r, k2, msk, u):
        um_loc = msk * u                              # (n/p, m)
        um_full = jax.lax.all_gather(um_loc, "data", axis=0, tiled=True)
        return lk_mvm_fused_rows(k1r, k2, msk, u, um_full, noise,
                                 block_n=block_n, block_m=block_m,
                                 precision=precision, interpret=interpret)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("data", None), P(None, None), P("data", None),
                  P("data", None)),
        out_specs=P("data", None),
        check_vma=False,
    )
    return functools.partial(fn, K1_rows, K2, mask)


def dist_cg_solve(A, b, tol=0.01, max_iters=10_000, x0=None):
    """CG on distributed grid vectors (the reductions are global jnp.sums,
    which XLA lowers to psums over the sharded rows). ``x0`` warm-starts
    the solve (scheduler refits re-solve against a nearby operator)."""
    b_norm = jnp.sqrt(jnp.sum(b * b))
    safe = jnp.where(b_norm == 0, 1.0, b_norm)
    if x0 is None:
        x0 = jnp.zeros_like(b)
    r0 = b - A(x0)

    def cond(state):
        _, _, _, rs, it = state
        return jnp.logical_and(jnp.sqrt(rs) / safe > tol, it < max_iters)

    def step(state):
        x, r, p, rs, it = state
        Ap = A(p)
        alpha = rs / jnp.maximum(jnp.sum(p * Ap), 1e-30)
        x = x + alpha * p
        r = r - alpha * Ap
        rs_new = jnp.sum(r * r)
        p = r + (rs_new / jnp.maximum(rs, 1e-30)) * p
        return (x, r, p, rs_new, it + 1)

    x, _, _, rs, it = jax.lax.while_loop(
        cond, step, (x0, r0, r0, jnp.sum(r0 * r0), jnp.int32(0)))
    return x, it, jnp.sqrt(rs) / safe


def dist_mll_value(mesh: Mesh, params_ls, params_tls, params_os, params_noise,
                   X, t, Y, mask, t_kernel="matern12", jitter=1e-6,
                   cg_tol=0.01, cg_max_iters=10_000):
    """Distributed MLL quadratic term (row-sharded X / Y / mask).

    Builds K1's row block per device (all-gather of X), runs distributed CG,
    and returns -0.5 y^T alpha (the log-det term uses SLQ with the same
    distributed operator; see core.slq). Used by the dry-run 'lkgp' cell and
    the scaling benchmark's distributed mode.
    """

    def build_k1_rows(x_loc, x_same):
        x_full = jax.lax.all_gather(x_same, "data", axis=0, tiled=True)
        return rbf_ard(x_loc, x_full, params_ls)

    k1_rows = jax.shard_map(
        build_k1_rows, mesh=mesh,
        in_specs=(P("data", None), P("data", None)),
        out_specs=P("data", None), check_vma=False)(X, X)
    # jitter on the diagonal of the row block
    n = X.shape[0]
    diag = jitter * jnp.eye(n, dtype=X.dtype)
    k1_rows = k1_rows + diag

    K2 = KERNELS_1D[t_kernel](t, t, params_tls, params_os)
    K2 = K2 + jitter * jnp.eye(t.shape[0], dtype=t.dtype)

    A = dist_lk_operator(mesh, k1_rows, K2, mask, params_noise)
    alpha, iters, rel = dist_cg_solve(A, Y * mask, tol=cg_tol,
                                      max_iters=cg_max_iters)
    quad = -0.5 * jnp.sum((Y * mask) * alpha)
    return quad, iters, rel
