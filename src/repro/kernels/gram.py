"""Pallas TPU kernel: fused RBF-ARD gram matrix.

K[i, j] = outputscale * exp(-0.5 * || (x_i - x_j) / l ||^2)

A naive jnp implementation either materialises the (n, n, d) broadcast
difference tensor or does three separate HBM passes (row norms, matmul,
exp). The wrapper pre-scales (z = x / l); the kernel computes per
(bi, bj) tile

    sq[i, j] = |z_i|^2 + |z_j|^2 - 2 z_i . z_j

accumulating the dot product over d-chunks on the MXU, and applies the
exp epilogue in VMEM — one HBM write total. The kernel runs in f32 (f64
callers are cast at the boundary and the result cast back), with the
block rules of :mod:`repro.kernels.lk_mvm`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..analysis.vmem import block_edge
from .lk_mvm import _pad_to, resolve_interpret, smem_scalar_spec

__all__ = ["rbf_gram_pallas"]


def _gram_kernel(zi_ref, zj_ref, scale_ref, o_ref, acc_ref, ni_ref, nj_ref,
                 *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        ni_ref[...] = jnp.zeros_like(ni_ref)
        nj_ref[...] = jnp.zeros_like(nj_ref)

    zi = zi_ref[...]
    zj = zj_ref[...]
    acc_ref[...] += jax.lax.dot_general(zi, zj, (((1,), (1,)), ((), ())),
                                        precision=jax.lax.Precision.HIGHEST,
                                        preferred_element_type=jnp.float32)
    ni_ref[...] += jnp.sum(zi * zi, axis=1, keepdims=True)
    nj_ref[...] += jnp.sum(zj * zj, axis=1, keepdims=True)

    @pl.when(k == nk - 1)
    def _done():
        sq = ni_ref[...] + nj_ref[...].T - 2.0 * acc_ref[...]
        sq = jnp.maximum(sq, 0.0)
        o_ref[...] = (scale_ref[0, 0] * jnp.exp(-0.5 * sq)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_d", "interpret"))
def rbf_gram_pallas(x1: jnp.ndarray, x2: jnp.ndarray, lengthscale: jnp.ndarray,
                    outputscale=1.0, *, block_n: int = 128, block_d: int = 128,
                    interpret: bool | None = None) -> jnp.ndarray:
    """RBF-ARD gram matrix between x1 (n, d) and x2 (p, d)."""
    interpret = resolve_interpret(interpret)
    n, d = x1.shape
    p = x2.shape[0]
    ls = jnp.asarray(lengthscale, jnp.float32)
    z1 = x1.astype(jnp.float32) / ls
    z2 = x2.astype(jnp.float32) / ls

    bn = block_edge(block_n, n)
    bp = block_edge(block_n, p)
    bd = block_edge(block_d, d, min_edge=1)
    z1p = _pad_to(z1, (bn, bd))  # zero-padded d contributes 0 to sq-dist
    z2p = _pad_to(z2, (bp, bd))
    npad, dpad = z1p.shape
    ppad = z2p.shape[0]
    scale = jnp.asarray(outputscale, jnp.float32).reshape(1, 1)

    gk = dpad // bd
    out = pl.pallas_call(
        functools.partial(_gram_kernel, nk=gk),
        grid=(npad // bn, ppad // bp, gk),
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((bp, bd), lambda i, j, k: (j, k)),
            smem_scalar_spec(),
        ],
        out_specs=pl.BlockSpec((bn, bp), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((npad, ppad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, bp), jnp.float32),
                        pltpu.VMEM((bn, 1), jnp.float32),
                        pltpu.VMEM((bp, 1), jnp.float32)],
        interpret=interpret,
    )(z1p, z2p, scale)
    return out[:n, :p].astype(x1.dtype)
