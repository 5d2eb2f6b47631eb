"""Jitted public wrappers that dispatch Pallas kernels or jnp oracles.

On TPU the Pallas path is used; on other backends the default is the jnp
oracle, with ``force_pallas=True`` running the kernels anyway (in interpret
mode on the CPU, for validation). The MVM routes through the single-pass fused
kernel by default (``fused=False`` selects the two-stage baseline); block
sizes come from the :mod:`repro.kernels.autotune` cache when not given.
"""
from __future__ import annotations

import jax

from .autotune import autotune_blocks
from .gram import rbf_gram_pallas
from .lk_mvm import lk_mvm_pallas
from .ref import lk_mvm_ref, rbf_gram_ref

__all__ = ["lk_mvm_op", "rbf_gram_op"]


def _use_pallas(force_pallas: bool) -> bool:
    return force_pallas or jax.default_backend() == "tpu"


def lk_mvm_op(K1, K2, mask, u, noise=0.0, *, force_pallas: bool = False,
              block_n: int | None = None, block_m: int | None = None,
              fused: bool = True, precision: str = "f32"):
    if _use_pallas(force_pallas):
        if block_n is None or block_m is None:
            n, m = mask.shape
            B = 1
            for s in u.shape[:-2]:
                B *= s
            # timed=False: safe at jit trace time (cache lookup/heuristic
            # only); benchmarks pre-fill the cache with timed results.
            blocks = autotune_blocks(n, m, B, precision=precision,
                                     timed=False)
            if blocks is None:
                # No candidate fits the VMEM budget at this shape (e.g.
                # m >= 8192: one fused row strip alone exceeds 16 MiB).
                # The two-stage kernel keeps its intermediate in HBM.
                fused = False
                blocks = (128, 128)
            bn, bm = blocks
            block_n = block_n if block_n is not None else bn
            block_m = block_m if block_m is not None else bm
        return lk_mvm_pallas(K1, K2, mask, u, noise,
                             block_n=block_n, block_m=block_m,
                             fused=fused, precision=precision)
    return lk_mvm_ref(K1, K2, mask, u, noise)


def rbf_gram_op(x1, x2, lengthscale, outputscale=1.0, *,
                force_pallas: bool = False, block_n: int = 128,
                block_d: int = 128):
    if _use_pallas(force_pallas):
        return rbf_gram_pallas(x1, x2, lengthscale, outputscale,
                               block_n=block_n, block_d=block_d)
    return rbf_gram_ref(x1, x2, lengthscale, outputscale)
