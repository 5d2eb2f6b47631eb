"""Pallas TPU kernels: masked latent-Kronecker MVM.

Computes   out = mask * (K1 @ (mask * U) @ K2) + noise * (mask * U)

This is the inner loop of every CG iteration in the paper (Section 2): on
GPU/GPyTorch it is two cuBLAS calls plus separate elementwise masking
kernels, i.e. four full HBM round-trips of the (B, n, m) intermediate.

Two implementations live here:

:func:`lk_mvm_fused` (the default behind :func:`lk_mvm_pallas`)
    ONE ``pallas_call``. Grid (B, n-rows, m-cols) with an inner K1-row
    sweep; each step recomputes the per-block-row tile
    ``T = (mask * U)[k, :] @ K2[:, j]`` straight into VMEM and
    accumulates ``K1[i, k] @ T`` — the (B, n, m) f32 intermediate NEVER
    touches HBM. The masked input ``mask * U`` is formed once by the
    wrapper (fused by XLA into the padding copy); the noise/mask epilogue
    reads its own (i, j) tiles of mask and U. The recompute factor on the
    cheap first product is n/block_n on its O(n m^2) term — for
    learning-curve grids (m << n, m <~ block) this is bounded by the
    O(n^2 m) second product, while HBM traffic drops by the full
    intermediate round-trip. Supports a bf16-inputs / f32-accumulate mode
    (``precision="bf16"``); block sizes come from
    :mod:`repro.kernels.autotune` when not given explicitly. VMEM per step
    is O(block_n * m + m * block_m), so the fused kernel targets the
    paper's regime m <~ 4096. :func:`lk_mvm_fused_rows` runs the same
    kernel on one row shard (the distributed engine's per-shard body).

:func:`lk_mvm_two_stage` (the committed baseline the benchmarks gate
    against) — two ``pallas_call``s with the masked intermediate
    materialised in HBM between them:

    Stage R (right):  T   = (mask * U) @ K2        grid (B, n/bn, m/bj, m/bk)
    Stage L (left):   out = mask * (K1 @ T) + noise * (mask * U)
                                                   grid (B, n/bi, m/bj, n/bk)

Accumulation always runs over the innermost grid axis into an f32 VMEM
scratch; epilogues apply the mask and noise term on the final step.

Mosaic constraints the wrappers honour: every operand reaching a
``pallas_call`` is f32 or bf16 (f64 callers are cast at the boundary and
the result cast back — the kernels accumulate in f32 either way), every
block index is int32 (a bare ``0`` is int64 under ``jax_enable_x64``), and
block edges follow :func:`repro.analysis.vmem.effective_blocks` (whole
axis, or a multiple of the 128-lane tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..analysis.vmem import block_edge, check_fused_blocks, effective_blocks

__all__ = ["lk_mvm_pallas", "lk_mvm_fused", "lk_mvm_fused_rows",
           "lk_mvm_two_stage"]


def resolve_interpret(interpret: bool | None) -> bool:
    """Interpret mode only where Mosaic cannot run: on the CPU backend."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def _zero():
    """Block index 0 as int32: Mosaic refuses the int64 a bare 0 becomes
    under ``jax_enable_x64``."""
    return jnp.int32(0)


def smem_scalar_spec():
    """BlockSpec of a (1, 1) f32 scalar in SMEM (int32 index map)."""
    return pl.BlockSpec((1, 1), lambda *_: (_zero(), _zero()),
                        memory_space=pltpu.SMEM)


def _compute_dtype(precision: str):
    if precision not in ("f32", "bf16"):
        raise ValueError(f"precision must be 'f32' or 'bf16', got {precision!r}")
    return jnp.bfloat16 if precision == "bf16" else jnp.float32


def _dot(a, b):
    """MXU product with f32 accumulation; f32 operands at full f32
    precision (Mosaic's default contraction for f32 is not)."""
    prec = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    return jax.lax.dot(a, b, precision=prec,
                       preferred_element_type=jnp.float32)


def _stage_right_kernel(u_ref, mask_ref, k2_ref, o_ref, acc_ref, *, nk: int):
    """T[b, i, j] += (mask*U)[b, i, k] @ K2[k, j]."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot(u_ref[0] * mask_ref[...], k2_ref[...])

    @pl.when(k == nk - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _stage_left_kernel(k1_ref, t_ref, mask_ref, u_ref, noise_ref, o_ref,
                       acc_ref, *, nk: int):
    """out[b, i, j] = mask * (K1[i, k] @ T[b, k, j]) + noise * mask * U."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot(k1_ref[...], t_ref[0])

    @pl.when(k == nk - 1)
    def _done():
        mask = mask_ref[...]
        noise = noise_ref[0, 0]
        out = mask * acc_ref[...] + noise * (mask * u_ref[0])
        o_ref[0] = out.astype(o_ref.dtype)


def _pad_to(x, mults):
    pads = [(0, (-s) % mult) for s, mult in zip(x.shape, mults)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


@functools.partial(jax.jit, static_argnames=("block_n", "block_m", "interpret"))
def lk_mvm_two_stage(K1: jnp.ndarray, K2: jnp.ndarray, mask: jnp.ndarray,
                     u: jnp.ndarray, noise=0.0, *, block_n: int = 128,
                     block_m: int = 128,
                     interpret: bool | None = None) -> jnp.ndarray:
    """Two-stage masked Kronecker MVM (HBM-materialised intermediate).

    Kept as the benchmark baseline for the fused kernel; u: (..., n, m) ->
    same shape. Zero-padding to block multiples is harmless: padded
    rows/cols of mask are zero, K2/K1 padding contributes zero partial
    products.
    """
    interpret = resolve_interpret(interpret)
    n, m = mask.shape
    batch_shape = u.shape[:-2]
    dtype = u.dtype
    f32 = lambda x: jnp.asarray(x).astype(jnp.float32)  # noqa: E731
    u3 = f32(u).reshape((-1, n, m))
    B = u3.shape[0]

    bn = block_edge(block_n, n)
    bm = block_edge(block_m, m)
    K1p = _pad_to(f32(K1), (bn, bn))
    K2p = _pad_to(f32(K2), (bm, bm))
    maskp = _pad_to(f32(mask), (bn, bm))
    up = _pad_to(u3, (1, bn, bm))
    npad, mpad = maskp.shape
    noise_arr = jnp.asarray(noise, jnp.float32).reshape(1, 1)

    gn, gm, gkm, gkn = npad // bn, mpad // bm, mpad // bm, npad // bn

    # Stage R: T = (mask * U) @ K2
    t = pl.pallas_call(
        functools.partial(_stage_right_kernel, nk=gkm),
        grid=(B, gn, gm, gkm),
        in_specs=[
            pl.BlockSpec((1, bn, bm), lambda b, i, j, k: (b, i, k)),   # U
            pl.BlockSpec((bn, bm), lambda b, i, j, k: (i, k)),         # mask
            pl.BlockSpec((bm, bm), lambda b, i, j, k: (k, j)),         # K2
        ],
        out_specs=pl.BlockSpec((1, bn, bm), lambda b, i, j, k: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, npad, mpad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, bm), jnp.float32)],
        interpret=interpret,
    )(up, maskp, K2p)

    # Stage L: out = mask * (K1 @ T) + noise * mask * U
    out = pl.pallas_call(
        functools.partial(_stage_left_kernel, nk=gkn),
        grid=(B, gn, gm, gkn),
        in_specs=[
            pl.BlockSpec((bn, bn), lambda b, i, j, k: (i, k)),         # K1
            pl.BlockSpec((1, bn, bm), lambda b, i, j, k: (b, k, j)),   # T
            pl.BlockSpec((bn, bm), lambda b, i, j, k: (i, j)),         # mask
            pl.BlockSpec((1, bn, bm), lambda b, i, j, k: (b, i, j)),   # U
            smem_scalar_spec(),                                        # noise
        ],
        out_specs=pl.BlockSpec((1, bn, bm), lambda b, i, j, k: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, npad, mpad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, bm), jnp.float32)],
        interpret=interpret,
    )(K1p, t, maskp, up, noise_arr)

    return out[:, :n, :m].reshape(*batch_shape, n, m).astype(dtype)


def _fused_kernel(k1_ref, um_ref, k2_ref, mask_ref, u_ref, noise_ref, o_ref,
                  acc_ref, *, nk: int):
    """out[b, i, j] = mask[i, j] * (sum_k K1[i, k] @ (um[b, k, :] @ K2[:, j]))
    + noise * mask[i, j] * u[b, i, j], with T tiles living only in VMEM.

    ``um = mask * u`` over all rows of the K1 sweep (k indexes GLOBAL
    block rows); the epilogue's mask/u tiles are dedicated inputs at the
    output block (i, j), so the same kernel serves the square MVM and one
    row shard of it (i indexes the shard's local rows).
    """
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Stage-R tile for block-row k: (bn, m) x (m, bm) — the full m sweep
    # in one MXU pass, straight into VMEM.
    t = _dot(um_ref[0], k2_ref[...])
    acc_ref[...] += _dot(k1_ref[...], t.astype(um_ref.dtype))

    @pl.when(k == nk - 1)
    def _done():
        msk = mask_ref[...].astype(jnp.float32)
        noise = noise_ref[0, 0]
        o_ref[0] = msk * acc_ref[...] + noise * (
            msk * u_ref[0].astype(jnp.float32))


def _fused_call(K1_rows, K2, mask_rows, u_rows, um, noise, *, block_n: int,
                block_m: int, precision: str, interpret: bool | None):
    """One fused ``pallas_call``: rows (B, n_local, m) of the masked MVM.

    K1_rows: (n_local, n); K2: (m, m); mask_rows: (n_local, m); u_rows:
    (B, n_local, m) feed the epilogue; um: (B, n, m) is ``mask * u`` over
    every row of the K1 sweep. Returns f32 (B, n_local, m).
    """
    interpret = resolve_interpret(interpret)
    compute_dtype = _compute_dtype(precision)
    n_local, m = mask_rows.shape
    n = um.shape[-2]
    B = u_rows.shape[0]

    # Static VMEM guard (trace time, shapes only): an oversized block
    # choice fails here with an actionable message instead of at Mosaic
    # compile time on TPU — or worse, "working" in interpret mode on CPU
    # and OOMing the first time the same trace reaches hardware.
    check_fused_blocks(n, m, block_n, block_m, precision)
    bn, bm, _ = effective_blocks(n, m, block_n, block_m, precision)
    cast = lambda x: jnp.asarray(x).astype(compute_dtype)  # noqa: E731
    K1p = _pad_to(cast(K1_rows), (bn, bn))
    K2p = _pad_to(cast(K2), (bm, bm))
    maskp = _pad_to(cast(mask_rows), (bn, bm))    # exact in bf16: 0/1
    up = _pad_to(cast(u_rows), (1, bn, bm))
    ump = _pad_to(cast(um), (1, bn, bm))
    nlpad, mpad = maskp.shape
    npad = ump.shape[1]       # K1 cols and um rows: n padded to bn alike
    noise_arr = jnp.asarray(noise, jnp.float32).reshape(1, 1)

    gi, gj, gk = nlpad // bn, mpad // bm, npad // bn
    out = pl.pallas_call(
        functools.partial(_fused_kernel, nk=gk),
        grid=(B, gi, gj, gk),
        in_specs=[
            pl.BlockSpec((bn, bn), lambda b, i, j, k: (i, k)),            # K1
            pl.BlockSpec((1, bn, mpad),
                         lambda b, i, j, k: (b, k, _zero())),             # um strip
            pl.BlockSpec((mpad, bm), lambda b, i, j, k: (_zero(), j)),    # K2 strip
            pl.BlockSpec((bn, bm), lambda b, i, j, k: (i, j)),            # mask
            pl.BlockSpec((1, bn, bm), lambda b, i, j, k: (b, i, j)),      # u
            smem_scalar_spec(),                                           # noise
        ],
        out_specs=pl.BlockSpec((1, bn, bm), lambda b, i, j, k: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, nlpad, mpad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, bm), jnp.float32)],   # accumulator
        interpret=interpret,
    )(K1p, ump, K2p, maskp, up, noise_arr)
    return out[:, :n_local, :m]


@functools.partial(jax.jit, static_argnames=("block_n", "block_m",
                                             "precision", "interpret"))
def lk_mvm_fused(K1: jnp.ndarray, K2: jnp.ndarray, mask: jnp.ndarray,
                 u: jnp.ndarray, noise=0.0, *, block_n: int = 128,
                 block_m: int = 128, precision: str = "f32",
                 interpret: bool | None = None) -> jnp.ndarray:
    """Single-pass masked Kronecker MVM. u: (..., n, m) -> same shape.

    One ``pallas_call``; the stage-R tile stays in VMEM (see module
    docstring). ``precision="bf16"`` casts the matmul inputs to bfloat16
    and accumulates in f32 (the mask/noise epilogue stays f32); the output
    keeps u's dtype. Zero-padding to block multiples is harmless: padded
    rows/cols of mask are zero, K2/K1 padding contributes zero partial
    products.
    """
    n, m = mask.shape
    u3 = u.reshape((-1, n, m))
    out = _fused_call(K1, K2, mask, u3, mask * u3, noise, block_n=block_n,
                      block_m=block_m, precision=precision,
                      interpret=interpret)
    return out.reshape(u.shape).astype(u.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "block_m",
                                             "precision", "interpret"))
def lk_mvm_fused_rows(K1_rows: jnp.ndarray, K2: jnp.ndarray,
                      mask_rows: jnp.ndarray, u_rows: jnp.ndarray,
                      um_full: jnp.ndarray, noise=0.0, *, block_n: int = 128,
                      block_m: int = 128, precision: str = "f32",
                      interpret: bool | None = None) -> jnp.ndarray:
    """Fused masked Kronecker MVM for ONE row shard of the latent grid.

    This is the per-shard body of the distributed fused path (see
    :func:`repro.distributed.lkgp_dist.dist_lk_mvm_fused`): the caller
    all-gathers ``um_full = mask * u`` (n, m) once per MVM and every shard
    runs the fused kernel on its local row block.

    K1_rows: (n_local, n) local row block of K1; mask_rows / u_rows:
    (n_local, m) local rows of mask / u; um_full: (n, m) gathered masked
    input. Returns (n_local, m) =
    ``mask_rows * (K1_rows @ (um_full @ K2)) + noise * (mask_rows * u_rows)``.

    Rank-2 only (the shard_map body is rank-2; engines lax.map the batch).
    Block sizes are judged against the global n, the length of the K1
    sweep.
    """
    out = _fused_call(K1_rows, K2, mask_rows, u_rows[None], um_full[None],
                      noise, block_n=block_n, block_m=block_m,
                      precision=precision, interpret=interpret)
    return out[0].astype(u_rows.dtype)


def lk_mvm_pallas(K1, K2, mask, u, noise=0.0, *, block_n: int = 128,
                  block_m: int = 128, interpret: bool | None = None,
                  fused: bool = True,
                  precision: str = "f32") -> jnp.ndarray:
    """Masked Kronecker MVM (back-compatible entry point).

    Dispatches to the single-pass :func:`lk_mvm_fused` kernel by default;
    ``fused=False`` runs the committed two-stage baseline.
    """
    if fused:
        return lk_mvm_fused(K1, K2, mask, u, noise, block_n=block_n,
                            block_m=block_m, precision=precision,
                            interpret=interpret)
    return lk_mvm_two_stage(K1, K2, mask, u, noise, block_n=block_n,
                            block_m=block_m, interpret=interpret)
