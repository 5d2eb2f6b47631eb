"""Pallas TPU kernels: masked latent-Kronecker MVM.

Computes   out = mask * (K1 @ (mask * U) @ K2) + noise * (mask * U)

This is the inner loop of every CG iteration in the paper (Section 2): on
GPU/GPyTorch it is two cuBLAS calls plus separate elementwise masking
kernels, i.e. four full HBM round-trips of the (B, n, m) intermediate.

:func:`lk_mvm_fused` is the one entry point (the ``pallas`` engine's
MVM). It picks its layout from the shape, with blocks from
:func:`repro.analysis.vmem.kernel_blocks` unless given:

* the **square kernel**, where K2 and one right-hand side's tiles fit
  VMEM (:func:`repro.analysis.vmem.square_plan`): ONE ``pallas_call`` in
  the transposed, lane-dense layout
  ``out[b]^T = mask^T * (K2^T um[b]^T K1^T) + noise * um[b]^T`` with
  ``um = mask * U``. Grid (B-chunks, n-lane tiles, K1 sweep): each step
  forms the stage-R tile ``T = K2^T um[b]^T`` for a chunk of right-hand
  sides straight into VMEM, stacked on the MXU's rows, and accumulates
  ``T @ K1^T[k, j]`` into the resident output block, so K1 is read once
  per chunk and the (B, n, m) f32 intermediate NEVER touches HBM. m is
  held whole, padded to the sublane multiple; block_n is the depth of the
  K1 sweep, and the lane tile and the chunk follow from the observed B
  and the VMEM model. The transposes fuse into the wrapper's padding
  copies;
* the **row-strip kernel** below, where m is too wide to hold K2 whole
  (thousands of epochs) but a (block_n, block_m) pair fits;
* the XLA body :func:`repro.core.mvm.lk_mvm` (einsums at ``HIGHEST``),
  where no block pair fits (m in the thousands with n in the hundreds).

Both kernels support a bf16-inputs / f32-accumulate mode
(``precision="bf16"``).

:func:`lk_mvm_fused_rows` (the distributed engine's per-shard body) runs
the row-strip kernel over one row shard, in the (n, m) layout. Grid
(B, n-rows, m-cols) with an inner K1-row sweep; each step recomputes the
per-block-row tile ``T = (mask * U)[k, :] @ K2[:, j]`` straight into VMEM
and accumulates ``K1[i, k] @ T``; the noise/mask epilogue reads its own
(i, j) tiles of mask and U. VMEM per step is O(block_n * m + m * block_m),
so it reaches m <~ 4096.

Accumulation runs over the innermost grid axis: into an f32 VMEM scratch
whose epilogue applies the mask and noise term on the final step
(row-strip kernel), or into the resident output block, masked as it goes
(square kernel).

Mosaic constraints the wrappers honour: every operand reaching a
``pallas_call`` is f32 or bf16 (f64 callers are cast at the boundary and
the result cast back — the kernels accumulate in f32 either way), every
block index is int32 (a bare ``0`` is int64 under ``jax_enable_x64``), and
block edges follow :func:`repro.analysis.vmem.effective_blocks` or
:func:`repro.analysis.vmem.square_plan` (whole axis, or a multiple of the
128-lane tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..analysis.vmem import (check_fused_blocks, effective_blocks,
                             kernel_blocks, square_plan)
from ..core.mvm import lk_mvm

__all__ = ["lk_mvm_fused", "lk_mvm_fused_rows"]


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


def _pad_to(x, mults):
    pads = [(0, (-s) % mult) for s, mult in zip(x.shape, mults)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def _fused_kernel(k1_ref, um_ref, k2_ref, mask_ref, u_ref, noise_ref, o_ref,
                  acc_ref, *, nk: int):
    """out[b, i, j] = mask[i, j] * (sum_k K1[i, k] @ (um[b, k, :] @ K2[:, j]))
    + noise * mask[i, j] * u[b, i, j], with T tiles living only in VMEM.

    ``um = mask * u`` over all rows of the K1 sweep (k indexes GLOBAL
    block rows); the epilogue's mask/u tiles are dedicated inputs at the
    output block (i, j), so the same kernel serves the whole MVM (where m
    is too wide for the square layout) and one row shard of it (i indexes
    the shard's local rows).
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
        # the HLO name the benchmark's roofline reader matches, whatever
        # jit wraps the call
        name="lk_mvm_fused",
    )(K1p, ump, K2p, maskp, up, noise_arr)
    return out[:, :n_local, :m]


def _square_kernel(um_ref, k2t_ref, k1t_ref, mask_ref, noise_ref, o_ref,
                   t_ref, *, chunk: int, m8: int, bk: int, tn: int, ts: int):
    """Transposed layout, a chunk of right-hand sides per step:
    out[b]^T = mask^T * (K2^T um[b]^T K1^T) + noise * um[b]^T.

    Per step (c, j, k): stage R ``T = K2^T @ um[b]^T[:, k]`` for each b of
    the chunk, stacked on the rows of one (chunk * m8, bk) tile, then
    ``out[c, :, :, j] += mask^T * (T @ K1^T[k, j])`` in lane sub-tiles of
    ts, whose left operand has chunk * m8 rows and right operand ts lanes.
    The output block stays resident across the k sweep and is the
    accumulator.
    """
    j, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def stage_r(b, carry):
        # m8 is a sublane multiple of the tile's dtype: aligned rows
        row = pl.multiple_of(b * m8, 32 // t_ref.dtype.itemsize)
        t_ref[pl.ds(row, m8), :] = _dot(k2t_ref[...],
                                        um_ref[b]).astype(t_ref.dtype)
        return carry

    jax.lax.fori_loop(_zero(), jnp.int32(chunk), stage_r, 0)
    t = t_ref[...]

    def accumulate(cols):
        prod = _dot(t, k1t_ref[:, cols]).reshape(chunk, m8, -1)
        o_ref[:, :, cols] += mask_ref[:, cols].astype(jnp.float32)[None] * prod

    def noise(cols):
        o_ref[:, :, cols] += noise_ref[0, 0] * um_ref[...].astype(jnp.float32)

    # Lane offsets are static where one slice covers the block: Mosaic
    # takes a dynamic lane offset only at a provable multiple of 128.
    if ts == tn:
        accumulate(slice(None))
    else:
        def lane_tile(s, carry):
            accumulate(pl.ds(pl.multiple_of(s * ts, ts), ts))
            return carry

        jax.lax.fori_loop(_zero(), jnp.int32(tn // ts), lane_tile, 0)

    # The noise term of the strip um[:, :, k] lands in the output tile
    # that holds the same lanes.
    strips = tn // bk
    if strips == 1:
        pl.when(k == j)(lambda: noise(slice(None)))
    else:
        first = j * strips
        pl.when((k >= first) & (k < first + strips))(lambda: noise(
            pl.ds(pl.multiple_of((k - first) * bk, bk), bk)))


def _square_call(K1, K2, mask, u3, noise, *, plan, precision: str,
                 interpret: bool | None):
    """The square fused MVM in one ``pallas_call``: (B, n, m) -> f32.

    The wrapper hands the kernel (mask * u)^T as (B, m8, n_pad), K1^T and
    K2^T, zero-padded: padded rows of mask^T and columns of (mask * u)^T
    are zero, so the result is exact. The transposes fuse into the copies
    that pad them; the output is transposed back and sliced.
    """
    interpret = resolve_interpret(interpret)
    compute_dtype = _compute_dtype(precision)
    n, m = mask.shape
    B = u3.shape[0]
    cast = lambda x: jnp.asarray(x).astype(compute_dtype)  # noqa: E731
    m8, n_pad, bk, tn, chunk = (plan.m8, plan.n_pad, plan.bk, plan.tn,
                                plan.chunk)
    umt = _pad_to(jnp.swapaxes(cast(mask * u3), 1, 2), (1, m8, n_pad))
    k1t = _pad_to(cast(K1).T, (n_pad, n_pad))
    k2t = _pad_to(cast(K2).T, (m8, m8))
    maskt = _pad_to(cast(mask).T, (m8, n_pad))      # exact in bf16: 0/1
    noise_arr = jnp.asarray(noise, jnp.float32).reshape(1, 1)

    z = _zero
    out = pl.pallas_call(
        functools.partial(_square_kernel, chunk=chunk, m8=m8, bk=bk, tn=tn,
                          ts=plan.ts),
        grid=plan.grid(B),
        in_specs=[
            pl.BlockSpec((chunk, m8, bk), lambda c, j, k: (c, z(), k)),  # um^T
            pl.BlockSpec((m8, m8), lambda c, j, k: (z(), z())),          # K2^T
            pl.BlockSpec((bk, tn), lambda c, j, k: (k, j)),              # K1^T
            pl.BlockSpec((m8, tn), lambda c, j, k: (z(), j)),            # mask^T
            smem_scalar_spec(),                                          # noise
        ],
        out_specs=pl.BlockSpec((chunk, m8, tn), lambda c, j, k: (c, z(), j)),
        out_shape=jax.ShapeDtypeStruct((B, m8, n_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((chunk * m8, bk), compute_dtype)],
        interpret=interpret,
        # the HLO name the benchmark's roofline reader matches, whatever
        # jit wraps the call; B stays the output's first dimension
        name="lk_mvm_fused",
    )(umt, k2t, k1t, maskt, noise_arr)
    return jnp.swapaxes(out, 1, 2)[:, :n, :m]


@functools.partial(jax.jit, static_argnames=("block_n", "block_m",
                                             "precision", "interpret"))
def lk_mvm_fused(K1: jnp.ndarray, K2: jnp.ndarray, mask: jnp.ndarray,
                 u: jnp.ndarray, noise=0.0, *, block_n: int | None = None,
                 block_m: int | None = None, precision: str = "f32",
                 interpret: bool | None = None) -> jnp.ndarray:
    """Single-pass masked Kronecker MVM. u: (..., n, m) -> same shape.

    The layout follows from the shape (see module docstring): the square
    kernel if its plan fits, else the row-strip kernel, else the XLA
    body. ``block_n`` / ``block_m`` default to
    :func:`repro.analysis.vmem.kernel_blocks` (n, m); given explicitly,
    they are kept, and a row-strip pair over the VMEM budget raises
    :class:`repro.analysis.vmem.VmemBudgetError`. ``block_n`` is the
    depth of the K1 sweep; the square layout holds m whole, so
    ``block_m`` only reaches the row-strip kernel.

    ``precision="bf16"`` casts the matmul inputs to bfloat16 and
    accumulates in f32 (the mask/noise epilogue stays f32); the output
    keeps u's dtype. Zero-padding to block multiples is harmless: padded
    rows/cols of mask are zero, K2/K1 padding contributes zero partial
    products.
    """
    n, m = mask.shape
    if block_n is None or block_m is None:
        blocks = kernel_blocks(n, m, precision)
        if blocks is None:
            return lk_mvm(K1, K2, mask, u, noise).astype(u.dtype)
        block_n = blocks[0] if block_n is None else block_n
        block_m = blocks[1] if block_m is None else block_m
    u3 = u.reshape((-1, n, m))
    plan = square_plan(n, m, u3.shape[0], block_n, precision)
    if plan is not None:
        out = _square_call(K1, K2, mask, u3, noise, plan=plan,
                           precision=precision, interpret=interpret)
    else:
        out = _fused_call(K1, K2, mask, u3, mask * u3, noise,
                          block_n=block_n, block_m=block_m,
                          precision=precision, interpret=interpret)
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
