"""Static VMEM budget checker for the fused latent-Kronecker MVM kernels.

The row-strip fused kernel (:mod:`repro.kernels.lk_mvm` ``_fused_call``,
behind ``lk_mvm_fused_rows`` and, where m is too wide for the square
layout, ``lk_mvm_fused``) keeps, per grid step, a K1 block, a full row
strip of the masked input ``mask * U``, a full K2 column strip, the
(i, j) mask and U tiles of the epilogue, the output block, and one f32
accumulator resident in VMEM. The square kernel (``_square_call``, behind
``lk_mvm_fused``) works in the transposed, lane-dense layout and keeps a
chunk of right-hand sides per step (:func:`square_plan`).
TPU VMEM is ~16 MiB per core; a (block_n, block_m) choice whose resident
set exceeds it fails at ``pallas_call`` compile time on hardware, and
invisibly on CPU where the kernel runs in interpret mode. This module
computes the **exact** bytes implied by a block choice (including
(sublane, lane) tile rounding and the pipeline's double buffering) so
oversized configurations are rejected *before* ``pallas_call`` ever runs:

* :func:`effective_blocks` — the block edges the kernels really use: an
  axis that one block covers is taken whole, a tiled axis uses the block
  rounded up to the 128-lane tile (Mosaic accepts no other edge);
* :func:`fused_vmem_breakdown` / :func:`fused_vmem_bytes` — the byte
  model, mirroring the kernel's BlockSpecs one-to-one;
* :func:`check_fused_blocks` — raise :class:`VmemBudgetError` when a
  choice exceeds the budget (called by the fused kernels themselves);
* :func:`best_fitting_blocks` — the fitting candidate pair with the
  fewest grid steps (the distributed engine's per-shard blocks);
* :func:`kernel_blocks` — the blocks ``lk_mvm_fused`` takes when none
  are given, or None where no pair fits and it runs the XLA body;
* :func:`square_plan` / :func:`square_vmem_breakdown` — the square
  kernel's tiles and batch chunk, and their byte model;
* :func:`audit_candidate_space` — sweep representative shape buckets and
  report every (shape, candidate) combination over budget; the
  *filtered* choosers are provably clean while the raw {64, 128, 256}
  grid is not (see tests/test_analysis.py).

Pure stdlib — importable (and CI-checkable) without jax.
"""
from __future__ import annotations

from dataclasses import dataclass

__all__ = ["VMEM_BUDGET_BYTES", "VmemBudgetError", "VmemBreakdown",
           "block_edge", "effective_blocks", "fused_vmem_breakdown",
           "fused_vmem_bytes", "check_fused_blocks", "best_fitting_blocks",
           "kernel_blocks", "SquarePlan", "SquareVmemBreakdown",
           "square_vmem_breakdown", "square_plan", "audit_candidate_space"]

VMEM_BUDGET_BYTES = 16 * 1024 * 1024   # 16 MiB per TPU core

# Candidate block edges and the minimum block edge per precision.
_CANDIDATES = (64, 128, 256)
_MIN_EDGE = {"f32": 8, "bf16": 16}
_ITEMSIZE = {"f32": 4, "bf16": 2}
_SUBLANE = {4: 8, 2: 16}   # itemsize -> sublane multiple
_LANE = 128


class VmemBudgetError(ValueError):
    """A (block_n, block_m) choice does not fit the per-core VMEM budget."""


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """Bytes of a 2-D VMEM buffer after (sublane, lane) tile rounding."""
    r = _round_up(max(rows, 1), _SUBLANE[itemsize])
    c = _round_up(max(cols, 1), _LANE)
    return r * c * itemsize


def block_edge(block: int, extent: int, min_edge: int = 8) -> int:
    """Edge of a block along an axis of length ``extent``.

    Mosaic tiles the last two dimensions of every block in (sublane,
    lane) = (8, 128) units, unless the block spans the whole dimension.
    So an axis that one block covers is taken whole (at least
    ``min_edge``), and a tiled axis uses ``block`` rounded up to a lane
    multiple.
    """
    edge = _round_up(block, _LANE)
    return max(min_edge, extent) if edge >= extent else edge


def effective_blocks(n: int, m: int, block_n: int, block_m: int,
                     precision: str = "f32") -> tuple[int, int, int]:
    """(bn, bm, mpad) exactly as the fused kernels derive them.

    ``n`` is the length of the K1 sweep (the global row count for the
    row-sharded kernel).
    """
    min_edge = _MIN_EDGE[precision]
    bn = block_edge(block_n, n, min_edge)
    bm = block_edge(block_m, m, min_edge)
    return bn, bm, _round_up(m, bm)


@dataclass(frozen=True)
class VmemBreakdown:
    """Exact per-grid-step VMEM bytes of the fused kernel."""
    k1_block: int        # (bn, bn) K1 tile
    u_strip: int         # (bn, mpad) row strip of mask * U
    k2_strip: int        # (mpad, bm) K2 column strip
    mask_tile: int       # (bn, bm) epilogue mask tile
    u_tile: int          # (bn, bm) epilogue U tile
    out_block: int       # (bn, bm) f32 output tile
    scratch: int         # (bn, bm) f32 accumulator
    double_buffered: int # pipelined copies of inputs + output
    total: int

    def fits(self, budget: int = VMEM_BUDGET_BYTES) -> bool:
        return self.total <= budget


def fused_vmem_breakdown(n: int, m: int, block_n: int, block_m: int,
                         precision: str = "f32") -> VmemBreakdown:
    """Byte-exact VMEM model of one fused-kernel grid step.

    Mirrors the kernel's BlockSpecs: inputs and the output are double
    buffered by the Pallas pipeline (two resident copies each); the
    accumulator is a single f32 buffer. ``B`` does not appear: the batch
    axis is the outermost grid dimension, one b per step. The output is
    always f32 (the wrappers cast back to the caller's dtype).
    """
    if precision not in _ITEMSIZE:
        raise ValueError(f"precision must be 'f32' or 'bf16', "
                         f"got {precision!r}")
    ib = _ITEMSIZE[precision]
    bn, bm, mpad = effective_blocks(n, m, block_n, block_m, precision)
    k1 = _tile_bytes(bn, bn, ib)
    u = _tile_bytes(bn, mpad, ib)
    k2 = _tile_bytes(mpad, bm, ib)
    mask_tile = _tile_bytes(bn, bm, ib)
    u_tile = _tile_bytes(bn, bm, ib)
    out = _tile_bytes(bn, bm, 4)
    scratch = _tile_bytes(bn, bm, 4)
    inputs_once = k1 + u + k2 + mask_tile + u_tile
    double = inputs_once + out     # the second pipelined copy of each
    total = 2 * inputs_once + 2 * out + scratch
    return VmemBreakdown(k1_block=k1, u_strip=u, k2_strip=k2,
                         mask_tile=mask_tile, u_tile=u_tile, out_block=out,
                         scratch=scratch, double_buffered=double, total=total)


def fused_vmem_bytes(n: int, m: int, block_n: int, block_m: int,
                     precision: str = "f32") -> int:
    return fused_vmem_breakdown(n, m, block_n, block_m, precision).total


def check_fused_blocks(n: int, m: int, block_n: int, block_m: int,
                       precision: str = "f32",
                       budget: int = VMEM_BUDGET_BYTES) -> VmemBreakdown:
    """Raise :class:`VmemBudgetError` if the choice exceeds the budget."""
    bd = fused_vmem_breakdown(n, m, block_n, block_m, precision)
    if not bd.fits(budget):
        bn, bm, mpad = effective_blocks(n, m, block_n, block_m, precision)
        raise VmemBudgetError(
            f"lk_mvm_fused blocks (block_n={block_n}, block_m={block_m}) "
            f"at shape (n={n}, m={m}, {precision}) need {bd.total} bytes "
            f"of VMEM (> budget {budget}): the (bn={bn}, mpad={mpad}) row "
            f"and column strips are {bd.u_strip + bd.k2_strip} bytes, "
            "twice over when double buffered. Use smaller blocks, or leave "
            "them to lk_mvm_fused, which runs the XLA body where no pair "
            "fits.")
    return bd


def _grid_steps(n: int, m: int, bn: int, bm: int) -> int:
    """Grid work per batch item: (n/bn rows) x (m/bm cols) x (n/bn k-sweep)."""
    gn = -(-n // bn)
    gm = -(-m // bm)
    return gn * gm * gn


def best_fitting_blocks(n: int, m: int, precision: str = "f32",
                        candidates: tuple[int, ...] = _CANDIDATES,
                        budget: int = VMEM_BUDGET_BYTES
                        ) -> tuple[int, int] | None:
    """The fitting candidate pair with the fewest grid steps, or None.

    Fewest grid steps == fewest stage-R recomputes; ties break toward
    larger blocks. Returns None when no candidate pair fits — the fused
    kernel cannot run this shape within budget (``lk_mvm_fused`` then runs
    the XLA body).
    """
    best: tuple[int, int] | None = None
    best_key: tuple | None = None
    for bn in candidates:
        for bm in candidates:
            if not fused_vmem_breakdown(n, m, bn, bm,
                                        precision).fits(budget):
                continue
            key = (_grid_steps(n, m, *effective_blocks(
                n, m, bn, bm, precision)[:2]), -bn, -bm)
            if best_key is None or key < best_key:
                best, best_key = (bn, bm), key
    return best


@dataclass(frozen=True)
class SquarePlan:
    """Tiles of the square fused kernel (transposed layout, see
    ``repro.kernels.lk_mvm._square_call``). Grid (B/chunk, n_pad/tn,
    n_pad/bk); the last axis sweeps K1."""
    bk: int        # K1 rows per step: depth of the sweep
    tn: int        # K1 columns per step: lanes of the output block
    chunk: int     # right-hand sides per step, stacked on the MXU's rows
    m8: int        # m padded to the sublane multiple
    n_pad: int     # n padded to tn

    @property
    def ts(self) -> int:
        """Lanes of one main product inside a step: the output block is
        accumulated in sub-tiles of ts, which bounds the product's value."""
        return next((t for t in (_SUBTILE, 384, 256) if self.tn % t == 0),
                    self.tn)

    def grid(self, B: int) -> tuple[int, int, int]:
        return (-(-B // self.chunk), self.n_pad // self.tn,
                self.n_pad // self.bk)

    def steps(self, B: int) -> int:
        gc, gj, gk = self.grid(B)
        return gc * gj * gk


@dataclass(frozen=True)
class SquareVmemBreakdown:
    """Per-grid-step VMEM bytes of the square fused kernel: its
    BlockSpecs and scratch exactly, Mosaic's temporaries as a bound."""
    um_block: int        # (chunk, m8, bk) (mask * U)^T strip
    k2t: int             # (m8, m8) K2^T, whole
    k1_block: int        # (bk, tn) K1^T block
    mask_block: int      # (m8, tn) mask^T block
    out_block: int       # (chunk, m8, tn) f32 output, the accumulator
    t_scratch: int       # (chunk * m8, bk) stage-R tile
    product: int         # (chunk * m8, ts) f32 value of one main product
    temporaries: int     # Mosaic's values around the product (bound)
    total: int

    def fits(self, budget: int = VMEM_BUDGET_BYTES) -> bool:
        return self.total <= budget


def square_vmem_breakdown(plan: SquarePlan,
                          precision: str = "f32") -> SquareVmemBreakdown:
    """VMEM model of one square-kernel grid step.

    Mirrors its BlockSpecs byte for byte: inputs and the output are double
    buffered by the Pallas pipeline; the stage-R scratch is one buffer,
    and one lane sub-tile of the main product's f32 value is held before
    it is added to the output. Mosaic's own values around that product
    (the operands split into bf16 parts at HIGHEST, the masked product)
    are bounded by twice the stage-R tile and K1 sub-tile plus one more
    product: compiled for a v5e, the kernel needed less than this bound
    at every plan tried, and up to 3.3 MB more than the BlockSpecs alone.
    """
    ib = _ITEMSIZE[precision]
    c, m8, bk, tn = plan.chunk, plan.m8, plan.bk, plan.tn
    um = c * _tile_bytes(m8, bk, ib)
    k2t = _tile_bytes(m8, m8, ib)
    k1 = _tile_bytes(bk, tn, ib)
    mask = _tile_bytes(m8, tn, ib)
    out = c * _tile_bytes(m8, tn, 4)
    t = _tile_bytes(c * m8, bk, ib)
    prod = _tile_bytes(c * m8, plan.ts, 4)
    temps = 2 * (t + _tile_bytes(bk, plan.ts, ib)) + prod
    total = 2 * (um + k2t + k1 + mask + out) + t + prod + temps
    return SquareVmemBreakdown(um_block=um, k2t=k2t, k1_block=k1,
                               mask_block=mask, out_block=out, t_scratch=t,
                               product=prod, temporaries=temps, total=total)


_SUBTILE = 512     # lanes of one main product inside a step
# Per-step overhead of the square kernel, in f32 multiply-adds at HIGHEST:
# about 0.5 us a step on a v5e, where such multiply-adds run at about
# 16e12 a second.
_STEP_MACS = 1 << 23


def square_plan(n: int, m: int, B: int, block_n: int,
                precision: str = "f32",
                budget: int = VMEM_BUDGET_BYTES) -> SquarePlan | None:
    """The square kernel's tiles for (B, n, m), or None if none fits.

    ``block_n`` sets the sweep depth bk as :func:`block_edge` does. The
    lane tile tn (a multiple of bk; n is padded to a multiple of it) and
    the batch chunk are chosen from the observed B: the fitting pair with
    the least MXU work, counting the padding of n, the rows of a padded
    last chunk and the stage-R product recomputed for each lane tile,
    plus a fixed cost per grid step. None when K2 and one right-hand
    side's tiles alone exceed the budget (m in the thousands); the
    row-strip kernel takes those.
    """
    if precision not in _ITEMSIZE:
        raise ValueError(f"precision must be 'f32' or 'bf16', "
                         f"got {precision!r}")
    m8 = _round_up(max(m, 1), _SUBLANE[_ITEMSIZE[precision]])
    bk = block_edge(block_n, n, _MIN_EDGE[precision])
    best, best_cost = None, None
    for tn in range(bk, _round_up(n, bk) + 1, bk):
        n_pad = _round_up(n, tn)
        for chunk in range(1, max(B, 1) + 1):
            plan = SquarePlan(bk=bk, tn=tn, chunk=chunk, m8=m8, n_pad=n_pad)
            if not square_vmem_breakdown(plan, precision).fits(budget):
                break            # larger chunks only grow
            rows = plan.grid(B)[0] * chunk * m8
            cost = (rows * n_pad * (n_pad + m8 * (n_pad // tn))
                    + _STEP_MACS * plan.steps(B))
            if best_cost is None or cost < best_cost:
                best, best_cost = plan, cost
    return best


def kernel_blocks(n: int, m: int,
                  precision: str = "f32") -> tuple[int, int] | None:
    """(block_n, block_m) of ``lk_mvm_fused`` at (n, m) when none are given.

    The smallest candidate covering each axis (the single-sweep regime),
    kept if the kernel it selects fits the budget: the square kernel,
    whose smallest batch chunk fits for every B if it fits for one, or
    else the row-strip kernel. Otherwise the best *fitting* row-strip
    pair; None when no candidate fits at all, and ``lk_mvm_fused`` runs
    the XLA body.
    """
    bn = next((c for c in _CANDIDATES if c >= n), _CANDIDATES[-1])
    bm = next((c for c in _CANDIDATES if c >= m), _CANDIDATES[-1])
    if (square_plan(n, m, 1, bn, precision) is not None
            or fused_vmem_breakdown(n, m, bn, bm, precision).fits()):
        return bn, bm
    return best_fitting_blocks(n, m, precision)


def audit_candidate_space(shapes=None,
                          candidates: tuple[int, ...] = _CANDIDATES,
                          budget: int = VMEM_BUDGET_BYTES) -> list[dict]:
    """Every (shape, precision, candidate) combination over budget.

    ``shapes`` defaults to the power-of-two (n, m) buckets up to
    (8192, 8192) — the paper's target regime. The returned rows are what
    the raw {64, 128, 256} grid *could* pick without the VMEM filter; that
    the filtered chooser (:func:`best_fitting_blocks` over the same
    shapes) never returns one of them is the invariant the CI gate
    enforces.
    """
    if shapes is None:
        buckets = [2 ** k for k in range(3, 14)]        # 8 .. 8192
        shapes = [(n, m) for n in buckets for m in buckets]
    rows = []
    for n, m in shapes:
        for precision in ("f32", "bf16"):
            for bn in candidates:
                for bm in candidates:
                    bd = fused_vmem_breakdown(n, m, bn, bm, precision)
                    if not bd.fits(budget):
                        rows.append({
                            "n": n, "m": m, "precision": precision,
                            "block_n": bn, "block_m": bm,
                            "bytes": bd.total, "budget": budget,
                        })
    return rows
