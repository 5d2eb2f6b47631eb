"""Static VMEM budget checker for the fused latent-Kronecker MVM kernel.

The fused kernel (:mod:`repro.kernels.lk_mvm`, shared by ``lk_mvm_fused``
and ``lk_mvm_fused_rows``) keeps, per grid step, a K1 block, a full row
strip of the masked input ``mask * U``, a full K2 column strip, the
(i, j) mask and U tiles of the epilogue, the output block, and one f32
accumulator resident in VMEM.
TPU VMEM is ~16 MiB per core; a (block_n, block_m) choice whose resident
set exceeds it fails at ``pallas_call`` compile time on hardware — long
after the autotuner committed to it, and invisibly on CPU where the
kernel runs in interpret mode. This module computes the **exact** bytes
implied by a block choice (including (sublane, lane) tile rounding and
the pipeline's double buffering) so oversized configurations are rejected
*before* ``pallas_call`` ever runs:

* :func:`effective_blocks` — the block edges the kernels really use: an
  axis that one block covers is taken whole, a tiled axis uses the block
  rounded up to the 128-lane tile (Mosaic accepts no other edge);
* :func:`fused_vmem_breakdown` / :func:`fused_vmem_bytes` — the byte
  model, mirroring the kernel's BlockSpecs one-to-one;
* :func:`check_fused_blocks` — raise :class:`VmemBudgetError` when a
  choice exceeds the budget (called by the fused kernels themselves);
* :func:`best_fitting_blocks` — the largest-throughput candidate pair
  that fits (used by the autotuner to filter its sweep);
* :func:`audit_candidate_space` — sweep representative shape buckets and
  report every (shape, candidate) combination the autotuner could emit
  that does not fit; the *filtered* sweep is provably clean while the raw
  {64, 128, 256} grid is not (see tests/test_analysis.py).

Pure stdlib — importable (and CI-checkable) without jax.
"""
from __future__ import annotations

from dataclasses import dataclass

__all__ = ["VMEM_BUDGET_BYTES", "VmemBudgetError", "VmemBreakdown",
           "block_edge", "effective_blocks", "fused_vmem_breakdown",
           "fused_vmem_bytes", "check_fused_blocks", "best_fitting_blocks",
           "audit_candidate_space"]

VMEM_BUDGET_BYTES = 16 * 1024 * 1024   # 16 MiB per TPU core

# Matches repro.kernels.lk_mvm: candidate sweep and minimum block edges.
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
            "twice over when double buffered. Use smaller blocks, or the "
            "two-stage kernel (fused=False) whose intermediate lives in "
            "HBM.")
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

    Fewest grid steps == fewest stage-R recomputes (the analytic optimum
    the autotuner's heuristic mode targets); ties break toward larger
    blocks. Returns None when no candidate pair fits — the fused kernel
    cannot run this shape within budget and callers must fall back to the
    two-stage kernel.
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


def audit_candidate_space(shapes=None,
                          candidates: tuple[int, ...] = _CANDIDATES,
                          budget: int = VMEM_BUDGET_BYTES) -> list[dict]:
    """Every (shape, precision, candidate) combination over budget.

    ``shapes`` defaults to the power-of-two (n, m) buckets the autotuner
    caches on, up to (8192, 8192) — the paper's target regime. The
    returned rows are what the raw {64, 128, 256} sweep *could* pick
    without the VMEM filter; an empty result for the filtered chooser
    (:func:`best_fitting_blocks` composed over the same shapes) is the
    invariant the CI gate enforces.
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
