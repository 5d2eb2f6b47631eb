"""Block-size autotuner for the fused latent-Kronecker MVM kernel.

The fused kernel's best (block_n, block_m) depends on the grid shape: a
block_n that covers n keeps the kernel in its single-K1-sweep regime (no
stage-R recompute, every operand read once), while larger-than-needed
blocks waste VMEM and padding FLOPs. The autotuner picks per-shape blocks
from a small sweep over ``CANDIDATE_BLOCKS`` ({64, 128, 256}):

* **timed mode** (default on TPU, or ``timed=True``): each candidate is
  compiled and timed on a synthetic problem of the bucketed shape,
  validated against the :mod:`repro.kernels.ref` oracle, and the fastest
  candidate wins. A candidate the compiler refuses, or one that disagrees
  with the oracle, is an error naming the shape and blocks — never
  skipped.
* **heuristic mode** (default off-TPU, and always under ``jit`` tracing —
  timing inside a trace is meaningless): the smallest candidate covering
  each axis, i.e. the analytic single-sweep optimum.

Results are cached per (n, m, B) power-of-two bucket (+ precision +
backend), so the sweep runs once per shape family per process. The
benchmark suite (``benchmarks/bench_mvm.py``) pre-fills the cache with
timed results; later jitted traces reuse them via :func:`autotune_blocks`.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.vmem import (best_fitting_blocks, effective_blocks,
                             fused_vmem_breakdown)

__all__ = ["CANDIDATE_BLOCKS", "autotune_blocks", "clear_cache",
           "cache_contents"]

CANDIDATE_BLOCKS = (64, 128, 256)

_CACHE: dict[tuple, "tuple[int, int] | None"] = {}
_MISS = object()   # cached None is a real answer ("no candidate fits")


def _bucket(x: int) -> int:
    """Next power of two >= x (min 8): shapes in one bucket share blocks."""
    b = 8
    while b < x:
        b *= 2
    return b


def clear_cache() -> None:
    _CACHE.clear()


def cache_contents() -> dict:
    return dict(_CACHE)


def _heuristic(n: int, m: int,
               precision: str = "f32") -> tuple[int, int] | None:
    """Smallest candidate covering each axis (single-sweep regime).

    VMEM-guarded since PR 6: if the covering pair does not fit the 16 MiB
    budget (``repro.analysis.vmem``), fall back to the best *fitting*
    candidate; None when no candidate fits at all — the fused kernel
    cannot run this shape and callers must take the two-stage path.
    """
    bn = next((c for c in CANDIDATE_BLOCKS if c >= n), CANDIDATE_BLOCKS[-1])
    bm = next((c for c in CANDIDATE_BLOCKS if c >= m), CANDIDATE_BLOCKS[-1])
    if fused_vmem_breakdown(n, m, bn, bm, precision).fits():
        return bn, bm
    return best_fitting_blocks(n, m, precision,
                               candidates=CANDIDATE_BLOCKS)


def _candidate_pairs(n: int, m: int, precision: str = "f32"):
    """Deduplicated, VMEM-fitting candidate pairs for the timed sweep.

    Oversized pairs are excluded *statically*: on TPU they would fail at
    Mosaic compile time (wasting a sweep slot), and in interpret mode
    they would time fine and poison the cache with a config that OOMs on
    hardware.
    """
    seen, pairs = set(), []
    for bn in CANDIDATE_BLOCKS:
        for bm in CANDIDATE_BLOCKS:
            eff = effective_blocks(n, m, bn, bm, precision)[:2]
            if eff in seen:
                continue
            seen.add(eff)
            if fused_vmem_breakdown(n, m, bn, bm, precision).fits():
                pairs.append((bn, bm))
    return pairs


def _time_candidate(fn, args, reps: int = 3) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        # Timing harness: the per-iteration sync IS the measurement.
        jax.block_until_ready(fn(*args))  # lint: disable=RA103
        best = min(best, time.perf_counter() - t0)
    return best


def autotune_blocks(n: int, m: int, B: int = 1, *, precision: str = "f32",
                    timed: bool | None = None,
                    interpret: bool | None = None,
                    atol: float = 1e-4) -> tuple[int, int] | None:
    """Pick (block_n, block_m) for the fused kernel at shape (B, n, m).

    ``timed=None`` resolves to True on TPU and False elsewhere. Timed
    sweeps validate every candidate against the jnp oracle; a candidate
    that fails to compile or to match raises ``RuntimeError`` naming
    (n, m, B, block_n, block_m) — the heuristic is never a silent
    substitute for a sweep. Safe to call at ``jit`` trace time with
    ``timed=False`` (pure-python cache lookup / heuristic — no
    compilation, no timing).

    Every candidate considered (timed or heuristic) is pre-filtered
    against the exact VMEM budget model (:mod:`repro.analysis.vmem`).
    Returns ``None`` when *no* candidate fits — e.g. m >= 8192, where a
    single row strip exceeds 16 MiB — meaning the fused kernel cannot run
    this shape and the caller must use the two-stage kernel.
    """
    key = (_bucket(n), _bucket(m), _bucket(max(B, 1)), precision,
           jax.default_backend())
    hit = _CACHE.get(key, _MISS)
    if hit is not _MISS:
        return hit
    if timed is None:
        timed = jax.default_backend() == "tpu"
    if not timed:
        blocks = _heuristic(n, m, precision)
        _CACHE[key] = blocks
        return blocks

    # Import here: repro.kernels.lk_mvm has no dependency on this module,
    # but keeping the top level import-light avoids cycles via ref.py.
    from .lk_mvm import lk_mvm_fused
    from .ref import lk_mvm_ref

    nb, mb, Bb = key[0], key[1], key[2]
    rng = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(rng, 3)
    A = jax.random.normal(k1, (nb, nb), jnp.float32)
    K1 = A @ A.T / nb + 0.5 * jnp.eye(nb, dtype=jnp.float32)
    C = jax.random.normal(k2, (mb, mb), jnp.float32)
    K2 = C @ C.T / mb + 0.5 * jnp.eye(mb, dtype=jnp.float32)
    mask = jnp.ones((nb, mb), jnp.float32)
    u = jax.random.normal(k3, (Bb, nb, mb), jnp.float32)
    # The oracle at full f32 precision: XLA's default on TPU contracts f32
    # in one bf16 pass, far coarser than the kernel's f32 mode.
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(lk_mvm_ref(K1, K2, mask, u, 0.1))
    scale = max(1.0, float(np.max(np.abs(ref))))

    best, best_t = None, float("inf")
    for bn, bm in _candidate_pairs(nb, mb, precision):
        def run(K1, K2, mask, u, _bn=bn, _bm=bm):
            return lk_mvm_fused(K1, K2, mask, u, 0.1, block_n=_bn,
                                block_m=_bm, precision=precision,
                                interpret=interpret)
        where = (f"lk_mvm_fused candidate (n={nb}, m={mb}, B={Bb}, "
                 f"block_n={bn}, block_m={bm}, {precision})")
        try:
            # Correctness screen of each candidate against the dense
            # reference needs the values on host.
            out = np.asarray(run(K1, K2, mask, u))  # lint: disable=RA103
        except jax.errors.JaxRuntimeError as e:
            raise RuntimeError(f"{where} was refused by the compiler after "
                               "passing the VMEM filter") from e
        tol = atol * scale if precision == "f32" else 0.1 * scale
        if not np.allclose(out, ref, atol=tol):
            err = np.max(np.abs(out - ref))
            raise RuntimeError(f"{where} disagrees with the jnp oracle "
                               f"(max abs error {err:.3g})")
        t = _time_candidate(run, (K1, K2, mask, u))
        if t < best_t:
            best, best_t = (bn, bm), t
    _CACHE[key] = best
    return best
