"""Peaks of the chips the benchmark runs on, and the work of its kernels.

Peaks are keyed by JAX's ``device_kind``. Source: Google Cloud
documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB HBM at 819 GB/s). A device that is not in the table is an error.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add them "
                       "to perfbench/roofline.py with their source") from None


def lk_mvm_flops(B: int, n: int, m: int) -> float:
    """Operations of one masked latent-Kronecker MVM of a (B, n, m) stack:
    (mask U) K2 then K1 (.), two multiply-adds per product term."""
    return 2.0 * B * (n * n * m + n * m * m)


def lk_mvm_bytes(B: int, n: int, m: int) -> float:
    """Least f32 bytes such an MVM moves: K1, K2 and the mask read once,
    U read twice (masked sweep and epilogue) and the output written once."""
    return 4.0 * (n * n + m * m + n * m + 3 * B * n * m)


def least_seconds(flops: float, nbytes: float, device_kind: str):
    """(seconds, bound): the roofline's least time and which peak binds."""
    p = peaks(device_kind)
    tf, tb = flops / p["flops"], nbytes / p["bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
