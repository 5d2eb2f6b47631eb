"""Pallas TPU kernels for the paper's compute hot-spot, the masked MVM."""
from .lk_mvm import lk_mvm_fused, lk_mvm_fused_rows

__all__ = ["lk_mvm_fused", "lk_mvm_fused_rows"]
