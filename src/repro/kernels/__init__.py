"""Pallas TPU kernels (interpret=True validated on CPU; see ops.py)."""
from repro.kernels.ops import (
    BSR, BSROperand, bsr_from_dense, bsr_from_scipy, bsr_operand,
    bsr_to_dense, bsr_transpose,
    spmm, spmm_t, fused_project_mask, gram_matrix,
)

__all__ = ["BSR", "BSROperand", "bsr_from_dense", "bsr_from_scipy",
           "bsr_operand", "bsr_to_dense", "bsr_transpose",
           "spmm", "spmm_t", "fused_project_mask", "gram_matrix"]
