"""Pallas kernels for the router hot paths.

``MAX_K_FUSED`` is the single source of truth for the fused-epilogue K
ceiling: above it, one (bb, K) score tile plus the argmax epilogue no
longer fits a VMEM-friendly block, and both ``dueling_select`` and the
fused SGLD path fall back to scores + XLA.  It is defined *before* the
``.ops`` import so the kernel submodules can ``from repro.kernels import
MAX_K_FUSED`` while this package is still initializing; repro-lint's
kernel-budget pass (``kernel/maxk-duplicate-definition``) enforces that
no submodule grows its own copy.
"""
# K above this no longer fits one VMEM tile for the argmax epilogue; fall
# back to scores + XLA argmax (router pools are K <= ~100 in practice).
MAX_K_FUSED = 1024

# Matmul precision inside the router kernels: f32 operands contract in f32.
# Mosaic's default for f32 operands is a single bf16 pass, which moved the
# fused SGLD gradient 8e-3 (relative) off its autodiff reference on a TPU
# v5e; the interpret lowering, and so the "xla" backend on the CPU, is f32
# either way.
KERNEL_PRECISION = "highest"

from .ops import (dueling_score_op, dueling_select_op, flash_attention_op,
                  rglru_scan_op, sgld_potential_op, ssd_scan_op)

__all__ = ["MAX_K_FUSED", "dueling_score_op", "dueling_select_op",
           "flash_attention_op", "rglru_scan_op", "sgld_potential_op",
           "ssd_scan_op"]
