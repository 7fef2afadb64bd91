"""Pallas TPU kernels for the FW-Lasso hot loop (validated in interpret
mode on CPU; enabled on real TPUs via FWConfig/solver flags).

fw_grad:          sampled column-block scores (scalar-prefetch gather)
residual_update:  fused R <- (1-lam) R + lam (y - dt z)
colstats:         fused z^T y and ||z||^2 setup pass
sparse_grad:      sampled block-ELL scores (sparse twin of fw_grad)
sparse_colstats:  fused sparse z^T y and ||z||^2 (sparse twin of colstats),
                  and its single-output twin X^T w (the certified gap)
fused_step:       K fused FW iterations per launch, co-state VMEM-resident
lanes:            in-kernel gather/scatter of an (m,) vector (lane layout)
"""
from repro.kernels.fw_grad.ops import fw_vertex
from repro.kernels.fw_grad.fw_grad import sampled_scores
from repro.kernels.residual_update.residual_update import residual_update
from repro.kernels.colstats.colstats import colstats
from repro.kernels.sparse_grad.sparse_grad import sparse_sampled_scores
from repro.kernels.sparse_colstats.sparse_colstats import (
    sparse_colstats_fused,
    sparse_xtw,
)
from repro.kernels.fused_step.fused_step import (
    dense_fused_chunk,
    sparse_fused_chunk,
)

__all__ = [
    "fw_vertex",
    "sampled_scores",
    "residual_update",
    "colstats",
    "sparse_sampled_scores",
    "sparse_colstats_fused",
    "sparse_xtw",
    "dense_fused_chunk",
    "sparse_fused_chunk",
]
