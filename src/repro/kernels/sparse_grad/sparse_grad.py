"""Pallas TPU kernel: sampled sparse-block FW scores (DESIGN.md §Sparse).

The sparse twin of ``kernels/fw_grad``: the hot loop of the stochastic FW
iteration scores the sampled coordinates |z_i^T R| and reduces to the
argmax, but here z_i lives in the block-ELL layout of
``repro.sparse.matrix.SparseBlockMatrix`` — a (block_size, nnz_max) brick
of values plus the matching sample indices per feature block.

The sampled block ids are scalar-prefetched exactly like the dense
kernel: the BlockSpec index_map reads ``blk[i]``, so grid step i DMAs ONE
(block_size x nnz_max) values brick and its row-index brick from HBM,
gathers the referenced residual entries from the VMEM-resident residual
(m floats in the lane layout of ``kernels/lanes`` — small by
construction in the p >> m regime the paper targets), and segment-dots
them on the VPU. Per grid step the kernel
reads O(block_size * nnz_max) instead of the dense kernel's
O(block_size * m): at col_density 0.002 that is a ~500x traffic cut.

Padded ELL slots carry value 0.0 at row 0, and padded tail FEATURES are
all-zero rows, so both score exactly 0 and the caller masks global
indices >= p out of the argmax (same §Padding contract as fw_grad).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lanes import gather_lanes, to_lanes


def _kernel(blk_ref, vals_ref, rows_ref, r_ref, out_ref):
    """One sampled block: gather residual entries, segment-dot, negate."""
    vals = vals_ref[0].astype(jnp.float32)  # (block_size, nnz_max)
    gathered = gather_lanes(r_ref, rows_ref[0])  # (block_size, nnz_max)
    out_ref[0, 0, :] = -jnp.sum(vals * gathered, axis=1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparse_sampled_scores(
    values: jax.Array,  # (nblocks, block_size, nnz_max)
    rows: jax.Array,  # (nblocks, block_size, nnz_max) int32
    r: jax.Array,  # (m,) residual
    blk: jax.Array,  # (nb,) int32 sampled block indices
    *,
    interpret: bool = False,
) -> jax.Array:
    """Scores (nb * block_size,) for the sampled feature blocks.

    The score rows come out as (nb, 1, block_size), so each grid step
    writes a block whose last two dims equal the array's."""
    _, block_size, nnz_max = values.shape
    nb = blk.shape[0]
    r2d = to_lanes(r)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, block_size, nnz_max), lambda i, blk: (blk[i], 0, 0)),
            pl.BlockSpec((1, block_size, nnz_max), lambda i, blk: (blk[i], 0, 0)),
            pl.BlockSpec(r2d.shape, lambda i, blk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_size), lambda i, blk: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, 1, block_size), jnp.float32),
        interpret=interpret,
        name="fw_sparse_sampled_scores",
    )(blk, values, rows, r2d)
    return out.reshape(nb * block_size)
