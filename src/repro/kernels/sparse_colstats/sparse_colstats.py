"""Pallas TPU kernel: fused sparse setup pass (DESIGN.md §Sparse).

The sparse twin of ``kernels/colstats``: one sweep over the block-ELL
slots of a ``SparseBlockMatrix`` computing BOTH per-feature statistics
the solver precomputes once (paper §4.2),

    zty[i]    = z_i^T y
    znorm2[i] = ||z_i||^2

fused so the (block_size x nnz_max) values brick is read from HBM
exactly once. The grid walks every feature block in order (a full sweep,
so no scalar prefetch is needed — the index map IS the grid index); the
targets vector y stays VMEM-resident (m floats in the lane layout of
``kernels/lanes``, small by construction in the p >> m regime the paper
targets) and the per-slot gather + two
reductions run on the VPU. Traffic is O(total stored slots) instead of
the dense kernel's O(p * m).

Padded ELL slots (value 0.0 at row 0) and padded tail features
contribute exactly 0 to both outputs; the caller slices the feature
padding off (same §Padding contract as the dense colstats kernel). Each
block's statistics come out as a (1, 1, block_size) row of an
(nblocks, 1, block_size) array, so every block shape the chip's compiler
sees has last two dims equal to the array's.

``sparse_xtw`` is the single-output twin, ``fw_sparse_xtw``: the same
sweep and gather for any (m,) vector w, writing X^T w alone (no
||z||^2). It is the O(nnz) pass of the certified duality gap
(``sparse.ops.sparse_transpose_matvec``). The two keep distinct kernel
names, so a profile tells their calls apart.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lanes import gather_lanes, to_lanes


def _block_dots(vals, rows_ref, v_ref):
    """z_i^T v for each feature of one block: gather v (lane layout) at
    the stored rows, multiply by the f32 values, reduce over the slots."""
    gathered = gather_lanes(v_ref, rows_ref[0])  # (block_size, nnz_max)
    return jnp.sum(vals * gathered, axis=1)


def _kernel(vals_ref, rows_ref, y_ref, zty_ref, zn2_ref):
    """One feature block: gather y at the stored rows, fused dual reduce."""
    vals = vals_ref[0].astype(jnp.float32)  # (block_size, nnz_max)
    zty_ref[0, 0, :] = _block_dots(vals, rows_ref, y_ref)
    zn2_ref[0, 0, :] = jnp.sum(vals * vals, axis=1)


def _xtw_kernel(vals_ref, rows_ref, w_ref, out_ref):
    """One feature block of X^T w."""
    out_ref[0, 0, :] = _block_dots(vals_ref[0].astype(jnp.float32), rows_ref, w_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparse_colstats_fused(
    values: jax.Array,  # (nblocks, block_size, nnz_max)
    rows: jax.Array,  # (nblocks, block_size, nnz_max) int32
    y: jax.Array,  # (m,) targets
    *,
    interpret: bool = False,
):
    """(zty, znorm2) of padded length nblocks * block_size, f32."""
    nblocks, block_size, nnz_max = values.shape
    y2d = to_lanes(y)
    stat = jax.ShapeDtypeStruct((nblocks, 1, block_size), jnp.float32)
    zty, zn2 = pl.pallas_call(
        _kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((1, block_size, nnz_max), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, block_size, nnz_max), lambda i: (i, 0, 0)),
            pl.BlockSpec(y2d.shape, lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_size), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, block_size), lambda i: (i, 0, 0)),
        ],
        out_shape=[stat, stat],
        interpret=interpret,
        name="fw_sparse_colstats",
    )(values, rows, y2d)
    return zty.reshape(-1), zn2.reshape(-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparse_xtw(
    values: jax.Array,  # (nblocks, block_size, nnz_max)
    rows: jax.Array,  # (nblocks, block_size, nnz_max) int32
    w: jax.Array,  # (m,) any sample-axis vector
    *,
    interpret: bool = False,
) -> jax.Array:
    """X^T w of padded length nblocks * block_size, f32."""
    nblocks, block_size, nnz_max = values.shape
    w2d = to_lanes(w)
    out = pl.pallas_call(
        _xtw_kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((1, block_size, nnz_max), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, block_size, nnz_max), lambda i: (i, 0, 0)),
            pl.BlockSpec(w2d.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_size), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, 1, block_size), jnp.float32),
        interpret=interpret,
        name="fw_sparse_xtw",
    )(values, rows, w2d)
    return out.reshape(-1)
