"""Pallas TPU kernel: sampled column-block FW scores (DESIGN.md §4).

The hot loop of the stochastic FW iteration is computing the sampled
gradient coordinates |z_i^T R| for i in S and reducing to the argmax.
On TPU we sample ALIGNED ROW BLOCKS of the feature-major matrix Xt (p, m)
and drive the gather with a scalar-prefetched block-index array: the
BlockSpec index_map reads blk[i], so each grid step DMAs one
(block_size x m_tile) brick of Xt from HBM into VMEM, computes its
contribution to the scores on the MXU/VPU, and accumulates over m tiles.

Grid: (nb, m_tiles); the score block is revisited across the inner m
dimension (sequential on TPU), giving one HBM pass over the sampled rows
and zero intermediate materialization.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lanes import SUBLANES
from repro.kernels.padding import pad_rows


def _kernel(blk_ref, x_ref, r_ref, out_ref):
    """One (block_size x m_tile) brick: accumulate -X r into scores."""
    j = pl.program_id(1)
    partial = -jnp.dot(
        x_ref[...], r_ref[0, :], preferred_element_type=jnp.float32
    )  # (block_size,)

    @pl.when(j == 0)
    def _init():
        out_ref[0, 0, :] = partial

    @pl.when(j > 0)
    def _acc():
        out_ref[0, 0, :] = out_ref[0, 0, :] + partial


@functools.partial(
    jax.jit, static_argnames=("block_size", "m_tile", "interpret")
)
def sampled_scores(
    Xt: jax.Array,  # (p, m) feature-major design matrix
    r: jax.Array,  # (m,) residual
    blk: jax.Array,  # (nb,) int32 sampled block indices
    *,
    block_size: int = 256,
    m_tile: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Scores (nb * block_size,) for the sampled coordinates.

    Non-divisible shapes are handled by fallbacks rather than asserts:
    ``p % block_size != 0`` zero-pads the trailing rows of ``Xt`` (padded
    coordinates score exactly 0 — callers that must never select them mask
    by global index, see ``ops.fw_vertex``), and ``m % m_tile != 0`` drops
    to a single m tile. Scores come out as (nb, 1, block_size), so each
    grid step writes a block whose last two dims equal the array's.
    """
    p, m = Xt.shape
    nb = blk.shape[0]
    Xt = pad_rows(Xt, block_size)
    if m % m_tile != 0:
        m_tile = m  # small-m fallback: single tile
    m_tiles = m // m_tile

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, m_tiles),
        in_specs=[
            pl.BlockSpec((block_size, m_tile), lambda i, j, blk: (blk[i], j)),
            pl.BlockSpec((1, m_tile), lambda i, j, blk: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_size), lambda i, j, blk: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, 1, block_size), jnp.float32),
        interpret=interpret,
        name="fw_sampled_scores",
    )(blk, Xt, r.reshape(1, m))
    return out.reshape(nb * block_size)


@functools.partial(jax.jit, static_argnames=("m_tile", "interpret"))
def row_scores(
    Xt: jax.Array,  # (p, m) feature-major design matrix
    r: jax.Array,  # (m,) residual
    idx: jax.Array,  # (n,) int32 arbitrary row indices < p
    *,
    m_tile: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Scores (n,) of arbitrary rows, in ``idx`` order ('uniform' sampling).

    A single row is no legal block for the chip (its second-to-last dim
    must be a multiple of 8), so each index scores the aligned 8-row slab
    that holds it and keeps its own row's score."""
    idx = idx.astype(jnp.int32)
    slab = sampled_scores(
        Xt, r, idx // SUBLANES, block_size=SUBLANES, m_tile=m_tile,
        interpret=interpret,
    ).reshape(idx.shape[0], SUBLANES)
    return jnp.take_along_axis(slab, (idx % SUBLANES)[:, None], axis=1)[:, 0]
