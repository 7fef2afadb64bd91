"""In-kernel reads and writes of an (m,) vector at arbitrary row indices.

The sparse kernels gather the residual (or the targets) at the stored
row indices of block-ELL slots, and the fused sparse step scatter-adds
the winner column back into the residual. A TPU vector core has no
general gather: Mosaic lowers ``jnp.take_along_axis`` only within one
128-lane vreg row, whose operand and indices share one shape. So the
vector lives in VMEM in a lane layout, ``(ceil(m / 128), 128)``
(``to_lanes``), and a read of row ``r`` becomes sublane ``r >> 7``,
lane ``r & 127``:

* ``gather_lanes`` loops over the sublane rows: each turn loads one row
  by a dynamic sublane slice, lane-gathers it at every index and keeps
  the values whose row matches. Cost is O(rows * slots), and every value
  is copied, never combined, so the gather is exact.
* ``scatter_add_lanes`` turns the slots into a column (one transpose),
  builds each slot's lane one-hot, and adds each row's share with a
  dynamic sublane read-modify-write. A row index receives its slot's
  value plus zeros, so the result equals ``vec.at[rows].add(add)``.

Both run unchanged in interpret mode on the CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8


def lane_rows(m: int) -> int:
    """Sublane rows of the lane layout of an (m,) vector."""
    return -(-m // LANES)


def to_lanes(v: jax.Array) -> jax.Array:
    """(m,) -> (ceil(m/128), 128) f32, zero-padded (done in XLA)."""
    m = v.shape[0]
    h = lane_rows(m)
    return jnp.pad(v.astype(jnp.float32), (0, h * LANES - m)).reshape(h, LANES)


def from_lanes(v2d: jax.Array, m: int) -> jax.Array:
    """Inverse of ``to_lanes``."""
    return v2d.reshape(-1)[:m]


def _pad_lanes(x: jax.Array) -> jax.Array:
    pad = -x.shape[1] % LANES
    return jnp.pad(x, ((0, 0), (0, pad))) if pad else x


def gather_lanes(v_ref, rows: jax.Array) -> jax.Array:
    """``v[rows]`` for an (R, C) int32 index tile, R a multiple of 8, from
    the lane-layout VMEM ref ``v_ref`` of shape (H, 128). Returns (R, C)
    f32."""
    n_rows, width = rows.shape
    idx = _pad_lanes(rows)
    hi = idx >> 7
    lo = idx & (LANES - 1)
    n_chunks = idx.shape[1] // LANES

    def body(h, acc):
        row = jnp.broadcast_to(v_ref[pl.ds(h, 1), :], (n_rows, LANES))
        parts = []
        for c in range(n_chunks):
            sl = slice(c * LANES, (c + 1) * LANES)
            got = jnp.take_along_axis(row, lo[:, sl], axis=1)
            parts.append(jnp.where(hi[:, sl] == h, got, acc[:, sl]))
        return parts[0] if n_chunks == 1 else jnp.concatenate(parts, axis=1)

    init = jnp.zeros(idx.shape, jnp.float32)
    out = jax.lax.fori_loop(0, v_ref.shape[0], body, init)
    return out[:, :width]


def scatter_add_lanes(out_ref, rows: jax.Array, add: jax.Array) -> None:
    """``out.at[rows].add(add)`` in place on the lane-layout ref
    ``out_ref`` (H, 128), for one (1, C) row of int32 indices and its
    (1, C) f32 addends. Indices within the row must be distinct, except
    for padded slots, which add 0.0 at row 0."""
    idx = _pad_lanes(rows)
    val = _pad_lanes(add.astype(jnp.float32))
    tile = (SUBLANES, idx.shape[1])
    # (Cp, 1): the slots down the sublanes (Mosaic transposes 8-row tiles)
    col_idx = jnp.broadcast_to(idx, tile).T[:, :1]
    col_val = jnp.broadcast_to(val, tile).T[:, :1]
    hi = col_idx >> 7
    lane = jax.lax.broadcasted_iota(jnp.int32, (col_idx.shape[0], LANES), 1)
    onehot = jnp.where((col_idx & (LANES - 1)) == lane, col_val, 0.0)

    def body(h, carry):
        share = jnp.sum(jnp.where(hi == h, onehot, 0.0), axis=0, keepdims=True)
        out_ref[pl.ds(h, 1), :] = out_ref[pl.ds(h, 1), :] + share
        return carry

    jax.lax.fori_loop(0, out_ref.shape[0], body, 0)
