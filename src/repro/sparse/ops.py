"""Solver-facing ops over SparseBlockMatrix (XLA fallback + kernel dispatch).

These are the sparse twins of the three O(m)/O(kappa*m) primitives the
backends share (DESIGN.md §4.5), plus the matvecs the warm start and the
certification-time duality gap need. Everything is a dense gather +
reduction over the rectangular block-ELL arrays, so all ops jit cleanly
and cost O(touched_slots) = O(kappa * nnz_max) instead of O(kappa * m).

Score/stat accumulation is f32 regardless of storage dtype (the dense
Pallas kernels' ``preferred_element_type=jnp.float32`` contract), but the
solver-facing results are returned in the matrix's STORAGE dtype — the
same boundary the dense XLA backend has (``Xt @ y`` on bf16 accumulates
in f32 and yields bf16), which keeps the solver's weakly-typed scalar
recursions in the storage dtype end to end.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.kernels.sparse_colstats.sparse_colstats import (
    sparse_colstats_fused,
    sparse_xtw,
)
from repro.kernels.sparse_grad.ref import sparse_sampled_scores_ref
from repro.kernels.sparse_grad.sparse_grad import sparse_sampled_scores
from repro.sparse.matrix import SparseBlockMatrix

ExtraFn = Callable[[jax.Array], jax.Array]


def sparse_block_scores(
    mat: SparseBlockMatrix,
    resid: jax.Array,
    blk: jax.Array,
    *,
    use_kernel: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """FW scores (-z_i^T R) for the features of the sampled blocks.

    ``use_kernel`` routes through the Pallas scalar-prefetch kernel
    (``kernels/sparse_grad``); otherwise the pure-XLA oracle runs — the
    off-TPU production path, not just a test double.
    """
    if use_kernel:
        return sparse_sampled_scores(
            mat.values, mat.rows, resid, blk, interpret=interpret
        )
    return sparse_sampled_scores_ref(mat.values, mat.rows, resid, blk)


def sparse_fw_vertex_general(
    mat: SparseBlockMatrix,
    w: jax.Array,
    blk: jax.Array,
    *,
    use_kernel: bool = False,
    interpret: bool = False,
    extra_fn: Optional[ExtraFn] = None,
):
    """(i_star, g_raw, g_sel) over the sampled blocks, masking padding.

    ``g_raw`` is the linear score -z^T w; ``g_sel`` additionally carries
    the oracle's per-coordinate shift ``extra_fn(idx)`` (the elastic-net
    ``+l2 * alpha_i`` term — with ``extra_fn=None`` the two coincide).
    Padded ELL slots and padded tail features score exactly 0, but they
    must still be excluded from the argmax (an all-zero sample would
    otherwise select a phantom coordinate) — same contract as the dense
    ``fw_grad.ops.fw_vertex`` with ``p_valid``. ``extra_fn`` sees clipped
    gathers for padded idx >= p, which the mask makes unselectable.
    """
    scores = sparse_block_scores(
        mat, w, blk, use_kernel=use_kernel, interpret=interpret
    )
    idx = (
        blk[:, None] * mat.block_size + jnp.arange(mat.block_size)[None, :]
    ).reshape(-1)
    sel = scores if extra_fn is None else scores + extra_fn(idx)
    mag = jnp.where(idx < mat.p, jnp.abs(sel), -1.0)
    j = jnp.argmax(mag)
    return idx[j], scores[j].astype(mat.dtype), sel[j].astype(mat.dtype)


def sparse_fw_vertex(
    mat: SparseBlockMatrix,
    resid: jax.Array,
    blk: jax.Array,
    *,
    use_kernel: bool = False,
    interpret: bool = False,
):
    """(i_star, g_star) over the sampled blocks — the pure-linear (lasso)
    reduction of ``sparse_fw_vertex_general``."""
    i_star, g_star, _ = sparse_fw_vertex_general(
        mat, resid, blk, use_kernel=use_kernel, interpret=interpret
    )
    return i_star, g_star


def sparse_gather_scores(mat: SparseBlockMatrix, w: jax.Array, idx: jax.Array):
    """Raw f32 scores -z_i^T w for arbitrary sampled coordinates
    ('uniform' mode). Single-feature gathers have no aligned-block
    structure to prefetch, so this is XLA-only. ``idx`` entries are < p by
    construction."""
    b = idx // mat.block_size
    t = idx % mat.block_size
    vals = mat.values[b, t].astype(jnp.float32)  # (kappa, nnz_max)
    rows = mat.rows[b, t]
    return -jnp.sum(vals * jnp.take(w.astype(jnp.float32), rows, axis=0), axis=1)


def sparse_gather_vertex_general(
    mat: SparseBlockMatrix,
    w: jax.Array,
    idx: jax.Array,
    *,
    extra_fn: Optional[ExtraFn] = None,
):
    """(i_star, g_raw, g_sel) for arbitrary sampled coordinates, with the
    optional oracle score shift (see ``sparse_fw_vertex_general``)."""
    scores = sparse_gather_scores(mat, w, idx)
    sel = scores if extra_fn is None else scores + extra_fn(idx)
    j = jnp.argmax(jnp.abs(sel))
    return idx[j], scores[j].astype(mat.dtype), sel[j].astype(mat.dtype)


def sparse_gather_vertex(mat: SparseBlockMatrix, resid: jax.Array, idx: jax.Array):
    """(i_star, g_star) for arbitrary sampled coordinates (lasso form)."""
    i_star, g_star, _ = sparse_gather_vertex_general(mat, resid, idx)
    return i_star, g_star


def sparse_colstats(
    mat: SparseBlockMatrix,
    y: jax.Array,
    *,
    use_kernel: bool = False,
    interpret: bool = False,
):
    """One pass over the stored slots: z_i^T y and ||z_i||^2 (paper §4.2).

    O(total stored nnz) instead of the dense O(p * m) sweep. With
    ``use_kernel`` the fused Pallas twin (``kernels/sparse_colstats``)
    computes both statistics in one pass over the ELL bricks — the
    sparse analogue of ``kernels/colstats`` for the TPU setup pass; the
    XLA sweep is the production CPU path. Accumulates in f32 and returns
    length-p arrays in the storage dtype (padding sliced off).
    """
    if use_kernel:
        zty_pad, zn2_pad = sparse_colstats_fused(
            mat.values, mat.rows, y, interpret=interpret
        )
        return (
            zty_pad[: mat.p].astype(mat.dtype),
            zn2_pad[: mat.p].astype(mat.dtype),
        )
    vals = mat.values.astype(jnp.float32)
    gathered = jnp.take(y.astype(jnp.float32), mat.rows, axis=0)
    zty = jnp.sum(vals * gathered, axis=2).reshape(-1)[: mat.p]
    znorm2 = jnp.sum(vals * vals, axis=2).reshape(-1)[: mat.p]
    return zty.astype(mat.dtype), znorm2.astype(mat.dtype)


def sparse_column(mat: SparseBlockMatrix, i: jax.Array):
    """(values, rows) ELL slots of feature ``i`` — the z_star the residual
    recursion (eq. 10) touches. One dynamic gather of nnz_max slots."""
    b = i // mat.block_size
    t = i % mat.block_size
    return mat.values[b, t], mat.rows[b, t]


def sparse_column_dense(mat: SparseBlockMatrix, i: jax.Array) -> jax.Array:
    """Dense (m,) column z_i via margin-scatter of the ELL slots.

    The logistic bisection line search needs the whole direction vector
    d_margin = delta_t * z_star - margin, so the sparse column is
    materialized once per step — O(nnz_max) scatter-adds into an O(m)
    zeros vector, amortized against the O(m)-per-probe bisection that
    consumes it. Padded slots add 0.0 at row 0 (structural no-op).
    """
    vals, rows = sparse_column(mat, i)
    z = jnp.zeros((mat.m,), mat.dtype)
    return z.at[rows].add(vals.astype(mat.dtype))


def sparse_residual_update(
    resid: jax.Array,
    y: jax.Array,
    col_vals: jax.Array,
    col_rows: jax.Array,
    lam: jax.Array,
    delta_t: jax.Array,
) -> jax.Array:
    """R <- (1-lam) R + lam (y - delta_t z_star), z_star sparse.

    The dense O(m) part is two vector ops; the z_star term is a
    scatter-add over nnz_max slots (padded slots add 0.0 at row 0 — a
    structural no-op).
    """
    out = (1.0 - lam) * resid + lam * y
    return out.at[col_rows].add((-lam * delta_t) * col_vals.astype(resid.dtype))


def sparse_matvec(mat: SparseBlockMatrix, beta: jax.Array) -> jax.Array:
    """X @ alpha for a coefficient vector of length p (warm-start init)."""
    pp = mat.p_padded
    beta_pad = jnp.zeros((pp,), jnp.float32).at[: mat.p].set(
        beta.astype(jnp.float32)
    )
    contrib = mat.values.reshape(pp, mat.nnz_max).astype(jnp.float32) * beta_pad[:, None]
    out = jnp.zeros((mat.m,), jnp.float32)
    out = out.at[mat.rows.reshape(-1)].add(contrib.reshape(-1))
    return out.astype(beta.dtype)


def sparse_transpose_matvec(
    mat: SparseBlockMatrix,
    r: jax.Array,
    *,
    use_kernel: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Xt @ r over ALL features — O(total nnz). Certification/grids only
    (the certified gap, duality_gap, lambda_grid); the hot loop never
    calls this.

    With ``use_kernel`` the Pallas twin of the column-statistics kernel
    (``fw_sparse_xtw``, ``kernels/sparse_colstats``) makes the one pass
    over the ELL bricks; otherwise an XLA gather and reduce, the CPU and
    distributed path. Accumulates in f32 and returns length p in the
    storage dtype.
    """
    if use_kernel:
        out = sparse_xtw(mat.values, mat.rows, r, interpret=interpret)
        return out[: mat.p].astype(mat.dtype)
    vals = mat.values.astype(jnp.float32)
    gathered = jnp.take(r.astype(jnp.float32), mat.rows, axis=0)
    return jnp.sum(vals * gathered, axis=2).reshape(-1)[: mat.p].astype(mat.dtype)
