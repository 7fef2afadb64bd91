"""Distributed implementations of the engine's vertex / colstats / update
contract (DESIGN.md §Distributed).

These are the collectives behind ``FWConfig(backend='distributed')``:
``core.vertex`` dispatches here (lazily — this package layers ABOVE the
core) when the engine step runs inside the shard_map built by
``repro.distributed.driver``. Everything in this module assumes the
sharding vocabulary of ``DistSpec`` / ``repro.distributed.shard``:

    matrix   feature blocks over ``model_axis``, samples over ``data_axis``
             (a dense (p_local, m_local) tile, or a local SparseBlockMatrix
             whose ELL rows are LOCAL sample indices);
    w, v, y  per-"data"-slice (m_local,) vectors, replicated over "model";
    beta,    REPLICATED length-p vectors (O(p) per host is ~17 MB at the
    stats    paper's p = 4.2M — the O(nnz)/O(p*m) matrix is what sharding
             must split);
    scalars  replicated (every shard computes the same line search).

Per-iteration communication budget (the scalability story at cluster
scale): ONE psum of the |S| sampled partial scores over BOTH axes
(completes the gradient coordinates AND zero-fills non-owners, so the
argmax runs on a replicated score vector — same tie-breaking as the
single-device engine, which is what makes uniform-sampling trajectories
bit-identical on a 1-data-shard mesh), one psum of the winning column's
(m_local,) slice over "model", and the O(1) scalar psums of the oracle
recursions. Everything else is local O(kappa * nnz) work.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import vertex
from repro.core.solver_config import FWConfig
from repro.obs import trace as obs_trace
from repro.sparse import ops as sparse_ops
from repro.sparse.matrix import SparseBlockMatrix


def _count_collective(name: str):
    """Trace-time collective counter: these functions run inside jit /
    shard_map, so the counter fires once per collective SITE per compiled
    program (NOT per executed iteration — XLA replays the compiled loop
    without re-entering Python). That is exactly the comm-structure
    audit a trace wants: how many psum/all_gather sites each program
    carries, keyed by which primitive."""
    obs_trace.get_tracer().counter(f"dist/collectives/{name}", 1)


def _spec(cfg: FWConfig):
    spec = vertex.dist_spec(cfg)
    if spec is None:
        raise ValueError("distributed backend ops need cfg.backend='distributed'")
    return spec


def _both_axes(spec):
    return (spec.data_axis, spec.model_axis)


def feature_range(Xt_l, spec):
    """(offset, p_local) of this shard's global feature range. The local
    feature count is PADDED (whole blocks / equal tiles), so the mapping
    global = offset + local holds uniformly across shards."""
    if isinstance(Xt_l, SparseBlockMatrix):
        p_loc = Xt_l.p_padded
    else:
        p_loc = Xt_l.shape[0]
    mo = jax.lax.axis_index(spec.model_axis)
    return mo * p_loc, p_loc


# --------------------------------------------------------------------------
# Sampled-vertex selection
# --------------------------------------------------------------------------


def _local_scores(Xt_l, w_l, idx, off, p_loc):
    """Masked local partial scores for GLOBAL sampled coordinates ``idx``:
    the owner shard contributes its partial -z_i^T w over its sample
    slice, everyone else exact zeros (so the completing psum is also the
    owner selection)."""
    own = (idx >= off) & (idx < off + p_loc)
    loc = jnp.clip(idx - off, 0, p_loc - 1)
    if isinstance(Xt_l, SparseBlockMatrix):
        raw = sparse_ops.sparse_gather_scores(Xt_l, w_l, loc)
    else:
        rows = jnp.take(Xt_l, loc, axis=0)  # (|S|, m_local)
        raw = -(rows @ w_l)
    return jnp.where(own, raw, 0.0)


def dist_sample_vertex(
    Xt_l, w_l: jax.Array, key: jax.Array, p: int, cfg: FWConfig, extra_fn=None
):
    """Distributed twin of ``vertex.sample_vertex``: global index stream
    (a pure function of the replicated key — bit-identical to the
    single-device draw), masked local partial scores, ONE psum over
    (data, model) to complete + replicate them, then a replicated argmax.

    Returns the engine contract ``(i_star, g_raw, g_sel, n_scored)`` with
    every output replicated across the mesh.
    """
    spec = _spec(cfg)
    _count_collective("score_psum")
    off, p_loc = feature_range(Xt_l, spec)
    is_sparse = isinstance(Xt_l, SparseBlockMatrix)

    if cfg.sampling == "block" and is_sparse:
        # aligned global blocks (the shared draw — same stream as the
        # single-device sparse backend), scored through the block-ELL
        # kernel path
        bs = Xt_l.block_size
        blk = vertex.sample_blocks(key, -(-p // bs), bs, cfg)
        nb_req = blk.shape[0]
        nb_loc = p_loc // bs
        mo = jax.lax.axis_index(spec.model_axis)
        own_blk = (blk >= mo * nb_loc) & (blk < (mo + 1) * nb_loc)
        loc_blk = jnp.clip(blk - mo * nb_loc, 0, nb_loc - 1)
        scores_l = sparse_ops.sparse_block_scores(
            Xt_l,
            w_l,
            loc_blk,
            use_kernel=vertex.use_sparse_kernel(cfg),
            interpret=vertex.use_interpret(cfg),
        ).reshape(nb_req, bs)
        raw = jax.lax.psum(
            jnp.where(own_blk[:, None], scores_l, 0.0), _both_axes(spec)
        ).reshape(-1)
        idx = (blk[:, None] * bs + jnp.arange(bs)[None, :]).reshape(-1)
        n_scored = nb_req * bs
    else:
        # 'uniform' / 'full' (and dense 'block', whose XLA index stream is
        # already a flat wrapped-gather): global indices, width-1 gathers
        idx = vertex.sample_indices(key, p, cfg)
        raw = jax.lax.psum(
            _local_scores(Xt_l, w_l, idx, off, p_loc), _both_axes(spec)
        )
        n_scored = idx.shape[0]

    sel = raw if extra_fn is None else raw + extra_fn(idx)
    mag = jnp.where(idx < p, jnp.abs(sel), -1.0)
    j = jnp.argmax(mag)
    dtype = Xt_l.dtype
    if is_sparse:
        # the sparse single-device path casts f32 scores to storage dtype
        return idx[j], raw[j].astype(dtype), sel[j].astype(dtype), n_scored
    return idx[j], raw[j], sel[j], n_scored


def dist_score_indices(Xt_l, w_l: jax.Array, idx: jax.Array, cfg: FWConfig):
    """Distributed twin of ``vertex.score_indices``: the step rules'
    re-scoring pass over caller-chosen coordinates (the away/pairwise
    active-set buffer, the lazy-LMO winner cache). Same masked-owner
    partial scores as the sampled draw, ONE psum over BOTH axes to
    complete the gradient coordinates and replicate them — this is the
    score psum extended to the away candidates, so every step rule runs
    under ``backend='distributed'`` with replicated selections."""
    spec = _spec(cfg)
    _count_collective("rescore_psum")
    off, p_loc = feature_range(Xt_l, spec)
    raw = jax.lax.psum(
        _local_scores(Xt_l, w_l, idx, off, p_loc), _both_axes(spec)
    )
    if isinstance(Xt_l, SparseBlockMatrix):
        # the sparse single-device path hands back storage-dtype scores
        raw = raw.astype(Xt_l.dtype)
    return raw


# --------------------------------------------------------------------------
# Winning-column broadcast + eq. 10 update
# --------------------------------------------------------------------------


def _owned_column(Xt_l, i_star, spec):
    """This shard's contribution to the winning column's LOCAL sample
    slice: the owner materializes it (dense slice or sparse scatter of
    the ELL slots), everyone else exact zeros. The psum over "model" is
    the winning-column broadcast."""
    off, p_loc = feature_range(Xt_l, spec)
    own = (i_star >= off) & (i_star < off + p_loc)
    loc = jnp.clip(i_star - off, 0, p_loc - 1)
    if isinstance(Xt_l, SparseBlockMatrix):
        vals, rows = sparse_ops.sparse_column(Xt_l, loc)
        z = jnp.zeros((Xt_l.m,), Xt_l.dtype)
        z = z.at[rows].add(jnp.where(own, vals.astype(Xt_l.dtype), 0.0))
    else:
        z = jnp.where(
            own, jax.lax.dynamic_slice_in_dim(Xt_l, loc, 1, axis=0)[0], 0.0
        )
    return jax.lax.psum(z, spec.model_axis)


def dist_column_update(Xt_l, v_l, y_l, i_star, lam, delta_t, cfg: FWConfig):
    """v <- (1-lam) v + lam (y - delta_t z_star) on the local "data" slice
    (eq. 10 / margin recursion), winning column broadcast as a masked
    psum over "model" — in the sparse layout the owner's contribution is
    an O(nnz_max) scatter of the PRE-SCALED slot values, so the broadcast
    carries one (m_local,) vector regardless of p.

    Both branches replay the exact op sequence of their single-device
    twin (``sparse_ops.sparse_residual_update`` / the dense jnp
    expression): the psum only ever adds exact zeros from non-owners, so
    a 1-data-shard mesh stays bit-identical to one device.
    """
    spec = _spec(cfg)
    _count_collective("column_broadcast")
    if isinstance(Xt_l, SparseBlockMatrix):
        off, p_loc = feature_range(Xt_l, spec)
        own = (i_star >= off) & (i_star < off + p_loc)
        loc = jnp.clip(i_star - off, 0, p_loc - 1)
        vals, rows = sparse_ops.sparse_column(Xt_l, loc)
        out = (1.0 - lam) * v_l + lam * y_l
        contrib = jnp.zeros_like(v_l).at[rows].add(
            (-lam * delta_t) * jnp.where(own, vals.astype(v_l.dtype), 0.0)
        )
        return out + jax.lax.psum(contrib, spec.model_axis)
    z = _owned_column(Xt_l, i_star, spec)
    return (1.0 - lam) * v_l + lam * (y_l - delta_t * z)


def dist_column_dense(Xt_l, i_star, cfg: FWConfig) -> jax.Array:
    """Local (m_local,) slice of the dense winning column (the logistic
    bisection's direction vector)."""
    _count_collective("column_broadcast")
    return _owned_column(Xt_l, i_star, _spec(cfg))


# --------------------------------------------------------------------------
# Column statistics, matvec, full gradient (setup / certification passes)
# --------------------------------------------------------------------------


def _gather_model(x_l, spec):
    """Concatenate per-shard feature vectors into the replicated global
    (padded) feature axis, ordered by model-shard index."""
    return jax.lax.all_gather(x_l, spec.model_axis, tiled=True)


def dist_colstats(Xt_l, y_l: jax.Array, cfg: FWConfig, p: int):
    """(zty, znorm2, yty) replicated at the TRUE global p: local sweeps
    over the shard's features, psum over "data" to complete the sample
    axis, all_gather over "model" to assemble the feature axis. One-time
    setup pass (§4.2) — O(nnz_local) compute, O(p) comm, once per solve."""
    spec = _spec(cfg)
    _count_collective("colstats_gather")
    if isinstance(Xt_l, SparseBlockMatrix):
        vals = Xt_l.values.astype(jnp.float32)
        gathered = jnp.take(y_l.astype(jnp.float32), Xt_l.rows, axis=0)
        zty_l = jnp.sum(vals * gathered, axis=2).reshape(-1)  # (p_local,)
        zn2_l = jnp.sum(vals * vals, axis=2).reshape(-1)
        dtype = Xt_l.dtype
    else:
        zty_l = Xt_l @ y_l
        # same fused einsum as the single-device precompute_colstats — the
        # bit-identity contract needs identical per-shard rounding (and it
        # skips the O(p_local * m_local) squared temporary)
        zn2_l = jnp.einsum("pm,pm->p", Xt_l, Xt_l)
        dtype = Xt_l.dtype
    zty_l = jax.lax.psum(zty_l, spec.data_axis)
    zn2_l = jax.lax.psum(zn2_l, spec.data_axis)
    zty = _gather_model(zty_l, spec)[:p].astype(dtype)
    znorm2 = _gather_model(zn2_l, spec)[:p].astype(dtype)
    yty = jax.lax.psum(jnp.dot(y_l, y_l), spec.data_axis)
    return zty, znorm2, yty


def _beta_slice(beta: jax.Array, off, p_loc: int, p: int):
    """This shard's slice of the replicated beta, zero-padded past the
    true p (gather with clipped indices + mask — dynamic_slice would
    clamp the start and misalign the last shard)."""
    gidx = off + jnp.arange(p_loc)
    vals = jnp.take(beta, jnp.clip(gidx, 0, p - 1))
    return jnp.where(gidx < p, vals, 0.0)


def dist_matvec(Xt_l, beta: jax.Array, cfg: FWConfig) -> jax.Array:
    """Local (m_local,) slice of X alpha from the replicated beta —
    warm-start initialization. psum over "model" completes the feature
    sum."""
    spec = _spec(cfg)
    _count_collective("matvec_psum")
    off, p_loc = feature_range(Xt_l, spec)
    b_l = _beta_slice(beta, off, p_loc, beta.shape[0]).astype(Xt_l.dtype)
    if isinstance(Xt_l, SparseBlockMatrix):
        v_l = sparse_ops.sparse_matvec(Xt_l, b_l)
    else:
        v_l = b_l @ Xt_l
    return jax.lax.psum(v_l, spec.model_axis)


def dist_grad_full(Xt_l, w_l: jax.Array, cfg: FWConfig) -> jax.Array:
    """Replicated full linear gradient -X^T w over the PADDED feature
    axis (callers slice [:p]) — the certification pass behind the oracle
    ``gap()`` protocol. O(nnz_local) compute + one O(p) all_gather."""
    spec = _spec(cfg)
    _count_collective("grad_gather")
    if isinstance(Xt_l, SparseBlockMatrix):
        vals = Xt_l.values.astype(jnp.float32)
        gathered = jnp.take(w_l.astype(jnp.float32), Xt_l.rows, axis=0)
        g_l = -jnp.sum(vals * gathered, axis=2).reshape(-1)
        g_l = jax.lax.psum(g_l, spec.data_axis).astype(Xt_l.dtype)
    else:
        g_l = jax.lax.psum(-(Xt_l @ w_l), spec.data_axis)
    return _gather_model(g_l, spec)
