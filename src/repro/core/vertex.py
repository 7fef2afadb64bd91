"""Sampled-vertex dispatch shared by the whole FW solver family.

This module owns everything between "the oracle handed us an (m,)
co-gradient vector" and "here is the winning FW vertex": drawing the
sampling set S (paper §4.1/§4.5), scoring the sampled coordinates on the
selected backend ('xla' | 'pallas' | 'sparse'), and reducing to the
argmax. It is objective-agnostic: every score is the LINEAR form

    raw_i = -z_i^T w        (w = the oracle's co-gradient vector)

optionally shifted by a per-coordinate additive term ``extra_fn(idx)``
(the elastic-net's ``+l2 * alpha_i``). The lasso passes ``w = R`` and no
extra term, which replays the exact op sequence (and index stream) of
the pre-engine solver — see tests/test_engine.py for the bit-identity
regression. The logistic oracle passes ``w = -grad_margin`` (negation is
exact in IEEE, so scores equal ``z_i^T grad_margin`` bitwise).

Also here: the backend-dispatched O(m) column recursions every oracle's
state update needs (eq. 10 and its margin analogue), and the dense
column accessor the logistic bisection line search uses.

The fourth backend, 'distributed', routes every primitive to
``repro.distributed.backend`` (lazy import — that package sits ABOVE the
core in the layering): the same engine step then runs unchanged inside a
shard_map over a (data, model) mesh, with the matrix shard-local, beta
and the column statistics replicated, and the residual/margin sliced
over "data". Oracles reach the sample axis only through ``mdot`` /
``msum`` here, which psum over ``cfg.dist.data_axis`` exactly when the
distributed backend is active — single-device solves compile to the
plain reductions.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro import devices
from repro.core.solver_config import FWConfig
from repro.kernels.fused_step import fused_step as _fused_step
from repro.kernels.fw_grad.fw_grad import row_scores as _row_scores_kernel
from repro.kernels.fw_grad.fw_grad import sampled_scores as _sampled_scores_kernel
from repro.kernels.fw_grad.ops import fw_vertex as _fw_vertex_kernel
from repro.kernels.lanes import SUBLANES
from repro.kernels.padding import pad_rows as _pad_features
from repro.kernels.residual_update.residual_update import (
    residual_update as _residual_update_kernel,
)
from repro.obs import phases as obs_phases
from repro.sparse import ops as sparse_ops
from repro.sparse.matrix import SparseBlockMatrix

ExtraFn = Callable[[jax.Array], jax.Array]


def use_interpret(cfg: FWConfig) -> bool:
    """Pallas kernels compile natively on TPU, interpret everywhere else."""
    if cfg.interpret is not None:
        return cfg.interpret
    return devices.pallas_interpret()


def use_sparse_kernel(cfg: FWConfig) -> bool:
    """'sparse' backend: Pallas prefetch kernel on TPU, XLA gather elsewhere
    (the XLA path is the production CPU path, not a test stub)."""
    if cfg.sparse_kernel is not None:
        return cfg.sparse_kernel
    return not devices.pallas_interpret()


def dist_spec(cfg: Optional[FWConfig]):
    """The active DistSpec, or None outside the distributed backend."""
    if cfg is not None and cfg.backend == "distributed":
        if cfg.dist is None:
            raise ValueError(
                "backend='distributed' needs cfg.dist (built by "
                "repro.distributed.driver from the operand's mesh)"
            )
        return cfg.dist
    return None


def mdot(a: jax.Array, b: jax.Array, cfg: Optional[FWConfig] = None) -> jax.Array:
    """Sample-axis dot product, psum-completed over the "data" mesh axis
    when the distributed backend is active. Oracles MUST use this (and
    ``msum``) for any reduction over the m axis so their recursions stay
    correct when the residual/margin is a per-shard slice."""
    d = jnp.dot(a, b)
    spec = dist_spec(cfg)
    return jax.lax.psum(d, spec.data_axis) if spec is not None else d


def msum(x: jax.Array, cfg: Optional[FWConfig] = None) -> jax.Array:
    """Sample-axis sum — the ``mdot`` analogue for elementwise losses."""
    s = jnp.sum(x)
    spec = dist_spec(cfg)
    return jax.lax.psum(s, spec.data_axis) if spec is not None else s


def check_matrix_backend(Xt, cfg: FWConfig) -> None:
    """Trace-time guard: the matrix layout and the backend must agree."""
    if cfg.backend == "distributed":
        raise ValueError(
            "backend='distributed' only runs inside the shard_map built by "
            "repro.distributed.driver (solve / solve_batched / fw_path*); "
            "the single-device entry points cannot place mesh shards"
        )
    is_sparse = isinstance(Xt, SparseBlockMatrix)
    if is_sparse and cfg.backend != "sparse":
        raise ValueError(
            f"Xt is a SparseBlockMatrix but cfg.backend={cfg.backend!r}; "
            "use FWConfig(backend='sparse')"
        )
    if cfg.backend == "sparse" and not is_sparse:
        raise ValueError(
            "cfg.backend='sparse' needs a repro.sparse.SparseBlockMatrix "
            "design matrix (build one with SparseBlockMatrix.from_dense / "
            "from_coo or repro.data.make_sparse_proxy)"
        )


@obs_phases.scoped("fw/init")
def pad_backend_matrix(Xt, cfg: FWConfig):
    """Zero-pad trailing feature rows for the dense kernel grids — once per
    solve, OUTSIDE the hot loop (DESIGN.md §Padding): to whole blocks for
    'block'/'full', to whole 8-row slabs for 'uniform' (the kernels read
    a sampled row through its aligned slab). No-op for the other backends
    ('sparse' pads at construction, 'xla' wraps modulo p)."""
    if cfg.backend != "pallas":
        return Xt
    if cfg.sampling == "uniform":
        return _pad_features(Xt, SUBLANES)
    return _pad_features(Xt, cfg.block_size)


# --------------------------------------------------------------------------
# Sampling-set draws (paper §4.1 / §4.5)
# --------------------------------------------------------------------------


def sample_blocks(
    key: jax.Array, nblocks: int, block_size: int, cfg: FWConfig
) -> jax.Array:
    """THE aligned-block draw every backend shares: kappa//block_size
    blocks without replacement, clamped so the request never exceeds the
    available blocks (choice would otherwise error). Single source of
    the clamp + draw so the index stream cannot drift between the
    single-device and distributed backends (engine contract)."""
    nb = min(max(cfg.kappa // block_size, 1), nblocks)
    return jax.random.choice(key, nblocks, (nb,), replace=False).astype(jnp.int32)


def sample_block_starts(key: jax.Array, p: int, cfg: FWConfig) -> jax.Array:
    """Aligned block starts for 'block' sampling over a dense feature
    axis of true size p (geometry from cfg.block_size)."""
    return sample_blocks(key, -(-p // cfg.block_size), cfg.block_size, cfg)


def sample_indices(key: jax.Array, p: int, cfg: FWConfig) -> jax.Array:
    """Draw the sampling set S (paper §4.1 / §4.5).

    'uniform': kappa i.i.d. uniform draws (with replacement — O(kappa), the
       large-p-friendly reading of the paper's uniform kappa-subsets).
    'block':   kappa/block aligned blocks without replacement (TPU-native).
    'full':    deterministic FW (S = {1..p}).
    """
    if cfg.sampling == "full":
        return jnp.arange(p)
    if cfg.sampling == "uniform":
        return jax.random.randint(key, (cfg.kappa,), 0, p)
    if cfg.sampling == "block":
        starts = sample_block_starts(key, p, cfg)
        idx = starts[:, None] * cfg.block_size + jnp.arange(cfg.block_size)[None, :]
        return idx.reshape(-1) % p  # tail block wraps (documented in DESIGN.md)
    raise ValueError(f"unknown sampling mode {cfg.sampling!r}")


def sample_sparse_blocks(key: jax.Array, mat: SparseBlockMatrix, cfg: FWConfig):
    """Aligned block starts for the sparse backend. Block geometry comes
    from the MATRIX (cfg.block_size is a dense-kernel knob); same shared
    clamp + draw as every other backend."""
    return sample_blocks(key, mat.nblocks, mat.block_size, cfg)


# --------------------------------------------------------------------------
# Backend-dispatched vertex selection
# --------------------------------------------------------------------------


def _xla_vertex(Xt, w, key, p, cfg, extra_fn):
    idx = sample_indices(key, p, cfg)
    rows = jnp.take(Xt, idx, axis=0)  # (|S|, m) contiguous row gather
    raw = -(rows @ w)  # (|S|,) linear scores
    sel = raw if extra_fn is None else raw + extra_fn(idx)
    j = jnp.argmax(jnp.abs(sel))
    return idx[j], raw[j], sel[j], idx.shape[0]


def _kernel_vertex(Xt, w, key, p, cfg, extra_fn):
    """Sampled FW vertex via the Pallas scalar-prefetch gather kernel.

    'block'/'full' drive block_size-wide aligned bricks; 'uniform' scores
    each sampled row through its aligned 8-row slab (same index stream
    and argmax as the XLA gather path). ``Xt`` may carry zero-padded
    trailing rows (indices >= p are masked out of the argmax). Without an
    extra term the fused kernel argmax runs; with one, the per-coordinate
    scores come back and the shift + argmax run in XLA (the kernel
    reduction cannot see the extra term).
    """
    if cfg.sampling == "uniform":
        # same draw as the XLA path: the backends replay one index stream
        idx = sample_indices(key, p, cfg).astype(jnp.int32)
        raw = _row_scores_kernel(
            Xt, w, idx, m_tile=cfg.m_tile, interpret=use_interpret(cfg)
        )
        sel = raw if extra_fn is None else raw + extra_fn(idx)
        j = jnp.argmax(jnp.abs(sel))
        return idx[j], raw[j], sel[j], idx.shape[0]
    if cfg.sampling == "block":
        blk = sample_block_starts(key, p, cfg)
        bs = cfg.block_size
    elif cfg.sampling == "full":
        bs = cfg.block_size
        blk = jnp.arange(-(-p // bs), dtype=jnp.int32)
    else:
        raise ValueError(f"unknown sampling mode {cfg.sampling!r}")
    # dot-product accounting parity with the XLA path: 'full' scores every
    # REAL coordinate once (padded rows are free zeros, not sampled work);
    # 'block' counts nblocks*bs either way (the XLA path's wrapped tail
    # duplicates coords just as the kernel path's tail pads them).
    n_scored = p if cfg.sampling == "full" else blk.shape[0] * bs
    if extra_fn is None:
        i_star, g_star = _fw_vertex_kernel(
            Xt,
            w,
            blk,
            block_size=bs,
            m_tile=cfg.m_tile,
            interpret=use_interpret(cfg),
            p_valid=p,
        )
        return i_star, g_star, g_star, n_scored
    raw = _sampled_scores_kernel(
        Xt, w, blk, block_size=bs, m_tile=cfg.m_tile, interpret=use_interpret(cfg)
    )
    idx = (blk[:, None] * bs + jnp.arange(bs)[None, :]).reshape(-1)
    sel = raw + extra_fn(idx)
    mag = jnp.where(idx < p, jnp.abs(sel), -1.0)
    j = jnp.argmax(mag)
    return idx[j], raw[j], sel[j], n_scored


def _sparse_vertex(mat: SparseBlockMatrix, w, key, cfg, extra_fn):
    """Sampled FW vertex over the block-ELL matrix.

    'block'/'full' drive whole aligned blocks (kernel-dispatchable, the
    tail block is zero-padded at construction — no modulo wrap, so exact
    Lemma 1 uniformity holds for every p); 'uniform' is a width-1 XLA
    gather replaying the exact index stream of the dense XLA path.
    """
    if cfg.sampling == "uniform":
        idx = sample_indices(key, mat.p, cfg)
        i_star, g_raw, g_sel = sparse_ops.sparse_gather_vertex_general(
            mat, w, idx, extra_fn=extra_fn
        )
        return i_star, g_raw, g_sel, idx.shape[0]
    if cfg.sampling == "block":
        blk = sample_sparse_blocks(key, mat, cfg)
        n_scored = blk.shape[0] * mat.block_size
    elif cfg.sampling == "full":
        blk = jnp.arange(mat.nblocks, dtype=jnp.int32)
        n_scored = mat.p
    else:
        raise ValueError(f"unknown sampling mode {cfg.sampling!r}")
    i_star, g_raw, g_sel = sparse_ops.sparse_fw_vertex_general(
        mat,
        w,
        blk,
        use_kernel=use_sparse_kernel(cfg),
        interpret=use_interpret(cfg),
        extra_fn=extra_fn,
    )
    return i_star, g_raw, g_sel, n_scored


def score_indices(
    Xt,
    w: jax.Array,
    idx: jax.Array,
    p: int,
    cfg: FWConfig,
    extra_fn: Optional[ExtraFn] = None,
):
    """Linear scores ``raw_i = -z_i^T w`` at CALLER-CHOSEN global
    coordinates ``idx`` — the re-scoring primitive behind the away-vertex
    argmin (active-set buffer) and the lazy-LMO winner cache (DESIGN.md
    §StepRule). Backend-dispatched like ``sample_vertex`` but with no
    draw and no argmax: the step rule owns the masking/reduction.

    Negative or out-of-range indices are the rules' "empty slot" markers;
    they come back with an arbitrary score and MUST be masked by the
    caller (``idx >= 0 & idx < p``). Returns ``(raw, sel)`` with
    ``sel = raw + extra_fn(idx)`` (same array when ``extra_fn is None``).
    """
    safe = jnp.clip(idx, 0, p - 1).astype(jnp.int32)
    if cfg.backend == "distributed":
        from repro.distributed import backend as dist_backend

        raw = dist_backend.dist_score_indices(Xt, w, safe, cfg)
    elif cfg.backend == "sparse":
        raw = sparse_ops.sparse_gather_scores(Xt, w, safe).astype(Xt.dtype)
    elif cfg.backend == "pallas":
        raw = _row_scores_kernel(
            Xt, w, safe, m_tile=cfg.m_tile, interpret=use_interpret(cfg)
        )
    else:
        rows = jnp.take(Xt, safe, axis=0)  # (|idx|, m) row gather
        raw = -(rows @ w)
    sel = raw if extra_fn is None else raw + extra_fn(safe)
    return raw, sel


def sample_vertex(
    Xt,
    w: jax.Array,
    key: jax.Array,
    p: int,
    cfg: FWConfig,
    extra_fn: Optional[ExtraFn] = None,
):
    """Draw S and return the winning vertex on the configured backend.

    Returns ``(i_star, g_raw, g_sel, n_scored)``: the selected global
    coordinate, its LINEAR score ``-z^T w``, its selected (extra-shifted)
    score, and how many length-m dot products were consumed. With
    ``extra_fn is None`` the two scores are the same array.
    """
    if cfg.backend == "distributed":
        from repro.distributed import backend as dist_backend

        return dist_backend.dist_sample_vertex(Xt, w, key, p, cfg, extra_fn)
    if cfg.backend == "sparse":
        return _sparse_vertex(Xt, w, key, cfg, extra_fn)
    if cfg.backend == "pallas":
        return _kernel_vertex(Xt, w, key, p, cfg, extra_fn)
    return _xla_vertex(Xt, w, key, p, cfg, extra_fn)


# --------------------------------------------------------------------------
# Fused multi-step chunk dispatch (kernels/fused_step, DESIGN.md §Perf)
# --------------------------------------------------------------------------


_warned_unfused_rules: set = set()


def fused_supported(oracle, cfg: FWConfig) -> bool:
    """Trace-time gate for the chunked K-steps-per-dispatch hot loop.

    Fusion needs (a) ``cfg.fuse_steps > 1``, (b) an oracle with a
    closed-form line search exposed through the ``fused_*`` protocol
    (lasso / elastic-net; the logistic bisection falls back to the
    per-step loop), (c) 'uniform' sampling — the K x kappa index stream
    must be pregenerable as a pure function of (key, cfg, p) — (d) a
    single-device backend (the distributed driver forces fuse_steps=1
    for now), and (e) a step rule that composes with the megakernel's
    per-step records (``classic`` only: the other rules' direction
    selection reads live iterate state the chunk cannot pregather, so
    they declare ``fused_ok=False`` and fall back to per-step with a
    one-time warning — explicitly, never silently; DESIGN.md §StepRule).
    """
    base = (
        cfg.fuse_steps > 1
        and cfg.sampling == "uniform"
        and getattr(oracle, "fused_kind", None) is not None
        and cfg.backend != "distributed"
    )
    if not base:
        return False
    if cfg.step_rule != "classic":
        from repro.core import step_rule as step_rule_lib

        rule = step_rule_lib.get_rule(cfg)
        if not rule.fused_ok:
            if cfg.step_rule not in _warned_unfused_rules:
                _warned_unfused_rules.add(cfg.step_rule)
                import warnings

                warnings.warn(
                    f"step_rule={cfg.step_rule!r} does not compose with "
                    f"the fused multi-step chunk (fuse_steps="
                    f"{cfg.fuse_steps}); falling back to per-step "
                    "execution (fuse_steps=1 semantics)",
                    stacklevel=2,
                )
            return False
    return True


def use_fused_kernel(oracle, cfg: FWConfig) -> bool:
    """Chunk executor choice: the Pallas megakernel drives the 'pallas'
    backend and the kernel-dispatched 'sparse' backend; 'xla' and the
    XLA-gather sparse path chunk through a fori_loop over the unfused
    engine step (bit-exact by construction). So does a chunk whose
    scalar-prefetched K x kappa streams would not fit SMEM
    (``fused_step.fits_smem``): the shape, not a fault, picks it."""
    kernel_backend = cfg.backend == "pallas" or (
        cfg.backend == "sparse" and use_sparse_kernel(cfg)
    )
    return kernel_backend and _fused_step.fits_smem(
        cfg.fuse_steps, cfg.kappa, oracle.fused_needs_alpha
    )


def run_fused_kernel(
    oracle, Xt, y, resid, scal, idx, zty_s, zn2_s, alpha_s, k0, delta,
    cfg: FWConfig,
):
    """Invoke the fused megakernel on the configured layout. Returns
    ``(i_star, lam, delta_t, no_progress, resid_out, (S, F, Q))`` — the
    per-step records the engine replays into beta/scale/stopping state."""
    kw = dict(
        oracle=oracle,
        eps_den=cfg.eps_den,
        gap_rtol=cfg.gap_rtol,
        refresh_every=cfg.refresh_every,
        max_iters=cfg.max_iters,
        interpret=use_interpret(cfg),
    )
    if isinstance(Xt, SparseBlockMatrix):
        return _fused_step.sparse_fused_chunk(
            Xt.values, Xt.rows, y, resid, scal, idx, zty_s, zn2_s, alpha_s,
            k0, delta, **kw,
        )
    return _fused_step.dense_fused_chunk(
        Xt, y, resid, scal, idx, zty_s, zn2_s, alpha_s, k0, delta, **kw
    )


# --------------------------------------------------------------------------
# Backend-dispatched O(m) column recursions
# --------------------------------------------------------------------------


def apply_column_update(Xt, v, y_vec, i_star, lam, delta_t, cfg: FWConfig):
    """v <- (1-lam) v + lam (y_vec - delta_t * z_star), backend-dispatched.

    This is eq. 10 with ``v = R, y_vec = y``; with ``v = margin,
    y_vec = 0, delta_t -> -delta_t`` it is the logistic margin recursion
    m <- (1-lam) m + lam delta_t z_star.
    """
    if cfg.backend == "distributed":
        from repro.distributed import backend as dist_backend

        return dist_backend.dist_column_update(
            Xt, v, y_vec, i_star, lam, delta_t, cfg
        )
    if cfg.backend == "sparse":
        col_vals, col_rows = sparse_ops.sparse_column(Xt, i_star)
        return sparse_ops.sparse_residual_update(
            v, y_vec, col_vals, col_rows, lam, delta_t
        )
    z_star = jax.lax.dynamic_slice_in_dim(Xt, i_star, 1, axis=0)[0]
    if cfg.backend == "pallas":
        return _residual_update_kernel(
            v, y_vec, z_star, lam, delta_t,
            m_tile=cfg.m_tile, interpret=use_interpret(cfg),
        )
    return (1.0 - lam) * v + lam * (y_vec - delta_t * z_star)


def column_dense(Xt, i_star, cfg: FWConfig) -> jax.Array:
    """Dense (m,) column z_star — the logistic bisection needs the whole
    direction vector. Sparse backend scatters the ELL slots (O(nnz_max) +
    one O(m) zeros init, amortized against the O(m) bisection probes).
    Distributed: each shard gets its own "data"-slice of the column."""
    if cfg.backend == "distributed":
        from repro.distributed import backend as dist_backend

        return dist_backend.dist_column_dense(Xt, i_star, cfg)
    if cfg.backend == "sparse":
        return sparse_ops.sparse_column_dense(Xt, i_star)
    return jax.lax.dynamic_slice_in_dim(Xt, i_star, 1, axis=0)[0]


def matvec(Xt, beta: jax.Array, cfg: Optional[FWConfig] = None) -> jax.Array:
    """X @ alpha for warm-start initialization, either matrix layout.
    Distributed: the replicated beta hits the local shard and a psum over
    "model" completes the local sample-slice of X alpha."""
    if dist_spec(cfg) is not None:
        from repro.distributed import backend as dist_backend

        return dist_backend.dist_matvec(Xt, beta, cfg)
    if isinstance(Xt, SparseBlockMatrix):
        return sparse_ops.sparse_matvec(Xt, beta)
    return beta @ Xt


def grad_full(Xt, w: jax.Array, cfg: Optional[FWConfig] = None) -> jax.Array:
    """Full LINEAR gradient -X^T w over every feature — the O(nnz)/O(p*m)
    certification pass behind the oracle ``gap()`` protocol, never the hot
    loop. Distributed: local features psum over "data", all_gather over
    "model" — replicated on every shard. May return backend-padded length;
    callers slice [:p]."""
    if dist_spec(cfg) is not None:
        from repro.distributed import backend as dist_backend

        return dist_backend.dist_grad_full(Xt, w, cfg)
    if isinstance(Xt, SparseBlockMatrix):
        return -sparse_ops.sparse_transpose_matvec(Xt, w)
    return -(Xt @ w)
