"""Pallas TPU megakernel: K fused FW iterations per launch (DESIGN.md §Perf).

The randomized FW iteration is tiny — one |S|-column sampled score, an
O(1) closed-form line search (paper eq. 8), an O(m) co-state recursion
(eq. 10) — so at paper scale the unfused hot loop is launch/HBM-bound:
every iteration pays a dispatch plus a round trip of the (m,) co-state
and the scalar recursion state. This kernel executes ``K = fuse_steps``
consecutive iterations in ONE launch, keeping the co-state (residual /
margin) and the scalar recursions (S, F, Q, the alpha-space scale
product, the winner carry) resident in VMEM/SMEM for the whole chunk.

Grid: ``(K, kappa)`` — grid step (s, j) scores the j-th sampled
coordinate of chunk-iteration s. The K x kappa uniform index stream is
pregenerated by the engine (a pure function of ``(key, cfg, p)``) and
scalar-prefetched together with the per-coordinate column statistics
(z^T y, ||z||^2) and — for the elastic-net — the chunk-start alpha
values at the sampled coordinates. The BlockSpec index_map reads the
prefetched stream, so each grid step DMAs the aligned 8-row slab that
holds one sampled column (dense: an (8, m) slab of Xt; sparse: a
(1, 8, nnz_max) values+rows slab of the block-ELL layout) and keeps that
column's row — a single row is no legal block for the chip, whose
block dims must be multiples of (8, 128) or equal to the array's —
while the argmax, line search, and state recursions never leave the
core. The sparse co-state lives in the lane layout of
``kernels/lanes``, which is what makes its gather and scatter lower.

Inside a chunk iteration (j sweeps the sample set, update at the last j):

    sampled-column gather -> score vs co-gradient -> running masked
    argmax (winner column carried in VMEM) -> closed-form line search
    (the oracle's ``fused_line_search``) -> co-state update (eq. 10,
    fused in VMEM) -> S/F(/Q) recursions + the periodic exact S/F
    refresh on the unfused cadence.

CLASSIC STEPS ONLY: the chunk hard-codes the t = -1 FW update, so only
``step_rule='classic'`` composes with it — the away/pairwise/PARTAN/
lazy rules carry direction state the record format cannot replay, and
``vertex.fused_supported`` routes them to the per-step loop with a
one-time warning before this module is ever entered (DESIGN.md
§StepRule fallback matrix).

The kernel emits only the per-step step records (i*, lam, delta-tilde,
scores, the gap-stall flag) and the final co-state: the O(p) coefficient
scatter-adds and the stopping statistics are REPLAYED outside from those
records with exactly the unfused op sequence (``engine._fused_replay``),
which is what makes the fused lasso trajectory bit-identical to the
unfused one. Oracles come in as static hashable objects and contribute
only scalar algebra (``fused_line_search`` / ``fused_scalar_update`` /
``fused_score_shift``) — this module never imports the solver core.

Alpha-space ledger (elastic-net): the +l2*a score shift needs live
per-coordinate alpha values, but beta stays outside the kernel. Since the
FW step in alpha space is alpha <- (1-lam) alpha + lam dt e_i*, the kernel
tracks the running product P = prod(1-lam_t) and a K-slot correction
ledger (i*_t, c_t), with every slot rescaled by (1-lam) per step:
alpha_i at step s = P_s * alpha0_i + sum_{t<s, i*_t = i} c_t. This
reconstruction is mathematically exact but reassociates the unfused
scale*beta products, so fused EN trajectories match to rounding rather
than bitwise (DESIGN.md §Perf).

Memory per core (the fusion contract): the (m,) co-state, targets, and
winner column (3m floats) in VMEM + the K-slot ledger + scalars; beta
(p,) and Xt never reside. The K x |S| index and statistic streams are
scalar-prefetched into SMEM, which is small: ``fits_smem`` bounds them,
and the engine runs the per-step executor where they do not fit.

Iterations past ``max_iters`` inside the trailing chunk are masked in
kernel and replay (max_iters stays exact); the stall/patience rule is
the chunk driver's, checked between launches (overshoot <= K-1,
DESIGN.md §Stopping).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lanes import (
    SUBLANES,
    from_lanes,
    gather_lanes,
    scatter_add_lanes,
    to_lanes,
)
from repro.kernels.padding import pad_rows

# step-record layout: N_REC floats per step in the flat SMEM record output
REC_LAM, REC_DT, REC_RAW, REC_SEL, REC_STALL = 0, 1, 2, 3, 4
N_REC = 8

# Scalar-prefetch bytes the chunk may put in SMEM. v5e's SMEM is 1 MiB,
# and its compiler refuses a chunk whose streams exceed it (K=8 at
# kappa = 1% of e2006-log1p's p); a quarter stays for the kernel's own
# scalars. K=8, kappa=8192 lasso streams fill the budget exactly.
SMEM_PREFETCH_BUDGET = 768 * 1024


def prefetch_bytes(K: int, kappa: int, needs_alpha: bool) -> int:
    """SMEM bytes of the scalar-prefetched streams: the K x kappa index
    stream, z^T y and ||z||^2 at it, and (elastic-net) alpha at it."""
    return 4 * K * kappa * (4 if needs_alpha else 3)


def fits_smem(K: int, kappa: int, needs_alpha: bool) -> bool:
    """Whether a K-step chunk at this kappa fits the SMEM budget."""
    return prefetch_bytes(K, kappa, needs_alpha) <= SMEM_PREFETCH_BUDGET


def _select(vec, pos):
    """``vec[pos]`` of an (n,) vector at a traced position: a masked sum
    of one entry and zeros, so exact."""
    hit = jax.lax.broadcasted_iota(jnp.int32, vec.shape, 0) == pos
    return jnp.sum(jnp.where(hit, vec, jnp.zeros_like(vec)))


def _select_row(tile, pos):
    """Row ``pos`` of an (n, w) tile as (1, w), exactly (masked sum)."""
    hit = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0) == pos
    return jnp.sum(
        jnp.where(hit, tile, jnp.zeros_like(tile)), axis=0, keepdims=True
    )


def _fused_kernel(
    # scalar prefetch (SMEM): flattened (K*kappa,) streams + [k0]
    idx_ref, zty_ref, zn2_ref, asamp_ref, int_ref,
    # SMEM input: [s_quad, f_lin, q_norm, delta]
    scal_ref,
    # VMEM slabs (dense: Xt rows; sparse: values + rows slots), then y, R0
    *refs,
    oracle,
    layout: str,
    slab: int,
    eps_den: float,
    gap_rtol: float,
    refresh_every: int,
    max_iters: int,
):
    if layout == "dense":
        (x_ref, y_ref, r0_ref, stepf_ref, stepi_ref, rout_ref, sout_ref,
         best, ibest, acc, zbuf, ladd, lidx) = refs
    else:
        (vals_ref, rows_ref, y_ref, r0_ref, stepf_ref, stepi_ref, rout_ref,
         sout_ref, best, ibest, acc, zbuf, zrows, ladd, lidx) = refs
    s = pl.program_id(0)
    j = pl.program_id(1)
    n_steps = pl.num_programs(0)
    kappa = pl.num_programs(1)
    flat = s * kappa + j
    my_idx = idx_ref[flat]
    pos = my_idx % slab  # the sampled column's row within its slab

    @pl.when((s == 0) & (j == 0))
    def _init_chunk():
        rout_ref[...] = r0_ref[...]
        acc[0] = scal_ref[0]  # S = ||X alpha||^2
        acc[1] = scal_ref[1]  # F = (X alpha)^T y
        acc[2] = scal_ref[2]  # Q = ||alpha||^2 (elastic-net)
        acc[3] = 1.0  # P = running prod(1 - lam) (alpha space)
        if oracle.fused_needs_alpha:
            ladd[...] = jnp.zeros_like(ladd)
            lidx[...] = jnp.full_like(lidx, -1)

    @pl.when(j == 0)
    def _init_step():
        best[0] = -1.0  # running |selected score| (first-max argmax)

    # ---- score the j-th sampled coordinate against the live residual ----
    if layout == "dense":
        x = x_ref[...].astype(jnp.float32)  # (slab, m)
        # the unfused kernel's slab product, so the score rounds identically
        scores = -jnp.dot(x, rout_ref[0, :], preferred_element_type=jnp.float32)
        col = _select_row(x, pos)  # (1, m)
    else:
        vals = vals_ref[0].astype(jnp.float32)  # (slab, nnz_max)
        rws = rows_ref[0]
        # axis-1 reduce per slot row — the reduction shape of the unfused
        # sparse_gather_scores, so the score rounds identically
        scores = -jnp.sum(vals * gather_lanes(rout_ref, rws), axis=1)
        col = _select_row(vals, pos)  # (1, nnz_max)
        col_rows = _select_row(rws, pos)
    raw = _select(scores, pos)

    if oracle.fused_needs_alpha:
        corr = jnp.sum(jnp.where(lidx[...] == my_idx, ladd[...], 0.0))
        a_i = acc[3] * asamp_ref[flat] + corr
        sel = raw + oracle.fused_score_shift(a_i)
    else:
        a_i = jnp.float32(0.0)
        sel = raw

    better = jnp.abs(sel) > best[0]
    best[0] = jnp.where(better, jnp.abs(sel), best[0])
    best[1] = jnp.where(better, raw, best[1])
    best[2] = jnp.where(better, sel, best[2])
    best[3] = jnp.where(better, zty_ref[flat], best[3])
    best[4] = jnp.where(better, zn2_ref[flat], best[4])
    best[5] = jnp.where(better, a_i, best[5])
    ibest[0] = jnp.where(better, my_idx, ibest[0])
    zbuf[...] = jnp.where(better, col, zbuf[...])
    if layout != "dense":
        zrows[...] = jnp.where(better, col_rows, zrows[...])

    # ---- last sampled coordinate: line search + state recursions --------
    @pl.when(j == kappa - 1)
    def _advance():
        k_glob = int_ref[0] + s
        g_raw, g_sel, a_star = best[1], best[2], best[5]
        delta = scal_ref[3]
        delta_t = -delta * jnp.sign(g_sel)
        scal3 = (acc[0], acc[1], acc[2])
        lam, no_progress, g_lin = oracle.fused_line_search(
            scal3, g_raw, g_sel, a_star, delta_t, best[3], best[4],
            eps_den, gap_rtol,
        )
        rec = s * N_REC
        stepf_ref[rec + REC_LAM] = lam
        stepf_ref[rec + REC_DT] = delta_t
        stepf_ref[rec + REC_RAW] = g_raw
        stepf_ref[rec + REC_SEL] = g_sel
        stepf_ref[rec + REC_STALL] = jnp.where(no_progress, 1.0, 0.0)
        stepi_ref[s] = ibest[0]

        @pl.when(k_glob < max_iters)
        def _apply():
            one_m = 1.0 - lam
            r = rout_ref[...]
            yv = y_ref[...]
            # eq. 10 co-state recursion, fused in VMEM (replays the
            # backend residual-update op sequence exactly)
            if layout == "dense":
                rout_ref[...] = (1.0 - lam) * r + lam * (yv - delta_t * zbuf[...])
            else:
                rout_ref[...] = (1.0 - lam) * r + lam * yv
                scatter_add_lanes(rout_ref, zrows[...], (-lam * delta_t) * zbuf[...])
            ns, nf, nq = oracle.fused_scalar_update(
                scal3, g_lin, a_star, lam, delta_t, best[3], best[4]
            )
            # periodic exact S/F refresh on the unfused cadence; the EN
            # Q refresh needs beta and is reconciled by the chunk driver
            refresh = (k_glob % refresh_every) == (refresh_every - 1)
            v = yv - rout_ref[...]
            if layout == "dense":
                # (1, m) @ (m,): a product the chip lowers, unlike 1-D dot
                vv = jnp.dot(v, v[0], preferred_element_type=jnp.float32)[0]
                vy = jnp.dot(v, yv[0], preferred_element_type=jnp.float32)[0]
            else:
                vv = jnp.sum(v * v)
                vy = jnp.sum(v * yv)
            acc[0] = jnp.where(refresh, vv, ns)
            acc[1] = jnp.where(refresh, vy, nf)
            acc[2] = nq
            if oracle.fused_needs_alpha:
                acc[3] = acc[3] * one_m
                lane = jax.lax.broadcasted_iota(jnp.int32, ladd.shape, 1)
                ladd[...] = jnp.where(lane == s, lam * delta_t, ladd[...] * one_m)
                lidx[...] = jnp.where(lane == s, ibest[0], lidx[...])

        @pl.when(s == n_steps - 1)
        def _emit_final():
            sout_ref[0] = acc[0]
            sout_ref[1] = acc[1]
            sout_ref[2] = acc[2]


def _call(kernel, grid_spec, K, res_shape, interpret, operands):
    out_shape = (
        jax.ShapeDtypeStruct((K * N_REC,), jnp.float32),  # per-step records
        jax.ShapeDtypeStruct((K,), jnp.int32),  # i_star
        jax.ShapeDtypeStruct(res_shape, jnp.float32),  # final residual
        jax.ShapeDtypeStruct((N_REC,), jnp.float32),  # final (S, F, Q)
    )
    stepf, stepi, rout, sout = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="fw_fused_step",
    )(*operands)
    stepf = stepf.reshape(K, N_REC)
    return (
        stepi,
        stepf[:, REC_LAM],
        stepf[:, REC_DT],
        stepf[:, REC_STALL] > 0.5,
        rout,
        (sout[0], sout[1], sout[2]),
    )


def _common(scal, delta, k0):
    scal_in = jnp.stack(
        [
            scal[0].astype(jnp.float32),
            scal[1].astype(jnp.float32),
            scal[2].astype(jnp.float32),
            delta.astype(jnp.float32),
        ]
    )
    ints = jnp.asarray(k0, jnp.int32).reshape(1)
    return scal_in, ints


def _out_specs(res_shape):
    """Records and final scalars in SMEM (scalar stores at step s); the
    residual resident in VMEM across the whole grid."""
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return [smem, smem, pl.BlockSpec(res_shape, lambda s, j, *_: (0, 0)), smem]


def _scratch(col_shape, K, sparse):
    """best/ibest/acc scalars + winner column (+ its rows, sparse) + the
    EN alpha-ledger."""
    shapes = [
        pltpu.SMEM((8,), jnp.float32),  # winner carry: mag/raw/sel/zty/zn2/a
        pltpu.SMEM((1,), jnp.int32),  # winner index
        pltpu.SMEM((8,), jnp.float32),  # S, F, Q, P accumulators
        pltpu.VMEM(col_shape, jnp.float32),  # winner column / values
    ]
    if sparse:
        shapes.append(pltpu.VMEM(col_shape, jnp.int32))  # winner rows
    shapes += [
        pltpu.VMEM((1, K), jnp.float32),  # ledger: correction values
        pltpu.VMEM((1, K), jnp.int32),  # ledger: touched coordinates
    ]
    return shapes


def _streams(idx, zty_s, zn2_s, alpha_s, ints):
    if alpha_s is None:
        alpha_s = jnp.zeros((1,), jnp.float32)
    return (idx.reshape(-1), zty_s.reshape(-1), zn2_s.reshape(-1),
            alpha_s.reshape(-1), ints)


def dense_fused_chunk(
    Xt: jax.Array,  # (p, m) feature-major design matrix
    y: jax.Array,  # (m,)
    resid: jax.Array,  # (m,) chunk-start co-state
    scal,  # (S, F, Q) chunk-start scalar recursions
    idx: jax.Array,  # (K, kappa) int32 pregenerated uniform index stream
    zty_s: jax.Array,  # (K, kappa) f32 pregathered z^T y at idx
    zn2_s: jax.Array,  # (K, kappa) f32 pregathered ||z||^2 at idx
    alpha_s,  # (K, kappa) f32 chunk-start alpha at idx, or None
    k0,  # () int32 global iteration counter at chunk start
    delta,  # () traced l1 radius
    *,
    oracle,
    eps_den: float,
    gap_rtol: float,
    refresh_every: int,
    max_iters: int,
    interpret: bool = False,
):
    """K fused FW steps over a dense feature-major matrix. Returns
    ``(i_star (K,), lam (K,), delta_t (K,), no_progress (K,),
    resid_out (m,), (S, F, Q))`` for the engine's replay."""
    K, kappa = idx.shape
    m = y.shape[0]
    scal_in, ints = _common(scal, delta, k0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(K, kappa),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # scal_in
            pl.BlockSpec(
                (SUBLANES, m),
                lambda s, j, idx, *_: (idx[s * kappa + j] // SUBLANES, 0),
            ),
            pl.BlockSpec((1, m), lambda s, j, *_: (0, 0)),  # y
            pl.BlockSpec((1, m), lambda s, j, *_: (0, 0)),  # resid_in
        ],
        out_specs=_out_specs((1, m)),
        scratch_shapes=_scratch((1, m), K, sparse=False),
    )
    kernel = functools.partial(
        _fused_kernel,
        oracle=oracle,
        layout="dense",
        slab=SUBLANES,
        eps_den=eps_den,
        gap_rtol=gap_rtol,
        refresh_every=refresh_every,
        max_iters=max_iters,
    )
    operands = _streams(idx, zty_s, zn2_s, alpha_s, ints) + (
        scal_in,
        pad_rows(Xt.astype(jnp.float32), SUBLANES),
        y.astype(jnp.float32).reshape(1, m),
        resid.astype(jnp.float32).reshape(1, m),
    )
    out = _call(kernel, grid_spec, K, (1, m), interpret, operands)
    return out[:4] + (out[4][0],) + out[5:]


def sparse_fused_chunk(
    values: jax.Array,  # (nblocks, block_size, nnz_max)
    rows: jax.Array,  # (nblocks, block_size, nnz_max) int32
    y: jax.Array,
    resid: jax.Array,
    scal,
    idx: jax.Array,  # (K, kappa) int32 — global feature ids < p
    zty_s: jax.Array,
    zn2_s: jax.Array,
    alpha_s,
    k0,
    delta,
    *,
    oracle,
    eps_den: float,
    gap_rtol: float,
    refresh_every: int,
    max_iters: int,
    interpret: bool = False,
):
    """K fused FW steps over the block-ELL sparse layout: grid step (s, j)
    DMAs the (1, 8, nnz_max) values+rows slab holding the sampled feature
    (the whole block when block_size is no multiple of 8), and the
    winner's slots are scatter-added into the lane-layout VMEM residual.
    Same return contract as the dense twin."""
    K, kappa = idx.shape
    m = y.shape[0]
    bs = values.shape[1]
    nnz_max = values.shape[2]
    slab = SUBLANES if bs % SUBLANES == 0 else bs
    scal_in, ints = _common(scal, delta, k0)
    y2d = to_lanes(y)

    def _slab(s, j, idx, *_):
        i = idx[s * kappa + j]
        return (i // bs, (i % bs) // slab, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(K, kappa),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # scal_in
            pl.BlockSpec((1, slab, nnz_max), _slab),  # values slab
            pl.BlockSpec((1, slab, nnz_max), _slab),  # row-index slab
            pl.BlockSpec(y2d.shape, lambda s, j, *_: (0, 0)),  # y
            pl.BlockSpec(y2d.shape, lambda s, j, *_: (0, 0)),  # resid_in
        ],
        out_specs=_out_specs(y2d.shape),
        scratch_shapes=_scratch((1, nnz_max), K, sparse=True),
    )
    kernel = functools.partial(
        _fused_kernel,
        oracle=oracle,
        layout="sparse",
        slab=slab,
        eps_den=eps_den,
        gap_rtol=gap_rtol,
        refresh_every=refresh_every,
        max_iters=max_iters,
    )
    operands = _streams(idx, zty_s, zn2_s, alpha_s, ints) + (
        scal_in, values, rows, y2d, to_lanes(resid),
    )
    out = _call(kernel, grid_spec, K, y2d.shape, interpret, operands)
    return out[:4] + (from_lanes(out[4], m),) + out[5:]
