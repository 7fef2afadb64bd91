"""Backend-dispatched stochastic Frank-Wolfe engine (DESIGN.md §Engine).

ONE hot loop serves the whole solver family (lasso / logistic /
elastic-net) on all three backends ('xla' | 'pallas' | 'sparse'). The
paper presents the extensions as "easily obtained" from Algorithm 2 —
the randomized linear-minimization oracle and the O(m) state recursions
are identical; only the gradient-with-respect-to-state and the line
search change — and the engine encodes exactly that split:

* the ENGINE owns the iteration skeleton: PRNG stream, sampled-vertex
  selection (delegated to ``core.vertex``), the scaled-iterate
  beta/scale update with underflow renormalization, the
  ||alpha^{k+1}-alpha^k||_inf stopping statistic with patience, and the
  while_loop / scan / batched-lane drivers;
* a PROBLEM ORACLE supplies the objective-specific pieces through a
  small protocol (see below). Oracles are hashable frozen dataclasses,
  passed statically into the jitted entry points, so each
  (oracle, cfg) pair compiles exactly once and a traced ``delta``
  serves a whole regularization path per compile.

Oracle protocol — what a new objective must provide:

    needs_stats: bool          class attr; True to precompute ColStats
    extra_dots: int            per-step dot-product surcharge (accounting)
    init_co(y, v, beta, dtype, cfg)
                               co-state from X@alpha0 (``v``; None = cold)
    cograd(co, y) -> (m,)      w with sampled linear scores = -z_i^T w
    score_extra(beta, scale)   optional per-coordinate score shift
                               (idx-array -> addend), e.g. EN's +l2*a_i
    line_search(...)           -> (lam, no_progress, aux); ``no_progress``
                               feeds the stall counter (gap_rtol rule),
                               ``aux`` is forwarded to update_co
    update_co(...) -> co       the O(m)/O(1) state recursions + refresh
    objective(y, stats, co, cfg)
                               final objective value
    gap(Xt, y, alpha, delta, cfg)
                               certified FW duality gap at ``alpha``
                               (alpha^T grad + delta*||grad||_inf with the
                               oracle's OWN gradient — one full O(nnz)
                               pass; delegates to ``oracle_gap`` below)

``cfg`` reaches every reduction over the sample axis so one oracle
definition serves the single-device backends AND the mesh-sharded
'distributed' backend (repro.distributed): oracles touch the m axis only
through ``vertex.mdot`` / ``vertex.msum``, which psum over the "data"
mesh axis exactly when cfg says the distributed backend is active.

What the engine guarantees to oracles: the index stream is a pure
function of (key, cfg, p) shared by every backend ('uniform' replays
bit-identically across backends); padded coordinates (dense-kernel tail
rows, sparse tail features, padded ELL slots) score exactly zero and are
masked out of the argmax, so ``i_star < p`` always; ``beta``, ``stats``
and results stay at the true p regardless of backend padding.

``FWConfig.fuse_steps = K > 1`` turns both loop drivers into CHUNKED
drivers (DESIGN.md §Perf/§Stopping): each while_loop turn advances K
iterations in one dispatch — through the ``kernels/fused_step`` Pallas
megakernel (co-state and scalar recursions VMEM-resident across all K
steps) on the kernel backends, or a fori_loop over the unfused ``step``
elsewhere — and the stall/patience stopping rule is checked between
chunks (overshoot <= K-1; max_iters stays exact via in-chunk masking).
The megakernel emits per-step records that ``_fused_replay`` turns into
the O(p) coefficient updates with the unfused op sequence, keeping the
fused uniform-lasso trajectory bit-identical to fuse_steps=1.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import vertex
from repro.core.solver_config import FWConfig
from repro.obs import metrics as obs_metrics
from repro.obs import telemetry as obs_telemetry
from repro.resilience import validate as _validate
from repro.kernels.colstats.colstats import colstats as _colstats_kernel
from repro.sparse import ops as sparse_ops
from repro.sparse.matrix import SparseBlockMatrix


class ColStats(NamedTuple):
    """Per-column statistics precomputed once before the iterations (§4.2)."""

    zty: jax.Array  # (p,)  z_i^T y
    znorm2: jax.Array  # (p,)  ||z_i||^2
    yty: jax.Array  # ()    y^T y


class EngineState(NamedTuple):
    """Loop state shared by every oracle. ``alpha = scale * beta``; ``co``
    is the oracle's co-state pytree (residual/margin + scalar recursions)."""

    beta: jax.Array  # (p,) unscaled coefficients
    scale: jax.Array  # ()  multiplicative scale
    co: Any  # oracle co-state (NamedTuple pytree)
    maxabs: jax.Array  # ()  running upper bound on ||alpha||_inf
    step_inf: jax.Array  # ()  ||alpha^{k+1} - alpha^k||_inf (bound)
    stall: jax.Array  # ()  consecutive sub-tolerance steps
    n_dots: jax.Array  # ()  length-m dot products consumed so far
    k: jax.Array  # ()  iteration counter
    key: jax.Array  # PRNG key
    # step-rule-owned pytree slot (DESIGN.md §StepRule): () for classic,
    # the active-set buffer for away/pairwise, (alpha_prev, X alpha_prev)
    # for partan, (winner cache, phi) for lazy
    rule: Any = ()
    # telemetry ring (DESIGN.md §Observability): () when
    # cfg.telemetry is None — a leafless pytree, so the default loop
    # carry (and jaxpr) is unchanged — else an obs.TelemetryRing filled
    # per iteration by the step / step rules / fused replay
    tel: Any = ()


class SolveResult(NamedTuple):
    alpha: jax.Array
    objective: jax.Array
    iterations: jax.Array
    n_dots: jax.Array
    active: jax.Array  # () number of nonzero coefficients
    converged: jax.Array
    # certified FW duality gap at alpha (cfg.report_gap; None otherwise)
    gap: Optional[jax.Array] = None
    # iterations actually advanced per dispatch: cfg.fuse_steps when the
    # fused chunk engaged, else 1 (the distributed driver forces 1, and
    # non-classic step rules / non-fusable oracles fall back) — callers
    # can tell what actually ran without re-deriving the gating
    effective_fuse_steps: Optional[jax.Array] = None
    # the final telemetry ring when cfg.telemetry is set (None otherwise);
    # lane-axis-batched from solve_batched. Decode on the host with
    # obs.telemetry.ring_to_records
    telemetry: Optional[Any] = None


def precompute_colstats(
    Xt, y: jax.Array, cfg: Optional[FWConfig] = None
) -> ColStats:
    """One full pass over X: z_i^T y and ||z_i||^2 for every column (§4.2).

    With ``cfg.backend == 'pallas'`` the fused single-sweep kernel
    (repro.kernels.colstats) computes both statistics in one HBM pass.
    A SparseBlockMatrix sweeps its stored slots only — O(nnz), not
    O(p*m) — through the fused ``kernels/sparse_colstats`` Pallas twin
    when the sparse-kernel dispatch is on (TPU auto / forced by cfg).
    """
    if isinstance(Xt, SparseBlockMatrix):
        if cfg is not None:
            zty, znorm2 = sparse_ops.sparse_colstats(
                Xt,
                y,
                use_kernel=vertex.use_sparse_kernel(cfg),
                interpret=vertex.use_interpret(cfg),
            )
        else:
            zty, znorm2 = sparse_ops.sparse_colstats(Xt, y)
        return ColStats(zty=zty, znorm2=znorm2, yty=jnp.dot(y, y))
    if cfg is not None and cfg.backend == "pallas":
        zty, znorm2 = _colstats_kernel(
            Xt, y, m_tile=cfg.m_tile, interpret=vertex.use_interpret(cfg)
        )
    else:
        zty = Xt @ y
        # fused row-norm contraction: XLA lowers the einsum to a reduce
        # without materializing the O(p*m) squared temporary that
        # ``jnp.sum(Xt * Xt, axis=1)`` pays on the non-pallas path
        znorm2 = jnp.einsum("pm,pm->p", Xt, Xt)
    return ColStats(zty=zty, znorm2=znorm2, yty=jnp.dot(y, y))


def _patience(cfg: FWConfig) -> int:
    return cfg.patience if cfg.sampling != "full" else 1


def dot_dtype():
    """Accounting dtype of the ``n_dots`` counter. int32 overflows at the
    paper's scale (p = 4M with ``sampling='full'`` wraps after ~500
    iterations), so the counter is widened: exact int64 when the host
    enables x64, float32 otherwise — overflow-free and monotone, exact up
    to 2^24 and magnitude-correct beyond (JAX silently demotes 64-bit
    dtypes without the x64 flag, so requesting int64 unconditionally
    would quietly hand back the int32 this replaces)."""
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.float32


def init_state(oracle, Xt, y, key, alpha0=None, cfg=None, p=None) -> EngineState:
    """Start from the null solution, or warm-start from ``alpha0``.

    ``p`` overrides the feature count read off ``Xt`` — the distributed
    driver passes the GLOBAL p while ``Xt`` is a local shard; ``cfg``
    reaches the warm-start matvec and the oracle co-state init so their
    sample-axis reductions complete across the mesh.
    """
    p = Xt.shape[0] if p is None else p
    dtype = Xt.dtype
    if alpha0 is None:
        beta = jnp.zeros((p,), dtype)
        v = None
        maxabs = jnp.zeros((), dtype)
    else:
        beta = alpha0.astype(dtype)
        v = vertex.matvec(Xt, beta, cfg)  # X alpha, O(nnz) sparse
        maxabs = jnp.max(jnp.abs(beta))
    co = oracle.init_co(y, v, beta, dtype, cfg)
    rule_state: Any = ()
    if cfg is not None and cfg.step_rule != "classic":
        # lazy import: the rules layer on top of the engine (§StepRule)
        from repro.core import step_rule as step_rule_lib

        rule_state = step_rule_lib.get_rule(cfg).init_state(
            oracle, cfg, beta, co, y
        )
    tel: Any = ()
    if cfg is not None and cfg.telemetry is not None:
        tel = obs_telemetry.init_ring(cfg.telemetry)
    return EngineState(
        beta=beta,
        scale=jnp.ones((), dtype),
        co=co,
        maxabs=maxabs,
        step_inf=jnp.full((), jnp.inf, dtype),
        stall=jnp.zeros((), jnp.int32),
        n_dots=jnp.zeros((), dot_dtype()),
        k=jnp.zeros((), jnp.int32),
        key=key,
        rule=rule_state,
        tel=tel,
    )


def apply_coeff_update(beta, scale, maxabs, stall, a_star, i_star, lam,
                       delta_t, no_progress, cfg: FWConfig):
    """Steps 5 + stopping statistics of the FW iteration: the scaled-
    iterate coefficient update with underflow renorm, and the
    ||alpha^{k+1}-alpha^k||_inf bound / stall bookkeeping (§Stopping).

    ONE definition shared by the unfused ``step`` and the fused chunk's
    ``_fused_replay`` — the fused bit-identity contract (DESIGN.md
    §Perf) depends on the two paths executing this exact op sequence.
    Returns ``(beta, scale, maxabs, step_inf, stall)``.
    """
    one_m = 1.0 - lam
    new_scale = scale * one_m
    # renormalize when the scale underflows (rare O(p) event)
    need_renorm = new_scale < cfg.renorm_threshold
    beta, scale = jax.lax.cond(
        need_renorm,
        lambda b, s: (b * s, jnp.ones((), b.dtype)),
        lambda b, s: (b, s),
        beta,
        new_scale,
    )
    beta = beta.at[i_star].add(delta_t * lam / jnp.maximum(scale, cfg.eps_den))
    # stopping statistic: ||alpha_{k+1} - alpha_k||_inf upper bound
    alpha_istar_new = scale * beta[i_star]
    step_inf = lam * jnp.maximum(maxabs, jnp.abs(delta_t - a_star))
    maxabs = jnp.maximum(one_m * maxabs, jnp.abs(alpha_istar_new))
    stall = jnp.where((step_inf <= cfg.tol) | no_progress, stall + 1, 0)
    return beta, scale, maxabs, step_inf, stall


def step(oracle, Xt, y, stats, state: EngineState, cfg: FWConfig, delta) -> EngineState:
    """One randomized Frank-Wolfe step (paper Algorithm 2, any oracle).

    ``delta`` may be a traced array: the l1 radius enters the math only
    through scalar formulas, so keeping it dynamic lets a whole
    regularization path reuse ONE compiled solver (§Perf). ``Xt`` may be
    feature-padded (``vertex.pad_backend_matrix``); ``beta`` and
    ``stats`` stay at the true p.
    """
    p = state.beta.shape[0]
    key, sub = jax.random.split(state.key)

    # -- step 2: score the sampled coordinates against the co-gradient ------
    w = oracle.cograd(state.co, y)
    extra_fn = oracle.score_extra(state.beta, state.scale)
    i_star, g_raw, g_sel, n_scored = vertex.sample_vertex(
        Xt, w, sub, p, cfg, extra_fn
    )

    # -- step 3: FW vertex sign (eq. 6) -------------------------------------
    delta_t = -delta * jnp.sign(g_sel)  # delta-tilde

    # -- step 4: oracle line search (closed-form eq. 8, or bisection) -------
    a_star = state.scale * state.beta[i_star]
    lam, no_progress, aux = oracle.line_search(
        Xt, y, stats, state.co, i_star, g_raw, g_sel, a_star, delta_t, cfg
    )

    # -- step 5 + §Stopping statistics (shared with the fused replay) ------
    beta, scale, maxabs, step_inf, stall = apply_coeff_update(
        state.beta, state.scale, state.maxabs, state.stall, a_star, i_star,
        lam, delta_t, no_progress, cfg,
    )

    # -- step 6: oracle state recursions (eq. 10 / margin + S/F/Q + refresh)
    co = oracle.update_co(
        Xt, y, stats, state.co, beta, scale, i_star, a_star, lam, delta_t,
        state.k, cfg, aux,
    )

    n_dots = state.n_dots + n_scored + oracle.extra_dots
    tel = state.tel
    if cfg is not None and cfg.telemetry is not None:
        # sampled FW duality gap -<grad, delta_t e_i - alpha> =
        # <grad, alpha> - delta_t * sel_i — for the closed-form oracles
        # this IS the line-search numerator (O(1) scalars; logistic pays
        # one O(m) reduction per recorded objective)
        if cfg.telemetry.record_objective:
            gap = (
                oracle.grad_dot_alpha(
                    state.co, stats, y, state.beta, state.scale, cfg
                )
                - delta_t * g_sel
            )
            objective = oracle.objective(y, stats, co, cfg)
        else:
            gap = objective = jnp.nan
        tel = obs_telemetry.record(
            tel, k=state.k, i_star=i_star, event=obs_telemetry.EVENT_FW,
            lam=lam, gap=gap, objective=objective, step_inf=step_inf,
            stall=stall, n_dots=n_dots,
        )

    return EngineState(
        beta=beta,
        scale=scale,
        co=co,
        maxabs=maxabs,
        step_inf=step_inf,
        stall=stall,
        n_dots=n_dots,
        k=state.k + 1,
        key=key,
        rule=state.rule,
        tel=tel,
    )


def rule_step(oracle, Xt, y, stats, state: EngineState, cfg: FWConfig,
              delta) -> EngineState:
    """One iteration under the configured step rule (DESIGN.md §StepRule).

    ``classic`` IS ``step`` — same function, same jaxpr, so the default
    trajectory stays bit-identical to the pre-rule engine. The other
    rules dispatch through ``core.step_rule`` (lazy import: the rules
    layer on top of the engine and would otherwise cycle)."""
    if cfg is None or cfg.step_rule == "classic":
        return step(oracle, Xt, y, stats, state, cfg, delta)
    from repro.core import step_rule as step_rule_lib

    return step_rule_lib.get_rule(cfg).step(
        oracle, Xt, y, stats, state, cfg, delta
    )


# --------------------------------------------------------------------------
# Fused multi-step chunks (FWConfig.fuse_steps > 1, DESIGN.md §Perf)
# --------------------------------------------------------------------------


def _fused_streams(oracle, stats, state: EngineState, cfg: FWConfig, p: int):
    """Pregenerate the chunk's K x kappa uniform index stream — replaying
    the unfused per-step (split, randint) chain exactly, so the stream
    stays the same pure function of (key, cfg, p) on every path — plus
    the pregathered per-coordinate column statistics and (for oracles
    whose line search needs live alpha values) the chunk-start alpha at
    the sampled coordinates."""

    def draw(key, _):
        key, sub = jax.random.split(key)
        return key, jax.random.randint(sub, (cfg.kappa,), 0, p)

    key_new, idx = jax.lax.scan(draw, state.key, None, length=cfg.fuse_steps)
    zty_s = jnp.take(stats.zty, idx).astype(jnp.float32)
    zn2_s = jnp.take(stats.znorm2, idx).astype(jnp.float32)
    alpha_s = None
    if oracle.fused_needs_alpha:
        alpha_s = (state.scale * jnp.take(state.beta, idx)).astype(jnp.float32)
    return key_new, idx, zty_s, zn2_s, alpha_s


def _fused_replay(oracle, state: EngineState, cfg: FWConfig, i_stars, lams,
                  delta_ts, no_progs):
    """Replay the kernel's per-step records into the O(p) coefficient
    updates and the stopping statistics — through the SAME
    ``apply_coeff_update`` the unfused step runs, which is what keeps
    the fused lasso trajectory bit-identical to fuse_steps=1. Steps at
    k >= max_iters are skipped (max_iters never overshoots).

    With telemetry on, the replay is also where the megakernel's
    per-step records are plumbed into the ring (one record per live
    step; objective/gap are NaN here — the kernel emits no per-step
    objective, which is why ``record_objective`` routes the chunk to the
    fori-of-step executor instead)."""
    telemetry_on = cfg.telemetry is not None
    per_step_dots = cfg.kappa + oracle.extra_dots

    def apply(c, t):
        beta, scale, maxabs, step_inf, stall, k, tel = c
        i_star, lam, delta_t = i_stars[t], lams[t], delta_ts[t]
        a_star = scale * beta[i_star]
        beta, scale, maxabs, step_inf, stall = apply_coeff_update(
            beta, scale, maxabs, stall, a_star, i_star, lam, delta_t,
            no_progs[t], cfg,
        )
        if telemetry_on:
            tel = obs_telemetry.record(
                tel, k=k, i_star=i_star, event=obs_telemetry.EVENT_FW,
                lam=lam, gap=jnp.nan, objective=jnp.nan, step_inf=step_inf,
                stall=stall,
                n_dots=state.n_dots + (k + 1 - state.k) * per_step_dots,
            )
        return beta, scale, maxabs, step_inf, stall, k + 1, tel

    def body(t, c):
        return jax.lax.cond(c[5] < cfg.max_iters, lambda: apply(c, t), lambda: c)

    init = (state.beta, state.scale, state.maxabs, state.step_inf,
            state.stall, state.k, state.tel)
    return jax.lax.fori_loop(0, cfg.fuse_steps, body, init)


def _fused_kernel_chunk(oracle, Xt_run, y, stats, state: EngineState,
                        cfg: FWConfig, delta) -> EngineState:
    """One K-step chunk through the ``kernels/fused_step`` megakernel:
    pregenerate/pregather the streams, run the K fused iterations with
    the co-state VMEM-resident, then replay the emitted step records
    into the coefficient/stopping state."""
    p = state.beta.shape[0]
    key_new, idx, zty_s, zn2_s, alpha_s = _fused_streams(
        oracle, stats, state, cfg, p
    )
    resid0, scal0 = oracle.fused_pack_co(state.co)
    i_stars, lams, delta_ts, no_progs, resid_out, scal_out = (
        vertex.run_fused_kernel(
            oracle, Xt_run, y, resid0, scal0, idx, zty_s, zn2_s, alpha_s,
            state.k, delta, cfg,
        )
    )
    beta, scale, maxabs, step_inf, stall, k_new, tel = _fused_replay(
        oracle, state, cfg, i_stars, lams, delta_ts, no_progs
    )
    co = oracle.fused_unpack_co(resid_out.astype(resid0.dtype), scal_out)
    if oracle.fused_needs_alpha:
        # the in-kernel Q recursion has no beta for the periodic exact
        # refresh; reconcile it at chunk granularity when the chunk
        # crossed a refresh boundary (drift window <= refresh_every + K)
        steps = state.k + jnp.arange(cfg.fuse_steps)
        hit = jnp.any(
            ((steps % cfg.refresh_every) == cfg.refresh_every - 1)
            & (steps < cfg.max_iters)
        )
        q_exact = jnp.dot(beta, beta) * scale**2
        co = co._replace(
            q_norm=jnp.where(hit, q_exact, co.q_norm).astype(co.q_norm.dtype)
        )
    n_active = k_new - state.k
    n_dots = state.n_dots + (
        n_active * (cfg.kappa + oracle.extra_dots)
    ).astype(state.n_dots.dtype)
    return EngineState(
        beta=beta,
        scale=scale,
        co=co,
        maxabs=maxabs,
        step_inf=step_inf,
        stall=stall,
        n_dots=n_dots,
        k=k_new,
        key=key_new,
        rule=state.rule,
        tel=tel,
    )


def _fused_ref_chunk(oracle, Xt_run, y, stats, state: EngineState,
                     cfg: FWConfig, delta) -> EngineState:
    """The non-kernel chunk executor: K unfused engine steps under one
    fori_loop — bit-exact vs fuse_steps=1 by construction. Steps past
    max_iters are skipped; the §Stopping check is the caller's (between
    chunks)."""

    def body(t, s):
        return jax.lax.cond(
            s.k < cfg.max_iters,
            lambda st: step(oracle, Xt_run, y, stats, st, cfg, delta),
            lambda st: st,
            s,
        )

    return jax.lax.fori_loop(0, cfg.fuse_steps, body, state)


def fused_chunk(oracle, Xt_run, y, stats, state: EngineState, cfg: FWConfig,
                delta) -> EngineState:
    """Advance K = cfg.fuse_steps iterations in one dispatch (megakernel
    on the kernel backends, fori_loop of ``step`` elsewhere).

    ``telemetry.record_objective`` routes kernel backends to the
    fori-of-step executor too: the megakernel's per-step records carry
    (i_star, lam, stall) but no objective/gap scalars, and the ref
    executor is bit-identical by construction — chunked dispatch (and
    its K-fold stopping-check savings) is preserved either way."""
    needs_per_step = cfg.telemetry is not None and cfg.telemetry.record_objective
    if vertex.use_fused_kernel(oracle, cfg) and not needs_per_step:
        return _fused_kernel_chunk(oracle, Xt_run, y, stats, state, cfg, delta)
    return _fused_ref_chunk(oracle, Xt_run, y, stats, state, cfg, delta)


def certified_gap(oracle, Xt, y, co, beta, scale, delta, cfg=None) -> jax.Array:
    """Exact FW duality gap g(alpha) = alpha^T grad + delta*||grad||_inf
    from a live co-state — one full-gradient pass (O(nnz) sparse,
    O(p*m) dense), certification only, never the hot loop.

    Oracle-generic: the gradient is the linear part -X^T w (w = the
    oracle's co-gradient) plus its ``score_extra`` shift over every
    coordinate (the elastic-net's +l2*alpha). Under the distributed
    backend the gradient assembles via psum/all_gather and the returned
    scalar is replicated on every shard.
    """
    p = beta.shape[0]
    w = oracle.cograd(co, y)
    grad = vertex.grad_full(Xt, w, cfg)[:p]  # Xt may be backend-padded
    extra_fn = oracle.score_extra(beta, scale)
    if extra_fn is not None:
        grad = grad + extra_fn(jnp.arange(p))
    alpha = scale * beta
    return jnp.dot(alpha, grad) + delta * jnp.max(jnp.abs(grad))


def oracle_gap(oracle, Xt, y, alpha, delta, cfg=None) -> jax.Array:
    """Certified duality gap at a bare coefficient vector: rebuild the
    oracle co-state from X alpha, then ``certified_gap``. This is the
    shared implementation behind every oracle's ``gap()`` protocol
    method (replaces the lasso-only ``duality_gap`` special case)."""
    v = vertex.matvec(Xt, alpha, cfg)
    co = oracle.init_co(y, v, alpha, alpha.dtype, cfg)
    return certified_gap(
        oracle, Xt, y, co, alpha, jnp.ones((), alpha.dtype), delta, cfg
    )


def run_loop(oracle, Xt_run, y, stats, state0, cfg, delta, patience):
    """The sequential while_loop shared by ``solve`` and the distributed
    driver: step until the §Stopping rule fires or max_iters.

    With ``cfg.fuse_steps = K > 1`` (and a fusable oracle/sampling mode,
    ``vertex.fused_supported``) each loop turn advances a K-step fused
    chunk and the stall/patience rule is only checked BETWEEN chunks, so
    convergence stops may overshoot by at most K-1 iterations (max_iters
    stays exact — trailing chunk steps are masked; DESIGN.md §Stopping).
    """
    fused = vertex.fused_supported(oracle, cfg)
    spec = cfg.telemetry if cfg is not None else None
    # host streaming is a sequential-single-device feature: the batched
    # driver keeps lane rings device-resident, and under shard_map a
    # callback would fire per mesh cell
    stream = (
        spec is not None
        and spec.stream_to is not None
        and cfg.backend != "distributed"
    )

    def cond(state: EngineState):
        return (state.k < cfg.max_iters) & (state.stall < patience)

    def body(state: EngineState):
        if fused:
            new = fused_chunk(oracle, Xt_run, y, stats, state, cfg, delta)
        else:
            new = rule_step(oracle, Xt_run, y, stats, state, cfg, delta)
        if stream:
            # chunk-boundary flush (fires only when the ring would wrap;
            # jax.debug.callback — no blocking host sync in the loop)
            new = new._replace(
                tel=obs_telemetry.stream_flush(new.tel, spec, final=False)
            )
        return new

    return jax.lax.while_loop(cond, body, state0)


def history_patience(n_iters: int) -> int:
    """The patience ``solve_with_history`` runs the loop with: stall can
    reach at most n_iters, so n_iters + 1 never stops early — the run
    executes exactly n_iters steps (the old fixed-length scan's
    semantics) while still going through the ONE shared ``run_loop``."""
    return int(n_iters) + 1


def _effective_fuse_steps(oracle, cfg) -> int:
    """What one loop dispatch actually advances: cfg.fuse_steps when the
    fused chunk engages (``vertex.fused_supported``), else 1 — surfaced
    on SolveResult so callers can tell what ran (the distributed driver
    forces 1; non-classic rules / bisection oracles fall back)."""
    if cfg is None:
        return 1
    return cfg.fuse_steps if vertex.fused_supported(oracle, cfg) else 1


def _result(
    oracle, Xt, y, stats, final: EngineState, patience: int, cfg, delta
) -> SolveResult:
    alpha = final.scale * final.beta
    gap = None
    if cfg is not None and cfg.report_gap:
        gap = certified_gap(
            oracle, Xt, y, final.co, final.beta, final.scale, delta, cfg
        )
    tel = None
    if cfg is not None and cfg.telemetry is not None:
        tel = final.tel
        if (
            cfg.telemetry.stream_to is not None
            and cfg.backend != "distributed"
        ):
            # drain whatever the chunk-boundary flushes haven't shipped
            tel = obs_telemetry.stream_flush(tel, cfg.telemetry, final=True)
    return SolveResult(
        alpha=alpha,
        objective=oracle.objective(y, stats, final.co, cfg),
        iterations=final.k,
        n_dots=final.n_dots,
        active=jnp.sum(alpha != 0.0),
        converged=final.stall >= patience,
        gap=gap,
        effective_fuse_steps=jnp.asarray(
            _effective_fuse_steps(oracle, cfg), jnp.int32
        ),
        telemetry=tel,
    )


@functools.partial(jax.jit, static_argnames=("oracle", "cfg"))
def solve(
    oracle,
    Xt,
    y: jax.Array,
    cfg: FWConfig,
    key: jax.Array,
    alpha0: Optional[jax.Array] = None,
    delta=None,
) -> SolveResult:
    """Run the oracle's Algorithm-2 analogue until
    ||alpha_{k+1}-alpha_k||_inf <= tol for ``patience`` consecutive
    iterations, or max_iters. ``delta`` (traced) overrides cfg.delta —
    one compile serves the whole path."""
    vertex.check_matrix_backend(Xt, cfg)
    delta = jnp.asarray(cfg.delta if delta is None else delta)
    stats = precompute_colstats(Xt, y, cfg) if oracle.needs_stats else None
    state0 = init_state(oracle, Xt, y, key, alpha0, cfg)
    patience = _patience(cfg)
    Xt = vertex.pad_backend_matrix(Xt, cfg)  # once, outside the hot loop
    final = run_loop(oracle, Xt, y, stats, state0, cfg, delta, patience)
    return _result(oracle, Xt, y, stats, final, patience, cfg, delta)


@functools.partial(jax.jit, static_argnames=("oracle", "cfg", "n_iters"))
def solve_with_history(
    oracle,
    Xt,
    y: jax.Array,
    cfg: FWConfig,
    key: jax.Array,
    n_iters: int,
    alpha0: Optional[jax.Array] = None,
):
    """Fixed-iteration run recording the objective per step (convergence
    plots). Returns (result, objective_history[n_iters]).

    Implemented ON the telemetry ring (DESIGN.md §Observability): the
    run is ``run_loop`` with a capacity-``n_iters`` history ring and
    ``history_patience`` (never stops early), so the step sequence is
    the regular solver's — fused chunks included, via the bit-identical
    fori-of-step executor that ``record_objective`` forces — and the
    history is ``telemetry.objective`` in iteration order (capacity ==
    n_iters means the ring never wraps: slot t is iteration t)."""
    hcfg = dataclasses.replace(
        cfg,
        max_iters=n_iters,
        telemetry=obs_telemetry.history_spec(cfg.telemetry, n_iters),
    )
    vertex.check_matrix_backend(Xt, hcfg)
    stats = precompute_colstats(Xt, y, hcfg) if oracle.needs_stats else None
    state0 = init_state(oracle, Xt, y, key, alpha0, hcfg)
    Xt_run = vertex.pad_backend_matrix(Xt, hcfg)
    delta = jnp.asarray(cfg.delta)
    final = run_loop(
        oracle, Xt_run, y, stats, state0, hcfg, delta, history_patience(n_iters)
    )
    hist = final.tel.objective[:n_iters]
    res = _result(oracle, Xt_run, y, stats, final, _patience(cfg), hcfg, delta)
    return res, hist


def _lane_mask(active: jax.Array, leaf: jax.Array) -> jax.Array:
    """Broadcast a (lanes,) bool against a (lanes, ...) state leaf."""
    return active.reshape(active.shape + (1,) * (leaf.ndim - 1))


def batched_loop(oracle, Xt_run, y, stats, states0, cfg, deltas, patience):
    """The lane-pruned while_loop shared by ``solve_batched`` and the
    distributed driver (repro.distributed.driver runs it inside its
    shard_map with per-shard operands). Returns (final states, saved).

    Under ``cfg.fuse_steps = K > 1`` every loop turn advances each active
    lane by one K-step chunk (through the XLA reference executor — the
    lanes already vmap the per-step backend kernels, and chunking them
    keeps that unchanged while cutting the lane-sync/stopping checks by
    K); converged lanes freeze at chunk granularity, so per-lane results
    equal the sequential fused solver's, overshoot <= K-1 included.
    """
    fused = vertex.fused_supported(oracle, cfg)
    chunk_len = cfg.fuse_steps if fused else 1

    def advance(s, d):
        if fused:
            return _fused_ref_chunk(oracle, Xt_run, y, stats, s, cfg, d)
        return rule_step(oracle, Xt_run, y, stats, s, cfg, d)

    def lane_active(states):
        return (states.k < cfg.max_iters) & (states.stall < patience)

    def cond(carry):
        states, _ = carry
        return jnp.any(lane_active(states))

    def body(carry):
        states, saved = carry
        active = lane_active(states)
        stepped = jax.vmap(advance)(states, deltas)
        merged = jax.tree_util.tree_map(
            lambda n, o: jnp.where(_lane_mask(active, n), n, o), stepped, states
        )
        return merged, saved + jnp.sum((~active).astype(jnp.int32)) * chunk_len

    return jax.lax.while_loop(cond, body, (states0, jnp.zeros((), jnp.int32)))


def batched_result(oracle, Xt_run, y, stats, final, patience, cfg, deltas):
    """Assemble the per-lane SolveResult (shared with the distributed
    driver); certified per-lane gaps when ``cfg.report_gap``."""
    alpha = final.scale[:, None] * final.beta
    objective = jax.vmap(lambda co: oracle.objective(y, stats, co, cfg))(final.co)
    gap = None
    if cfg.report_gap:
        # one lane at a time: each gap is a full O(nnz) pass whose
        # temporaries are matrix-sized, so a vmap would hold one per lane
        gap = jax.lax.map(
            lambda a: certified_gap(oracle, Xt_run, y, *a, cfg),
            (final.co, final.beta, final.scale, deltas),
        )
    return SolveResult(
        alpha=alpha,
        objective=objective,
        iterations=final.k,
        n_dots=final.n_dots,
        active=jnp.sum(alpha != 0.0, axis=1),
        converged=final.stall >= patience,
        gap=gap,
        effective_fuse_steps=jnp.asarray(
            _effective_fuse_steps(oracle, cfg), jnp.int32
        ),
        # lane-stacked rings (leading lane axis on every field)
        telemetry=final.tel if cfg.telemetry is not None else None,
    )


@functools.partial(jax.jit, static_argnames=("oracle", "cfg"))
def solve_batched(
    oracle,
    Xt,
    y: jax.Array,
    cfg: FWConfig,
    keys: jax.Array,
    alpha0s: jax.Array,
    deltas: jax.Array,
):
    """Solve a batch of lanes (one delta / key / warm start each) in ONE
    while_loop with per-lane early exit (DESIGN.md §Path).

    Unlike a plain vmap-of-while_loop, the lane states are batched
    explicitly: column statistics and init run once outside the lane
    axis, the loop condition is ``any(lane active)``, and converged lanes
    are frozen by a masked update — their PRNG stream, counters, and
    co-state stop advancing, so each lane's result is exactly what the
    sequential solver would produce. Returns ``(batched SolveResult,
    saved_iters)`` where ``saved_iters`` counts the lane-iterations NOT
    spent past each lane's own convergence (the pruning win vs running
    every lane to the slowest lane's stop).
    """
    vertex.check_matrix_backend(Xt, cfg)
    stats = precompute_colstats(Xt, y, cfg) if oracle.needs_stats else None
    # lane by lane, like the gaps: a warm start's X alpha0 is an O(nnz) pass
    states0 = jax.lax.map(
        lambda a: init_state(oracle, Xt, y, a[0], a[1], cfg), (keys, alpha0s)
    )
    patience = _patience(cfg)
    Xt_run = vertex.pad_backend_matrix(Xt, cfg)
    final, saved = batched_loop(
        oracle, Xt_run, y, stats, states0, cfg, deltas, patience
    )
    res = batched_result(oracle, Xt_run, y, stats, final, patience, cfg, deltas)
    return res, saved


# --------------------------------------------------------------------------
# Metrics-plane host shims (DESIGN.md §Observability)
# --------------------------------------------------------------------------


def _observe_solve(reg, entry: str, cfg: FWConfig, res: SolveResult,
                   elapsed_s: float) -> None:
    """Fold one finished entry-point dispatch into the metrics registry.

    Host-side only — runs AFTER the dispatch completes, never inside the
    jitted program, so installing a registry changes zero compiled bytes.
    Batched results count each lane individually in the totals; latency
    is per DISPATCH (the quantity the path driver amortizes)."""
    labels = dict(entry=entry, backend=cfg.backend, step_rule=cfg.step_rule)
    names = ("entry", "backend", "step_rule")
    iters = np.asarray(res.iterations, np.float64).reshape(-1)
    lanes = iters.size
    reg.counter(
        "fw_solves",
        "solver entry-point completions (batched lanes count individually)",
        names,
    ).inc(lanes, **labels)
    reg.counter(
        "fw_iterations", "FW iterations consumed across all solves", names
    ).inc(float(iters.sum()), **labels)
    reg.counter(
        "fw_n_dots", "length-m dot products consumed (paper's cost unit)",
        names,
    ).inc(float(np.asarray(res.n_dots, np.float64).sum()), **labels)
    n_conv = int(np.asarray(res.converged).reshape(-1).sum())
    outcomes = reg.counter(
        "fw_lane_outcomes",
        "lane stop reason: §Stopping rule ('converged') vs max_iters",
        names + ("outcome",),
    )
    if n_conv:
        outcomes.inc(n_conv, outcome="converged", **labels)
    if lanes - n_conv:
        outcomes.inc(lanes - n_conv, outcome="max_iters", **labels)
    reg.histogram(
        "fw_solve_latency_seconds",
        "wall time per entry-point dispatch, host-observed to completion",
        names,
    ).observe(elapsed_s, **labels)
    eff = 1
    if res.effective_fuse_steps is not None:
        eff = int(np.asarray(res.effective_fuse_steps).reshape(-1)[0])
    if cfg.fuse_steps > 1 and eff == 1:
        reg.counter(
            "fw_fused_fallback",
            "dispatches where fuse_steps>1 fell back to per-step loops "
            "(non-fusable oracle/sampling/rule)",
            names,
        ).inc(lanes, **labels)
    elif eff > 1:
        reg.counter(
            "fw_fused_chunks",
            "K-step fused chunks dispatched (lane-iterations / "
            "effective_fuse_steps)",
            names,
        ).inc(float(np.ceil(iters / eff).sum()), **labels)
    if res.gap is not None:
        gaps = np.asarray(res.gap, np.float64).reshape(-1)
        gaps = np.abs(gaps[np.isfinite(gaps)])
        if gaps.size:
            hist = reg.histogram(
                "fw_certified_gap",
                "certified FW duality gap at the returned iterate "
                "(cfg.report_gap)",
                names,
                buckets=obs_metrics.GAP_BUCKETS,
            )
            for g in gaps:
                hist.observe(float(g), **labels)


class _MetricsEntry:
    """Host shim over a jitted solver entry point.

    With no registry installed (the default) this is a straight
    pass-through — the compiled program and its dispatch path are
    untouched, which is what keeps the metrics-off contract as strong as
    the telemetry-off one. With a registry installed it times the
    dispatch to completion (``block_until_ready`` — jit calls return
    asynchronously) and folds totals/latency/gap into the registry.
    jit attributes (``_cache_size``, ``clear_cache``, ``lower``, ...)
    forward to the wrapped function, so cache bookkeeping like
    ``path.batched_solver_cache_size`` keeps working."""

    def __init__(self, fn, entry: str):
        self._fn = fn
        self._entry = entry
        self.__name__ = entry
        self.__doc__ = fn.__doc__
        self.__wrapped__ = fn

    def __call__(self, oracle, Xt, y, cfg, *args, **kwargs):
        # fail fast on NaN/Inf operands BEFORE tracing/compiling — a
        # poisoned matrix otherwise burns a silent max_iters run
        # (resilience/validate.py; REPRO_SKIP_INPUT_VALIDATION=1 opts out)
        _validate.validate_inputs(Xt, y)
        reg = obs_metrics.get_registry()
        if reg is None:
            return self._fn(oracle, Xt, y, cfg, *args, **kwargs)
        t0 = time.perf_counter()
        out = self._fn(oracle, Xt, y, cfg, *args, **kwargs)
        # solve returns a bare SolveResult; the history/batched entries
        # return (SolveResult, extra) — and SolveResult is itself a tuple
        res = out if isinstance(out, SolveResult) else out[0]
        jax.block_until_ready(res)
        _observe_solve(reg, self._entry, cfg, res, time.perf_counter() - t0)
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)


solve = _MetricsEntry(solve, "solve")
solve_with_history = _MetricsEntry(solve_with_history, "solve_with_history")
solve_batched = _MetricsEntry(solve_batched, "solve_batched")
