"""Mesh-sharded FW solve drivers (DESIGN.md §Distributed).

ONE shard_map wraps the SAME engine hot loop that serves the
single-device backends: ``engine.step`` runs verbatim per mesh cell with
``cfg.backend='distributed'``, so every oracle (lasso / logistic /
elastic-net), the lane-pruned batched driver, and both regularization-
path protocols scale to the mesh without a distributed fork of the
iteration. The only distributed-specific code is (a) the per-shard
operand reconstruction, (b) the setup collectives (colstats, warm-start
matvec), and (c) the drivers' entry/exit plumbing — the collectives
inside the step live in ``repro.distributed.backend`` behind the
``core.vertex`` dispatch.

Solvers compile once per (mesh, oracle, cfg, geometry, mode): ``delta``
stays a traced argument, so a whole regularization path — sequential or
lane-pruned batched — reuses one compiled program, exactly like the
single-device drivers (§Perf).
"""
from __future__ import annotations

import concurrent.futures
import contextlib
import dataclasses
import functools
import time
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import engine, path as path_lib, vertex
from repro.core.engine import ColStats
from repro.core.solver_config import FWConfig
from repro.distributed import backend as dbackend
from repro.distributed.shard import ShardedOperand
from repro.obs import metrics as obs_metrics
from repro.obs import telemetry as obs_telemetry
from repro.obs import trace as obs_trace
from repro.resilience import faults, validate as _validate
from repro.sparse.matrix import SparseBlockMatrix


_warned_fuse_steps = False


def dist_config(cfg: FWConfig, op: ShardedOperand) -> FWConfig:
    """The static config the engine step sees inside the shard_map: the
    distributed backend plus the operand's mesh vocabulary. The caller's
    ``backend`` field is irrelevant here — the operand layout decides.

    ``fuse_steps`` is forced to 1: the fused chunk (DESIGN.md §Perf) is
    single-device-only for now — a per-shard chunk would have to carry
    the score psum and the winning-column broadcast INSIDE the kernel
    (K collective rounds per launch), which is a follow-on (ROADMAP).
    The override is no longer silent: a one-time warning fires, and the
    effective value is surfaced on ``SolveResult.effective_fuse_steps``
    so callers can tell what actually ran."""
    global _warned_fuse_steps
    if cfg.fuse_steps != 1 and not _warned_fuse_steps:
        _warned_fuse_steps = True
        warnings.warn(
            f"distributed driver forces fuse_steps=1 (requested "
            f"{cfg.fuse_steps}): the fused multi-step chunk is "
            "single-device-only; see SolveResult.effective_fuse_steps "
            "for what actually ran",
            stacklevel=3,
        )
    return dataclasses.replace(
        cfg, backend="distributed", dist=op.spec, fuse_steps=1
    )


def _local_matrix(geom, mat_args):
    """Rebuild this cell's matrix view from the shard_map-local leaves."""
    layout, p, m, m_local, p_local, bs, nnz, nb_loc = geom
    if layout == "dense":
        return mat_args[0]
    values_l, rows_l = mat_args
    return SparseBlockMatrix(
        values=values_l[0],
        rows=rows_l[0],
        p=p_local,  # padded local range; global-p masking is the backend's
        m=m_local,
        block_size=bs,
        nnz_max=nnz,
    )


@functools.lru_cache(maxsize=64)
def _solver(mesh, oracle, cfg: FWConfig, geom, mode: str, warm: bool,
            n_iters: Optional[int]):
    """Build + jit the shard_map-wrapped driver for one static key."""
    spec = cfg.dist
    layout, p, m, m_local, p_local, bs, nnz, nb_loc = geom
    da, mo = spec.data_axis, spec.model_axis
    if layout == "dense":
        mat_specs = (P(mo, da),)
    else:
        mat_specs = (P(da, mo, None, None), P(da, mo, None, None))
    patience = engine._patience(cfg)

    def _prep(mat_args, y_l):
        Xt_l = _local_matrix(geom, mat_args)
        stats = (
            ColStats(*dbackend.dist_colstats(Xt_l, y_l, cfg, p))
            if oracle.needs_stats
            else None
        )
        return Xt_l, stats

    def _init(Xt_l, y_l, key, alpha0):
        return engine.init_state(
            oracle, Xt_l, y_l, key, alpha0 if warm else None, cfg, p
        )

    if mode == "solve":

        def body(*args):
            *mat_args, y_l, key, alpha0, delta = args
            Xt_l, stats = _prep(mat_args, y_l)
            state0 = _init(Xt_l, y_l, key, alpha0)
            final = engine.run_loop(
                oracle, Xt_l, y_l, stats, state0, cfg, delta, patience
            )
            return engine._result(
                oracle, Xt_l, y_l, stats, final, patience, cfg, delta
            )

    elif mode == "history":

        def body(*args):
            *mat_args, y_l, key, alpha0 = args
            Xt_l, stats = _prep(mat_args, y_l)
            state0 = _init(Xt_l, y_l, key, alpha0)
            # ring-based history (DESIGN.md §Observability): cfg already
            # carries max_iters=n_iters + a capacity-n_iters ring (see
            # solve_with_history below), and history_patience never
            # stops early — the SAME run_loop as mode="solve" replays
            # the old fixed-length scan's exact step sequence
            final = engine.run_loop(
                oracle, Xt_l, y_l, stats, state0, cfg,
                jnp.asarray(cfg.delta), engine.history_patience(n_iters),
            )
            res = engine._result(
                oracle, Xt_l, y_l, stats, final, patience, cfg,
                jnp.asarray(cfg.delta),
            )
            return res, final.tel.objective[:n_iters]

    elif mode == "batched":

        def body(*args):
            *mat_args, y_l, keys, alpha0s, deltas = args
            Xt_l, stats = _prep(mat_args, y_l)
            # lane by lane, as in engine.solve_batched (O(nnz) warm starts)
            states0 = jax.lax.map(
                lambda a: _init(Xt_l, y_l, a[0], a[1]), (keys, alpha0s)
            )
            final, saved = engine.batched_loop(
                oracle, Xt_l, y_l, stats, states0, cfg, deltas, patience
            )
            res = engine.batched_result(
                oracle, Xt_l, y_l, stats, final, patience, cfg, deltas
            )
            return res, saved

    elif mode in ("rinit", "rchunk", "rrebuild", "rresult"):
        # Resilient chunked executor programs (resilience/guards.py):
        # the solve loop is driven from the HOST in chunks so a watchdog
        # can inspect and heal the state between dispatches. The state
        # crosses the shard_map boundary with its data-sharded co leaves
        # all-gathered to replicated global form ("gather out") and
        # re-sliced to the local rows on the way back in ("scatter in")
        # — an exact round trip, so chunked == monolithic bit-for-bit.
        n_data = mesh.shape[da]

        def _gather_state(state):
            def g(leaf):
                if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == m_local:
                    return jax.lax.all_gather(leaf, da, tiled=True)
                return leaf

            return state._replace(co=jax.tree_util.tree_map(g, state.co))

        def _scatter_state(state):
            def s(leaf):
                if (
                    getattr(leaf, "ndim", 0) >= 1
                    and leaf.shape[0] == m_local * n_data
                ):
                    i = jax.lax.axis_index(da)
                    return jax.lax.dynamic_slice_in_dim(
                        leaf, i * m_local, m_local
                    )
                return leaf

            return state._replace(
                co=jax.tree_util.tree_map(s, state.co)
            )

        if mode == "rinit":

            def body(*args):
                *mat_args, y_l, key, alpha0 = args
                Xt_l, _ = _prep(mat_args, y_l)
                return _gather_state(_init(Xt_l, y_l, key, alpha0))

        elif mode == "rchunk":
            n_turns = n_iters  # loop turns per dispatch, not iterations

            def body(*args):
                *mat_args, y_l, state, delta = args
                Xt_l, stats = _prep(mat_args, y_l)
                state = _scatter_state(state)

                def turn(s):
                    return engine.rule_step(
                        oracle, Xt_l, y_l, stats, s, cfg, delta
                    )

                def fbody(_, s):
                    return jax.lax.cond(
                        (s.k < cfg.max_iters) & (s.stall < patience),
                        turn,
                        lambda st: st,
                        s,
                    )

                state = jax.lax.fori_loop(0, n_turns, fbody, state)
                return _gather_state(state)

        elif mode == "rrebuild":

            def body(*args):
                *mat_args, y_l, state = args
                Xt_l, _ = _prep(mat_args, y_l)
                state = _scatter_state(state)
                alpha = state.scale * state.beta
                v = vertex.matvec(Xt_l, alpha, cfg)
                co = oracle.init_co(y_l, v, alpha, state.beta.dtype, cfg)
                return _gather_state(state._replace(co=co))

        else:  # rresult

            def body(*args):
                *mat_args, y_l, state, delta = args
                Xt_l, stats = _prep(mat_args, y_l)
                state = _scatter_state(state)
                return engine._result(
                    oracle, Xt_l, y_l, stats, state, patience, cfg, delta
                )

    else:  # pragma: no cover - internal
        raise ValueError(f"unknown driver mode {mode!r}")

    n_extra = {
        "solve": 4,       # y, key, alpha0, delta
        "history": 3,     # y, key, alpha0
        "batched": 4,     # y, keys, alpha0s, deltas
        "rinit": 3,       # y, key, alpha0
        "rchunk": 3,      # y, state, delta
        "rrebuild": 2,    # y, state
        "rresult": 3,     # y, state, delta
    }[mode]
    n_operands = len(mat_specs) + n_extra
    in_specs = mat_specs + (P(da),) + (P(),) * (n_operands - len(mat_specs) - 1)
    mapped = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False
    )
    return jax.jit(mapped)


def _traced_solver(*key):
    """``_solver`` plus compile detection for the dispatch spans: returns
    ``(fn, fresh)`` where ``fresh`` flags a new static key — the next
    call pays trace + XLA compile, and the span that wraps it should say
    so instead of letting a 100x first-call duration read as a collective
    regression."""
    before = _solver.cache_info().misses
    fn = _solver(*key)
    return fn, _solver.cache_info().misses > before


def _alpha0_arr(op: ShardedOperand, alpha0):
    if alpha0 is None:
        return jnp.zeros((op.p,), op.dtype)
    return jnp.asarray(alpha0, op.dtype)


class DispatchTimeoutError(RuntimeError):
    """A shard_map dispatch exceeded the active ``dispatch_policy``
    timeout on every allowed attempt."""


@dataclasses.dataclass(frozen=True)
class DispatchPolicy:
    timeout_s: float
    retries: int = 1


_policy: Optional[DispatchPolicy] = None


@contextlib.contextmanager
def dispatch_policy(timeout_s: float, retries: int = 1):
    """Bound every distributed dispatch in the with-block to
    ``timeout_s`` wall seconds, re-dispatching up to ``retries`` times
    before raising :class:`DispatchTimeoutError` (DESIGN.md
    §Resilience). Each attempt runs the dispatch to completion
    (``block_until_ready``) on a worker thread; a timed-out attempt's
    thread cannot be cancelled — it is abandoned (XLA has no dispatch
    cancellation) — so this is a straggler detector, not a reaper.
    Re-dispatches are counted as ``fw_dist_redispatches`` in the
    metrics registry."""
    global _policy
    prev = _policy
    _policy = DispatchPolicy(float(timeout_s), int(retries))
    try:
        yield
    finally:
        _policy = prev


def _call_with_policy(entry: str, fn, args):
    """Run one dispatch under the active timeout policy (pass-through
    when none is installed). The injected-delay fault site lives inside
    the attempt, so a one-shot delay spec stalls the first attempt only
    and the re-dispatch lands clean."""
    pol = _policy

    def _attempt():
        faults.maybe_delay("dist_dispatch")
        out = fn(*args)
        if pol is not None:
            jax.block_until_ready(out)
        return out

    if pol is None:
        return _attempt()
    reg = obs_metrics.get_registry()
    for attempt in range(pol.retries + 1):
        ex = concurrent.futures.ThreadPoolExecutor(max_workers=1)
        fut = ex.submit(_attempt)
        try:
            return fut.result(timeout=pol.timeout_s)
        except concurrent.futures.TimeoutError:
            if reg is not None:
                reg.counter(
                    "fw_dist_redispatches",
                    "distributed dispatch attempts abandoned after the "
                    "dispatch_policy timeout",
                    ("entry",),
                ).inc(1, entry=entry)
        finally:
            ex.shutdown(wait=False, cancel_futures=True)
    raise DispatchTimeoutError(
        f"dist/{entry} exceeded {pol.timeout_s}s on "
        f"{pol.retries + 1} attempt(s)"
    )


def _dispatch(entry: str, fresh: bool, dcfg: FWConfig, fn, args, **span_kw):
    """Run one shard_map dispatch under its tracer span and — only when a
    metrics registry is installed — time it to completion and fold
    dispatch latency, program-freshness counters, per-lane solve totals,
    and the tracer's trace-time collective counters into the registry.
    Registry-off is a straight pass-through: no block_until_ready, no
    extra host sync (same contract as ``engine._MetricsEntry``)."""
    reg = obs_metrics.get_registry()
    tracer = obs_trace.get_tracer()
    t0 = time.perf_counter()
    with tracer.span(f"dist/{entry}", cat="dist", new_program=fresh,
                     **span_kw):
        out = _call_with_policy(entry, fn, args)
        if reg is not None:
            jax.block_until_ready(out)
    if reg is not None:
        elapsed = time.perf_counter() - t0
        # solve returns a bare SolveResult; history/batched return
        # (SolveResult, extra) — and SolveResult is itself a tuple
        res = out if isinstance(out, engine.SolveResult) else out[0]
        reg.counter(
            "fw_dist_dispatches",
            "distributed shard_map dispatches by program freshness "
            "('fresh' paid trace + XLA compile)",
            ("entry", "program"),
        ).inc(1, entry=entry, program="fresh" if fresh else "cached")
        reg.histogram(
            "fw_dist_dispatch_seconds",
            "host wall time per distributed dispatch (compile included "
            "when the program is fresh)",
            ("entry",),
        ).observe(elapsed, entry=entry)
        engine._observe_solve(reg, f"dist/{entry}", dcfg, res, elapsed)
        # per-collective trace-time counters (dist/collectives/*) and the
        # dist span-duration histograms ride the incremental bridge
        obs_metrics.tracer_to_registry(tracer, reg)
    return out


def solve(
    oracle,
    op: ShardedOperand,
    cfg: FWConfig,
    key: jax.Array,
    alpha0: Optional[jax.Array] = None,
    delta=None,
) -> engine.SolveResult:
    """Distributed twin of ``engine.solve``: same stopping rule, same
    trajectory contract (uniform sampling replays the single-device
    index stream; on a 1-data-shard mesh the sparse lasso run is
    bit-identical). All result leaves come back replicated."""
    _validate.validate_inputs(op, op.y)
    dcfg = dist_config(cfg, op)
    fn, fresh = _traced_solver(op.mesh, oracle, dcfg, op.geom, "solve",
                               alpha0 is not None, None)
    delta = jnp.asarray(cfg.delta if delta is None else delta)
    return _dispatch(
        "solve", fresh, dcfg, fn,
        (*op.matrix_args, op.y, key, _alpha0_arr(op, alpha0), delta),
        layout=op.geom[0],
    )


def solve_with_history(
    oracle,
    op: ShardedOperand,
    cfg: FWConfig,
    key: jax.Array,
    n_iters: int,
    alpha0: Optional[jax.Array] = None,
):
    """Fixed-iteration distributed run recording the objective per step
    (through the telemetry ring — same machinery as the single-device
    ``engine.solve_with_history``)."""
    _validate.validate_inputs(op, op.y)
    dcfg = dist_config(cfg, op)
    hcfg = dataclasses.replace(
        dcfg,
        max_iters=int(n_iters),
        telemetry=obs_telemetry.history_spec(dcfg.telemetry, int(n_iters)),
    )
    fn, fresh = _traced_solver(op.mesh, oracle, hcfg, op.geom, "history",
                               alpha0 is not None, int(n_iters))
    return _dispatch(
        "solve_with_history", fresh, hcfg, fn,
        (*op.matrix_args, op.y, key, _alpha0_arr(op, alpha0)),
        n_iters=int(n_iters),
    )


def solve_batched(
    oracle,
    op: ShardedOperand,
    cfg: FWConfig,
    keys: jax.Array,
    alpha0s: jax.Array,
    deltas: jax.Array,
):
    """Lane-pruned batched solve under ONE shard_map: the engine's
    masked-lane while_loop runs per mesh cell (collectives vmap over the
    lane axis), so converged lanes freeze exactly as on one device.
    Returns ``(batched SolveResult, saved_iters)``."""
    _validate.validate_inputs(op, op.y)
    dcfg = dist_config(cfg, op)
    fn, fresh = _traced_solver(op.mesh, oracle, dcfg, op.geom, "batched",
                               True, None)
    return _dispatch(
        "solve_batched", fresh, dcfg, fn,
        (*op.matrix_args, op.y, keys, jnp.asarray(alpha0s, op.dtype),
         jnp.asarray(deltas)),
        lanes=int(jnp.asarray(deltas).shape[0]),
    )


def fw_path(
    op: ShardedOperand,
    deltas,
    base_cfg: FWConfig,
    seed: int = 0,
    oracle=None,
    report_gap: bool = True,
    *,
    checkpoint_dir=None,
    checkpoint_every: int = 1,
    resume_from=None,
) -> path_lib.PathResult:
    """Sequential regularization path on the mesh (paper §5 protocol,
    l1-rescaling warm starts). Certified duality gaps (oracle ``gap()``
    gradients) ride along by default — ``PathPoint.gap``. Checkpoint /
    resume kwargs behave exactly as on ``path.fw_path`` (the loop state
    lives on the host, so mesh runs snapshot and resume identically)."""
    cfg = dataclasses.replace(base_cfg, report_gap=report_gap)

    def solve_fn(oracle_, Xt_, y_, cfg_, key, alpha0, delta):
        return solve(oracle_, op, cfg_, key, alpha0, delta)

    return path_lib.fw_path(op, op.y, deltas, cfg, seed, oracle,
                            solve_fn=solve_fn,
                            checkpoint_dir=checkpoint_dir,
                            checkpoint_every=checkpoint_every,
                            resume_from=resume_from)


def fw_path_batched(
    op: ShardedOperand,
    deltas,
    base_cfg: FWConfig,
    seed: int = 0,
    lane_width: Optional[int] = None,
    oracle=None,
    report_gap: bool = True,
    *,
    checkpoint_dir=None,
    checkpoint_every: int = 1,
    resume_from=None,
) -> path_lib.PathResult:
    """Lane-pruned batched path on the mesh: chunks of deltas solve as
    lanes of ONE compiled distributed program; converged lanes freeze
    early and the pruning win reports as ``PathResult.saved_iters``."""
    cfg = dataclasses.replace(base_cfg, report_gap=report_gap)

    def solve_batched_fn(oracle_, Xt_, y_, cfg_, keys, alpha0s, d_arr):
        return solve_batched(oracle_, op, cfg_, keys, alpha0s, d_arr)

    return path_lib.fw_path_batched(
        op, op.y, deltas, cfg, seed, lane_width, oracle,
        solve_batched_fn=solve_batched_fn,
        checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every,
        resume_from=resume_from,
    )


@functools.lru_cache(maxsize=64)
def _gap_fn(mesh, oracle, cfg: FWConfig, geom):
    """Cached jitted shard_map gap program (one compile per static key,
    like ``_solver`` — alpha and delta stay traced)."""
    spec = cfg.dist

    def body(*args):
        *mat_args, y_l, a, d = args
        Xt_l = _local_matrix(geom, mat_args)
        return engine.oracle_gap(oracle, Xt_l, y_l, a, d, cfg)

    if geom[0] == "dense":
        mat_specs = (P(spec.model_axis, spec.data_axis),)
    else:
        mat_specs = (
            P(spec.data_axis, spec.model_axis, None, None),
        ) * 2
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=mat_specs + (P(spec.data_axis), P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(mapped)


def certified_gap(
    oracle, op: ShardedOperand, alpha: jax.Array, delta, cfg: FWConfig
) -> jax.Array:
    """Standalone certified duality gap at ``alpha`` on the mesh (the
    oracle ``gap()`` protocol run under shard_map)."""
    dcfg = dist_config(cfg, op)
    fn = _gap_fn(op.mesh, oracle, dcfg, op.geom)
    with obs_trace.get_tracer().span("dist/certified_gap", cat="dist"):
        return fn(
            *op.matrix_args, op.y, jnp.asarray(alpha, op.dtype),
            jnp.asarray(delta),
        )
