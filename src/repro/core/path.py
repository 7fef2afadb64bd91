"""Regularization-path drivers (paper §5 protocol), oracle-generic.

Protocol reproduced from the paper:
  * 100-point grid in log scale;
  * penalized solvers (CD/SCD/FISTA-reg) sweep lam_max -> lam_min with
    lam_max = ||X^T y||_inf (the null-solution threshold) and
    lam_min = lam_max / 100, warm-starting each problem from the previous;
  * constrained solvers (FW, projected accelerated gradient) sweep
    delta_min -> delta_max with delta_max = ||alpha(lam_min)||_1 (taken from
    a high-precision CD solve, as the paper does to give every solver the
    same "sparsity budget") and delta_min = delta_max / 100;
  * FW warm start uses the paper's rescaling heuristic: the previous
    solution is scaled so its l1 norm equals the next delta (the solution
    is known to lie on the boundary when delta < ||alpha_LS||_1).

Both FW drivers take an optional problem ``oracle`` (DESIGN.md §Engine;
default lasso), so the same path protocol — including the batched
multi-delta lane driver with converged-lane pruning — serves the whole
solver family (lasso / logistic / elastic-net) on every backend.
``FWConfig.step_rule`` (DESIGN.md §StepRule) rides through both drivers
unchanged: the rule's extra state is part of ``EngineState.rule``, so
warm starts re-init it per grid point and the batched lanes carry it
per lane; non-classic rules simply run the path per-step
(``vertex.fused_supported`` gates ``fuse_steps`` off with one warning).
"""
from __future__ import annotations

import time
from typing import Callable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines, engine, fw_lasso
from repro.core.solver_config import CDConfig, FISTAConfig, FWConfig
from repro.obs import metrics as obs_metrics
from repro.obs import monitor as obs_monitor
from repro.obs import trace as obs_trace
from repro.resilience import checkpoint as path_ckpt
from repro.resilience import faults as _faults
from repro.sparse import ops as sparse_ops
from repro.sparse.matrix import SparseBlockMatrix


class PathPoint(NamedTuple):
    reg: float  # lam or delta
    objective: float  # the oracle's objective at this grid point
    l1: float
    active: int
    iterations: int
    n_dots: int
    seconds: float
    alpha_nnz_idx: np.ndarray
    alpha_nnz_val: np.ndarray
    # certified FW duality gap (oracle gap(), FWConfig.report_gap); NaN off
    gap: float = float("nan")


class PathResult(NamedTuple):
    points: List[PathPoint]
    total_seconds: float
    total_dots: int
    total_iters: int
    # lane-iterations pruned by the batched driver's per-lane early exit
    # (0 for the sequential drivers)
    saved_iters: int = 0
    # turns of the batched engine loop over the chunks this call ran: per
    # chunk, ceil(max lane iterations / effective_fuse_steps) (0 for the
    # sequential drivers)
    loop_turns: int = 0

    @property
    def mean_active(self) -> float:
        return float(np.mean([pt.active for pt in self.points]))


def _xty(Xt, y):
    """X^T y for either matrix layout (both path drivers accept a dense
    feature-major array OR a SparseBlockMatrix)."""
    if isinstance(Xt, SparseBlockMatrix):
        return sparse_ops.sparse_transpose_matvec(Xt, y)
    return Xt @ y


def lambda_grid(Xt, y, n_points: int = 100, ratio: float = 100.0) -> np.ndarray:
    """Glmnet-style grid: lam_max = ||X^T y||_inf, descending log scale."""
    lam_max = float(jnp.max(jnp.abs(_xty(Xt, y))))
    lam_min = lam_max / ratio
    return np.geomspace(lam_max, lam_min, n_points)


def delta_grid(delta_max: float, n_points: int = 100, ratio: float = 100.0) -> np.ndarray:
    """Constrained-form grid: delta_min -> delta_max, ascending log scale."""
    return np.geomspace(delta_max / ratio, delta_max, n_points)


def _sparsify(alpha: jax.Array):
    a = np.asarray(alpha)
    idx = np.nonzero(a)[0]
    return idx, a[idx]


def _point_gap(gap, lane=None) -> float:
    """PathPoint.gap from SolveResult.gap (None when report_gap is off)."""
    if gap is None:
        return float("nan")
    return float(gap if lane is None else gap[lane])


def _observe_point(reg, driver: str, cfg: FWConfig, seconds: float) -> None:
    """Per-grid-point latency into the metrics plane (no-op when the
    registry is None — the metrics-off default)."""
    if reg is None:
        return
    reg.histogram(
        "fw_path_point_seconds",
        "wall time per regularization-path grid point (batched lanes "
        "amortize their chunk dispatch)",
        ("driver", "backend"),
    ).observe(seconds, driver=driver, backend=cfg.backend)


def _finish_path(reg, tracer) -> None:
    """End-of-path bridge: fold the tracer's spans/counters accumulated
    during this path (incl. the distributed backend's trace-time
    collective counters) into the registry."""
    if reg is not None:
        obs_metrics.tracer_to_registry(tracer, reg)


def fw_path(
    Xt,
    y,
    deltas: np.ndarray,
    base_cfg: FWConfig,
    seed: int = 0,
    oracle=None,
    *,
    solve_fn=None,
    checkpoint_dir=None,
    checkpoint_every: int = 1,
    resume_from=None,
) -> PathResult:
    """Stochastic-FW path with the paper's l1-rescaling warm start.

    ``oracle`` selects the objective (default ``fw_lasso.LASSO``; pass
    ``fw_logistic.LOGISTIC`` or an ``ENOracle(l2)`` for the extensions).
    ``solve_fn`` overrides the engine entry point — the distributed
    driver injects its shard_map solver here so the SAME path protocol
    (and ``PathPoint.gap`` certification when ``cfg.report_gap``) runs on
    a mesh. Signature: ``solve_fn(oracle, Xt, y, cfg, key, alpha0,
    delta) -> SolveResult``.

    Checkpoint/resume (DESIGN.md §Resilience): with ``checkpoint_dir``
    set, the loop state (completed points, post-split PRNG key, warm
    start) snapshots atomically every ``checkpoint_every`` grid points;
    ``resume_from=<dir>`` restores the newest valid snapshot and replays
    ONLY the remaining points — bit-identical to the uninterrupted run
    (each point's index stream is a pure function of the key at its
    boundary and the carried alpha).
    """
    oracle = fw_lasso.LASSO if oracle is None else oracle
    if solve_fn is None:
        solve_fn = lambda o, X, yv, c, k, a0, d: engine.solve(
            o, X, yv, c, k, a0, delta=d
        )
    key = jax.random.PRNGKey(seed)
    alpha = None
    points = []
    start = 0
    if resume_from is not None:
        loaded = path_ckpt.load_path_checkpoint(resume_from)
        if loaded is not None:
            start, key, alpha, points, _ = loaded
    tracer = obs_trace.get_tracer()
    reg = obs_metrics.get_registry()
    mon = obs_monitor.StepMonitor()
    t_total = time.perf_counter()
    total_dots = sum(pt.n_dots for pt in points)
    total_iters = sum(pt.iterations for pt in points)
    n = len(deltas)
    cfg = base_cfg  # delta passes as a traced arg: ONE compile per path
    with tracer.span("fw_path", cat="path", n_points=n,
                     backend=cfg.backend, rule=cfg.step_rule):
        for g in range(start, n):
            d = deltas[g]
            _faults.check_kill("path_point", g)
            if alpha is not None:
                l1 = float(jnp.sum(jnp.abs(alpha)))
                if l1 > 1e-12:
                    alpha = alpha * (float(d) / l1)  # paper's rescaling heuristic
            key, sub = jax.random.split(key)
            mon.begin()
            t0 = time.perf_counter()
            with tracer.span("fw_path/point", cat="path", delta=float(d)):
                res = solve_fn(oracle, Xt, y, cfg, sub, alpha, float(d))
                res.alpha.block_until_ready()
            dt = time.perf_counter() - t0
            # the first grid point pays the path's one compile; EWMA
            # straggler detection flags anything else that stalls
            if mon.end() and mon.step > 1:
                tracer.instant("fw_path/straggler_point", cat="path",
                               point=mon.step, seconds=dt)
            _observe_point(reg, "sequential", cfg, dt)
            alpha = res.alpha
            idx, val = _sparsify(alpha)
            points.append(
                PathPoint(
                    reg=float(d),
                    objective=float(res.objective),
                    l1=float(jnp.sum(jnp.abs(alpha))),
                    active=int(res.active),
                    iterations=int(res.iterations),
                    n_dots=int(res.n_dots),
                    seconds=dt,
                    alpha_nnz_idx=idx,
                    alpha_nnz_val=val,
                    gap=_point_gap(res.gap),
                )
            )
            total_dots += int(res.n_dots)
            total_iters += int(res.iterations)
            if checkpoint_dir is not None and (
                (g + 1) % checkpoint_every == 0 or g == n - 1
            ):
                path_ckpt.save_path_checkpoint(
                    checkpoint_dir, g + 1, key, alpha, points
                )
    _finish_path(reg, tracer)
    return PathResult(points, time.perf_counter() - t_total, total_dots, total_iters)


def batched_solver_cache_size() -> int:
    """Distinct compilations of the batched lane solver (see tests)."""
    return engine.solve_batched._cache_size()


def clear_batched_solver_cache() -> None:
    engine.solve_batched.clear_cache()


def _observe_chunk(reg, cfg: FWConfig, real_lanes: int, conv, saved: int,
                   seconds: float) -> None:
    """One batched lane chunk into the metrics plane."""
    lbl = dict(backend=cfg.backend)
    reg.counter(
        "fw_lanes_admitted",
        "delta lanes admitted to batched path chunks",
        ("backend",),
    ).inc(real_lanes, **lbl)
    reg.counter(
        "fw_lane_freezes",
        "lanes frozen by per-lane early exit (converged before "
        "the chunk's while_loop drained)",
        ("backend",),
    ).inc(int(conv.sum()), **lbl)
    reg.counter(
        "fw_lane_saved_iterations",
        "lane-iterations pruned vs running every lane to the "
        "slowest lane's stop",
        ("backend",),
    ).inc(saved, **lbl)
    reg.histogram(
        "fw_path_chunk_seconds",
        "wall time per batched lane-chunk dispatch",
        ("backend",),
    ).observe(seconds, **lbl)
    # one latency sample per REAL grid point (amortized over the chunk
    # dispatch) — sample counts line up with the sequential driver's, so
    # the two are comparable
    for _ in range(real_lanes):
        _observe_point(reg, "batched", cfg, seconds / real_lanes)


def fw_path_batched(
    Xt,
    y,
    deltas: np.ndarray,
    base_cfg: FWConfig,
    seed: int = 0,
    lane_width: Optional[int] = None,
    oracle=None,
    *,
    solve_batched_fn=None,
    checkpoint_dir=None,
    checkpoint_every: int = 1,
    resume_from=None,
) -> PathResult:
    """Stochastic-FW path solved in parallel delta lanes (DESIGN.md §Path).

    The ascending delta grid is cut into chunks of ``lane_width`` deltas;
    each chunk is solved by ONE invocation of the batched engine loop, so
    a 100-point grid runs as ~8 batched solves instead of 100 sequential
    ones. Warm start keeps the paper's rescaling heuristic per lane: every
    lane starts from the previous chunk's densest solution scaled so its l1
    norm equals the lane's delta. The final (ragged) chunk is padded by
    repeating the last delta so every chunk shares one compiled program.
    Lanes that converge early are frozen by the engine's masked update;
    the skipped lane-iterations are summed into ``PathResult.saved_iters``.
    ``solve_batched_fn`` overrides ``engine.solve_batched`` (same
    signature) — the distributed driver's injection point.

    Checkpoint/resume works at lane-chunk granularity:
    ``checkpoint_every`` counts CHUNKS here, and ``resume_from=``
    replays only the remaining chunks bit-identically (the per-chunk key
    split and densest-solution carry fully determine the continuation).
    """
    oracle = fw_lasso.LASSO if oracle is None else oracle
    if solve_batched_fn is None:
        solve_batched_fn = engine.solve_batched
    deltas = np.asarray(deltas, dtype=np.float64)
    n = len(deltas)
    if lane_width is None:
        lane_width = max(1, -(-n // 8))  # ~8 sequential batched solves
    n_chunks = -(-n // lane_width)
    pad = n_chunks * lane_width - n
    padded = np.concatenate([deltas, np.repeat(deltas[-1:], pad)])

    key = jax.random.PRNGKey(seed)
    p = Xt.shape[0]
    carry = jnp.zeros((p,), Xt.dtype)  # densest solution seen so far
    points: List[Optional[PathPoint]] = [None] * n
    start_chunk = 0
    total_saved = 0
    if resume_from is not None:
        loaded = path_ckpt.load_path_checkpoint(resume_from)
        if loaded is not None:
            start_chunk, key, carry, done_points, total_saved = loaded
            for i, pt in enumerate(done_points):
                points[i] = pt
    tracer = obs_trace.get_tracer()
    reg = obs_metrics.get_registry()
    loop_turns = 0
    t_total = time.perf_counter()
    total_dots = sum(pt.n_dots for pt in points if pt is not None)
    total_iters = sum(pt.iterations for pt in points if pt is not None)
    with tracer.span("fw_path_batched", cat="path", n_points=n,
                     lane_width=lane_width, n_chunks=n_chunks,
                     backend=base_cfg.backend):
        for c in range(start_chunk, n_chunks):
            _faults.check_kill("path_chunk", c)
            chunk = padded[c * lane_width : (c + 1) * lane_width]
            real_lanes = min(lane_width, n - c * lane_width)  # ragged final chunk
            with tracer.span("fw_path_batched/chunk", cat="path", chunk=c):
                with tracer.span("fw_path_batched/warm_start", cat="path"):
                    d_arr = jnp.asarray(chunk, Xt.dtype)
                    l1 = jnp.sum(jnp.abs(carry))
                    # per-lane rescaling warm start; carry == 0 (first chunk)
                    # stays 0
                    scale = d_arr / jnp.maximum(l1, 1e-12)
                    alpha0s = carry[None, :] * scale[:, None]
                    key, *subs = jax.random.split(key, lane_width + 1)
                t0 = time.perf_counter()
                with tracer.span("fw_path_batched/solve", cat="path"):
                    res, _ = solve_batched_fn(
                        oracle, Xt, y, base_cfg, jnp.stack(subs), alpha0s, d_arr
                    )
                    res.alpha.block_until_ready()
                dt = time.perf_counter() - t0
                with tracer.span("fw_path_batched/collect", cat="path"):
                    carry = res.alpha[-1]
                    alphas = np.asarray(res.alpha)
                    # pruning win for the REAL lanes only: iterations each was
                    # spared while the chunk's while_loop kept running for
                    # slower lanes (the engine's own count would also include
                    # the phantom padded lanes)
                    iters = np.asarray(res.iterations)
                    chunk_saved = int(np.sum(iters.max() - iters[:real_lanes]))
                    total_saved += chunk_saved
                    # every lane, padded ones included, keeps the loop turning
                    fuse = (1 if res.effective_fuse_steps is None
                            else int(np.asarray(res.effective_fuse_steps)))
                    loop_turns += -(-int(iters.max()) // fuse)
                    conv = np.asarray(res.converged)[:real_lanes]
                    if reg is not None:
                        _observe_chunk(reg, base_cfg, real_lanes, conv,
                                       chunk_saved, dt)
                    for i in range(real_lanes):
                        g = c * lane_width + i
                        idx, val = _sparsify(alphas[i])
                        points[g] = PathPoint(
                            reg=float(chunk[i]),
                            objective=float(res.objective[i]),
                            l1=float(np.sum(np.abs(alphas[i]))),
                            active=int(res.active[i]),
                            iterations=int(res.iterations[i]),
                            n_dots=int(res.n_dots[i]),
                            seconds=dt / real_lanes,
                            alpha_nnz_idx=idx,
                            alpha_nnz_val=val,
                            gap=_point_gap(res.gap, i),
                        )
                        total_dots += int(res.n_dots[i])
                        total_iters += int(res.iterations[i])
                if checkpoint_dir is not None and (
                    (c + 1) % checkpoint_every == 0 or c == n_chunks - 1
                ):
                    with tracer.span("fw_path_batched/checkpoint", cat="path"):
                        n_done = min((c + 1) * lane_width, n)
                        path_ckpt.save_path_checkpoint(
                            checkpoint_dir, c + 1, key, carry, points[:n_done],
                            saved_iters=total_saved,
                        )
    _finish_path(reg, tracer)
    return PathResult(
        points,
        time.perf_counter() - t_total,
        total_dots,
        total_iters,
        saved_iters=total_saved,
        loop_turns=loop_turns,
    )


def _penalized_path(solve_fn, Xt, y, lams, seed: int) -> PathResult:
    key = jax.random.PRNGKey(seed)
    alpha = None
    points = []
    t_total = time.perf_counter()
    total_dots = 0
    total_iters = 0
    for lam in lams:
        key, sub = jax.random.split(key)
        t0 = time.perf_counter()
        res = solve_fn(Xt, y, float(lam), sub, alpha)
        res.alpha.block_until_ready()
        dt = time.perf_counter() - t0
        alpha = res.alpha
        idx, val = _sparsify(alpha)
        points.append(
            PathPoint(
                reg=float(lam),
                objective=float(res.objective),
                l1=float(jnp.sum(jnp.abs(alpha))),
                active=int(res.active),
                iterations=int(res.iterations),
                n_dots=int(res.n_dots),
                seconds=dt,
                alpha_nnz_idx=idx,
                alpha_nnz_val=val,
            )
        )
        total_dots += int(res.n_dots)
        total_iters += int(res.iterations)
    return PathResult(points, time.perf_counter() - t_total, total_dots, total_iters)


def cd_path(Xt, y, lams, base_cfg: CDConfig, seed: int = 0) -> PathResult:
    def solve(Xt, y, lam, key, alpha0):
        return baselines.cd_solve(Xt, y, base_cfg, key, alpha0, lam=lam)

    return _penalized_path(solve, Xt, y, lams, seed)


def fista_path(Xt, y, regs, base_cfg: FISTAConfig, seed: int = 0) -> PathResult:
    def solve(Xt, y, reg, key, alpha0):
        return baselines.fista_solve(Xt, y, base_cfg, key, alpha0, reg=reg)

    # constrained sweeps ascending (sparse -> dense), penalized descending.
    return _penalized_path(solve, Xt, y, regs, seed)
