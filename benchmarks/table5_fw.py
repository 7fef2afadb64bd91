"""Paper Table 5: stochastic FW at |S| = 1%, 2%, 3% of p over the path —
time, speedup vs CD, iterations, dot products, mean active features.

Both path drivers are timed per sampling fraction: the sequential
``fw_path`` and the batched-lane ``fw_path_batched`` (DESIGN.md §Path),
with the batched row recording its speedup over sequential AND the
lane-iterations pruned by the per-lane early exit (``saved_iters``).
The sparse section runs the SAME path protocol with ``backend='sparse'``
on the sparse-native text dataset (real converted shards when
scripts/fetch_libsvm.py has run, proxy otherwise) vs the dense XLA
backend on its densified equivalent (feasible at bench scale only —
which is the point). The solver-family section times the logistic and
elastic-net oracles through the same engine on both backends
(DESIGN.md §Engine).

All rows are mirrored into BENCH_table5.json (BenchJSON).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (
    CSV, CI_DATASETS, SCALE, BenchJSON, load_dataset, load_sparse_dataset, path_grids,
)
from repro.core import CDConfig, FWConfig, LOGISTIC, ENOracle, engine, path as path_lib
from repro.core.sampling import kappa_fraction
from repro.utils.timing import Timer, timed

N_POINTS = 20 if SCALE == "ci" else 100
SPARSE_BENCH_DATASET = "e2006-tfidf"


def run(csv: CSV, datasets=None):
    js = BenchJSON("BENCH_table5.json")
    datasets = datasets or CI_DATASETS
    for name in datasets:
        Xt, y, ds = load_dataset(name)
        p, m = Xt.shape
        lams, deltas = path_grids(Xt, y, N_POINTS)

        # CD reference time for the speedup column
        t0 = time.perf_counter()
        cd_res = path_lib.cd_path(Xt, y, lams, CDConfig(lam=0.0, max_sweeps=200, tol=1e-3))
        cd_time = time.perf_counter() - t0
        csv.emit(
            f"table5/{name}/cd_ref", cd_time * 1e6 / N_POINTS,
            f"m={m};p={p};dots={cd_res.total_dots};mean_active={cd_res.mean_active:.1f}",
        )
        js.add(f"table5/{name}/cd_ref", m=m, p=p, n_points=N_POINTS,
               seconds=cd_time, dots=cd_res.total_dots,
               mean_active=cd_res.mean_active)

        for frac in (0.01, 0.02, 0.03):
            kappa = kappa_fraction(p, frac)
            cfg = FWConfig(
                delta=1.0, kappa=kappa, sampling="uniform",
                max_iters=20_000, tol=1e-3,
            )
            t0 = time.perf_counter()
            res = path_lib.fw_path(Xt, y, deltas, cfg)
            dt = time.perf_counter() - t0
            csv.emit(
                f"table5/{name}/fw_{int(frac*100)}pct",
                dt * 1e6 / N_POINTS,
                f"m={m};p={p};kappa={kappa};speedup_vs_cd={cd_time/dt:.1f}x;"
                f"iters={res.total_iters};dots={res.total_dots};"
                f"mean_active={res.mean_active:.1f};"
                f"dots_vs_cd={cd_res.total_dots / max(res.total_dots,1):.1f}x",
            )
            js.add(f"table5/{name}/fw_{int(frac*100)}pct", m=m, p=p, kappa=kappa,
                   n_points=N_POINTS, seconds=dt, iters=res.total_iters,
                   dots=res.total_dots, mean_active=res.mean_active,
                   speedup_vs_cd=cd_time / dt)

            lane_width = max(1, -(-N_POINTS // 8))
            t0 = time.perf_counter()
            res_b = path_lib.fw_path_batched(Xt, y, deltas, cfg, lane_width=lane_width)
            dt_b = time.perf_counter() - t0
            csv.emit(
                f"table5/{name}/fw_{int(frac*100)}pct_batched",
                dt_b * 1e6 / N_POINTS,
                f"m={m};p={p};kappa={kappa};lane_width={lane_width};"
                f"chunks={-(-N_POINTS // lane_width)};"
                f"speedup_vs_seq={dt/dt_b:.1f}x;speedup_vs_cd={cd_time/dt_b:.1f}x;"
                f"iters={res_b.total_iters};dots={res_b.total_dots};"
                f"saved_iters={res_b.saved_iters};"
                f"mean_active={res_b.mean_active:.1f}",
            )
            js.add(f"table5/{name}/fw_{int(frac*100)}pct_batched", m=m, p=p,
                   kappa=kappa, lane_width=lane_width, n_points=N_POINTS,
                   seconds=dt_b, iters=res_b.total_iters, dots=res_b.total_dots,
                   saved_iters=res_b.saved_iters,
                   mean_active=res_b.mean_active, speedup_vs_seq=dt / dt_b,
                   speedup_vs_cd=cd_time / dt_b)

    _run_sparse_section(csv, js)
    _run_family_section(csv, js)
    _run_fused_section(csv, js)
    _run_distributed_section(csv, js)
    js.write()


def _sparse_delta_max(mat, y, ds) -> float:
    """l1 budget for the delta grid. Proxies expose their generating
    coefficients; real datasets (coef=None) fall back to the analytic
    ratio y^T y / ||X^T y||_inf — the l1 scale at which the best single
    predictor would explain the targets — as a dense-solver-free stand-in
    for the paper's CD-derived "sparsity budget"."""
    if ds.coef is not None:
        return 0.5 * float(np.abs(np.asarray(ds.coef)).sum())
    xty = np.abs(np.asarray(path_lib._xty(mat, jnp.asarray(y))))
    # y^T y over the null-solution threshold ||X^T y||_inf: the l1 scale at
    # which the best single predictor would explain the targets
    return float(np.dot(y, y) / max(xty.max(), 1e-12))


def _run_sparse_section(csv: CSV, js: BenchJSON):
    """backend='sparse' vs dense XLA on the same text dataset (real
    converted shards when present, proxy otherwise)."""
    mat, y, ds = load_sparse_dataset(SPARSE_BENCH_DATASET)
    p, m = mat.shape
    deltas = path_lib.delta_grid(_sparse_delta_max(mat, y, ds), n_points=N_POINTS)
    kappa = kappa_fraction(p, 0.01)
    timers = {}
    results = {}
    arms = [("sparse", mat)]
    if 4 * p * m < 2 << 30:  # densified arm only when it fits (proxies do;
        arms.insert(0, ("xla", mat.to_dense()))  # the real sizes do not)
    for backend, A in arms:
        cfg = FWConfig(
            delta=1.0, kappa=kappa, sampling="uniform",
            max_iters=20_000, tol=1e-3, backend=backend,
        )
        t = timers.setdefault(backend, Timer())
        with timed(f"table5/sparse/fw_path_{backend}", sink=t):
            res = path_lib.fw_path(A, y, deltas, cfg)
        results[backend] = res
        csv.emit(
            f"table5/{SPARSE_BENCH_DATASET}-sparse/fw_1pct_{backend}",
            t.total * 1e6 / N_POINTS,
            f"m={m};p={p};kappa={kappa};nnz_max={mat.nnz_max};"
            f"iters={res.total_iters};dots={res.total_dots};"
            f"mean_active={res.mean_active:.1f}",
        )
        js.add(f"table5/{SPARSE_BENCH_DATASET}-sparse/fw_1pct_{backend}",
               m=m, p=p, kappa=kappa, nnz_max=mat.nnz_max, backend=backend,
               n_points=N_POINTS, seconds=t.total,
               iters=res.total_iters, dots=res.total_dots,
               mean_active=res.mean_active)
    if "xla" in results:
        obj_rel = abs(
            results["sparse"].points[-1].objective - results["xla"].points[-1].objective
        ) / max(abs(results["xla"].points[-1].objective), 1e-12)
        csv.emit(
            f"table5/{SPARSE_BENCH_DATASET}-sparse/speedup",
            timers["xla"].total / timers["sparse"].total * 100,
            f"sparse_vs_dense={timers['xla'].total/timers['sparse'].total:.1f}x;"
            f"final_obj_rel_diff={obj_rel:.2e}",
        )
        js.add(f"table5/{SPARSE_BENCH_DATASET}-sparse/speedup",
               sparse_vs_dense=timers["xla"].total / timers["sparse"].total,
               final_obj_rel_diff=obj_rel)
    section = Timer()
    for t in timers.values():
        section.merge(t)
    js.add(f"table5/{SPARSE_BENCH_DATASET}-sparse/section_total",
           seconds=section.total, paths=section.count)


def _run_family_section(csv: CSV, js: BenchJSON):
    """Logistic / elastic-net oracles through the SAME engine paths
    (DESIGN.md §Engine): per-oracle sparse-vs-dense solve times plus a
    batched logistic path with lane pruning."""
    mat, y_reg, ds = load_sparse_dataset(SPARSE_BENCH_DATASET, prefer_real=False)
    p, m = mat.shape
    Xt_dense = mat.to_dense()
    y_cls = jnp.sign(y_reg) + (y_reg == 0)  # {-1,+1} labels for logistic
    kappa = kappa_fraction(p, 0.01)
    delta = _sparse_delta_max(mat, np.asarray(y_reg), ds)
    oracles = {
        "logistic": (LOGISTIC, y_cls),
        "elasticnet": (ENOracle(l2=1.0), y_reg),
    }
    for oname, (oracle, y) in oracles.items():
        for backend, A in (("xla", Xt_dense), ("sparse", mat)):
            cfg = FWConfig(
                delta=delta, kappa=kappa, sampling="uniform",
                max_iters=2_000, tol=1e-4, backend=backend,
            )
            key = jax.random.PRNGKey(0)
            res = engine.solve(oracle, A, y, cfg, key)  # compile
            res.alpha.block_until_ready()
            t0 = time.perf_counter()
            res = engine.solve(oracle, A, y, cfg, key)
            res.alpha.block_until_ready()
            dt = time.perf_counter() - t0
            csv.emit(
                f"table5/family/{oname}_{backend}",
                dt * 1e6,
                f"m={m};p={p};kappa={kappa};iters={int(res.iterations)};"
                f"dots={int(res.n_dots)};obj={float(res.objective):.4g};"
                f"active={int(res.active)}",
            )
            js.add(f"table5/family/{oname}_{backend}", m=m, p=p, kappa=kappa,
                   backend=backend, seconds=dt, iters=int(res.iterations),
                   dots=int(res.n_dots), objective=float(res.objective),
                   active=int(res.active))

    # batched logistic path: the pruned-lane driver over the family oracle
    deltas = path_lib.delta_grid(delta, n_points=max(4, N_POINTS // 4))
    cfg = FWConfig(delta=1.0, kappa=kappa, sampling="uniform",
                   max_iters=2_000, tol=1e-4, backend="sparse")
    lane_width = min(4, len(deltas))  # multi-lane chunks so pruning can fire
    t0 = time.perf_counter()
    res_b = path_lib.fw_path_batched(mat, y_cls, deltas, cfg,
                                     lane_width=lane_width, oracle=LOGISTIC)
    dt_b = time.perf_counter() - t0
    csv.emit(
        "table5/family/logistic_sparse_path_batched",
        dt_b * 1e6 / len(deltas),
        f"m={m};p={p};n_points={len(deltas)};lane_width={lane_width};"
        f"iters={res_b.total_iters};saved_iters={res_b.saved_iters}",
    )
    js.add("table5/family/logistic_sparse_path_batched", m=m, p=p,
           n_points=len(deltas), lane_width=lane_width, seconds=dt_b,
           iters=res_b.total_iters, saved_iters=res_b.saved_iters)


def _run_fused_section(csv: CSV, js: BenchJSON):
    """Fused-vs-unfused (FWConfig.fuse_steps, ISSUE 5) wall time for the
    SAME regularization path: one sequential ``fw_path`` per K on the
    dense synthetic dataset and on the sparse text proxy, so the bench
    trajectory records what K iterations per dispatch buys end to end
    (chunked stopping may spend up to K-1 extra iterations per grid
    point — both the time and the iteration counts land in the JSON)."""
    arms = []
    Xt, y, _ = load_dataset("synthetic-10000")
    arms.append(("xla", Xt, y))
    mat, ys, _ = load_sparse_dataset(SPARSE_BENCH_DATASET, prefer_real=False)
    arms.append(("sparse", mat, ys))
    n_pts = max(4, N_POINTS // 4)
    for backend, A, yv in arms:
        p, m = A.shape
        deltas = path_lib.delta_grid(
            float(jnp.max(jnp.abs(path_lib._xty(A, yv)))) * 0.02, n_points=n_pts
        )
        kappa = kappa_fraction(p, 0.01)
        base = {}
        for K in (1, 8):
            cfg = FWConfig(delta=1.0, kappa=kappa, sampling="uniform",
                           max_iters=20_000, tol=1e-3, backend=backend,
                           fuse_steps=K)
            t0 = time.perf_counter()
            res = path_lib.fw_path(A, yv, deltas, cfg)
            dt = time.perf_counter() - t0
            base.setdefault("t", dt)
            base.setdefault("obj", res.points[-1].objective)
            obj_rel = abs(res.points[-1].objective - base["obj"]) / max(
                abs(base["obj"]), 1e-12
            )
            tag = f"table5/fused/path_{backend}_k{K}"
            csv.emit(
                tag, dt * 1e6 / n_pts,
                f"m={m};p={p};kappa={kappa};n_points={n_pts};"
                f"iters={res.total_iters};speedup_vs_k1={base['t']/dt:.2f}x;"
                f"final_obj_rel_vs_k1={obj_rel:.2e}",
            )
            js.add(tag, m=m, p=p, kappa=kappa, n_points=n_pts, backend=backend,
                   fuse_steps=K, seconds=dt, iters=res.total_iters,
                   speedup_vs_k1=base["t"] / dt, final_obj_rel_vs_k1=obj_rel)


_DIST_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import json, time
import numpy as np, jax, jax.numpy as jnp
from repro.core import FWConfig, LASSO, engine
from repro import distributed as dist
from repro.data import make_regression, standardize
from repro.devices import device_label
from repro.sparse.matrix import SparseBlockMatrix

m, p, n_iters, kappa = %(m)d, %(p)d, %(n_iters)d, %(kappa)d
ds = standardize(make_regression(m=m, p=p, n_informative=20, noise=0.5, seed=0))
Xs = np.asarray(ds.X.T, np.float32).copy()
Xs[np.abs(Xs) < 0.04] = 0.0
mat = SparseBlockMatrix.from_dense(Xs, block_size=128)
y = np.asarray(ds.y)
cfg = FWConfig(delta=100.0, sampling="uniform", kappa=kappa,
               max_iters=n_iters, tol=0.0, patience=10**9)
key = jax.random.PRNGKey(0)

def timed(fn):
    fn().alpha.block_until_ready()            # compile
    t0 = time.perf_counter()
    fn().alpha.block_until_ready()
    return time.perf_counter() - t0

scfg = FWConfig(**{**cfg.__dict__, "backend": "sparse"})
t_single = timed(lambda: engine.solve(LASSO, mat, jnp.asarray(y), scfg, key))
rows = {"single_device": {"seconds_per_iter": t_single / n_iters}}
for n_data, n_model in ((1, 4), (2, 2)):
    mesh = dist.fw_mesh(n_data, n_model)
    op = dist.shard_sparse(mat, y, mesh)
    t_dist = timed(lambda: dist.solve(LASSO, op, cfg, key))
    # analytic per-iteration comm budget (DESIGN.md SDistributed): one
    # |S| score psum over both axes, one (m_local,) column psum over
    # "model", and the O(1) scalar psums of the oracle recursions
    comm = 4 * (kappa + op.m_local + 8)
    local = 8 * kappa * op.nnz_max + 4 * 4 * op.m_local
    rows["mesh_%%dx%%d" %% (n_data, n_model)] = {
        "seconds_per_iter": t_dist / n_iters,
        "vs_single": t_single / t_dist,
        "comm_bytes_per_iter": comm,
        "local_bytes_per_iter": local,
        "comm_fraction": comm / (comm + local),
    }
print("DISTRESULT" + json.dumps({"device": device_label(), "rows": rows}))
"""


def _run_distributed_section(csv: CSV, js: BenchJSON):
    """Distributed-vs-single-device per-iteration time + analytic comm
    fraction on a forced 4-device CPU mesh. Runs in a subprocess so this
    process keeps 1 device (DESIGN.md rule). The child is pinned to the
    CPU whatever the host has, and its rows carry the device it reports;
    a failed child fails the benchmark."""
    import json as json_mod
    import os
    import subprocess
    import sys

    params = dict(m=256, p=4096, n_iters=300, kappa=64)
    if SCALE == "ci":
        params = dict(m=128, p=1024, n_iters=150, kappa=32)
    proc = subprocess.run(
        [sys.executable, "-c", _DIST_SCRIPT % params],
        capture_output=True, text=True, timeout=1200,
        env={**os.environ, "PYTHONPATH": os.pathsep.join(sys.path)},
    )
    lines = [l for l in proc.stdout.splitlines() if l.startswith("DISTRESULT")]
    if proc.returncode != 0 or not lines:
        raise RuntimeError(
            f"distributed child failed (rc={proc.returncode}): "
            f"{proc.stderr[-800:]}"
        )
    out = json_mod.loads(lines[0][len("DISTRESULT"):])
    for name, row in out["rows"].items():
        csv.emit(
            f"table5/distributed/{name}",
            row["seconds_per_iter"] * 1e6,
            f"device={out['device']};"
            + ";".join(f"{k}={v:.4g}" for k, v in row.items()),
        )
        js.add(f"table5/distributed/{name}", **params, **row,
               device=out["device"])


if __name__ == "__main__":
    from repro.devices import enable_compile_cache

    enable_compile_cache()
    run(CSV())
