"""Quickstart: stochastic Frank-Wolfe Lasso vs coordinate descent.

Solves one constrained Lasso problem and a small regularization path on
synthetic data (paper §5.1 setup), printing objective / sparsity / dot
products for each solver.

    PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CDConfig, FISTAConfig, FWConfig, baselines, fw_solve
from repro.core import path as path_lib
from repro.core.sampling import kappa_confidence, kappa_percentile
from repro.data.synthetic import paper_synthetic


def main():
    print("== data: synthetic, m=200, p=10000, 100 informative (paper §5.1)")
    ds = paper_synthetic(10_000, 100, seed=0)
    Xt = jnp.asarray(np.ascontiguousarray(ds.X.T))
    y = jnp.asarray(ds.y)
    p, m = Xt.shape
    key = jax.random.PRNGKey(0)

    # --- single problem at a mid-path delta -------------------------------
    lam_grid = path_lib.lambda_grid(Xt, y, n_points=10)
    cd = baselines.cd_solve(Xt, y, CDConfig(lam=float(lam_grid[3]), max_sweeps=300, tol=1e-6), key)
    delta = float(jnp.sum(jnp.abs(cd.alpha)))
    print(f"   CD at lam={lam_grid[3]:.1f}: obj={float(cd.objective):.4f} "
          f"active={int(cd.active)} -> equivalent delta={delta:.2f}")

    kappa = kappa_percentile(0.02, 0.98)  # the paper's 194
    print(f"   kappa (top-2%, 98% confidence): {kappa}")
    for sampling, label in (("full", "deterministic FW"), ("uniform", f"stochastic FW k={kappa}")):
        cfg = FWConfig(delta=delta, kappa=kappa, sampling=sampling, max_iters=50_000, tol=1e-4)
        t0 = time.perf_counter()
        res = fw_solve(Xt, y, cfg, key)
        dt = time.perf_counter() - t0
        print(f"   {label:28s} obj={float(res.objective):.4f} active={int(res.active):4d} "
              f"iters={int(res.iterations):5d} dots={int(res.n_dots):9d} time={dt:.2f}s")

    # --- short path with warm starts ---------------------------------------
    print("== regularization path (10 points, paper protocol)")
    deltas = path_lib.delta_grid(delta, n_points=10)
    t0 = time.perf_counter()
    fw_path = path_lib.fw_path(Xt, y, deltas, FWConfig(delta=1.0, kappa=kappa, max_iters=50_000, tol=1e-3))
    print(f"   FW path: {time.perf_counter()-t0:.2f}s  mean_active={fw_path.mean_active:.1f} "
          f"dots={fw_path.total_dots}")
    t0 = time.perf_counter()
    cd_path = path_lib.cd_path(Xt, y, lam_grid, CDConfig(lam=0.0, max_sweeps=200, tol=1e-3))
    print(f"   CD path: {time.perf_counter()-t0:.2f}s  mean_active={cd_path.mean_active:.1f} "
          f"dots={cd_path.total_dots}")
    print(f"   dot-product advantage FW vs CD: "
          f"{cd_path.total_dots / max(fw_path.total_dots, 1):.1f}x")


if __name__ == "__main__":
    from repro.devices import enable_compile_cache

    enable_compile_cache()
    main()
