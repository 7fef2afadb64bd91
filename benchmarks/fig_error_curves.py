"""Paper Figs. 3/5/6: train/test MSE along the path (FW vs CD).
Validates: both solvers find the same best model / same error minimum."""
from __future__ import annotations

import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from benchmarks.common import CSV, SCALE, load_dataset, path_grids
from repro.core import CDConfig, FWConfig, path as path_lib
from repro.core.sampling import kappa_fraction

N_POINTS = 20 if SCALE == "ci" else 100
OUT = Path(__file__).resolve().parents[1] / "experiments" / "figures"


def _mse(ds, idx, val, test=False):
    X = ds.X_test if test else ds.X
    y = ds.y_test if test else ds.y
    if X is None:
        return float("nan")
    pred = X[:, idx] @ val
    return float(np.mean((pred - y) ** 2))


def run(csv: CSV, dataset: str = "synthetic-10000"):
    OUT.mkdir(parents=True, exist_ok=True)
    Xt, y, ds = load_dataset(dataset)
    p, m = Xt.shape
    lams, deltas = path_grids(Xt, y, N_POINTS)

    t0 = time.perf_counter()
    fw = path_lib.fw_path(
        Xt, y, deltas,
        FWConfig(delta=1.0, kappa=kappa_fraction(p, 0.03), max_iters=20000, tol=1e-3),
    )
    cd = path_lib.cd_path(Xt, y, lams, CDConfig(lam=0.0, max_sweeps=200, tol=1e-3))
    lines = ["solver,l1,train_mse,test_mse"]
    best = {}
    for sname, res in (("fw", fw), ("cd", cd)):
        tests = []
        for pt in res.points:
            tr = _mse(ds, pt.alpha_nnz_idx, pt.alpha_nnz_val, test=False)
            te = _mse(ds, pt.alpha_nnz_idx, pt.alpha_nnz_val, test=True)
            tests.append(te)
            lines.append(f"{sname},{pt.l1:.6g},{tr:.6g},{te:.6g}")
        best[sname] = float(np.nanmin(tests)) if tests else float("nan")
    out = OUT / f"error_curves_{dataset}.csv"
    out.write_text("\n".join(lines))
    dt = time.perf_counter() - t0
    rel = abs(best["fw"] - best["cd"]) / max(abs(best["cd"]), 1e-12)
    csv.emit(
        f"fig_err/{dataset}", dt * 1e6,
        f"best_test_mse_fw={best['fw']:.5g};best_test_mse_cd={best['cd']:.5g};"
        f"rel_gap={rel:.3f};csv={out.name}",
    )


if __name__ == "__main__":
    from repro.devices import enable_compile_cache

    enable_compile_cache()
    run(CSV())
