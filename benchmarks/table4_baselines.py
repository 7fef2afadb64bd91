"""Paper Table 4: baseline solvers (CD, SCD, FISTA-reg, FISTA-const) over
the full regularization path — time, iterations, dot products, mean active
features."""
from __future__ import annotations

import time

import jax

from benchmarks.common import CSV, CI_DATASETS, SCALE, load_dataset, path_grids
from repro.core import CDConfig, FISTAConfig, path as path_lib

N_POINTS = 20 if SCALE == "ci" else 100


def run(csv: CSV, datasets=None):
    datasets = datasets or CI_DATASETS
    for name in datasets:
        Xt, y, ds = load_dataset(name)
        p, m = Xt.shape
        lams, deltas = path_grids(Xt, y, N_POINTS)

        solvers = {
            "cd": lambda: path_lib.cd_path(
                Xt, y, lams, CDConfig(lam=0.0, max_sweeps=200, tol=1e-3)
            ),
            "scd": lambda: path_lib.cd_path(
                Xt, y, lams, CDConfig(lam=0.0, max_sweeps=200, tol=1e-3, stochastic=True)
            ),
            "fista_reg": lambda: path_lib.fista_path(
                Xt, y, lams, FISTAConfig(max_iters=500, tol=1e-3)
            ),
            "fista_const": lambda: path_lib.fista_path(
                Xt, y, deltas, FISTAConfig(constrained=True, max_iters=500, tol=1e-3)
            ),
        }
        for sname, fn in solvers.items():
            t0 = time.perf_counter()
            res = fn()
            dt = time.perf_counter() - t0
            csv.emit(
                f"table4/{name}/{sname}",
                dt * 1e6 / N_POINTS,
                f"m={m};p={p};iters={res.total_iters};dots={res.total_dots};"
                f"mean_active={res.mean_active:.1f};total_s={dt:.2f}",
            )


if __name__ == "__main__":
    from repro.devices import enable_compile_cache

    enable_compile_cache()
    run(CSV())
