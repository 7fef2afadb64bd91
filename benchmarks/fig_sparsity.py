"""Paper Fig. 4: active-feature growth along the path (FW vs CD vs FISTA).
Emits CSV curves under experiments/figures/."""
from __future__ import annotations

import time
from pathlib import Path

from benchmarks.common import CSV, SCALE, load_dataset, path_grids
from repro.core import CDConfig, FISTAConfig, FWConfig, path as path_lib
from repro.core.sampling import kappa_fraction

N_POINTS = 20 if SCALE == "ci" else 100
OUT = Path(__file__).resolve().parents[1] / "experiments" / "figures"


def run(csv: CSV, dataset: str = "e2006-tfidf"):
    OUT.mkdir(parents=True, exist_ok=True)
    Xt, y, ds = load_dataset(dataset)
    p, m = Xt.shape
    lams, deltas = path_grids(Xt, y, N_POINTS)

    t0 = time.perf_counter()
    curves = {
        "fw": path_lib.fw_path(
            Xt, y, deltas,
            FWConfig(delta=1.0, kappa=kappa_fraction(p, 0.02), max_iters=20000, tol=1e-3),
        ),
        "cd": path_lib.cd_path(Xt, y, lams, CDConfig(lam=0.0, max_sweeps=200, tol=1e-3)),
        "fista_const": path_lib.fista_path(
            Xt, y, deltas, FISTAConfig(constrained=True, max_iters=300, tol=1e-3)
        ),
    }
    lines = ["solver,reg,l1,active,objective"]
    for sname, res in curves.items():
        for pt in res.points:
            lines.append(f"{sname},{pt.reg:.6g},{pt.l1:.6g},{pt.active},{pt.objective:.6g}")
    out = OUT / f"sparsity_{dataset}.csv"
    out.write_text("\n".join(lines))
    dt = time.perf_counter() - t0
    mean = {k: v.mean_active for k, v in curves.items()}
    csv.emit(
        f"fig4/{dataset}", dt * 1e6,
        f"m={m};p={p};mean_active_fw={mean['fw']:.0f};mean_active_cd={mean['cd']:.0f};"
        f"mean_active_fista={mean['fista_const']:.0f};csv={out.name}",
    )


if __name__ == "__main__":
    from repro.devices import enable_compile_cache

    enable_compile_cache()
    run(CSV())
