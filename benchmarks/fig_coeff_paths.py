"""Paper Figs. 1-2: growth of the 10 most significant coefficients along
the path, FW vs CD (the paper's 'sanity check')."""
from __future__ import annotations

import time
from pathlib import Path

import numpy as np

from benchmarks.common import CSV, SCALE, load_dataset, path_grids
from repro.core import CDConfig, FWConfig, path as path_lib
from repro.core.sampling import kappa_confidence

N_POINTS = 20 if SCALE == "ci" else 100
OUT = Path(__file__).resolve().parents[1] / "experiments" / "figures"


def _dense(pt, p):
    a = np.zeros(p)
    a[pt.alpha_nnz_idx] = pt.alpha_nnz_val
    return a


def run(csv: CSV, dataset: str = "synthetic-10000"):
    OUT.mkdir(parents=True, exist_ok=True)
    Xt, y, ds = load_dataset(dataset)
    p, m = Xt.shape
    lams, deltas = path_grids(Xt, y, N_POINTS)

    t0 = time.perf_counter()
    # high-precision CD reference defines the "relevant" variables (paper §5.1)
    cd = path_lib.cd_path(Xt, y, lams, CDConfig(lam=0.0, max_sweeps=400, tol=1e-5))
    mean_abs = np.zeros(p)
    for pt in cd.points:
        mean_abs[pt.alpha_nnz_idx] += np.abs(pt.alpha_nnz_val)
    top10 = np.argsort(-mean_abs)[:10]

    # paper §5.1 sampling: kappa from the confidence rule with the empirical
    # sparsity estimate (mean active along the CD path)
    s_hat = max(1, int(round(cd.mean_active)))
    kappa = kappa_confidence(p, s_hat, 0.99)
    fw = path_lib.fw_path(
        Xt, y, deltas, FWConfig(delta=1.0, kappa=kappa, max_iters=20000, tol=1e-3)
    )

    lines = ["solver,point,reg," + ",".join(f"c{i}" for i in top10)]
    for sname, res in (("cd", cd), ("fw", fw)):
        for j, pt in enumerate(res.points):
            a = _dense(pt, p)
            vals = ",".join(f"{a[i]:.6g}" for i in top10)
            lines.append(f"{sname},{j},{pt.reg:.6g},{vals}")
    out = OUT / f"coeff_paths_{dataset}.csv"
    out.write_text("\n".join(lines))

    # agreement metric: sign+support overlap of top10 at the densest point
    a_cd = _dense(cd.points[-1], p)[top10]
    a_fw = _dense(fw.points[-1], p)[top10]
    agree = float(np.mean(np.sign(a_cd) == np.sign(a_fw)))
    dt = time.perf_counter() - t0
    csv.emit(
        f"fig12/{dataset}", dt * 1e6,
        f"kappa={kappa};s_hat={s_hat};top10_sign_agreement={agree:.2f};csv={out.name}",
    )


if __name__ == "__main__":
    from repro.devices import enable_compile_cache

    enable_compile_cache()
    run(CSV())
