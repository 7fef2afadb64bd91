"""The paper's headline experiment (abstract): the COMPLETE regularization
path on a problem with millions of variables in about a minute.

E2006-log1p-like proxy at full feature count (p = 4,272,227). Two builds:

* dense — reduced sample count (m) so the (m, p) matrix fits RAM; the
  per-iteration cost of stochastic FW is O(kappa * m), so the scaling
  story is faithful.
* ``--backend sparse`` — the block-ELL sparse build (DESIGN.md §Sparse)
  at the dataset's TRUE column density: storage is O(nnz), so the
  paper-size problem needs ~100s of MB instead of ~18 GB and the
  per-iteration cost drops to O(kappa * nnz_max).

    PYTHONPATH=src python examples/lasso_fullpath_4m.py            # p=1M default
    PYTHONPATH=src python examples/lasso_fullpath_4m.py --paper-size  # p=4.27M (needs ~18GB RAM)
    PYTHONPATH=src python examples/lasso_fullpath_4m.py --paper-size --backend sparse  # fits anywhere
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import FWConfig, path as path_lib
from repro.core.sampling import kappa_fraction
from repro.data.proxies import make_sparse_coo
from repro.data.synthetic import Dataset, standardize
from repro.sparse import SparseBlockMatrix


def make_wide_problem(p: int, m: int, n_rel: int, seed: int = 0) -> Dataset:
    """fp32 end-to-end (the generic standardize() upcasts to f64 — too slow
    at gigabyte scale); columns come out zero-mean unit-norm directly."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((m, p), dtype=np.float32)
    X -= X.mean(axis=0, dtype=np.float32)
    X /= np.sqrt((X * X).sum(axis=0, dtype=np.float32)) + 1e-12
    coef = np.zeros(p, np.float32)
    support = rng.choice(p, n_rel, replace=False)
    coef[support] = rng.standard_normal(n_rel).astype(np.float32) * 10
    y = X @ coef + 0.05 * rng.standard_normal(m).astype(np.float32)
    y -= y.mean()
    return Dataset(X, y.astype(np.float32), None, None, coef, f"wide-{p}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper-size", action="store_true", help="p=4,272,227")
    ap.add_argument("--p", type=int, default=500_000)
    ap.add_argument("--m", type=int, default=800)
    ap.add_argument("--points", type=int, default=100)
    ap.add_argument("--frac", type=float, default=0.01, help="|S| as fraction of p")
    ap.add_argument("--driver", choices=("sequential", "batched"), default="batched",
                    help="fw_path (one delta at a time) or fw_path_batched lanes")
    ap.add_argument("--backend", choices=("xla", "pallas", "sparse"), default="xla",
                    help="iteration engine; 'pallas' uses the fused TPU kernels, "
                         "'sparse' the block-ELL subsystem (no dense build)")
    ap.add_argument("--density", type=float, default=0.002,
                    help="column density for --backend sparse (E2006-log1p: 0.002)")
    args = ap.parse_args()
    p = 4_272_227 if args.paper_size else args.p

    t0 = time.perf_counter()
    if args.backend == "sparse":
        print(f"== generating SPARSE wide problem p={p:,} m={args.m} "
              f"density={args.density:g} (dense would be "
              f"{p * args.m * 4 / 1e9:.1f} GB)")
        rows, cols, vals, y_np, coef = make_sparse_coo(
            args.m, p, args.density, n_relevant=300, seed=0
        )
        Xt = SparseBlockMatrix.from_coo(rows, cols, vals, (args.m, p), block_size=256)
        y = jnp.asarray(y_np)
        print(f"   built in {time.perf_counter()-t0:.1f}s "
              f"({Xt.nbytes / 1e9:.2f} GB block-ELL, nnz_max={Xt.nnz_max})")
    else:
        print(f"== generating wide problem p={p:,} m={args.m} "
              f"({p * args.m * 4 / 1e9:.1f} GB design matrix)")
        ds = make_wide_problem(p, args.m, n_rel=300)
        Xt = jnp.asarray(np.ascontiguousarray(ds.X.T))
        y = jnp.asarray(ds.y)
        coef = ds.coef
        print(f"   built in {time.perf_counter()-t0:.1f}s")

    kappa = kappa_fraction(p, args.frac)
    # delta_max: the generator's true coefficients give an oracle l1 budget.
    # 0.5x keeps the path in the sparse regime where FW shines (the paper's
    # use case); the loose/dense end is FW's known slow regime (EXPERIMENTS
    # §Perf). A CD reference solve (the paper's protocol) is exercised at
    # smaller scale in benchmarks/ — too expensive at p~10^6 for a demo.
    delta_max = 0.5 * float(np.abs(coef).sum())
    deltas = path_lib.delta_grid(delta_max, n_points=args.points)
    # pallas wants aligned blocks (uniform reads an 8-row slab per sampled
    # row and leaves the MXU idle — DESIGN.md §4.5); block sampling
    # preserves Lemma 1
    sampling = "block" if args.backend == "pallas" else "uniform"
    cfg = FWConfig(delta=1.0, kappa=kappa, sampling=sampling,
                   max_iters=5000, tol=1e-3, backend=args.backend)

    print(f"== full path: {args.points} points, kappa={kappa:,} ({args.frac:.0%} of p), "
          f"driver={args.driver}, backend={args.backend}")
    t0 = time.perf_counter()
    if args.driver == "batched":
        res = path_lib.fw_path_batched(Xt, y, deltas, cfg)
    else:
        res = path_lib.fw_path(Xt, y, deltas, cfg)
    dt = time.perf_counter() - t0
    print(f"   PATH DONE in {dt:.1f}s  ({dt/args.points*1000:.0f} ms/point)")
    print(f"   total iters={res.total_iters} dots={res.total_dots:,} "
          f"mean_active={res.mean_active:.1f}")
    last = res.points[-1]
    print(f"   densest point: active={last.active} obj={last.objective:.4f}")


if __name__ == "__main__":
    from repro.devices import enable_compile_cache

    enable_compile_cache()
    main()
