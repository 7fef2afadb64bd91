"""Solver family on one engine: lasso / logistic / elastic-net, dense
and sparse, through the shared backend-dispatched FW hot loop
(DESIGN.md §Engine).

The paper (§6) presents logistic regression and the elastic-net as
"easily obtained" extensions of Algorithm 2 — same randomized
linear-minimization oracle, same O(m) state recursions, different
gradient-vs-state and line search. This example shows exactly that:
each solver is the same engine under a different problem oracle, so the
block-ELL sparse backend and the batched multi-delta path driver (with
converged-lane pruning) work for all three without per-solver code.

    PYTHONPATH=src python examples/solver_family.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ENOracle, FWConfig, LOGISTIC, engine, fw_solve
from repro.core import path as path_lib
from repro.core.fw_elasticnet import en_solve
from repro.core.fw_logistic import logistic_solve
from repro.data import make_sparse_proxy


def main():
    print("== data: sparse-native e2006-tfidf proxy (block-ELL, no dense X)")
    ds = make_sparse_proxy("e2006-tfidf", scale=0.02, seed=0)
    mat, y = ds.mat, jnp.asarray(ds.y)
    p, m = mat.shape
    print(f"   p={p} features, m={m} samples, nnz_max={mat.nnz_max}, "
          f"storage={mat.nbytes/1e6:.1f} MB (dense would be {4*p*m/1e6:.1f} MB)")
    Xt_dense = mat.to_dense()  # feasible at example scale, for comparison only
    y_cls = jnp.sign(y) + (y == 0)  # {-1,+1} labels for the logistic oracle
    key = jax.random.PRNGKey(0)
    delta = 0.5 * float(np.abs(np.asarray(ds.coef)).sum())

    # --- one engine, three oracles, two backends each ---------------------
    base = dict(delta=delta, kappa=max(64, p // 100), sampling="uniform",
                max_iters=10_000, tol=1e-4)
    runs = [
        ("lasso", lambda A, cfg: fw_solve(A, y, cfg, key)),
        ("logistic", lambda A, cfg: logistic_solve(A, y_cls, cfg, key)),
        ("elastic-net l2=1", lambda A, cfg: en_solve(A, y, cfg, 1.0, key)),
    ]
    for name, solve in runs:
        for backend, A in (("xla", Xt_dense), ("sparse", mat)):
            cfg = FWConfig(backend=backend, **base)
            res = solve(A, cfg)  # compile
            t0 = time.perf_counter()
            res = solve(A, cfg)
            res.alpha.block_until_ready()
            dt = time.perf_counter() - t0
            print(f"   {name:16s} {backend:6s}: obj={float(res.objective):12.4f} "
                  f"active={int(res.active):4d} iters={int(res.iterations):5d} "
                  f"{dt*1e3:7.1f} ms")

    # --- family regularization paths on the batched pruned driver ---------
    print("== batched multi-delta paths (converged lanes pruned early)")
    deltas = path_lib.delta_grid(delta, n_points=8)
    cfg = FWConfig(delta=1.0, kappa=max(64, p // 100), sampling="uniform",
                   max_iters=10_000, tol=1e-4, backend="sparse")
    for name, oracle, yy in (
        ("lasso", None, y),
        ("logistic", LOGISTIC, y_cls),
        ("elastic-net", ENOracle(l2=1.0), y),
    ):
        res = path_lib.fw_path_batched(mat, yy, deltas, cfg, lane_width=4,
                                       oracle=oracle)
        objs = [pt.objective for pt in res.points]
        print(f"   {name:12s}: {len(res.points)} grid points in "
              f"{res.total_seconds:.2f}s, saved {res.saved_iters} lane-iters, "
              f"obj {objs[0]:.3g} -> {objs[-1]:.3g}")

    # --- fused sparse colstats kernel (setup pass) ------------------------
    from repro.devices import pallas_interpret
    from repro.sparse import ops as sops

    zty_k, zn2_k = sops.sparse_colstats(
        mat, y, use_kernel=True, interpret=pallas_interpret()
    )
    zty_r, zn2_r = sops.sparse_colstats(mat, y)
    print("== fused sparse colstats kernel max |diff| vs XLA sweep:",
          float(jnp.max(jnp.abs(zty_k - zty_r))),
          float(jnp.max(jnp.abs(zn2_k - zn2_r))))


if __name__ == "__main__":
    from repro.devices import enable_compile_cache

    enable_compile_cache()
    main()
