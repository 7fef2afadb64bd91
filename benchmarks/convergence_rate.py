"""Convergence-rate benchmarks.

Two sections:

* Proposition 2 validation — E[f(a_k)] - f* vs the 4C~_f/(k+2) bound
  (``run``, the historical section registered in benchmarks.run).
* Step-rule comparison — certified-gap-vs-n_dots curves for every
  ``FWConfig.step_rule`` (classic / away / pairwise / partan / lazy) on a
  pinned correlated design (``run_step_rules``). Correlated columns are
  where the rule zoo separates: classic FW zig-zags between near-parallel
  atoms while away/pairwise prune them, so the curves make the per-rule
  trade-off (progress per gradient dot) visible and diffable across PRs.

Both sections mirror their records into BENCH_convergence.json
(common.BenchJSON) — CI uploads that file as an artifact.
"""
from __future__ import annotations

import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import CSV, BenchJSON, load_dataset
from repro.core import FISTAConfig, FWConfig, LASSO, baselines, engine, fw_solve_with_history

OUT = Path(__file__).resolve().parents[1] / "experiments" / "figures"

# ---------------------------------------------------------------------------
# step-rule section: pinned correlated design (AR(1) columns, strong
# signals, delta well inside ||coef||_1 — the regime tests/test_step_rules.py
# certifies acceptance on)
STEP_RULES = ("classic", "away", "pairwise", "partan", "lazy")
RULE_DELTA = 40.0
RULE_BUDGETS = (16, 32, 64, 128, 256, 512, 1024)


def _corr_design(m=300, p=120, rho=0.6, k=10, scale=50.0, seed=11):
    rng = np.random.default_rng(seed)
    Z = rng.standard_normal((m, p)).astype(np.float32)
    X = np.empty_like(Z)
    X[:, 0] = Z[:, 0]
    for j in range(1, p):
        X[:, j] = rho * X[:, j - 1] + np.sqrt(1.0 - rho**2) * Z[:, j]
    coef = np.zeros(p, np.float32)
    coef[rng.choice(p, k, replace=False)] = (
        rng.standard_normal(k).astype(np.float32) * scale
    )
    y = X @ coef + rng.standard_normal(m).astype(np.float32)
    return jnp.asarray(X.T.copy()), jnp.asarray(y.astype(np.float32))


def _rule_cfg(rule: str, max_iters: int, tol: float, patience: int) -> FWConfig:
    return FWConfig(
        delta=RULE_DELTA, kappa=48, sampling="uniform", step_rule=rule,
        max_iters=max_iters, tol=tol, patience=patience,
    )


def run_step_rules(csv: CSV, js: BenchJSON | None = None):
    """Gap-vs-n_dots curve per step rule + a solve-to-tolerance summary."""
    own_js = js is None
    if own_js:
        js = BenchJSON("BENCH_convergence.json")
    Xt, y = _corr_design()
    key = jax.random.PRNGKey(1)
    for rule in STEP_RULES:
        t0 = time.perf_counter()
        # fixed-budget curve: tol=0 so every point runs its full budget
        curve = []
        for budget in RULE_BUDGETS:
            res = engine.solve(
                LASSO, Xt, y, _rule_cfg(rule, budget, 0.0, 10**9), key
            )
            gap = float(LASSO.gap(Xt, y, res.alpha, RULE_DELTA, None))
            curve.append(
                {"iters": int(res.iterations), "n_dots": int(res.n_dots),
                 "gap": gap, "objective": float(res.objective)}
            )
        # solve-to-tolerance summary (the §Stopping rule the tests pin)
        res = engine.solve(LASSO, Xt, y, _rule_cfg(rule, 1500, 1e-4, 20), key)
        gap = float(LASSO.gap(Xt, y, res.alpha, RULE_DELTA, None))
        dt = time.perf_counter() - t0
        csv.emit(
            f"convergence/step_rule/{rule}", dt * 1e6,
            f"iters={int(res.iterations)};n_dots={int(res.n_dots)};"
            f"gap={gap:.4g};converged={bool(res.converged)}",
        )
        js.add(
            f"convergence/step_rule/{rule}",
            rule=rule, delta=RULE_DELTA, shape=list(Xt.shape),
            curve=curve, iterations=int(res.iterations),
            n_dots=int(res.n_dots), gap=gap,
            objective=float(res.objective), converged=bool(res.converged),
        )
    if own_js:
        js.write()


def run(csv: CSV, dataset: str = "synthetic-10000", n_iters: int = 400, n_seeds: int = 5):
    OUT.mkdir(parents=True, exist_ok=True)
    js = BenchJSON("BENCH_convergence.json")
    Xt, y, _ = load_dataset(dataset)
    p, m = Xt.shape
    delta = 50.0

    t0 = time.perf_counter()
    ref = baselines.fista_solve(
        Xt, y, FISTAConfig(delta=delta, constrained=True, max_iters=20000, tol=1e-12),
        jax.random.PRNGKey(0),
    )
    fstar = float(ref.objective)

    cfg = FWConfig(delta=delta, kappa=max(p // 100, 64), sampling="uniform",
                   max_iters=10**6, tol=0.0, patience=10**9)
    hists = []
    for seed in range(n_seeds):
        _, h = fw_solve_with_history(Xt, y, cfg, jax.random.PRNGKey(seed), n_iters)
        hists.append(np.asarray(h))
    mean_h = np.mean(hists, 0) - fstar

    L = float(np.linalg.norm(np.asarray(Xt), 2) ** 2)
    Cf = 0.5 * (2 * delta) ** 2 * L
    ks = np.arange(1, n_iters + 1)
    bound = 4 * Cf / (ks + 2)
    lines = ["k,mean_gap,bound"] + [
        f"{k},{g:.6g},{b:.6g}" for k, g, b in zip(ks, mean_h, bound)
    ]
    (OUT / f"convergence_{dataset}.csv").write_text("\n".join(lines))
    frac_below = float(np.mean(mean_h[5:] <= bound[5:]))
    # empirical rate exponent: fit gap ~ k^alpha on the tail
    tail = slice(n_iters // 4, None)
    pos = mean_h[tail] > 1e-12
    alpha = (
        np.polyfit(np.log(ks[tail][pos]), np.log(mean_h[tail][pos]), 1)[0]
        if pos.sum() > 10 else float("nan")
    )
    dt = time.perf_counter() - t0
    csv.emit(
        f"prop2/{dataset}", dt * 1e6,
        f"frac_under_bound={frac_below:.3f};empirical_rate_k^{alpha:.2f};Cf={Cf:.3g}",
    )
    js.add(
        f"prop2/{dataset}",
        dataset=dataset, n_iters=n_iters, n_seeds=n_seeds,
        frac_under_bound=frac_below, empirical_rate=float(alpha), Cf=Cf,
    )
    run_step_rules(csv, js)
    js.write()


if __name__ == "__main__":
    from repro.devices import enable_compile_cache

    enable_compile_cache()
    run_step_rules(CSV())
