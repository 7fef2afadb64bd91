"""Engine refactor coverage (ISSUE 3 tentpole).

Four layers, none requiring hypothesis (these run in the minimal CI
image):
  * trajectory regression: the engine replays the PRE-refactor solvers'
    uniform-sampling runs exactly — goldens (selected coordinates,
    iteration/dot counts, objectives) were captured from the monolithic
    fw_lasso/fw_logistic/fw_elasticnet loops at the commit before the
    engine existed;
  * solver-family sparse-vs-dense parity: logistic and elastic-net on
    ``backend='sparse'`` replay the dense-XLA index stream (mirroring
    test_backend_parity for the lasso);
  * batched-vs-sequential path equivalence with converged-lane pruning
    on, for the lasso AND the extension oracles;
  * structural acceptance: the three solver modules define oracles only —
    no while_loop / sampling code of their own.
"""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ENOracle,
    FWConfig,
    LOGISTIC,
    engine,
    fw_solve,
    path as path_lib,
)
from repro.core.fw_elasticnet import en_solve
from repro.core.fw_logistic import logistic_solve
from repro.sparse import ops as sops
from repro.sparse.matrix import SparseBlockMatrix

DELTA = 150.0


def _sparsified(Xt, threshold=0.7, block_size=64):
    Xs = np.asarray(Xt).copy()
    Xs[np.abs(Xs) < threshold] = 0.0
    return jnp.asarray(Xs), SparseBlockMatrix.from_dense(Xs, block_size=block_size)


def _logistic_data(m=120, p=80, seed=0, sparse_threshold=None):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((m, p)).astype(np.float32)
    if sparse_threshold is not None:
        X[np.abs(X) < sparse_threshold] = 0.0
    w = np.zeros(p, np.float32)
    w[:5] = rng.standard_normal(5) * 2
    y = np.sign(X @ w + 0.1 * rng.standard_normal(m)).astype(np.float32)
    y[y == 0] = 1.0
    return jnp.asarray(X.T.copy()), jnp.asarray(y)


class TestPreRefactorGoldens:
    """The engine must replay the pre-refactor trajectories exactly.

    Golden values captured from the monolithic solver loops (commit
    faae249, PYTHONPATH=src on the CI CPU image) immediately before the
    engine extraction. Integer trajectory facts (iterations, dot counts,
    selected support) are asserted exactly — any deviation in the index
    stream, argmax, or stopping rule changes them; float objectives use
    a 1e-6 relative tolerance to stay robust to BLAS build differences.
    """

    def test_lasso_uniform_fixed_iterations(self, small_problem, rng_key):
        Xt, y, _ = small_problem
        cfg = FWConfig(delta=DELTA, sampling="uniform", kappa=60,
                       max_iters=300, tol=0.0, patience=10**9)
        res = fw_solve(Xt, y, cfg, rng_key)
        assert int(res.iterations) == 300
        assert int(res.n_dots) == 18000
        a = np.asarray(res.alpha)
        assert np.nonzero(a)[0].tolist() == [70, 272]
        np.testing.assert_allclose(float(res.objective), 751729.4375, rtol=1e-6)
        # coefficient values re-pinned in ISSUE 5: the fused-einsum
        # znorm2 in precompute_colstats rounds ~1 ulp differently from
        # the old sum(Xt*Xt, axis=1) sweep, shifting the line-search
        # denominators ~3e-6 relatively; support/iterations/dots/objective
        # are unchanged
        np.testing.assert_allclose(
            a[[70, 272]], [98.5285415649414, 51.47145080566406], rtol=1e-6
        )

    def test_lasso_uniform_converging_run(self, small_problem, rng_key):
        cfg = FWConfig(delta=DELTA, sampling="uniform", kappa=60,
                       max_iters=5000, tol=1e-4)
        Xt, y, _ = small_problem
        res = fw_solve(Xt, y, cfg, rng_key)
        assert int(res.iterations) == 25
        assert int(res.n_dots) == 1500
        assert bool(res.converged)
        np.testing.assert_allclose(float(res.objective), 751729.4375, rtol=1e-6)

    def test_lasso_sparse_backend_golden(self, small_problem, rng_key):
        Xt, y, _ = small_problem
        mat = SparseBlockMatrix.from_dense(np.asarray(Xt), block_size=64)
        cfg = FWConfig(delta=DELTA, sampling="uniform", kappa=60,
                       max_iters=300, tol=0.0, patience=10**9, backend="sparse")
        res = fw_solve(mat, y, cfg, rng_key)
        assert int(res.iterations) == 300
        np.testing.assert_allclose(float(res.objective), 751729.375, rtol=1e-6)

    def test_logistic_uniform_golden(self, rng_key):
        Xt, y = _logistic_data()
        cfg = FWConfig(delta=20.0, sampling="uniform", kappa=40,
                       max_iters=500, tol=0.0, patience=10**9)
        res = logistic_solve(Xt, y, cfg, rng_key)
        assert int(res.iterations) == 500
        # 40 sampled + 20 bisect + 2 endpoint + 1 gap-stall dot per step
        # (pre-refactor golden was 31000 before the sampled-gap stall
        # statistic added its O(m) dot in PR 4)
        assert int(res.n_dots) == 31500
        assert int(res.active) == 37
        np.testing.assert_allclose(float(res.objective), 3.0054101943969727, rtol=1e-6)

    def test_elasticnet_uniform_golden(self, small_problem, rng_key):
        Xt, y, _ = small_problem
        cfg = FWConfig(delta=30.0, sampling="uniform", kappa=60,
                       max_iters=800, tol=0.0, patience=10**9)
        res = en_solve(Xt, y, cfg, 1.0, rng_key)
        assert int(res.iterations) == 800
        assert int(res.n_dots) == 48000
        assert int(res.active) == 2
        np.testing.assert_allclose(float(res.objective), 828006.375, rtol=1e-6)


class TestSolverFamilySparseParity:
    """logistic_solve / en_solve accept a SparseBlockMatrix with
    FWConfig(backend='sparse') and agree with their dense-XLA results
    ('uniform' replays the same index stream, so runs are comparable
    step for step)."""

    def test_elasticnet_sparse_matches_dense(self, small_problem, rng_key):
        Xt, y, _ = small_problem
        Xd, mat = _sparsified(Xt)
        base = dict(delta=30.0, sampling="uniform", kappa=60,
                    max_iters=2000, tol=1e-5)
        res_d = en_solve(Xd, y, FWConfig(**base), 1.0, rng_key)
        res_s = en_solve(mat, y, FWConfig(backend="sparse", **base), 1.0, rng_key)
        assert int(res_s.iterations) == int(res_d.iterations)
        rel = abs(float(res_s.objective) - float(res_d.objective)) / abs(
            float(res_d.objective)
        )
        assert rel < 1e-4
        assert float(jnp.sum(jnp.abs(res_s.alpha))) <= 30.0 * (1 + 1e-4)

    def test_logistic_sparse_matches_dense(self, rng_key):
        Xt, y = _logistic_data(sparse_threshold=0.7)
        mat = SparseBlockMatrix.from_dense(np.asarray(Xt), block_size=32)
        base = dict(delta=20.0, sampling="uniform", kappa=40,
                    max_iters=1500, tol=1e-6)
        res_d = logistic_solve(Xt, y, FWConfig(**base), rng_key)
        res_s = logistic_solve(mat, y, FWConfig(backend="sparse", **base), rng_key)
        rel = abs(float(res_s.objective) - float(res_d.objective)) / max(
            abs(float(res_d.objective)), 1e-9
        )
        assert rel < 1e-3
        assert float(jnp.sum(jnp.abs(res_s.alpha))) <= 20.0 * (1 + 1e-4)

    def test_logistic_sparse_block_sampling_converges(self, rng_key):
        """Block mode drives whole aligned ELL blocks (kernel-dispatchable)."""
        Xt, y = _logistic_data(sparse_threshold=0.7)
        mat = SparseBlockMatrix.from_dense(np.asarray(Xt), block_size=32)
        cfg = FWConfig(delta=20.0, sampling="block", kappa=64,
                       max_iters=2000, tol=1e-6, backend="sparse")
        res = logistic_solve(mat, y, cfg, rng_key)
        chance = y.shape[0] * np.log(2.0)
        assert float(res.objective) < 0.5 * chance

    def test_elasticnet_pallas_matches_xla(self, small_problem, rng_key):
        """The extra-term (+l2*a) score path through the Pallas sampled-
        scores kernel agrees with the XLA gather."""
        Xt, y, _ = small_problem
        base = dict(delta=30.0, sampling="block", kappa=64, block_size=32,
                    max_iters=2000, tol=1e-5)
        res_x = en_solve(Xt, y, FWConfig(**base), 1.0, rng_key)
        res_p = en_solve(Xt, y, FWConfig(backend="pallas", **base), 1.0, rng_key)
        rel = abs(float(res_p.objective) - float(res_x.objective)) / abs(
            float(res_x.objective)
        )
        assert rel < 1e-4

    def test_logistic_pallas_matches_xla(self, rng_key):
        """'uniform' replays the XLA index stream through the 8-row-slab
        kernel path; 'full' is deterministic modulo tail padding."""
        Xt, y = _logistic_data(p=300)
        for sampling, kw, tol in (
            ("uniform", dict(kappa=40), 1e-6),
            ("full", dict(block_size=128), 1e-4),
        ):
            base = dict(delta=10.0, sampling=sampling, max_iters=800,
                        tol=1e-6, **kw)
            res_x = logistic_solve(Xt, y, FWConfig(**base), rng_key)
            res_p = logistic_solve(Xt, y, FWConfig(backend="pallas", **base),
                                   rng_key)
            rel = abs(float(res_p.objective) - float(res_x.objective)) / max(
                abs(float(res_x.objective)), 1e-9
            )
            assert rel < tol, (sampling, rel)

    def test_logistic_delta_override_traced(self, rng_key):
        """One compiled logistic solver serves multiple deltas."""
        Xt, y = _logistic_data()
        cfg = FWConfig(delta=1.0, sampling="uniform", kappa=40,
                       max_iters=500, tol=1e-5)
        objs = [
            float(logistic_solve(Xt, y, cfg, rng_key, delta=d).objective)
            for d in (2.0, 8.0, 20.0)
        ]
        assert objs[0] >= objs[1] >= objs[2]  # larger budget, lower loss


class TestSparseColstatsKernel:
    def test_fused_kernel_matches_xla_sweep(self, rng_key):
        rng = np.random.default_rng(3)
        Xs = rng.standard_normal((130, 70)).astype(np.float32)  # p not | bs
        Xs[np.abs(Xs) < 1.0] = 0.0
        mat = SparseBlockMatrix.from_dense(Xs, block_size=32)
        y = jnp.asarray(rng.standard_normal(70).astype(np.float32))
        zty_k, zn2_k = sops.sparse_colstats(mat, y, use_kernel=True, interpret=True)
        zty_r, zn2_r = sops.sparse_colstats(mat, y)
        assert zty_k.shape == (130,) and zn2_k.shape == (130,)
        np.testing.assert_allclose(np.asarray(zty_k), np.asarray(zty_r),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(zn2_k), np.asarray(zn2_r),
                                   rtol=2e-5, atol=2e-5)

    def test_solver_end_to_end_with_kernel_colstats(self, small_problem, rng_key):
        """sparse_kernel=True routes BOTH the gradient and the colstats
        through the Pallas twins (interpret off-TPU)."""
        Xt, y, _ = small_problem
        _, mat = _sparsified(Xt)
        cfg = FWConfig(delta=DELTA, sampling="block", kappa=128,
                       max_iters=2000, tol=1e-5, backend="sparse",
                       sparse_kernel=True, interpret=True)
        ref = FWConfig(delta=DELTA, sampling="block", kappa=128,
                       max_iters=2000, tol=1e-5, backend="sparse",
                       sparse_kernel=False)
        res_k = fw_solve(mat, y, cfg, rng_key)
        res_r = fw_solve(mat, y, ref, rng_key)
        rel = abs(float(res_k.objective) - float(res_r.objective)) / abs(
            float(res_r.objective)
        )
        assert rel < 1e-4


class TestBatchedPathPruning:
    def test_lasso_batched_matches_sequential_with_pruning(self, small_problem):
        Xt, y, _ = small_problem
        deltas = path_lib.delta_grid(100.0, n_points=8)
        cfg = FWConfig(delta=1.0, kappa=60, max_iters=20000, tol=1e-4)
        seq = path_lib.fw_path(Xt, y, deltas, cfg)
        bat = path_lib.fw_path_batched(Xt, y, deltas, cfg, lane_width=4)
        assert seq.saved_iters == 0  # sequential driver never prunes
        # lanes converge at different iterations, so pruning must fire
        assert bat.saved_iters > 0
        for s, b in zip(seq.points, bat.points):
            rel = abs(b.objective - s.objective) / abs(s.objective)
            assert rel < 1e-3, (s.reg, rel)

    def test_elasticnet_batched_matches_sequential(self, small_problem):
        Xt, y, _ = small_problem
        oracle = ENOracle(l2=1.0)
        deltas = np.geomspace(3.0, 30.0, 6)
        cfg = FWConfig(delta=1.0, sampling="uniform", kappa=60,
                       max_iters=5000, tol=1e-5)
        seq = path_lib.fw_path(Xt, y, deltas, cfg, oracle=oracle)
        bat = path_lib.fw_path_batched(Xt, y, deltas, cfg, lane_width=3,
                                       oracle=oracle)
        for s, b in zip(seq.points, bat.points):
            rel = abs(b.objective - s.objective) / max(abs(s.objective), 1e-9)
            assert rel < 1e-3, (s.reg, rel)
        assert bat.saved_iters >= 0

    def test_logistic_path_objective_monotone(self, rng_key):
        Xt, y = _logistic_data()
        cfg = FWConfig(delta=1.0, sampling="uniform", kappa=40,
                       max_iters=1500, tol=1e-6)
        deltas = np.geomspace(1.0, 20.0, 4)
        res = path_lib.fw_path(Xt, y, deltas, cfg, oracle=LOGISTIC)
        objs = [pt.objective for pt in res.points]
        assert objs == sorted(objs, reverse=True)  # loss falls as delta grows


class TestOracleGap:
    """The oracle ``gap()`` protocol (ISSUE 4): certified duality gaps
    with each oracle's OWN gradient, replacing the lasso-only
    ``duality_gap`` special case."""

    def test_lasso_gap_matches_legacy_duality_gap(self, small_problem, rng_key):
        from repro.core import fw_lasso
        from repro.core.fw_lasso import LASSO as lasso_oracle

        Xt, y, _ = small_problem
        cfg = FWConfig(delta=DELTA, kappa=60, max_iters=2000, tol=1e-4)
        res = fw_solve(Xt, y, cfg, rng_key)
        state = fw_lasso.init_state(Xt, y, rng_key, alpha0=res.alpha)
        legacy = float(fw_lasso.duality_gap(Xt, state, DELTA))
        new = float(lasso_oracle.gap(Xt, y, res.alpha, DELTA))
        assert abs(new - legacy) <= 1e-6 * max(abs(legacy), 1.0)

    @pytest.mark.parametrize("which", ["logistic", "elasticnet"])
    def test_extension_gap_bounds_suboptimality(self, small_problem, rng_key, which):
        """FW duality: f(alpha) - f* <= g(alpha). A long high-accuracy run
        approximates f*; a short run's certified gap must cover its own
        suboptimality (each oracle's own gradient — the lasso formula
        would be wrong here)."""
        if which == "logistic":
            Xt, y = _logistic_data()
            oracle, delta = LOGISTIC, 8.0
            solve = lambda it, a0=None: logistic_solve(
                Xt, y, FWConfig(delta=delta, kappa=40, max_iters=it,
                                tol=0.0, patience=10**9), rng_key, alpha0=a0)
        else:
            Xt, y, _ = small_problem
            oracle, delta = ENOracle(l2=1.0), 30.0
            solve = lambda it, a0=None: en_solve(
                Xt, y, FWConfig(delta=delta, kappa=60, max_iters=it,
                                tol=0.0, patience=10**9), 1.0, rng_key, alpha0=a0)
        rough = solve(60)
        ref = solve(6000)
        gap = float(oracle.gap(Xt, y, rough.alpha, delta))
        subopt = float(rough.objective) - float(ref.objective)
        assert gap >= subopt - 1e-5 * max(abs(float(ref.objective)), 1.0)
        assert gap >= 0.0

    def test_report_gap_rides_solve_and_path(self, small_problem):
        """FWConfig.report_gap surfaces SolveResult.gap / PathPoint.gap."""
        Xt, y, _ = small_problem
        cfg = FWConfig(delta=DELTA, kappa=60, max_iters=2000, tol=1e-4,
                       report_gap=True)
        res = fw_solve(Xt, y, cfg, jax.random.PRNGKey(0))
        assert res.gap is not None and np.isfinite(float(res.gap))
        deltas = path_lib.delta_grid(100.0, n_points=4)
        for driver in (path_lib.fw_path, path_lib.fw_path_batched):
            pts = driver(Xt, y, deltas, cfg).points
            assert all(np.isfinite(pt.gap) for pt in pts)
            # converged grid points certify a noise-level gap
            assert all(abs(pt.gap) < 1e-4 * abs(pt.objective) for pt in pts)
        off = FWConfig(delta=DELTA, kappa=60, max_iters=200, tol=1e-4)
        assert fw_solve(Xt, y, off, jax.random.PRNGKey(0)).gap is None


class TestGapStall:
    """The gap_rtol noise-floor stall wired into the logistic and
    elastic-net line searches (ISSUE 4 satellite): a warm start from a
    converged iterate terminates in ~patience iterations instead of
    micro-oscillating to max_iters."""

    def test_elasticnet_warm_restart_stalls_immediately(self, small_problem, rng_key):
        Xt, y, _ = small_problem
        cfg = FWConfig(delta=30.0, sampling="uniform", kappa=60,
                       max_iters=4000, tol=1e-6)
        base = en_solve(Xt, y, cfg, 1.0, rng_key)
        assert bool(base.converged)
        warm = en_solve(Xt, y, cfg, 1.0, rng_key, alpha0=base.alpha)
        assert bool(warm.converged)
        # a handful of genuine refinement steps (the restart recomputes
        # the S/F scalars exactly) + the patience-long stall tail — far
        # from max_iters=4000
        assert int(warm.iterations) <= 3 * cfg.patience

    def test_logistic_warm_restart_stalls(self, rng_key):
        rng = np.random.default_rng(0)
        X = rng.standard_normal((60, 40)).astype(np.float32)
        w0 = np.zeros(40, np.float32)
        w0[:3] = rng.standard_normal(3) * 2
        y = np.sign(X @ w0 + 0.05 * rng.standard_normal(60)).astype(np.float32)
        y[y == 0] = 1.0
        Xt, yj = jnp.asarray(X.T.copy()), jnp.asarray(y)
        cfg = FWConfig(delta=2.0, sampling="uniform", kappa=20,
                       max_iters=6000, tol=1e-4, gap_rtol=1e-3)
        base = logistic_solve(Xt, yj, cfg, rng_key)
        assert bool(base.converged)
        warm = logistic_solve(Xt, yj, cfg, rng_key, alpha0=base.alpha)
        assert bool(warm.converged)
        assert int(warm.iterations) <= int(base.iterations) // 4


class TestFusedChunk:
    """ISSUE 5 tentpole: ``FWConfig.fuse_steps`` chunked drivers + the
    ``kernels/fused_step`` megakernel.

    Acceptance: fuse_steps=8 reproduces the fuse_steps=1 uniform-lasso
    trajectory BIT-IDENTICALLY on alpha (fixed-iteration runs, where the
    stopping rule never fires) with equal iteration/dot counts, on all
    three backends; converging runs may overshoot stall stops by at most
    K-1 iterations (stopping checked between chunks, DESIGN.md
    §Stopping). EN runs through the alpha-space ledger (rounding-level
    parity on the megakernel, bit-exact on the fori-of-step executor);
    logistic falls back to the per-step loop exactly.
    """

    FIXED = dict(delta=DELTA, sampling="uniform", kappa=60,
                 max_iters=300, tol=0.0, patience=10**9)

    def test_lasso_xla_bit_identical(self, small_problem, rng_key):
        # max_iters=300 is NOT a multiple of K=8: the trailing chunk's
        # masked steps must leave the trajectory and counters exact
        Xt, y, _ = small_problem
        r1 = fw_solve(Xt, y, FWConfig(**self.FIXED), rng_key)
        r8 = fw_solve(Xt, y, FWConfig(fuse_steps=8, **self.FIXED), rng_key)
        assert int(r8.iterations) == int(r1.iterations) == 300
        assert float(r8.n_dots) == float(r1.n_dots) == 18000
        np.testing.assert_array_equal(np.asarray(r8.alpha), np.asarray(r1.alpha))
        assert np.nonzero(np.asarray(r8.alpha))[0].tolist() == [70, 272]

    def test_lasso_pallas_megakernel_bit_identical(self, small_problem, rng_key):
        Xt, y, _ = small_problem
        base = dict(self.FIXED, max_iters=120, backend="pallas")
        r1 = fw_solve(Xt, y, FWConfig(**base), rng_key)
        r8 = fw_solve(Xt, y, FWConfig(fuse_steps=8, **base), rng_key)
        assert int(r8.iterations) == int(r1.iterations) == 120
        assert float(r8.n_dots) == float(r1.n_dots)
        np.testing.assert_array_equal(np.asarray(r8.alpha), np.asarray(r1.alpha))

    def test_lasso_sparse_bit_identical_both_executors(self, small_problem, rng_key):
        Xt, y, _ = small_problem
        mat = SparseBlockMatrix.from_dense(np.asarray(Xt), block_size=64)
        base = dict(self.FIXED, max_iters=120, backend="sparse")
        r1 = fw_solve(mat, y, FWConfig(**base), rng_key)
        # the default executor (XLA-gather sparse path) chunks through the
        # fori-of-step executor: bit-identical
        r8 = fw_solve(mat, y, FWConfig(fuse_steps=8, **base), rng_key)
        np.testing.assert_array_equal(np.asarray(r8.alpha), np.asarray(r1.alpha))
        # forced kernel dispatch drives the sparse megakernel (interpret).
        # Selections/step records replay exactly (same iterations, dots,
        # support); the in-kernel eq.-10 recursion may round 1 ulp apart
        # from the XLA sparse path (program-level FMA fusion — the same
        # caveat DESIGN.md documents for the distributed objective), so
        # alpha parity is rounding-level here.
        rk = fw_solve(
            mat, y,
            FWConfig(fuse_steps=8, sparse_kernel=True, interpret=True, **base),
            rng_key,
        )
        assert int(rk.iterations) == int(r1.iterations) == 120
        assert float(rk.n_dots) == float(r1.n_dots)
        a1, ak = np.asarray(r1.alpha), np.asarray(rk.alpha)
        assert np.nonzero(a1)[0].tolist() == np.nonzero(ak)[0].tolist()
        np.testing.assert_allclose(ak, a1, rtol=1e-5, atol=1e-5)

    def test_converging_overshoot_bounded(self, small_problem, rng_key):
        Xt, y, _ = small_problem
        base = dict(delta=DELTA, sampling="uniform", kappa=60,
                    max_iters=5000, tol=1e-4)
        r1 = fw_solve(Xt, y, FWConfig(**base), rng_key)
        r8 = fw_solve(Xt, y, FWConfig(fuse_steps=8, **base), rng_key)
        assert bool(r1.converged) and bool(r8.converged)
        assert int(r1.iterations) <= int(r8.iterations) <= int(r1.iterations) + 7
        rel = abs(float(r8.objective) - float(r1.objective)) / abs(
            float(r1.objective)
        )
        assert rel < 1e-6

    def test_elasticnet_fused_parity(self, small_problem, rng_key):
        Xt, y, _ = small_problem
        base = dict(delta=30.0, sampling="uniform", kappa=60,
                    max_iters=200, tol=0.0, patience=10**9)
        # fori-of-step executor: bit-exact
        e1 = en_solve(Xt, y, FWConfig(**base), 1.0, rng_key)
        e8 = en_solve(Xt, y, FWConfig(fuse_steps=8, **base), 1.0, rng_key)
        np.testing.assert_array_equal(np.asarray(e8.alpha), np.asarray(e1.alpha))
        # megakernel: the alpha-space score reconstruction reassociates
        # scale*beta, so parity is rounding-level, not bitwise
        p1 = en_solve(Xt, y, FWConfig(backend="pallas", **base), 1.0, rng_key)
        p8 = en_solve(
            Xt, y, FWConfig(backend="pallas", fuse_steps=8, **base), 1.0, rng_key
        )
        assert int(p8.iterations) == int(p1.iterations)
        rel = abs(float(p8.objective) - float(p1.objective)) / abs(
            float(p1.objective)
        )
        assert rel < 1e-5
        np.testing.assert_allclose(
            np.asarray(p8.alpha), np.asarray(p1.alpha), rtol=5e-4, atol=5e-4
        )

    def test_logistic_falls_back_bit_identical(self, rng_key):
        Xt, y = _logistic_data()
        base = dict(delta=20.0, sampling="uniform", kappa=40,
                    max_iters=200, tol=0.0, patience=10**9)
        l1 = logistic_solve(Xt, y, FWConfig(**base), rng_key)
        l8 = logistic_solve(Xt, y, FWConfig(fuse_steps=8, **base), rng_key)
        # no fused form (bisection line search): identical per-step loop,
        # no chunk overshoot anywhere
        assert int(l8.iterations) == int(l1.iterations)
        np.testing.assert_array_equal(np.asarray(l8.alpha), np.asarray(l1.alpha))

    def test_batched_path_fused_matches_sequential(self, small_problem):
        Xt, y, _ = small_problem
        deltas = path_lib.delta_grid(100.0, n_points=6)
        cfg1 = FWConfig(delta=1.0, kappa=60, max_iters=20000, tol=1e-4)
        cfg8 = FWConfig(delta=1.0, kappa=60, max_iters=20000, tol=1e-4,
                        fuse_steps=8)
        seq = path_lib.fw_path(Xt, y, deltas, cfg1)
        bat = path_lib.fw_path_batched(Xt, y, deltas, cfg8, lane_width=3)
        for s, b in zip(seq.points, bat.points):
            rel = abs(b.objective - s.objective) / abs(s.objective)
            assert rel < 1e-3, (s.reg, rel)
            # chunked lanes may overshoot their stall stop by <= K-1
            assert b.iterations <= s.iterations + 7

    def test_megakernel_matches_xla_ref(self, small_problem, rng_key):
        """kernels/fused_step kernel vs its pure-XLA mirror on the same
        pregenerated streams (dense + sparse layouts)."""
        from repro.core.fw_lasso import LASSO
        from repro.kernels import fused_step as fs

        Xt, y, _ = small_problem
        p, m = Xt.shape
        K, kappa = 8, 32
        rng = np.random.default_rng(5)
        resid = jnp.asarray(rng.standard_normal(m).astype(np.float32))
        idx = jnp.asarray(rng.integers(0, p, (K, kappa)), jnp.int32)
        stats = engine.precompute_colstats(Xt, y)
        zty_s = jnp.take(stats.zty, idx).astype(jnp.float32)
        zn2_s = jnp.take(stats.znorm2, idx).astype(jnp.float32)
        scal = (jnp.float32(3.0), jnp.float32(1.5), jnp.float32(0.0))
        kw = dict(oracle=LASSO, eps_den=1e-12, gap_rtol=1e-6,
                  refresh_every=64, max_iters=10**6)
        args = (y, resid, scal, idx, zty_s, zn2_s, None,
                jnp.int32(0), jnp.float32(40.0))
        def check(got, want):
            # selected coordinates + stall flags exact; float records and
            # the final residual/scalars to gather-order rounding
            np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
            np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(want[3]))
            for g, w in ((got[1], want[1]), (got[2], want[2]), (got[4], want[4])):
                np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                           rtol=1e-5, atol=1e-5)
            for g, w in zip(got[5], want[5]):
                np.testing.assert_allclose(float(g), float(w), rtol=1e-4)

        got = fs.dense_fused_chunk(Xt, *args, interpret=True, **kw)
        want = fs.dense_fused_chunk_ref(Xt, *args, **kw)
        check(got, want)

        mat = SparseBlockMatrix.from_dense(np.asarray(Xt), block_size=64)
        got_s = fs.sparse_fused_chunk(mat.values, mat.rows, *args,
                                      interpret=True, **kw)
        want_s = fs.sparse_fused_chunk_ref(mat.values, mat.rows, *args, **kw)
        check(got_s, want_s)

    def test_n_dots_accounting_is_overflow_safe(self, small_problem, rng_key):
        """ISSUE 5 satellite: the dot counter no longer wraps int32 (p=4M
        full sampling overflows after ~500 iterations). Without x64 the
        counter is f32 — exact for every pinned golden, monotone and
        positive far past 2^31."""
        assert engine.dot_dtype() in (jnp.int64, jnp.float32)
        Xt, y, _ = small_problem
        cfg = FWConfig(delta=DELTA, sampling="full", max_iters=10, tol=0.0,
                       patience=10**9)
        res = fw_solve(Xt, y, cfg, rng_key)
        # full sampling scores every real coordinate once per iteration
        # (patience=1 under 'full', so the run may stop before max_iters)
        assert float(res.n_dots) == int(res.iterations) * Xt.shape[0]
        big = jnp.zeros((), engine.dot_dtype()) + 2.0**31
        stepped = big + 4_000_000
        assert float(stepped) > float(big) > 0  # int32 would have wrapped


class TestEngineStructure:
    """Acceptance: ONE hot loop — the solver modules define oracles only."""

    @pytest.mark.parametrize(
        "module", ["fw_lasso", "fw_logistic", "fw_elasticnet"]
    )
    def test_solver_modules_have_no_loop_or_sampling(self, module):
        import importlib

        src = inspect.getsource(importlib.import_module(f"repro.core.{module}"))
        assert "while_loop" not in src
        assert "random.randint" not in src and "random.choice" not in src

    def test_one_shared_engine_loop(self):
        src = inspect.getsource(engine)
        assert src.count("jax.lax.while_loop") == 2  # solve + solve_batched

    def test_oracles_are_static_jit_keys(self):
        assert hash(ENOracle(l2=0.5)) == hash(ENOracle(l2=0.5))
        assert ENOracle(l2=0.5) == ENOracle(l2=0.5)
        assert ENOracle(l2=0.5) != ENOracle(l2=1.0)
