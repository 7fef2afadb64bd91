"""Sparse column-block subsystem (ISSUE 2 tentpole): storage format,
ops-vs-dense oracles, the sparse_grad Pallas kernel, and end-to-end
solver/path parity of ``backend='sparse'`` against the dense XLA path.

Shapes are deliberately NON-DIVISIBLE (p % block_size != 0) so the padded
tail block is always exercised, and the kernel tests run both dtypes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import FWConfig, fw_solve, path as path_lib
from repro.core.fw_lasso import duality_gap
from repro.kernels.sparse_grad.ref import sparse_sampled_scores_ref
from repro.kernels.sparse_grad.sparse_grad import sparse_sampled_scores
from repro.sparse import SparseBlockMatrix
from repro.sparse import ops as sops

DELTA = 150.0


def _sparse_dense_pair(p, m, density, seed, block_size=128, dtype=np.float32):
    """(dense Xt, SparseBlockMatrix, residual) with column-sparse structure."""
    rng = np.random.default_rng(seed)
    Xt = rng.standard_normal((p, m)).astype(dtype)
    Xt[rng.random((p, m)) > density] = 0.0
    mat = SparseBlockMatrix.from_dense(Xt, block_size=block_size)
    r = rng.standard_normal(m).astype(dtype)
    return Xt, mat, r


@pytest.fixture(scope="module")
def sparse_problem(small_problem):
    """The session small_problem (p=300, m=80) sparsified at density 0.05
    and converted; p=300 is NOT divisible by block_size=128."""
    rng = np.random.default_rng(7)
    Xt = np.asarray(small_problem[0]).copy()
    Xt[rng.random(Xt.shape) > 0.05] = 0.0
    # renormalize columns so the solver sees the §4.1 conditioning contract
    norms = np.sqrt((Xt * Xt).sum(axis=1, keepdims=True))
    norms[norms < 1e-12] = 1.0
    Xt = (Xt / norms).astype(np.float32)
    mat = SparseBlockMatrix.from_dense(Xt, block_size=128)
    return jnp.asarray(Xt), mat, small_problem[1]


class TestMatrixFormat:
    @pytest.mark.parametrize("p,m,bs", [(300, 80, 128), (777, 50, 256), (64, 33, 64)])
    def test_dense_roundtrip_nondivisible(self, p, m, bs):
        Xt, mat, _ = _sparse_dense_pair(p, m, 0.07, seed=p)
        assert mat.shape == (p, m)
        assert mat.p_padded % bs == 0 or mat.block_size != bs
        np.testing.assert_allclose(np.asarray(mat.to_dense()), Xt, atol=1e-7)

    def test_from_coo_matches_from_dense(self):
        Xt, mat, _ = _sparse_dense_pair(130, 40, 0.1, seed=1)
        feat, samp = np.nonzero(Xt)
        mat2 = SparseBlockMatrix.from_coo(
            samp, feat, Xt[feat, samp], (40, 130), block_size=128
        )
        np.testing.assert_array_equal(np.asarray(mat.values), np.asarray(mat2.values))
        np.testing.assert_array_equal(np.asarray(mat.rows), np.asarray(mat2.rows))

    def test_nnz_budget_too_small_raises(self):
        Xt, _, _ = _sparse_dense_pair(64, 32, 0.5, seed=2, block_size=64)
        required = int((np.asarray(Xt) != 0).sum(axis=1).max())
        with pytest.raises(ValueError, match="nnz budget"):
            SparseBlockMatrix.from_dense(Xt, block_size=64, nnz_max=required - 1)
        # exactly-sufficient budget is accepted
        mat = SparseBlockMatrix.from_dense(Xt, block_size=64, nnz_max=required)
        assert mat.nnz_max == required

    def test_index_out_of_range_raises(self):
        with pytest.raises(ValueError, match="out of range"):
            SparseBlockMatrix.from_coo([5], [0], [1.0], (4, 8))
        with pytest.raises(ValueError, match="out of range"):
            SparseBlockMatrix.from_coo([0], [9], [1.0], (4, 8))

    def test_pytree_roundtrip(self):
        """jit/vmap compatibility: the matrix flattens with static geometry."""
        _, mat, _ = _sparse_dense_pair(70, 20, 0.2, seed=3, block_size=32)
        leaves, treedef = jax.tree_util.tree_flatten(mat)
        assert len(leaves) == 2
        mat2 = jax.tree_util.tree_unflatten(treedef, leaves)
        assert mat2.shape == mat.shape and mat2.block_size == mat.block_size

    @settings(max_examples=20, deadline=None)
    @given(st.data())
    def test_coo_roundtrip_property(self, data):
        """Any duplicate-free COO set survives blocking + densification."""
        m = data.draw(st.integers(min_value=1, max_value=30), label="m")
        p = data.draw(st.integers(min_value=1, max_value=200), label="p")
        bs = data.draw(st.sampled_from([8, 32, 128]), label="bs")
        n_entries = data.draw(st.integers(min_value=0, max_value=min(150, m * p)))
        seed = data.draw(st.integers(min_value=0, max_value=2**16))
        rng = np.random.default_rng(seed)
        flat = rng.choice(m * p, size=n_entries, replace=False)
        rows, cols = flat // p, flat % p
        vals = rng.standard_normal(n_entries).astype(np.float32)
        vals[vals == 0.0] = 1.0
        mat = SparseBlockMatrix.from_coo(rows, cols, vals, (m, p), block_size=bs)
        dense = np.zeros((p, m), np.float32)
        dense[cols, rows] = vals
        np.testing.assert_allclose(np.asarray(mat.to_dense()), dense, atol=1e-7)


class TestOpsVsDense:
    def test_block_scores_with_padded_tail(self):
        Xt, mat, r = _sparse_dense_pair(300, 80, 0.1, seed=4)
        blk = jnp.asarray([0, 2], jnp.int32)  # block 2 = rows 256..299 + pad
        got = sops.sparse_block_scores(mat, jnp.asarray(r), blk)
        idx = (np.asarray(blk)[:, None] * 128 + np.arange(128)).reshape(-1)
        valid = idx < 300
        want = -(Xt[idx[valid]] @ r)
        np.testing.assert_allclose(np.asarray(got)[valid], want, rtol=2e-5, atol=2e-4)
        np.testing.assert_array_equal(np.asarray(got)[~valid], 0.0)

    def test_fw_vertex_masks_padded_features(self):
        Xt, mat, r = _sparse_dense_pair(130, 64, 0.3, seed=5)
        blk = jnp.arange(mat.nblocks, dtype=jnp.int32)  # 126 padded features
        i_star, g_star = sops.sparse_fw_vertex(mat, jnp.asarray(r), blk)
        assert int(i_star) < 130
        grad = -(Xt @ r)
        assert int(i_star) == int(np.argmax(np.abs(grad)))
        np.testing.assert_allclose(float(g_star), grad[int(i_star)], rtol=2e-5, atol=2e-4)

    def test_gather_vertex_uniform_indices(self):
        Xt, mat, r = _sparse_dense_pair(300, 40, 0.1, seed=6)
        idx = jnp.asarray([3, 77, 130, 299, 5], jnp.int32)
        i_star, g_star = sops.sparse_gather_vertex(mat, jnp.asarray(r), idx)
        scores = -(Xt[np.asarray(idx)] @ r)
        j = int(np.argmax(np.abs(scores)))
        assert int(i_star) == int(idx[j])
        np.testing.assert_allclose(float(g_star), scores[j], rtol=2e-5, atol=2e-4)

    def test_colstats_and_matvecs(self):
        Xt, mat, _ = _sparse_dense_pair(300, 80, 0.1, seed=8)
        rng = np.random.default_rng(0)
        y = rng.standard_normal(80).astype(np.float32)
        beta = rng.standard_normal(300).astype(np.float32)
        zty, zn2 = sops.sparse_colstats(mat, jnp.asarray(y))
        np.testing.assert_allclose(np.asarray(zty), Xt @ y, rtol=2e-5, atol=2e-4)
        np.testing.assert_allclose(np.asarray(zn2), (Xt * Xt).sum(1), rtol=2e-5, atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(sops.sparse_matvec(mat, jnp.asarray(beta))),
            beta @ Xt, rtol=2e-4, atol=2e-3,
        )
        np.testing.assert_allclose(
            np.asarray(sops.sparse_transpose_matvec(mat, jnp.asarray(y))),
            Xt @ y, rtol=2e-5, atol=2e-4,
        )

    def test_residual_update_scatter(self):
        Xt, mat, r = _sparse_dense_pair(300, 80, 0.1, seed=9)
        rng = np.random.default_rng(1)
        y = rng.standard_normal(80).astype(np.float32)
        i = 137
        cv, cr = sops.sparse_column(mat, jnp.asarray(i))
        got = sops.sparse_residual_update(
            jnp.asarray(r), jnp.asarray(y), cv, cr,
            jnp.asarray(0.25), jnp.asarray(-1.5),
        )
        want = (1 - 0.25) * r + 0.25 * (y - (-1.5) * Xt[i])
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-4)


class TestSparseKernel:
    """kernels/sparse_grad interpret-mode vs the XLA oracle."""

    @pytest.mark.parametrize("p,m,bs", [(300, 80, 128), (777, 300, 256)])
    def test_kernel_matches_ref_nondivisible(self, p, m, bs):
        _, mat, r = _sparse_dense_pair(p, m, 0.05, seed=p, block_size=bs)
        blk = jnp.arange(mat.nblocks, dtype=jnp.int32)
        got = sparse_sampled_scores(mat.values, mat.rows, jnp.asarray(r), blk,
                                    interpret=True)
        want = sparse_sampled_scores_ref(mat.values, mat.rows, jnp.asarray(r), blk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-4)

    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_kernel_dtypes(self, dtype):
        _, mat, r = _sparse_dense_pair(300, 96, 0.1, seed=11)
        mat = mat.astype(dtype)
        r = jnp.asarray(r).astype(dtype)
        blk = jnp.asarray([0, 2], jnp.int32)
        got = sparse_sampled_scores(mat.values, mat.rows, r, blk, interpret=True)
        want = sparse_sampled_scores_ref(mat.values, mat.rows, r, blk)
        tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=tol, atol=tol * 10)
        assert got.dtype == jnp.float32  # f32 accumulation contract


class TestLaneGather:
    """The in-kernel gather/scatter of ``kernels/lanes`` (the form the
    chip's compiler accepts: the vector in (ceil(m/128), 128) lane layout,
    one lane-gather per sublane row) must match plain indexing exactly,
    across several lane rows (m > 128) and several lane chunks
    (nnz_max > 128)."""

    @pytest.mark.parametrize("p,m,bs,density", [(300, 80, 128, 0.05),
                                                (130, 700, 32, 0.3)])
    def test_sampled_scores_multi_lane(self, p, m, bs, density):
        _, mat, r = _sparse_dense_pair(p, m, density, seed=p, block_size=bs)
        blk = jnp.arange(mat.nblocks, dtype=jnp.int32)
        got = sparse_sampled_scores(mat.values, mat.rows, jnp.asarray(r),
                                    blk, interpret=True)
        want = sparse_sampled_scores_ref(mat.values, mat.rows,
                                         jnp.asarray(r), blk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-5)

    def test_colstats_kernel_matches_xla(self):
        _, mat, r = _sparse_dense_pair(130, 700, 0.3, seed=9, block_size=32)
        assert mat.nnz_max > 128 and mat.m > 128
        y = jnp.asarray(r)
        z_k, n_k = sops.sparse_colstats(mat, y, use_kernel=True, interpret=True)
        z_x, n_x = sops.sparse_colstats(mat, y, use_kernel=False)
        np.testing.assert_allclose(np.asarray(z_k), np.asarray(z_x),
                                   rtol=1e-6, atol=1e-5)
        np.testing.assert_allclose(np.asarray(n_k), np.asarray(n_x),
                                   rtol=1e-6, atol=1e-5)

    def test_solver_end_to_end_kernel_vs_xla(self, sparse_problem, rng_key):
        """Block sampling draws the same blocks either way, so the kernel
        and the XLA gather solve the same problem to rounding."""
        _, mat, y = sparse_problem
        base = dict(delta=DELTA, sampling="block", kappa=128, max_iters=1500,
                    tol=1e-4, backend="sparse", interpret=True)
        res_k = fw_solve(mat, y, FWConfig(sparse_kernel=True, **base), rng_key)
        res_x = fw_solve(mat, y, FWConfig(sparse_kernel=False, **base), rng_key)
        rel = abs(float(res_k.objective) - float(res_x.objective)) / abs(
            float(res_x.objective)
        )
        assert rel < 1e-4

    def test_gather_and_scatter_are_exact(self):
        from jax.experimental import pallas as pl

        from repro.kernels import lanes

        rng = np.random.default_rng(3)
        m, C = 300, 140
        v = rng.standard_normal(m).astype(np.float32)
        rows = rng.integers(0, m, (8, C)).astype(np.int32)
        scat = rng.permutation(m)[:C].astype(np.int32)[None, :]
        add = rng.standard_normal((1, C)).astype(np.float32)

        def kernel(v_ref, rows_ref, srows_ref, add_ref, g_ref, out_ref):
            g_ref[...] = lanes.gather_lanes(v_ref, rows_ref[...])
            out_ref[...] = v_ref[...]
            lanes.scatter_add_lanes(out_ref, srows_ref[...], add_ref[...])

        v2d = lanes.to_lanes(jnp.asarray(v))
        g, out = pl.pallas_call(
            kernel,
            out_shape=(jax.ShapeDtypeStruct((8, C), jnp.float32),
                       jax.ShapeDtypeStruct(v2d.shape, jnp.float32)),
            interpret=True,
        )(v2d, jnp.asarray(rows), jnp.asarray(scat), jnp.asarray(add))
        np.testing.assert_array_equal(np.asarray(g), v[rows])
        want = v.copy()
        want[scat[0]] += add[0]
        np.testing.assert_array_equal(
            np.asarray(lanes.from_lanes(out, m)), want
        )


class TestSparseXtw:
    """``fw_sparse_xtw`` (interpret mode), the X^T w of the certified gap,
    against the XLA gather: p not a multiple of the block size, m not a
    multiple of 128, padded ELL slots, both storage dtypes."""

    @pytest.mark.parametrize("p,m,bs,density,dtype,tol", [
        (300, 80, 128, 0.05, np.float32, 1e-6),
        (777, 300, 256, 0.05, np.float32, 1e-6),
        (130, 700, 32, 0.3, np.float32, 1e-6),
        (300, 200, 128, 0.1, jnp.bfloat16, 1e-2),
    ])
    def test_kernel_matches_xla(self, p, m, bs, density, dtype, tol):
        _, mat, w = _sparse_dense_pair(p, m, density, seed=p + m, block_size=bs)
        mat = mat.astype(dtype)
        w = jnp.asarray(w).astype(dtype)
        counts = (np.asarray(mat.values, np.float32) != 0).sum(axis=2)
        assert counts.min() < mat.nnz_max  # some slots are padding
        got = sops.sparse_transpose_matvec(mat, w, use_kernel=True, interpret=True)
        want = sops.sparse_transpose_matvec(mat, w)
        assert got.shape == (p,) and got.dtype == want.dtype == mat.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol * 10)

    def test_padded_features_are_zero(self):
        from repro.kernels.sparse_colstats.sparse_colstats import sparse_xtw

        _, mat, w = _sparse_dense_pair(300, 80, 0.05, seed=5)
        out = sparse_xtw(mat.values, mat.rows, jnp.asarray(w), interpret=True)
        assert out.shape == (mat.p_padded,) and out.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(out[mat.p:]), 0.0)

    @pytest.mark.parametrize("entry", ["solve", "solve_batched"])
    def test_certified_gap_kernel_matches_xla(self, sparse_problem, rng_key, entry):
        """The gap a solve reports (one per lane in the batched entry) is
        the same with the kernel as with the XLA gather, and only the
        kernel configuration runs ``fw_sparse_xtw``."""
        from repro.core import engine
        from repro.core.fw_lasso import LASSO

        _, mat, y = sparse_problem
        deltas = jnp.asarray([10.0, 40.0, DELTA], jnp.float32)
        base = dict(delta=DELTA, backend="sparse", sampling="uniform", kappa=60,
                    max_iters=400, tol=1e-6, report_gap=True, interpret=True)
        if entry == "solve":
            args = (rng_key, None, DELTA)
        else:
            args = (jax.random.split(rng_key, deltas.shape[0]),
                    jnp.zeros((deltas.shape[0], mat.p), jnp.float32), deltas)
        fn = getattr(engine, entry)
        gaps = {}
        for kernel in (False, True):
            cfg = FWConfig(sparse_kernel=kernel, **base)
            jaxpr = str(jax.make_jaxpr(
                lambda: fn.__wrapped__(LASSO, mat, y, cfg, *args))())
            assert ("fw_sparse_xtw" in jaxpr) == kernel
            res = fn(LASSO, mat, y, cfg, *args)
            gaps[kernel] = np.asarray((res if entry == "solve" else res[0]).gap)
        assert gaps[True].shape == ((3,) if entry == "solve_batched" else ())
        assert np.all(gaps[False] > 0)
        np.testing.assert_allclose(gaps[True], gaps[False], rtol=1e-6)

    @pytest.mark.parametrize("family", ["lasso", "elastic_net", "logistic"])
    def test_oracle_gap_kernel_matches_xla(self, sparse_problem, rng_key, family):
        from repro.core.fw_elasticnet import ENOracle
        from repro.core.fw_lasso import LASSO
        from repro.core.fw_logistic import LOGISTIC

        _, mat, y = sparse_problem
        oracle = {"lasso": LASSO, "elastic_net": ENOracle(l2=0.5),
                  "logistic": LOGISTIC}[family]
        if family == "logistic":
            y = jnp.where(y >= 0, 1.0, -1.0).astype(jnp.float32)
        alpha = jax.random.normal(rng_key, (mat.p,), jnp.float32)
        alpha = alpha * (jax.random.uniform(rng_key, (mat.p,)) < 0.1)
        base = dict(delta=DELTA, backend="sparse", interpret=True)
        got = float(oracle.gap(mat, y, alpha, DELTA,
                               FWConfig(sparse_kernel=True, **base)))
        want = float(oracle.gap(mat, y, alpha, DELTA,
                                FWConfig(sparse_kernel=False, **base)))
        assert want > 0
        assert got == pytest.approx(want, rel=1e-6)


class TestSolverParity:
    """fw_solve(backend='sparse') == fw_solve(backend='xla') end to end on
    the SAME (sparsified) problem. p=300 is not block-divisible, so the
    padded tail block is always in play."""

    @pytest.mark.parametrize(
        "sampling,kw",
        [
            ("uniform", dict(kappa=60)),
            ("block", dict(kappa=256)),
            ("full", dict()),
        ],
    )
    def test_objective_parity(self, sparse_problem, rng_key, sampling, kw):
        Xt, mat, y = sparse_problem
        base = dict(delta=DELTA, sampling=sampling, max_iters=5000, tol=1e-6)
        res_x = fw_solve(Xt, y, FWConfig(block_size=128, **base, **kw), rng_key)
        res_s = fw_solve(mat, y, FWConfig(backend="sparse", **base, **kw), rng_key)
        rel = abs(float(res_s.objective) - float(res_x.objective)) / abs(
            float(res_x.objective)
        )
        assert rel < 1e-4, (sampling, rel)
        assert float(jnp.sum(jnp.abs(res_s.alpha))) <= DELTA * (1 + 1e-5)

    def test_uniform_sampling_identical_trajectory(self, sparse_problem, rng_key):
        """'uniform' replays the exact index stream of the dense XLA path,
        so iteration/dot counts agree exactly."""
        Xt, mat, y = sparse_problem
        base = dict(delta=DELTA, sampling="uniform", kappa=60, max_iters=2000, tol=1e-6)
        res_x = fw_solve(Xt, y, FWConfig(**base), rng_key)
        res_s = fw_solve(mat, y, FWConfig(backend="sparse", **base), rng_key)
        assert int(res_x.iterations) == int(res_s.iterations)
        assert int(res_x.n_dots) == int(res_s.n_dots)

    def test_sparse_kernel_backend_matches_ref_backend(self, sparse_problem, rng_key):
        """Forcing the Pallas sparse_grad kernel (interpret mode) must
        reproduce the XLA-gather sparse backend bit-for-bit."""
        _, mat, y = sparse_problem
        base = dict(delta=DELTA, sampling="block", kappa=256, max_iters=800, tol=1e-6)
        res_a = fw_solve(mat, y, FWConfig(backend="sparse", sparse_kernel=False, **base), rng_key)
        res_b = fw_solve(
            mat, y,
            FWConfig(backend="sparse", sparse_kernel=True, interpret=True, **base),
            rng_key,
        )
        assert float(res_a.objective) == float(res_b.objective)
        assert int(res_a.iterations) == int(res_b.iterations)

    def test_warm_start_and_duality_gap(self, sparse_problem, rng_key):
        Xt, mat, y = sparse_problem
        cfg = FWConfig(delta=DELTA, backend="sparse", sampling="uniform",
                       kappa=60, max_iters=5000, tol=1e-6)
        res = fw_solve(mat, y, cfg, rng_key)
        # warm start from the solution terminates quickly and stays feasible
        res2 = fw_solve(mat, y, cfg, rng_key, alpha0=res.alpha)
        assert int(res2.iterations) <= int(res.iterations)
        assert float(jnp.sum(jnp.abs(res2.alpha))) <= DELTA * (1 + 1e-5)
        # sparse duality gap agrees with the dense computation
        from repro.core.fw_lasso import init_state

        state = init_state(mat, y, rng_key, alpha0=res.alpha)
        gap_s = float(duality_gap(mat, state, DELTA))
        state_d = init_state(Xt, y, rng_key, alpha0=res.alpha)
        gap_d = float(duality_gap(Xt, state_d, DELTA))
        assert gap_s == pytest.approx(gap_d, rel=1e-3, abs=1e-2)

    def test_backend_matrix_mismatch_raises(self, sparse_problem, rng_key):
        Xt, mat, y = sparse_problem
        with pytest.raises(ValueError, match="SparseBlockMatrix"):
            fw_solve(Xt, y, FWConfig(delta=1.0, backend="sparse"), rng_key)
        with pytest.raises(ValueError, match="backend='sparse'"):
            fw_solve(mat, y, FWConfig(delta=1.0, backend="xla"), rng_key)

    @pytest.mark.parametrize("dtype,tol", [(np.float32, 1e-4), (jnp.bfloat16, 5e-2)])
    def test_solver_dtypes(self, sparse_problem, rng_key, dtype, tol):
        """The sparse backend runs (and stays feasible) in both storage
        dtypes; f32 additionally matches the dense objective tightly."""
        Xt, mat, y = sparse_problem
        cfg = FWConfig(delta=DELTA, backend="sparse", sampling="uniform",
                       kappa=60, max_iters=1500, tol=1e-6)
        res = fw_solve(mat.astype(dtype), y.astype(dtype), cfg, rng_key)
        assert bool(jnp.isfinite(res.objective))
        assert float(jnp.sum(jnp.abs(res.alpha.astype(jnp.float32)))) <= DELTA * (1 + tol)
        if dtype == np.float32:
            res_x = fw_solve(Xt, y, FWConfig(delta=DELTA, sampling="uniform",
                                             kappa=60, max_iters=1500, tol=1e-6), rng_key)
            rel = abs(float(res.objective) - float(res_x.objective)) / abs(
                float(res_x.objective)
            )
            assert rel < tol


class TestSparsePath:
    def test_paths_match_dense(self, sparse_problem):
        Xt, mat, y = sparse_problem
        deltas = path_lib.delta_grid(100.0, n_points=6)
        base = dict(delta=1.0, kappa=60, max_iters=8000, tol=1e-4)
        seq_d = path_lib.fw_path(Xt, y, deltas, FWConfig(**base))
        seq_s = path_lib.fw_path(mat, y, deltas, FWConfig(backend="sparse", **base))
        for d, s in zip(seq_d.points, seq_s.points):
            rel = abs(s.objective - d.objective) / max(abs(d.objective), 1e-9)
            assert rel < 1e-3, (d.reg, rel)
            assert s.l1 <= d.reg * (1 + 1e-4)

    def test_batched_path_on_sparse_matrix(self, sparse_problem):
        _, mat, y = sparse_problem
        deltas = path_lib.delta_grid(100.0, n_points=7)
        cfg = FWConfig(delta=1.0, kappa=60, max_iters=8000, tol=1e-4, backend="sparse")
        seq = path_lib.fw_path(mat, y, deltas, cfg)
        bat = path_lib.fw_path_batched(mat, y, deltas, cfg, lane_width=3)
        assert len(bat.points) == 7
        for s, b in zip(seq.points, bat.points):
            rel = abs(b.objective - s.objective) / max(abs(s.objective), 1e-9)
            assert rel < 1e-3, (s.reg, rel)

    def test_lambda_grid_sparse(self, sparse_problem):
        Xt, mat, y = sparse_problem
        lams_d = path_lib.lambda_grid(Xt, y, n_points=5)
        lams_s = path_lib.lambda_grid(mat, y, n_points=5)
        np.testing.assert_allclose(lams_s, lams_d, rtol=1e-5)
