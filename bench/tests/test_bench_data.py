"""The on-device generators' laws, and the plain reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.laws import block_ell_uniform, lowrank_dense

SPARSE = dict(m=300, p=6000, block_size=256, nnz_max=24, col_density=0.02,
              n_relevant=12, coef_scale=10.0, noise=0.05)
DENSE = dict(m=40, p=3000, mix_ratio=64, noise_x=0.5, n_relevant=10,
             coef_scale=10.0, noise=0.5)


@pytest.fixture(scope="module")
def sparse():
    return block_ell_uniform.generate(SPARSE, jax.random.key(2**31 + 11))


@pytest.fixture(scope="module")
def dense():
    return lowrank_dense.generate(DENSE, jax.random.key(5))


def test_block_ell_layout_and_law(sparse):
    vals = np.asarray(sparse["values"]).reshape(-1, SPARSE["nnz_max"])
    rows = np.asarray(sparse["rows"]).reshape(-1, SPARSE["nnz_max"])
    p, m = SPARSE["p"], SPARSE["m"]
    assert sparse["values"].shape == (-(-p // 256), 256, SPARSE["nnz_max"])
    live = vals != 0
    counts = live.sum(axis=1)
    assert counts[p:].sum() == 0  # padded tail features are empty
    # unit column norms wherever a column has a nonzero
    norms = np.sqrt((vals[:p] ** 2).sum(axis=1))
    assert np.allclose(norms[counts[:p] > 0], 1.0, atol=1e-5)
    # density: Poisson(0.02 * 300 = 6) nonzeros a column
    assert counts[:p].mean() == pytest.approx(SPARSE["col_density"] * m, rel=0.05)
    # distinct rows within a column, in range; padded slots at row 0
    for j in range(0, p, 37):
        r = rows[j, live[j]]
        assert len(set(r.tolist())) == r.size
        assert ((r >= 0) & (r < m)).all()
    assert (rows[~live] == 0).all()
    assert (vals >= 0).all()


def test_targets_centred_and_from_coef(sparse, dense):
    for data in (sparse, dense):
        y = np.asarray(data["y"], np.float64)
        assert abs(y.mean()) < 1e-4 * np.abs(y).max()
        assert int((np.asarray(data["coef"]) != 0).sum()) == (
            SPARSE if data["kind"] == "block_ell" else DENSE)["n_relevant"]


def test_dense_law_standardised(dense):
    xt = np.asarray(dense["xt"], np.float64)
    assert xt.shape == (DENSE["p"], DENSE["m"])
    assert np.allclose(xt.mean(axis=1), 0.0, atol=1e-5)
    assert np.allclose((xt**2).sum(axis=1), 1.0, atol=1e-4)


def test_same_seed_same_data(sparse):
    again = block_ell_uniform.generate(SPARSE, jax.random.key(2**31 + 11))
    other = block_ell_uniform.generate(SPARSE, jax.random.key(2**31 + 12))
    for k in ("values", "rows", "y", "coef"):
        assert np.array_equal(np.asarray(again[k]), np.asarray(sparse[k]))
    assert not np.array_equal(np.asarray(other["rows"]), np.asarray(sparse["rows"]))
    d1 = lowrank_dense.generate(DENSE, jax.random.key(9))
    d2 = lowrank_dense.generate(DENSE, jax.random.key(9))
    assert np.array_equal(np.asarray(d1["xt"]), np.asarray(d2["xt"]))


def _dense_of(data):
    vals = np.asarray(data["values"]).reshape(-1, SPARSE["nnz_max"])
    rows = np.asarray(data["rows"]).reshape(-1, SPARSE["nnz_max"])
    xt = np.zeros((vals.shape[0], data["m"]))
    np.add.at(xt, (np.repeat(np.arange(vals.shape[0]), vals.shape[1]), rows.ravel()),
              vals.ravel())
    return xt[: data["p"]]


def test_reference_evaluation_matches_numpy(sparse):
    xt = _dense_of(sparse)
    y = np.asarray(sparse["y"], np.float64)
    rng = np.random.default_rng(0)
    supports, deltas = [], []
    for n in (0, 5, 70):
        idx = rng.choice(SPARSE["p"], n, replace=False).astype(np.int32)
        val = rng.normal(size=n).astype(np.float32)
        supports.append((idx, val))
        deltas.append(float(np.abs(val).sum()) * 1.5 + 1.0)
    f, l1, gap = reference.evaluate(sparse, supports, deltas)
    for i, (idx, val) in enumerate(supports):
        alpha = np.zeros(SPARSE["p"])
        alpha[idx] = val
        r = y - xt.T @ alpha
        grad = -xt @ r
        assert f[i] == pytest.approx(0.5 * r @ r, rel=1e-5)
        assert l1[i] == pytest.approx(np.abs(alpha).sum(), rel=1e-6)
        want = alpha @ grad + deltas[i] * np.abs(grad).max()
        assert gap[i] == pytest.approx(want, rel=1e-4, abs=1e-3)


def test_program_fit_within_its_certificate_of_reference_optimum(dense):
    """The plain reference solver's optimum lies within the program's
    certified gap below the program's objective, and the reference's own
    certificate is tight."""
    from repro.core import fw_solve
    from repro.core.solver_config import FWConfig

    delta = 0.5 * float(jnp.sum(jnp.abs(dense["coef"]))) / 4
    res = fw_solve(dense["xt"], dense["y"], FWConfig(delta=delta, kappa=300,
                   report_gap=True), jax.random.PRNGKey(0), None, delta)
    alpha = np.asarray(res.alpha)
    idx = np.nonzero(alpha)[0]
    f, l1, gap = reference.evaluate(dense, [(idx, alpha[idx])], [delta])
    f_ref, g_ref = reference.optimum(dense, [delta])
    assert l1[0] <= delta * (1 + 1e-5)
    assert abs(g_ref[0]) <= 1e-4 * f_ref[0]
    assert f_ref[0] - g_ref[0] <= f[0] * (1 + 1e-6)
    # f32 rounding of f(alpha) is ~1e-7 relative
    assert f[0] - f_ref[0] <= max(gap[0], 0.0) + 1e-6 * f[0]


@pytest.mark.parametrize("kind", ["sparse", "dense"])
def test_reference_optimum_matches_a_long_plain_frank_wolfe(kind, sparse, dense):
    """The reference's objective at three radii agrees with deterministic
    Frank-Wolfe run for many iterations in float64, and its certified gap
    bounds the distance."""
    data = sparse if kind == "sparse" else dense
    xt = _dense_of(data) if kind == "sparse" else np.asarray(data["xt"], np.float64)
    y = np.asarray(data["y"], np.float64)
    dmax = 0.5 * float(np.abs(np.asarray(data["coef"])).sum())
    deltas = [dmax / 100, dmax / 10, dmax]
    f_ref, g_ref = reference.optimum(data, deltas)
    for d, fr, gr in zip(deltas, f_ref, g_ref):
        alpha = np.zeros(xt.shape[0])
        r = y.copy()
        for _ in range(4000):
            grad = -xt @ r
            j = int(np.argmax(np.abs(grad)))
            step = -alpha
            step[j] -= d * np.sign(grad[j])
            xd = xt.T @ step
            lam = float(np.clip((r @ xd) / max(xd @ xd, 1e-300), 0.0, 1.0))
            alpha += lam * step
            r -= lam * xd
        f_fw = 0.5 * r @ r
        assert fr - gr <= f_fw * (1 + 1e-6)
        assert fr <= f_fw * (1 + 1e-5)
        assert abs(gr) <= 1e-4 * fr
