"""A configuration that names its problem, its reference and its layout:
the l1-logistic reference, the law drawn over a mesh, and the logistic
check, on one CPU device (a (1, 1) mesh) at a size a test run holds."""
import dataclasses
import json
import shutil
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference, reference_logistic, run
from bench.laws import block_ell_sharded, block_ell_uniform
from bench.tests.conftest import LOGISTIC_SPEC, add_logistic_cells

ARGS = ["--seed", "2147483671", "--seconds", "0", "--trace", "0"]
SPEC = {k: LOGISTIC_SPEC[k] for k in ("m", "p", "block_size", "nnz_max", "col_density",
                                     "n_relevant", "coef_scale", "noise", "problem")}


def _mesh():
    from repro import distributed

    return distributed.fw_mesh(n_data=1, n_model=1, devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def logit():
    return block_ell_sharded.generate(SPEC, jax.random.key(2**31 + 5), mesh=_mesh())


def _dense_of(data):
    nnz = data["values"].shape[-1]
    vals = np.asarray(data["values"]).reshape(-1, nnz)
    rows = np.asarray(data["rows"]).reshape(-1, nnz)
    xt = np.zeros((vals.shape[0], data["m"]))
    np.add.at(xt, (np.repeat(np.arange(vals.shape[0]), nnz), rows.ravel()), vals.ravel())
    return xt[: data["p"]]


def _loss(xt, y, alpha):
    return float(np.logaddexp(0.0, -y * (xt.T @ alpha)).sum())


def _grad(xt, y, alpha):
    return xt @ (-y / (1.0 + np.exp(y * (xt.T @ alpha))))


def test_sharded_law_layout_labels_and_seed(logit):
    vals = np.asarray(logit["values"])
    rows = np.asarray(logit["rows"])
    p, m, nnz = SPEC["p"], SPEC["m"], SPEC["nnz_max"]
    assert vals.shape == (1, -(-p // 256), 256, nnz) == rows.shape
    vals, rows = vals.reshape(-1, nnz), rows.reshape(-1, nnz)
    live = vals != 0
    counts = live.sum(axis=1)
    assert counts[p:].sum() == 0
    norms = np.sqrt((vals[:p] ** 2).sum(axis=1))
    assert np.allclose(norms[counts[:p] > 0], 1.0, atol=1e-5)
    assert counts[:p].mean() == pytest.approx(SPEC["col_density"] * m, rel=0.05)
    for j in range(0, p, 37):
        r = rows[j, live[j]]
        assert len(set(r.tolist())) == r.size and ((r >= 0) & (r < m)).all()
    assert (rows[~live] == 0).all() and (vals >= 0).all()
    y = np.asarray(logit["y"])
    assert set(np.unique(y).tolist()) == {-1.0, 1.0}
    assert int((np.asarray(logit["coef"]) != 0).sum()) == SPEC["n_relevant"]
    again = block_ell_sharded.generate(SPEC, jax.random.key(2**31 + 5), mesh=_mesh())
    for k in ("values", "rows", "y", "coef"):
        assert np.array_equal(np.asarray(again[k]), np.asarray(logit[k]))


def test_sharded_law_draws_block_ell_uniforms_data():
    """Lasso targets, one key: the same design, targets and coefficients
    as block_ell_uniform, which the lasso reference reads alike."""
    spec = dict(SPEC, problem="lasso")
    key = jax.random.key(7)
    a = block_ell_sharded.generate(spec, key, mesh=_mesh())
    b = block_ell_uniform.generate(spec, key)
    for k in ("values", "rows"):
        assert np.array_equal(np.asarray(a[k])[0], np.asarray(b[k]))
    for k in ("y", "coef"):
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
    support = [(np.arange(0, 2000, 97, dtype=np.int32), np.full(21, 0.1, np.float32))]
    got, want = reference.evaluate(a, support, [5.0]), reference.evaluate(b, support, [5.0])
    assert all(np.array_equal(x, y) for x, y in zip(got, want))


def test_sharded_law_refuses_rows_over_chips_and_unknown_problems():
    with pytest.raises(ValueError, match="data axis of 2"):
        block_ell_sharded.program(SPEC, SimpleNamespace(shape={"data": 2, "model": 2}))
    with pytest.raises(ValueError, match="no targets"):
        block_ell_sharded.program(dict(SPEC, problem="poisson"), _mesh())


def test_logistic_reference_evaluation_matches_numpy(logit):
    xt = _dense_of(logit)
    y = np.asarray(logit["y"], np.float64)
    assert reference_logistic.f_zero(logit) == pytest.approx(SPEC["m"] * np.log(2.0), rel=1e-12)
    rng = np.random.default_rng(0)
    supports, deltas = [], []
    for n in (0, 5, 70):
        idx = rng.choice(SPEC["p"], n, replace=False).astype(np.int32)
        val = (0.3 * rng.normal(size=n)).astype(np.float32)
        supports.append((idx, val))
        deltas.append(float(np.abs(val).sum()) * 1.5 + 1.0)
    f, l1, gap = reference_logistic.evaluate(logit, supports, deltas)
    for i, (idx, val) in enumerate(supports):
        alpha = np.zeros(SPEC["p"])
        alpha[idx] = val
        grad = _grad(xt, y, alpha)
        assert f[i] == pytest.approx(_loss(xt, y, alpha), rel=1e-6)
        assert l1[i] == pytest.approx(np.abs(alpha).sum(), rel=1e-6)
        want = alpha @ grad + deltas[i] * np.abs(grad).max()
        assert gap[i] == pytest.approx(want, rel=1e-5, abs=1e-4)


def test_logistic_reference_optimum_matches_a_long_plain_frank_wolfe(logit):
    """The reference's objective at three radii agrees with deterministic
    Frank-Wolfe (exact line search by bisection) run for many iterations
    in float64, its certified gap bounds the distance, and it certifies
    under 2e-6 of f(0)."""
    xt = _dense_of(logit)
    y = np.asarray(logit["y"], np.float64)
    f0 = reference_logistic.f_zero(logit)
    dmax = 0.5 * float(np.abs(np.asarray(logit["coef"])).sum())
    deltas = [dmax / 100, dmax / 10, dmax]
    f_ref, g_ref = reference_logistic.optimum(logit, deltas)
    assert np.all(np.abs(g_ref) < 2e-6 * f0)
    for d, fr, gr in zip(deltas, f_ref, g_ref):
        alpha = np.zeros(xt.shape[0])
        for _ in range(1000):
            grad = _grad(xt, y, alpha)
            j = int(np.argmax(np.abs(grad)))
            step = -alpha
            step[j] -= d * np.sign(grad[j])
            u, du = xt.T @ alpha, xt.T @ step

            def slope(t):
                return du @ (-y / (1.0 + np.exp(y * (u + t * du))))

            lo, hi = 0.0, 1.0
            for _ in range(40):
                lo, hi = (lo, 0.5 * (lo + hi)) if slope(0.5 * (lo + hi)) > 0 else (0.5 * (lo + hi), hi)
            alpha += (1.0 if slope(1.0) <= 0 else 0.5 * (lo + hi)) * step
        f_fw = _loss(xt, y, alpha)
        # float32 sums over m terms of f(0)'s size are good to ~1e-6 of f
        assert fr - gr <= f_fw * (1 + 1e-6)
        assert fr <= f_fw * (1 + 1e-6)


def test_logistic_reference_grows_its_working_set_to_the_violations(monkeypatch):
    """A design whose optimum's support is many times the first working
    set certifies under GAP_TARGET of f(0) in at most 3 rounds and 3
    compiles of the working-set solve: the set grows to the columns that
    break the l1 ball's optimality conditions, not by doubling, and each
    round's solve stops on its own gap."""
    spec = dict(SPEC, m=2000, p=4000, col_density=0.01, n_relevant=60)
    data = block_ell_sharded.generate(spec, jax.random.key(2**31 + 9), mesh=_mesh())
    dmax = 0.5 * float(np.abs(np.asarray(data["coef"])).sum())
    first = 8
    monkeypatch.setattr(reference_logistic, "WORK_SET", first)
    solve = reference_logistic._restricted
    rounds = []

    def counted(*a):
        rounds.append(solve(*a))
        return rounds[-1]

    monkeypatch.setattr(reference_logistic, "_restricted", counted)
    compiled = solve._cache_size()
    f_ref, g_ref = reference_logistic.optimum(data, np.geomspace(dmax / 100, 3 * dmax, 4))
    support = int(np.max(np.sum(np.asarray(rounds[-1][0]) != 0, axis=1)))
    assert support >= 8 * first
    assert len(rounds) <= 3 and solve._cache_size() - compiled <= 3
    # each round's solve stopped on its own gap, not at its cap
    assert all(np.all(np.asarray(steps) < reference_logistic.MAX_STEPS) for _, steps in rounds)
    f0 = reference_logistic.f_zero(data)
    assert np.all(g_ref <= reference_logistic.GAP_TARGET * f0) and np.all(f_ref < f0)


@pytest.fixture(scope="module")
def logit_root(tiny_root, tmp_path_factory):
    root = tmp_path_factory.mktemp("logit_root")
    shutil.copytree(tiny_root, root, dirs_exist_ok=True)
    path_cell, fit_cell = add_logistic_cells(root, 1)
    return root, {"path": path_cell, "fit": fit_cell}


def _run(root, workload, **kw):
    return run.run(["--workload", workload] + ARGS, root=root, require_tpu=False, **kw)


@pytest.fixture
def fresh_programs():
    """Programs compiled before a fault is planted are not reused."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _wrap_mesh_entries(monkeypatch, before=None, after=None):
    """Plant a fault in the program's mesh entry points: ``before`` maps
    the operand they are given, ``after`` the result they return."""
    import repro.distributed
    from repro.distributed import driver

    real_solve, real_batched = driver.solve, driver.solve_batched
    before = before or (lambda op: op)
    after = after or (lambda res: res)

    def solve(oracle, op, *a, **k):
        return after(real_solve(oracle, before(op), *a, **k))

    def solve_batched(oracle, op, *a, **k):
        res, saved = real_batched(oracle, before(op), *a, **k)
        return after(res), saved

    monkeypatch.setattr(driver, "solve", solve)
    monkeypatch.setattr(repro.distributed, "solve", solve)
    monkeypatch.setattr(driver, "solve_batched", solve_batched)


def test_logistic_answer_altered_is_not_correct(logit_root, monkeypatch):
    """Every answer's coefficients scaled by 1.001 where the program
    produces them: its objective and gap no longer agree with them."""
    root, cells = logit_root
    _wrap_mesh_entries(monkeypatch, after=lambda res: res._replace(alpha=res.alpha * 1.001))
    out = _run(root, cells["path"])
    assert not out["correct"] and out["failed"] > 0
    assert out["checks"]["obj_err"]["value"] > out["checks"]["obj_err"]["limit"]


def test_logistic_fitted_to_flipped_labels_is_not_correct(logit_root, monkeypatch):
    root, cells = logit_root
    _wrap_mesh_entries(monkeypatch, before=lambda op: dataclasses.replace(op, y=-op.y))
    out = _run(root, cells["path"])
    assert not out["correct"] and out["failed"] == out["attempted"]


def test_logistic_step_leaving_state_unchanged_is_not_correct(logit_root, monkeypatch,
                                                               fresh_programs):
    """Only the comparison with the reference solver's optimum sees it:
    the answers' objectives and certificates are true to them."""
    from repro.core import engine

    def frozen(oracle, Xt, y, stats, state, cfg, delta):
        return state._replace(k=state.k + 1, stall=state.stall + 1)

    monkeypatch.setattr(engine, "step", frozen)
    root, cells = logit_root
    out = _run(root, cells["fit"])
    assert not out["correct"]
    assert out["checks"]["shortfall"]["value"] > out["checks"]["shortfall"]["limit"]
    assert out["checks"]["obj_err"]["value"] <= out["checks"]["obj_err"]["limit"]


def test_logistic_control_in_bfloat16_is_not_correct(logit_root):
    """The program cannot yet run the logistic oracle in bfloat16 (its
    line search's step is float32 against bfloat16 coefficients, PERF.md
    §7): the control stops with that error and prints no result, which
    fails the run. Once it runs, it has to read not correct."""
    root, cells = logit_root
    try:
        out = _run(root, cells["path"], control_dtype=jnp.bfloat16)
    except TypeError as e:
        assert "cond branches must have equal output types" in str(e)
        return
    assert not out["correct"]


def test_mesh_that_is_not_the_cells_chips_measures_nothing(logit_root, tmp_path):
    """A configuration's mesh that is not its cell's chips, or a problem
    the harness does not know, stops the run before set-up."""
    root = tmp_path / "root"
    shutil.copytree(logit_root[0], root)
    path_cell, fit_cell = logit_root[1]["path"], logit_root[1]["fit"]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        if w["name"] == path_cell:
            w["chips"] = 4
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(harness.CellError, match="mesh 1 x 1 is not the cell's 4 chips"):
        _run(root, path_cell)
    config = root / "bench/configs/l1logit-1.json"
    config.write_text(json.dumps(dict(json.loads(config.read_text()), problem="probit")))
    with pytest.raises(harness.CellError, match="probit"):
        _run(root, fit_cell)


def test_lasso_problem_written_out_checks_the_same(tiny_root, tmp_path):
    """``"problem": "lasso"`` written out changes no number compared."""
    out, notes = [], []
    for problem in ({}, {"problem": "lasso"}):
        root = tmp_path / f"root{len(out)}"
        shutil.copytree(tiny_root, root)
        path = root / "bench/configs/e2006-log1p.json"
        # p = 800 keeps the reference's working set, and its time, small
        path.write_text(json.dumps(dict(json.loads(path.read_text()), p=800, **problem)))
        notes.append({})
        out.append(_run(root, "e2006-log1p.fit-k194", notes=notes[-1]))
    assert out[0]["correct"] and out[0]["checks"] == out[1]["checks"]
    assert notes[0]["iterations"] == notes[1]["iterations"]
    assert notes[0]["ref_gap"] == notes[1]["ref_gap"]
