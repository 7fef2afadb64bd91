"""The harness end to end at a size a CPU test run holds: it finds its
pieces by name, judges a sound run correct, and judges the control and a
run with a broken timed path not correct."""
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, run
from bench.tests.conftest import (LOGISTIC_LIMITS, ROOT, commit_config, copy_root,
                                  make_tiny_root)

ARGS = ["--seed", "2147483659", "--seconds", "0.5", "--trace", "0"]


def _run(root, workload, **kw):
    return run.run(["--workload", workload] + ARGS, root=root, require_tpu=False, **kw)


@pytest.mark.parametrize("workload", ["e2006-log1p.path-1pct", "triazines.path-1pct",
                                      "e2006-log1p.fit-k194"])
def test_sound_run_is_correct(tiny_root, workload):
    out = _run(tiny_root, workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    cell = harness.find_cell(tiny_root, workload)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(out)[-1] == "checks"
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("workload", ["e2006-log1p.path-1pct", "triazines.path-1pct",
                                      "e2006-log1p.fit-k194"])
def test_control_in_bfloat16_is_not_correct(tiny_root, workload):
    out = _run(tiny_root, workload, control_dtype=jnp.bfloat16)
    assert not out["correct"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def _alter_alpha(monkeypatch, scale):
    """Break the timed path underneath: the engine's answers come back
    with their coefficients scaled where they are produced."""
    from repro.core import engine

    for name in ("solve", "solve_batched"):
        entry = getattr(engine, name)

        def wrapped(*a, _entry=entry, **k):
            out = _entry(*a, **k)
            if isinstance(out, engine.SolveResult):
                return out._replace(alpha=out.alpha * scale)
            res, extra = out
            return res._replace(alpha=res.alpha * scale), extra

        monkeypatch.setattr(engine, name, wrapped)


@pytest.fixture
def fresh_programs():
    """Programs compiled before a fault is planted are not reused."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("workload", ["e2006-log1p.path-1pct", "e2006-log1p.fit-k194"])
def test_altered_answer_is_not_correct(tiny_root, workload, monkeypatch):
    _alter_alpha(monkeypatch, 1.001)
    out = _run(tiny_root, workload)
    assert not out["correct"] and out["failed"] > 0


@pytest.mark.parametrize("workload", ["e2006-log1p.path-1pct", "triazines.path-1pct",
                                      "e2006-log1p.fit-k194"])
def test_step_leaving_state_unchanged_is_not_correct(tiny_root, workload, monkeypatch,
                                                      fresh_programs):
    """Every engine step returns its state unchanged (and counts itself
    stalled, so that the loop ends): the answers are the starting points,
    with objectives and certificates true to them. Only the comparison
    with the reference solver's optimum can see it."""
    from repro.core import engine

    def frozen(oracle, Xt, y, stats, state, cfg, delta):
        return state._replace(k=state.k + 1, stall=state.stall + 1)

    monkeypatch.setattr(engine, "step", frozen)
    out = _run(tiny_root, workload)
    assert not out["correct"]
    assert out["checks"]["shortfall"]["value"] > out["checks"]["shortfall"]["limit"]
    assert out["checks"]["obj_err"]["value"] <= out["checks"]["obj_err"]["limit"]


def test_half_of_the_lanes_left_out_is_not_correct(tiny_root, monkeypatch, fresh_programs):
    """The batched loop leaves every other lane of a chunk at its warm
    start; the lanes' objectives and certificates stay true to it."""
    from repro.core import engine

    real = engine.batched_loop

    def half(oracle, Xt_run, y, stats, states0, cfg, deltas, patience):
        final, saved = real(oracle, Xt_run, y, stats, states0, cfg, deltas, patience)
        keep = jnp.arange(deltas.shape[0]) % 2 == 0
        final = jax.tree_util.tree_map(
            lambda n, o: jnp.where(engine._lane_mask(keep, n), n, o), final, states0)
        return final, saved

    monkeypatch.setattr(engine, "batched_loop", half)
    out = _run(tiny_root, "e2006-log1p.path-k194")
    assert not out["correct"]
    assert out["checks"]["shortfall"]["value"] > out["checks"]["shortfall"]["limit"]


def _half_rows(X, y):
    """The design and targets with the second half of the samples left out."""
    from repro.sparse.matrix import SparseBlockMatrix

    m = y.shape[0]
    y = jnp.where(jnp.arange(m) < m // 2, y, 0.0).astype(y.dtype)
    if isinstance(X, SparseBlockMatrix):
        values = jnp.where(X.rows < m // 2, X.values, 0.0).astype(X.values.dtype)
        X = SparseBlockMatrix(values=values, rows=X.rows, p=X.p, m=X.m,
                              block_size=X.block_size, nnz_max=X.nnz_max)
    else:
        X = jnp.where(jnp.arange(m)[None, :] < m // 2, X, 0.0).astype(X.dtype)
    return X, y


@pytest.mark.parametrize("workload", ["e2006-log1p.path-1pct", "triazines.path-1pct",
                                      "e2006-log1p.fit-k194"])
def test_half_of_the_samples_left_out_is_not_correct(tiny_root, workload, monkeypatch):
    """The program's entry solves on half of the rows: the objective it
    reports is taken over the rest."""
    from repro.core import engine, path

    real_path, real_fit = path.fw_path_batched, engine.solve

    def half_path(X, y, *a, **k):
        return real_path(*_half_rows(X, y), *a, **k)

    def half_fit(oracle, X, y, *a, **k):
        return real_fit(oracle, *_half_rows(X, y), *a, **k)

    monkeypatch.setattr(path, "fw_path_batched", half_path)
    monkeypatch.setattr(engine, "solve", half_fit)
    out = _run(tiny_root, workload)
    assert not out["correct"] and out["failed"] > 0


def test_path_out_of_order_is_not_correct(tiny_root, monkeypatch):
    from repro.core import path

    real = path.fw_path_batched

    def reversed_grid(X, y, deltas, cfg, **kw):
        return real(X, y, np.asarray(deltas)[::-1].copy(), cfg, **kw)

    monkeypatch.setattr(path, "fw_path_batched", reversed_grid)
    out = _run(tiny_root, "e2006-log1p.path-k194")
    assert not out["correct"]
    assert out["checks"]["grid_err"]["value"] > 0


def test_no_tpu_no_result(tiny_root, capsys):
    rc = run.main(["--workload", "e2006-log1p.path-k194"] + ARGS)
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_new_files_are_found_without_edits(tiny_root, tmp_path):
    """A configuration, a traffic mix, a metric and a cell added as new
    files plus BENCHMARK.json entries are found; no existing file changes."""
    root = tmp_path / "root"
    shutil.copytree(tiny_root, root)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    bench = json.loads((root / "BENCHMARK.json").read_text())
    spec = json.loads((root / "bench/configs/triazines.json").read_text())
    spec.update(name="pyrim", m=24, p=2000)
    (root / "bench/configs/pyrim.json").write_text(json.dumps(spec))
    (root / "bench/traffic/path-2pct.json").write_text(json.dumps(
        {"entry": "path", "kappa_fraction": 0.02, "points": 10, "ratio": 100,
         "report_gap": True}))
    (root / "bench/limits/pyrim.path-2pct.json").write_text(json.dumps(
        json.loads((root / "bench/limits/triazines.path-1pct.json").read_text())))
    (root / "bench/metrics/points_per_path.path.py").write_text(
        "def read(ctx):\n    return None if ctx.trace is None else len(ctx.answers)\n")
    bench["configs"].append({"name": "pyrim", "source": "x", "file": "bench/configs/pyrim.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "pyrim.path-2pct", "config": "pyrim",
                               "traffic": "path-2pct", "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] == "path_s":
            m["workloads"].append("pyrim.path-2pct")
    bench["per_layer"].append({"name": "points_per_path.path", "unit": "points",
                               "better": "lower", "source": "program_counter",
                               "layer": "path driver", "moves": "path_s",
                               "workloads": ["pyrim.path-2pct"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.find_cell(root, "pyrim.path-2pct")
    assert cell.spec["p"] == 2000 and cell.traffic["kappa_fraction"] == 0.02
    assert [m["name"] for m in cell.per_layer] == ["points_per_path.path"]
    out = run.run(["--workload", "pyrim.path-2pct", "--seed", "3", "--seconds", "0",
                   "--trace", "1"], root=root, require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["metrics"] == {"points_per_path.path": {"value": 10.0, "unit": "points"}}
    for p, content in before.items():
        assert p.read_bytes() == content


LAWS = sorted(p.stem for p in (ROOT / "bench" / "laws").glob("*.py"))


def _files(root):
    return {str(p.relative_to(root)) for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.mark.parametrize("law", LAWS)
def test_tiny_root_shrinks_a_committed_configuration_of_each_law(tiny_root, tmp_path, law):
    """A configuration of any law, committed under bench/configs with its
    BENCHMARK.json entries, shrinks to its law's own test sizes and keeps
    its other keys (its mesh among them); every other file of the tiny
    root is what it is without it, byte for byte."""
    src, dst = tmp_path / "src", tmp_path / "dst"
    src.mkdir()
    dst.mkdir()
    copy_root(src)
    tiny = harness.law(src, {"law": law}).TINY
    spec = {"name": f"big-{law}", "law": law, "reference": "bench/reference.py",
            "coef_scale": 2.5, "mesh": {"data": 1, "model": 4}}
    spec.update({k: v * 1000 if isinstance(v, int) else v / 10 for k, v in tiny.items()})
    cell = f"big-{law}.fit-k194"
    commit_config(src, spec, {cell: "fit-k194"}, 4, LOGISTIC_LIMITS)
    make_tiny_root(dst, src=src)

    assert json.loads((dst / f"bench/configs/big-{law}.json").read_text()) == {**spec, **tiny}
    new = {f"bench/configs/big-{law}.json", f"bench/limits/{cell}.json"}
    assert _files(dst) == _files(tiny_root) | new
    for f in _files(tiny_root) - {"BENCHMARK.json"}:
        assert (dst / f).read_bytes() == (tiny_root / f).read_bytes(), f
    bench = json.loads((dst / "BENCHMARK.json").read_text())
    assert [w["name"] for w in bench["workloads"] if w["config"] == f"big-{law}"] == [cell]
    bench["configs"] = [c for c in bench["configs"] if c["name"] != f"big-{law}"]
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] != cell]
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w != cell]
    assert bench == json.loads((tiny_root / "BENCHMARK.json").read_text())
