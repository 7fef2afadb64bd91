"""Fixtures of the benchmark's tests: a tiny copy of the benchmark's
root (its code, and its cells at a size a CPU test run holds)."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import harness  # noqa: E402

TINY_POINTS = 20
# At these sizes the sampled set holds 30-60 columns, and the program's
# answers read shortfall 1.07-1.57 (CPU readings, four seeds a cell); an
# engine that never moves reads infinity. The limits set on the chip are
# for the cells' own sizes.
TINY_LIMITS = {"shortfall": 4.0}
# Cells whose files are in bench/ but which BENCHMARK.json does not list
# yet (unproven on the chip): the tests drive them all the same, so the
# dense design and the fit entry stay covered.
KEPT = {
    "e2006-log1p.path-1pct": ("e2006-log1p", "path-1pct-2lanes"),
    "triazines.path-1pct": ("triazines", "path-1pct"),
    "e2006-log1p.fit-k194": ("e2006-log1p", "fit-k194"),
    "e2006-log1p.path-k194": ("e2006-log1p", "path-k194-2lanes"),
}
KEPT_METRICS = {
    "path": {"path_s": "s"},
    "fit": {"fit_s": "s"},
}


def _add_kept(bench: dict, dst: Path) -> None:
    cells = {w["name"] for w in bench["workloads"]}
    configs = {c["name"] for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name, (config, traffic) in KEPT.items():
        if name in cells:
            continue
        if config not in configs:
            bench["configs"].append({"name": config, "source": "x", "reduced": [],
                                     "file": f"bench/configs/{config}.json", "why": "x"})
            configs.add(config)
        bench["workloads"].append({"name": name, "config": config, "traffic": traffic,
                                   "chips": 1, "why": "x"})
        entry = json.loads((dst / "bench" / "traffic" / f"{traffic}.json").read_text())["entry"]
        for metric, unit in KEPT_METRICS[entry].items():
            if metric not in e2e:
                e2e[metric] = {"name": metric, "unit": unit, "better": "lower",
                               "bound": 0.1, "source": "host_clock", "workloads": []}
                bench["end_to_end"].append(e2e[metric])
            e2e[metric]["workloads"].append(name)


# An l1-logistic design laid over a (1, n) mesh (law block_ell_sharded),
# with a path and a fit traffic of 4 points each, added as new files.
LOGISTIC_SPEC = {
    "name": "l1logit", "law": "block_ell_sharded", "problem": "logistic",
    "reference": "bench/reference_logistic.py", "m": 200, "p": 2000,
    "block_size": 256, "nnz_max": 16, "col_density": 0.03, "n_relevant": 10,
    "coef_scale": 2.0, "noise": 0.5, "delta_max_share": 0.5, "dtype": "float32",
}
LOGISTIC_TRAFFIC = {
    "path": {"entry": "path", "kappa": 194, "points": 4, "lane_width": 2,
             "ratio": 100, "report_gap": True},
    "fit": {"entry": "fit", "kappa": 194, "points": 4, "ratio": 100,
            "report_gap": True},
}
# CPU readings at this size, four seeds a cell (PERF.md §2): sound runs
# shortfall <= 1.0028, obj_err <= 1.1e-07, gap_err <= 2.4e-08, l1_excess
# <= 2.0e-06; answers scaled by 1.001 obj_err >= 5.36e-05, gap_err >=
# 5.15e-05 (l1_excess 1.0e-03: at its limit, not its number to catch);
# a frozen step shortfall inf; flipped labels obj_err >= 0.12
LOGISTIC_LIMITS = {"shortfall": 2.0, "obj_err": 1e-5, "gap_err": 1e-5,
                   "l1_excess": 1e-3}


# webspam trigram as l1-logistic (LIBSVM; Yuan, Ho & Lin, JMLR 13 (2012)),
# 350,000 x 16,609,143, laid over the four-chip host: the shape of the
# four-chip configuration a later change commits, at its published size
WEBSPAM_SPEC = dict(LOGISTIC_SPEC, name="webspam-trigram", m=350000, p=16609143,
                    nnz_max=128, col_density=78.6 / 350000, path_points=4,
                    mesh={"data": 1, "model": 4})
WEBSPAM_CELLS = {"webspam-trigram.path-k194": "path-k194-2lanes",
                 "webspam-trigram.fit-k194": "fit-k194"}


def commit_config(root: Path, spec: dict, cells: dict, chips: int, limits: dict) -> None:
    """Write the configuration ``spec`` and its cells (name -> traffic)
    into the benchmark root at ``root`` as a change that adds them does:
    new files under bench/ and new BENCHMARK.json entries."""
    config = spec["name"]
    (root / "bench" / "configs" / f"{config}.json").write_text(json.dumps(spec))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": config, "source": "x", "reduced": [],
                             "file": f"bench/configs/{config}.json", "why": "x"})
    for name, traffic in cells.items():
        (root / "bench" / "limits" / f"{name}.json").write_text(json.dumps({"limits": limits}))
        bench["workloads"].append({"name": name, "config": config, "traffic": traffic,
                                   "chips": chips, "why": "x"})
        entry = json.loads((root / "bench" / "traffic" / f"{traffic}.json").read_text())["entry"]
        for m in bench["end_to_end"]:
            if m["name"] == f"{entry}_s":
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def add_logistic_cells(root: Path, chips: int) -> list:
    """Add the l1-logistic cells to the benchmark root at ``root`` as new
    files and BENCHMARK.json entries, on a (1, chips) mesh; returns the
    cells' names."""
    config = f"l1logit-{chips}"
    cells = {}
    for entry, traffic in LOGISTIC_TRAFFIC.items():
        (root / "bench" / "traffic" / f"logit-{entry}.json").write_text(json.dumps(traffic))
        cells[f"{config}.{entry}"] = f"logit-{entry}"
    spec = dict(LOGISTIC_SPEC, name=config, mesh={"data": 1, "model": chips})
    commit_config(root, spec, cells, chips, LOGISTIC_LIMITS)
    return list(cells)


def copy_root(dst: Path, src: Path = ROOT) -> Path:
    """The benchmark's files of the root at ``src`` (BENCHMARK.json and
    bench/, its tests left out) copied to ``dst``."""
    shutil.copy(src / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(src / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return dst


def make_tiny_root(dst: Path, src: Path = ROOT) -> Path:
    """A benchmark root at ``dst`` from the one at ``src``: BENCHMARK.json
    (with the ``KEPT`` cells), the bench code as committed, the limits
    with ``TINY_LIMITS`` for these sizes, the configurations and paths
    shrunk. Each configuration takes the sizes its law states for a CPU
    test run (``TINY`` in ``bench/laws/<law>.py``) and keeps the rest,
    its mesh among them."""
    copy_root(dst, src)
    bench = json.loads((dst / "BENCHMARK.json").read_text())
    _add_kept(bench, dst)
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    for path in (dst / "bench" / "configs").glob("*.json"):
        spec = json.loads(path.read_text())
        spec.update(harness.law(dst, spec).TINY)
        path.write_text(json.dumps(spec))
    for lim in (dst / "bench" / "limits").glob("*.json"):
        doc = json.loads(lim.read_text())
        doc["limits"].update(TINY_LIMITS)
        lim.write_text(json.dumps(doc))
    for t in (dst / "bench" / "traffic").glob("*.json"):
        traffic = json.loads(t.read_text())
        traffic["points"] = TINY_POINTS
        t.write_text(json.dumps(traffic))
    return dst


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("bench_root"))
