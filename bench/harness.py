"""Finds a cell's pieces by the names in ``BENCHMARK.json``.

    configuration   the file its ``configs`` entry names (a JSON object of
                    sizes, with ``law``: the generator in
                    ``bench/laws/<law>.py``, and ``reference``: the file of
                    its plain reference). Optional keys: ``problem``,
                    "lasso" (the default) or "logistic", the problem its
                    targets pose and its program solves; ``mesh``,
                    ``{"data": a, "model": b}``, the layout of its data
                    over the cell's chips (a * b of them), which the law
                    draws the data onto
    traffic         ``bench/traffic/<traffic>.json``
    limits          ``bench/limits/<workload>.json``: the limit of each
                    number that decides ``correct``
    metric          ``bench/metrics/<name>.py``, whose ``read(ctx)``
                    returns the metric's value or None

A later change adds a configuration, a traffic mix, a cell or a metric by
adding files and entries; nothing here names one of them.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import NamedTuple

BENCH_DIR = "bench"
PROBLEMS = ("lasso", "logistic")


class CellError(ValueError):
    """A cell whose files do not fit together: nothing is measured."""


class Cell(NamedTuple):
    name: str
    chips: int
    spec: dict  # the configuration file
    traffic: dict  # the traffic file
    limits: dict
    end_to_end: list  # entries of BENCHMARK.json's end_to_end this cell reports
    per_layer: list  # entries of per_layer this cell reports


def load_module(path: Path) -> ModuleType:
    """Import a file by its path (its name may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_plugin_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(entry: dict, workload: str, reported: set) -> bool:
    if "workloads" in entry:
        return workload in entry["workloads"]
    return entry.get("moves") is None or entry["moves"] in reported


def find_cell(root: Path, workload: str) -> Cell:
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    spec = json.loads((root / configs[w["config"]]["file"]).read_text())
    problem = spec.get("problem", "lasso")
    if problem not in PROBLEMS:
        raise CellError(f"{w['config']}: unknown problem {problem!r}; known: {PROBLEMS}")
    if "mesh" in spec:
        a, b = int(spec["mesh"]["data"]), int(spec["mesh"]["model"])
        if a * b != int(w["chips"]):
            raise CellError(f"{workload}: its configuration's mesh {a} x {b} is not "
                            f"the cell's {w['chips']} chips")
    traffic = json.loads((root / BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((root / BENCH_DIR / "limits" / f"{workload}.json").read_text())["limits"]
    e2e = [m for m in bench["end_to_end"]
           if m["name"] == "setup_s" or _reports(m, workload, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, workload, names)]
    return Cell(workload, int(w["chips"]), spec, traffic, limits, e2e, per_layer)


def law(root: Path, spec: dict) -> ModuleType:
    return load_module(Path(root) / BENCH_DIR / "laws" / f"{spec['law']}.py")


def reference(root: Path, spec: dict) -> ModuleType:
    """The plain reference the configuration names: ``evaluate``,
    ``optimum`` and ``f_zero`` of its problem (loaded once a process, so
    that its compiled programs serve every run)."""
    return _load_once((Path(root) / spec["reference"]).resolve())


@functools.lru_cache(maxsize=None)
def _load_once(path: Path) -> ModuleType:
    return load_module(path)


def metric_reader(root: Path, name: str) -> ModuleType:
    return load_module(Path(root) / BENCH_DIR / "metrics" / f"{name}.py")
