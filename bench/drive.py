"""The one traffic driver: reads a traffic file's parameters and drives
the program's entry point with them.

A traffic file (``bench/traffic/<name>.json``) states what a user states:

    entry           "path": ``fw_path_batched`` over a delta grid; "fit":
                    ``solve`` at single deltas, cold
    kappa           sampling-set size |S|, or
    kappa_fraction  |S| = ceil(kappa_fraction * p)
    points, ratio   the grid: ``points`` deltas, log-spaced from
                    delta_max / ratio up to delta_max ("path"); for "fit",
                    the same deltas are the radii of the fits
    lane_width      (optional, "path") deltas solved together in one
                    batched solve; left out, the program's default
    report_gap      ask the program for its certified gap

delta_max is ``delta_max_share`` times the l1 norm of the generating
coefficients (the configuration file states the share). A configuration
may cap the points of its paths at ``path_points``: the grid then keeps
its span with that many points. Every other ``FWConfig`` field stays
at the library default, except ``backend="sparse"``, which a block-ELL
design requires.

The configuration's ``problem`` names the program's oracle
(``repro.core.LASSO`` or ``repro.core.LOGISTIC``), passed to the entry
points. On one device they are ``repro.core.path.fw_path_batched`` and
``repro.core.engine.solve``; where the law has laid the data over a
(data, model) mesh, ``repro.distributed.fw_path_batched`` and
``repro.distributed.solve`` on a ``ShardedOperand`` built from the placed
arrays.

The window runs whole units back to back: a path, or a round of fits
(one at each delta of the grid, in an order drawn from the seed, so
that every seed does the same work). One more starts only while the
time left holds one more at the mean so far, and at least one always
runs. Each answer is handed back with the host copy
of its coefficients as index/value pairs, as a caller holds it.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

# per-path and per-fit PRNG seeds are derived from the run's seed
_SEED_MIX = 1_000_003


def delta_max(data: dict, spec: dict) -> float:
    return float(spec["delta_max_share"]) * float(jnp.sum(jnp.abs(data["coef"])))


def delta_grid(dmax: float, points: int, ratio: float) -> np.ndarray:
    """``points`` deltas, log-spaced and ascending from dmax / ratio."""
    return np.geomspace(dmax / ratio, dmax, int(points))


def kappa_of(traffic: dict, p: int) -> int:
    if "kappa" in traffic:
        return int(traffic["kappa"])
    return int(math.ceil(float(traffic["kappa_fraction"]) * p))


def oracle_of(spec: dict):
    """The program's oracle for the configuration's ``problem``."""
    from repro.core import LASSO, LOGISTIC

    return {"lasso": LASSO, "logistic": LOGISTIC}[spec.get("problem", "lasso")]


def on_mesh(data: dict) -> bool:
    """Whether the law laid the data over a (data, model) mesh: block-ELL
    arrays of shape (data, model blocks, block_size, nnz_max)."""
    return data["kind"] == "block_ell" and data["values"].ndim == 4


def program_operands(data: dict, dtype=jnp.float32):
    """The program's design matrix and targets for the benchmark's data,
    in ``dtype`` (the control runs the program in bfloat16). Data laid
    over a mesh becomes one ``ShardedOperand``, built from the placed
    arrays as they lie; it holds the targets too."""
    from repro.sparse.matrix import SparseBlockMatrix

    y = data["y"].astype(dtype)
    if data["kind"] == "dense":
        return data["xt"].astype(dtype), y
    values = data["values"]
    if on_mesh(data):
        op = _sharded(data, values.astype(dtype), y)
        return op, op.y
    mat = SparseBlockMatrix(
        values=values.astype(dtype), rows=data["rows"], p=data["p"], m=data["m"],
        block_size=values.shape[1], nnz_max=values.shape[2],
    )
    return mat, y


def _sharded(data: dict, values, y):
    from repro.distributed import ShardedOperand, mesh_spec

    mesh = data["values"].sharding.mesh
    spec = mesh_spec(mesh)
    return ShardedOperand(
        mesh=mesh, spec=spec, p=data["p"], m=data["m"],
        m_local=y.shape[0] // spec.n_data, y=y, values=values, rows=data["rows"],
        block_size=values.shape[2], nnz_max=values.shape[3],
        nb_local=values.shape[1] // spec.n_model,
    )


def fw_config(data: dict, traffic: dict):
    from repro.core.solver_config import FWConfig

    kw = {}
    if data["kind"] == "block_ell":
        kw["backend"] = "sparse"
    return FWConfig(delta=1.0, kappa=kappa_of(traffic, data["p"]),
                    report_gap=bool(traffic["report_gap"]), **kw)


def _host_pairs(alpha) -> tuple:
    a = np.asarray(alpha)
    idx = np.nonzero(a)[0]
    return idx, a[idx]


class Driver:
    """Set-up, warm-up and the measured window of one cell.

    Answers are dicts with ``delta``, ``idx``, ``val``, ``objective``,
    ``gap``, ``iterations``, ``seconds`` and, for paths, ``path`` (the
    path's number in the window) and ``point`` (its grid index).
    """

    def __init__(self, data: dict, spec: dict, traffic: dict, seed: int,
                 dtype=jnp.float32):
        self.entry = traffic["entry"]
        if self.entry not in ("path", "fit"):
            raise ValueError(f"unknown traffic entry {self.entry!r}")
        self.seed = int(seed)
        self.X, self.y = program_operands(data, dtype)
        self.sharded = on_mesh(data)
        self.oracle = oracle_of(spec)
        self.cfg = fw_config(data, traffic)
        self.dmax = delta_max(data, spec)
        self.ratio = float(traffic["ratio"])
        points = min(int(traffic["points"]), int(spec.get("path_points", traffic["points"])))
        self.grid = delta_grid(self.dmax, points, self.ratio)
        self.lane_width = traffic.get("lane_width")
        self._rng = np.random.default_rng(self.seed)
        self.answers: list = []
        self.units = 0  # paths or rounds of fits finished in the window
        self.path_results: list = []

    # -- one unit of work ------------------------------------------------

    def _seed_of(self, i: int) -> int:
        return (self.seed * _SEED_MIX + i) % (2**31 - 1)

    def _path(self, i: int, grid, lane_width=None) -> list:
        t0 = time.perf_counter()
        kw = dict(seed=self._seed_of(i), lane_width=lane_width or self.lane_width,
                  oracle=self.oracle)
        if self.sharded:
            from repro import distributed

            res = distributed.fw_path_batched(self.X, grid, self.cfg,
                                              report_gap=self.cfg.report_gap, **kw)
        else:
            from repro.core import path

            res = path.fw_path_batched(self.X, self.y, grid, self.cfg, **kw)
        seconds = time.perf_counter() - t0
        self.path_results.append(res)
        return [
            {"path": i, "point": g, "delta": float(pt.reg), "idx": pt.alpha_nnz_idx,
             "val": pt.alpha_nnz_val, "objective": float(pt.objective),
             "gap": float(pt.gap), "iterations": int(pt.iterations),
             "seconds": seconds}
            for g, pt in enumerate(res.points)
        ]

    def _fit(self, i: int, delta: float) -> dict:
        t0 = time.perf_counter()
        key = jax.random.PRNGKey(self._seed_of(i))
        if self.sharded:
            from repro import distributed

            res = distributed.solve(self.oracle, self.X, self.cfg, key, None, delta)
        else:
            from repro.core import engine

            res = engine.solve(self.oracle, self.X, self.y, self.cfg, key, None, delta)
        idx, val = _host_pairs(res.alpha)
        objective = float(res.objective)
        gap = float(res.gap) if res.gap is not None else float("nan")
        iterations = int(res.iterations)
        return {"delta": delta, "idx": idx, "val": val, "objective": objective,
                "gap": gap, "iterations": iterations,
                "seconds": time.perf_counter() - t0}

    def _round(self, i: int) -> list:
        """One fit at each delta of the grid, in an order from the seed."""
        order = self._rng.permutation(len(self.grid))
        return [self._fit(i * len(self.grid) + k, float(self.grid[g]))
                for k, g in enumerate(order)]

    # -- phases ----------------------------------------------------------

    def warm_up(self) -> None:
        """Compile and run every program the window uses, on its shapes:
        one chunk of the path (every chunk runs the same program), its
        first ``lane_width`` deltas scaled down a thousandfold so that
        they take few iterations, or one fit."""
        if self.entry == "path":
            # the program's default lane width: ~8 batched solves a path
            width = self.lane_width or max(1, -(-len(self.grid) // 8))
            self._path(-1, self.grid[:width] * 1e-3, lane_width=width)
            self.path_results.clear()
        else:
            self._fit(-1, float(self.grid[0]))

    def window(self, seconds: float) -> float:
        """Run the window; returns its length in seconds (to the end of
        its last path or round of fits)."""
        t0 = time.perf_counter()
        elapsed = 0.0
        while True:
            i = self.units
            if self.entry == "path":
                self.answers.extend(self._path(i, self.grid))
            else:
                self.answers.extend(self._round(i))
            self.units += 1
            elapsed = time.perf_counter() - t0
            if elapsed + elapsed / self.units > seconds:
                return elapsed

    def release(self) -> None:
        """Drop the program's operands (its device buffers; a sharded
        operand holds its targets)."""
        self.X = self.y = None
