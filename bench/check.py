"""Whether what the timed path produced is correct.

Every answer of the window (each grid point of each path, or each fit)
is evaluated by the plain reference that the configuration names
(``reference``: ``bench/reference.py`` for the lasso,
``bench/reference_logistic.py`` for l1-logistic) from its returned
coefficients and the benchmark's own data, and judged by four numbers,
each against its limit from ``bench/limits/<cell>.json``:

    shortfall  (f(0) - (f_ref - g_ref)) / (f(0) - f(alpha)): how many times
               the decrease from the problem's f(0) (the reference's
               ``f_zero``: ||y||^2 / 2 for the lasso, log 2 a label for
               l1-logistic) that the reference certifies at the same
               radius (f_ref, g_ref: the reference solver's objective and
               certified gap, solved from zero) exceeds the answer's own
               decrease; infinite where the answer does not go below
               f(0), as an engine that never moves from alpha = 0
    obj_err    |reported objective - f(alpha)| / f(0)
    gap_err    |reported gap - g(alpha)| / f(0), g the certified Frank-Wolfe
               gap of the returned coefficients
    l1_excess  max(0, ||alpha||_1 / delta - 1)

The errors are shares of the problem's f(0), its own scale: where the
radius lets the design fit y (triazines' m = 186), f(alpha) itself goes
to ~0.
``shortfall`` is read on the answers at a sample of the radii, drawn from
the run's seed: every radius where there are at most ``REF_POINTS``, else
``REF_POINTS - 1`` drawn ones and the largest (the slowest to solve). The
other three are read on every answer.

A path also has to return its grid in order: ``grid_err`` counts the
points whose delta is not the grid's (limit 0). An answer with any
number above its limit, or not finite, has failed. The run's numbers
are the largest over its answers.
"""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("shortfall", "obj_err", "gap_err", "l1_excess")
REF_POINTS = 8


def reference_radii(answers: list, seed: int) -> np.ndarray:
    """The radii at which the reference solver is run."""
    radii = np.unique(np.array([a["delta"] for a in answers], np.float64))
    if radii.size <= REF_POINTS:
        return radii
    rng = np.random.default_rng(int(seed))
    pick = rng.choice(radii.size - 1, REF_POINTS - 1, replace=False)
    return np.sort(np.append(radii[pick], radii[-1]))


def readings(data: dict, answers: list, seed: int, reference) -> dict:
    """Per-answer numbers (arrays over answers; ``shortfall`` is -inf on
    the answers off the reference's radii) and, under ``ref``, the
    reference's own readings: the number of radii, its largest gap over
    f(0), and its smallest certified decrease over f(0). ``reference`` is
    the configuration's plain reference module."""
    supports = [(np.asarray(a["idx"], np.int32), np.asarray(a["val"], np.float32))
                for a in answers]
    deltas = np.array([a["delta"] for a in answers], np.float64)
    f, l1, gap = reference.evaluate(data, supports, deltas)
    radii = reference_radii(answers, seed)
    f_ref, g_ref = reference.optimum(data, radii)
    f_zero = reference.f_zero(data)
    ref_decrease = f_zero - (f_ref - g_ref)
    at = {float(d): i for i, d in enumerate(radii)}
    shortfall = np.full(len(answers), -np.inf)
    for j, d in enumerate(deltas):
        i = at.get(float(d))
        if i is not None:
            decrease = f_zero - f[j]
            shortfall[j] = ref_decrease[i] / decrease if decrease > 0 else np.inf
    rep_f = np.array([a["objective"] for a in answers], np.float64)
    rep_g = np.array([a["gap"] for a in answers], np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        return {
            "shortfall": shortfall,
            "obj_err": np.abs(rep_f - f) / f_zero,
            "gap_err": np.abs(rep_g - gap) / f_zero,
            "l1_excess": np.maximum(0.0, l1 / deltas - 1.0),
            "ref": {"radii": int(radii.size), "ref_gap": float(np.max(g_ref / f_zero)),
                    "ref_decrease": float(np.min(ref_decrease / f_zero))},
        }


def grid_errors(answers: list, grid) -> int:
    """Points missing from, out of order in, or off the expected grid."""
    by_path: dict = {}
    for a in answers:
        by_path.setdefault(a["path"], []).append(a)
    bad = 0
    for pts in by_path.values():
        got = [a["delta"] for a in sorted(pts, key=lambda a: a["point"])]
        bad += abs(len(got) - len(grid))
        bad += sum(1 for g, e in zip(got, grid)
                   if not math.isclose(g, float(e), rel_tol=1e-12))
    return bad


def judge(data: dict, answers: list, limits: dict, seed: int, reference,
          grid=None) -> dict:
    """{"correct", "attempted", "failed", "numbers": {name: (value, limit)},
    "ref": the reference's own readings}, judged with ``reference``, the
    configuration's plain reference module."""
    if answers:
        r = readings(data, answers, seed, reference)
    else:
        r = {k: np.zeros(0) for k in NUMBERS}
        r["ref"] = {}
    ok = np.ones(len(answers), bool)
    numbers = {}
    for name in NUMBERS:
        vals = r[name]
        fine = np.isfinite(vals) & (vals <= limits[name])
        if name == "shortfall":
            fine |= vals == -np.inf  # not at a reference radius
        ok &= fine
        seen = vals[vals != -np.inf] if name == "shortfall" else vals
        worst = float(np.max(np.where(np.isfinite(seen), seen, np.inf))) if seen.size else 0.0
        numbers[name] = (worst, float(limits[name]))
    correct = bool(answers) and bool(ok.all())
    if grid is not None:
        bad = grid_errors(answers, grid)
        numbers["grid_err"] = (float(bad), 0.0)
        correct = correct and bad == 0
    return {"correct": correct, "attempted": len(answers),
            "failed": int((~ok).sum()), "numbers": numbers, "ref": r["ref"]}
