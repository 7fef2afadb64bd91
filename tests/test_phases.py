"""Device phases of the engine and the host spans of the path driver
(DESIGN.md §Observability).

  * every op a profile can time in a compiled entry point maps to an
    ``fw/`` phase through ``obs.phases.op_phases``, on the plain and the
    sparse backends;
  * the default tracer keeps no events, however many paths run;
  * ``PathResult.loop_turns`` counts the batched loop's turns.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FWConfig, engine
from repro.core import path as path_lib
from repro.core.fw_lasso import LASSO
from repro.obs import phases
from repro.obs import trace as obs_trace
from repro.sparse.matrix import SparseBlockMatrix

# ops that run on the device in their own right (and so are timed
# in a profile), with the control flow that holds them
TIMED = ("fusion", "custom-call", "while", "conditional", "copy",
         "copy-start", "copy-done")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+.*?\s([\w\-]+)\(", re.M)
LANES = 2


def _matrix(Xt, backend):
    if backend == "xla":
        return Xt
    Xs = np.asarray(Xt).copy()
    Xs[np.abs(Xs) < 0.7] = 0.0
    return SparseBlockMatrix.from_dense(Xs, block_size=64)


def _dispatch(entry, A, y, cfg):
    """Run ``entry`` once; returns the arguments it ran with."""
    key = jax.random.PRNGKey(0)
    if entry == "solve":
        args = (LASSO, A, y, cfg, key, None, 40.0)
    else:
        p = A.shape[0]
        alpha0s = jnp.full((LANES, p), 0.01, jnp.float32)
        args = (LASSO, A, y, cfg, jax.random.split(key, LANES), alpha0s,
                jnp.asarray([20.0, 40.0]))
    jax.block_until_ready(getattr(engine, entry)(*args))
    return args


@pytest.mark.parametrize("entry", ["solve", "solve_batched"])
@pytest.mark.parametrize("backend", ["xla", "sparse"])
def test_every_timed_op_maps_to_a_phase(small_problem, backend, entry):
    Xt, y, _ = small_problem
    A = _matrix(Xt, backend)
    cfg = FWConfig(delta=40.0, kappa=20, max_iters=30, report_gap=True,
                   backend=backend)
    phases.forget(entry)
    args = _dispatch(entry, A, y, cfg)
    phase_map = phases.op_phases(entry)
    text = getattr(engine, entry).lower(*args).compile().as_text()
    timed = {n for n, op in _INSTR.findall(text) if op in TIMED}
    assert timed
    assert {n: phase_map.get(n) for n in timed
            if phase_map.get(n) not in phases.PHASES} == {}
    expected = {"fw/colstats", "fw/loop/score", "fw/loop/update", "fw/gap",
                "fw/result"} | ({"fw/init"} if entry == "solve_batched" else set())
    assert expected <= set(phase_map.values())


def test_phase_of_takes_the_innermost_scope():
    assert phases.phase_of("jit(solve)/fw/result/fw/gap/dot_general") == "fw/gap"
    assert phases.phase_of(
        "jit(solve_batched)/fw/loop/while/body/vmap(fw/loop/score)/gather"
    ) == "fw/loop/score"
    assert phases.phase_of("jit(solve)/fw/loop/while/body/lt") == "fw/loop"
    assert phases.phase_of("jit(f)/fw/loopy/add") is None
    assert phases.phase_of("Xt") is None


def test_remember_keeps_one_signature_per_shape(small_problem):
    Xt, y, _ = small_problem
    cfg = FWConfig(delta=40.0, kappa=20, max_iters=10)
    phases.forget("solve")
    for d in (10.0, 20.0, 30.0):  # one signature: delta is traced
        engine.solve(LASSO, Xt, y, cfg, jax.random.PRNGKey(0), None, d)
    assert len(phases._remembered["solve"]) == 1
    engine.solve(LASSO, Xt[:200], y, cfg, jax.random.PRNGKey(0), None, 10.0)
    assert len(phases._remembered["solve"]) == 2


def test_default_tracer_keeps_no_events(small_problem):
    Xt, y, _ = small_problem
    cfg = FWConfig(delta=40.0, kappa=20, max_iters=30, report_gap=True)
    for seed in range(2):
        path_lib.fw_path_batched(Xt, y, np.geomspace(4.0, 40.0, 4), cfg,
                                 seed=seed, lane_width=LANES)
    tracer = obs_trace.get_tracer()
    assert tracer.events == []
    assert tracer.span_table() == {}


@pytest.mark.parametrize("fuse", [1, 4])
def test_loop_turns_count_the_slowest_lane(small_problem, fuse):
    Xt, y, _ = small_problem
    cfg = FWConfig(delta=40.0, kappa=20, max_iters=150, tol=1e-3, patience=5,
                   fuse_steps=fuse)
    res = path_lib.fw_path_batched(Xt, y, np.geomspace(4.0, 150.0, 4), cfg,
                                   lane_width=LANES)
    iters = [pt.iterations for pt in res.points]
    expected = sum(-(-max(iters[c:c + LANES]) // fuse)
                   for c in range(0, len(iters), LANES))
    assert res.loop_turns == expected > 0
    if fuse == 1:
        assert len(set(iters)) > 1  # lanes stopped at different turns
