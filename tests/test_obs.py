"""Observability subsystem coverage (ISSUE 7 tentpole).

Four layers:
  * telemetry-off bit-identity: ``FWConfig(telemetry=None)`` (the
    default every pinned golden runs under) and ``telemetry=...`` must
    produce bitwise-identical trajectories on every backend and step
    rule — the ring is an observer, never a participant;
  * ring contents: the per-iteration records must agree with
    ``solve_with_history`` (which is itself now implemented ON the
    ring), wrap correctly, and carry the right step-rule event codes;
  * host plumbing: streaming sinks receive every record exactly once,
    the tracer emits Perfetto-loadable trace_event JSON (validated by
    the schema checker, including rejection cases), ``timed`` stays off
    stdout, and the monitors detect injected stragglers without sleeps;
  * report rendering: ring + tracer -> markdown/JSON artifacts.
"""
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ENOracle, FWConfig, LOGISTIC, engine
from repro.core.fw_lasso import LASSO
from repro.obs import (
    EVENT_AWAY,
    EVENT_DROP,
    EVENT_FW,
    EVENT_LAZY_HIT,
    EVENT_PAIRWISE,
    EVENT_PARTAN,
    StepMonitor,
    TelemetrySpec,
    Tracer,
    build_report,
    get_tracer,
    register_sink,
    render_markdown,
    ring_to_records,
    unregister_sink,
    use_tracer,
    validate_chrome_trace,
    write_report,
)
from repro.sparse.matrix import SparseBlockMatrix
from repro.utils.timing import Timer, timed

DELTA = 150.0


def _base_cfg(**kw):
    base = dict(delta=DELTA, kappa=40, sampling="uniform", max_iters=120,
                tol=0.0, patience=10**9)
    base.update(kw)
    return FWConfig(**base)


def _sparse_mat(Xt, threshold=0.7, block_size=64):
    Xs = np.asarray(Xt).copy()
    Xs[np.abs(Xs) < threshold] = 0.0
    return SparseBlockMatrix.from_dense(Xs, block_size=block_size)


class TestTelemetryOffBitIdentity:
    """The telemetry ring must be invisible to the trajectory: same
    alpha, iterations, and dot counts bit for bit, ring on or off.
    (The pinned goldens in test_engine/test_step_rules/test_distributed
    all run with the default ``telemetry=None`` — those pin the OFF
    program; these pin ON == OFF.)"""

    @pytest.mark.parametrize("backend", ["xla", "pallas", "sparse"])
    def test_backends_identical(self, small_problem, rng_key, backend):
        Xt, y, _ = small_problem
        A = _sparse_mat(Xt) if backend == "sparse" else Xt
        kw = dict(backend=backend)
        if backend == "pallas":
            kw["interpret"] = True
        off = engine.solve(LASSO, A, y, _base_cfg(**kw), rng_key)
        on = engine.solve(
            LASSO, A, y,
            _base_cfg(**kw, telemetry=TelemetrySpec(capacity=64)), rng_key,
        )
        np.testing.assert_array_equal(np.asarray(off.alpha), np.asarray(on.alpha))
        assert int(off.iterations) == int(on.iterations)
        assert int(off.n_dots) == int(on.n_dots)
        assert off.telemetry is None and on.telemetry is not None

    @pytest.mark.parametrize("rule", ["away", "pairwise", "partan", "lazy"])
    def test_step_rules_identical(self, small_problem, rng_key, rule):
        Xt, y, _ = small_problem
        off = engine.solve(LASSO, Xt, y, _base_cfg(step_rule=rule), rng_key)
        on = engine.solve(
            LASSO, Xt, y,
            _base_cfg(step_rule=rule, telemetry=TelemetrySpec(capacity=64)),
            rng_key,
        )
        np.testing.assert_array_equal(np.asarray(off.alpha), np.asarray(on.alpha))
        assert int(off.n_dots) == int(on.n_dots)

    @pytest.mark.parametrize("oracle", [LOGISTIC, ENOracle(l2=0.7)],
                             ids=["logistic", "elasticnet"])
    def test_family_identical(self, small_problem, rng_key, oracle):
        Xt, y, _ = small_problem
        yv = jnp.sign(y) + (y == 0) if oracle is LOGISTIC else y
        off = engine.solve(oracle, Xt, yv, _base_cfg(max_iters=60), rng_key)
        on = engine.solve(
            oracle, Xt, yv,
            _base_cfg(max_iters=60, telemetry=TelemetrySpec(capacity=64)),
            rng_key,
        )
        np.testing.assert_array_equal(np.asarray(off.alpha), np.asarray(on.alpha))

    def test_fused_solve_identical(self, small_problem, rng_key):
        """telemetry-on with record_objective forces the bit-identical
        fori-of-step executor — the fused trajectory must not move."""
        Xt, y, _ = small_problem
        mat = _sparse_mat(Xt)
        kw = dict(backend="sparse", sparse_kernel=True, interpret=True,
                  fuse_steps=8)
        off = engine.solve(LASSO, mat, y, _base_cfg(**kw), rng_key)
        on = engine.solve(
            LASSO, mat, y,
            _base_cfg(**kw, telemetry=TelemetrySpec(capacity=64)), rng_key,
        )
        np.testing.assert_array_equal(np.asarray(off.alpha), np.asarray(on.alpha))


class TestRingContents:
    def test_ring_matches_history(self, small_problem, rng_key):
        """The ring's objective column IS the solve_with_history curve."""
        Xt, y, _ = small_problem
        cfg = _base_cfg()
        n = 100
        res_h, hist = engine.solve_with_history(LASSO, Xt, y, cfg, rng_key, n)
        ring = engine.solve(
            LASSO, Xt, y,
            _base_cfg(max_iters=n, telemetry=TelemetrySpec(capacity=n)),
            rng_key,
        ).telemetry
        assert hist.shape == (n,)
        np.testing.assert_array_equal(np.asarray(hist), np.asarray(ring.objective))
        # history's own result surfaces its ring too
        assert res_h.telemetry is not None
        np.testing.assert_array_equal(
            np.asarray(res_h.telemetry.objective[:n]), np.asarray(hist)
        )

    def test_fused_history_matches_unfused(self, small_problem, rng_key):
        """fuse_steps=K history == K=1 history (the old scan always ran
        per-step; the ring-based version must keep that contract)."""
        Xt, y, _ = small_problem
        mat = _sparse_mat(Xt)
        _, h1 = engine.solve_with_history(
            LASSO, mat, y, _base_cfg(backend="sparse", sparse_kernel=True,
                                     interpret=True), rng_key, 60,
        )
        _, h8 = engine.solve_with_history(
            LASSO, mat, y, _base_cfg(backend="sparse", sparse_kernel=True,
                                     interpret=True, fuse_steps=8),
            rng_key, 60,
        )
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h8))

    def test_wrap_keeps_last_records(self, small_problem, rng_key):
        Xt, y, _ = small_problem
        res = engine.solve(
            LASSO, Xt, y,
            _base_cfg(max_iters=100, telemetry=TelemetrySpec(capacity=32)),
            rng_key,
        )
        ring = res.telemetry
        assert int(ring.cursor) == 100  # true count survives the wrap
        rec = ring_to_records(ring)
        np.testing.assert_array_equal(rec["k"], np.arange(68, 100))
        np.testing.assert_array_equal(rec["record_index"], np.arange(68, 100))
        assert np.all(np.diff(rec["n_dots"]) > 0)  # cumulative

    def test_kernel_chunk_records(self, small_problem, rng_key):
        """record_objective=False keeps the megakernel chunk executor;
        its replayed records must agree with the per-step run on the
        step facts the kernel emits (i_star, lam, k, n_dots), with the
        unrecorded objective/gap columns NaN."""
        Xt, y, _ = small_problem
        mat = _sparse_mat(Xt)
        spec = TelemetrySpec(capacity=64, record_objective=False)
        fused = engine.solve(
            LASSO, mat, y,
            _base_cfg(backend="sparse", sparse_kernel=True, interpret=True,
                      fuse_steps=8, telemetry=spec),
            rng_key,
        )
        ref = engine.solve(
            LASSO, mat, y,
            _base_cfg(backend="sparse", sparse_kernel=True, interpret=True,
                      telemetry=spec),
            rng_key,
        )
        a, b = ring_to_records(fused.telemetry), ring_to_records(ref.telemetry)
        for field in ("k", "i_star", "lam", "n_dots", "event"):
            np.testing.assert_array_equal(a[field], b[field], err_msg=field)
        assert np.all(np.isnan(a["objective"])) and np.all(np.isnan(a["gap"]))

    def test_objective_gap_recorded(self, small_problem, rng_key):
        Xt, y, _ = small_problem
        res = engine.solve(
            LASSO, Xt, y, _base_cfg(telemetry=TelemetrySpec(capacity=200)),
            rng_key,
        )
        rec = ring_to_records(res.telemetry)
        assert not np.any(np.isnan(rec["objective"]))
        assert not np.any(np.isnan(rec["gap"]))
        # final recorded objective is the result objective
        np.testing.assert_allclose(
            rec["objective"][-1], float(res.objective), rtol=1e-6
        )

    def test_batched_lane_rings(self, small_problem):
        """solve_batched carries one ring per lane; frozen lanes stop
        recording, so each lane's cursor equals its iteration count."""
        Xt, y, _ = small_problem
        cfg = FWConfig(delta=1.0, kappa=40, sampling="uniform",
                       max_iters=400, tol=1e-3, patience=10,
                       telemetry=TelemetrySpec(capacity=32))
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        deltas = jnp.asarray([20.0, 80.0, 150.0], Xt.dtype)
        alpha0s = jnp.zeros((3, Xt.shape[0]), Xt.dtype)
        res, _ = engine.solve_batched(LASSO, Xt, y, cfg, keys, alpha0s, deltas)
        assert res.telemetry is not None
        np.testing.assert_array_equal(
            np.asarray(res.telemetry.cursor), np.asarray(res.iterations)
        )


class TestStepRuleEvents:
    def _events(self, Xt, y, key, rule, **kw):
        res = engine.solve(
            LASSO, Xt, y,
            _base_cfg(step_rule=rule, telemetry=TelemetrySpec(capacity=256),
                      **kw),
            key,
        )
        return ring_to_records(res.telemetry), res

    def test_away_codes(self, small_problem, rng_key):
        rec, res = self._events(*small_problem[:2], rng_key, "away")
        ev = set(rec["event"].tolist())
        assert EVENT_AWAY in ev  # away steps actually fired
        assert ev <= {EVENT_FW, EVENT_AWAY, EVENT_DROP}

    def test_pairwise_codes(self, small_problem, rng_key):
        rec, _ = self._events(*small_problem[:2], rng_key, "pairwise")
        ev = set(rec["event"].tolist())
        assert EVENT_PAIRWISE in ev
        assert ev <= {EVENT_FW, EVENT_PAIRWISE, EVENT_DROP}

    def test_partan_one_record_per_iteration(self, small_problem, rng_key):
        rec, res = self._events(*small_problem[:2], rng_key, "partan")
        # the classic half-step's record is AMENDED, not duplicated
        assert len(rec["k"]) == int(res.iterations)
        assert set(rec["event"].tolist()) == {EVENT_PARTAN}
        np.testing.assert_array_equal(rec["k"], np.arange(int(res.iterations)))

    def test_lazy_hits_recorded(self, small_problem, rng_key):
        rec, res = self._events(*small_problem[:2], rng_key, "lazy")
        ev = set(rec["event"].tolist())
        assert EVENT_LAZY_HIT in ev
        assert ev <= {EVENT_FW, EVENT_LAZY_HIT}
        assert not np.any(np.isnan(rec["gap"]))


class TestStreaming:
    def test_sink_receives_every_record_once(self, small_problem, rng_key):
        Xt, y, _ = small_problem
        batches = []
        register_sink("test-sink", batches.append)
        try:
            engine.solve(
                LASSO, Xt, y,
                _base_cfg(max_iters=50,
                          telemetry=TelemetrySpec(capacity=16,
                                                  stream_to="test-sink")),
                rng_key,
            ).alpha.block_until_ready()
            jax.effects_barrier()
        finally:
            unregister_sink("test-sink")
        idx = np.concatenate([b["record_index"] for b in batches])
        np.testing.assert_array_equal(np.sort(idx), np.arange(50))
        assert len(batches) >= 2  # wrap flushes + the final flush
        ks = np.concatenate([b["k"] for b in batches])
        np.testing.assert_array_equal(np.sort(ks), np.arange(50))

    def test_unregistered_sink_is_noop(self, small_problem, rng_key):
        Xt, y, _ = small_problem
        res = engine.solve(
            LASSO, Xt, y,
            _base_cfg(max_iters=20,
                      telemetry=TelemetrySpec(capacity=8,
                                              stream_to="nobody-home")),
            rng_key,
        )
        assert int(res.telemetry.cursor) == 20


class TestStreamingWrapAndFreeze:
    """ISSUE 9 satellite: wrapped-ring ``stream_to`` flush cadence and
    the ring invariants the batched driver's lane freezing must keep."""

    def _stream(self, Xt, y, key, cfg):
        batches = []
        register_sink("wrap-test", batches.append)
        try:
            res = engine.solve(LASSO, Xt, y, cfg, key)
            res.alpha.block_until_ready()
            jax.effects_barrier()
        finally:
            unregister_sink("wrap-test")
        return res, batches

    def test_wrap_boundary_batches_are_full_rings(self, small_problem,
                                                  rng_key):
        """Non-final flushes fire exactly at wrap boundaries, so every
        one delivers a full ring; the leftover drains in one partial
        final flush."""
        Xt, y, _ = small_problem
        _, batches = self._stream(
            Xt, y, rng_key,
            _base_cfg(max_iters=50,
                      telemetry=TelemetrySpec(capacity=16,
                                              stream_to="wrap-test")),
        )
        assert [len(b["record_index"]) for b in batches] == [16, 16, 16, 2]
        idx = np.concatenate([b["record_index"] for b in batches])
        np.testing.assert_array_equal(idx, np.arange(50))  # in order, no gaps

    def test_exact_multiple_skips_empty_final_flush(self, small_problem,
                                                    rng_key):
        """iterations % capacity == 0: the last wrap flush already
        drained everything and the final flush must not deliver an empty
        batch."""
        Xt, y, _ = small_problem
        _, batches = self._stream(
            Xt, y, rng_key,
            _base_cfg(max_iters=32,
                      telemetry=TelemetrySpec(capacity=16,
                                              stream_to="wrap-test")),
        )
        assert [len(b["record_index"]) for b in batches] == [16, 16]

    def test_partial_final_flush_on_early_stop(self, small_problem, rng_key):
        """A patience stop mid-ring drains exactly the recorded
        remainder: streamed records == cursor == iterations."""
        Xt, y, _ = small_problem
        res, batches = self._stream(
            Xt, y, rng_key,
            _base_cfg(delta=20.0, tol=1e-3, patience=10, max_iters=400,
                      telemetry=TelemetrySpec(capacity=64,
                                              stream_to="wrap-test")),
        )
        it = int(res.iterations)
        assert it < 400  # the stop actually fired early
        assert int(res.telemetry.cursor) == it
        assert int(res.telemetry.flushed) == it
        idx = np.concatenate([b["record_index"] for b in batches])
        np.testing.assert_array_equal(idx, np.arange(it))
        assert len(batches[-1]["record_index"]) == it % 64 or it % 64 == 0

    def _batched(self, Xt, y, capacity, **cfg_kw):
        base = dict(delta=1.0, kappa=40, sampling="uniform", max_iters=400,
                    tol=1e-3, patience=10,
                    telemetry=TelemetrySpec(capacity=capacity))
        base.update(cfg_kw)
        cfg = FWConfig(**base)
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        deltas = jnp.asarray([20.0, 80.0, 150.0], Xt.dtype)
        alpha0s = jnp.zeros((3, Xt.shape[0]), Xt.dtype)
        return engine.solve_batched(LASSO, Xt, y, cfg, keys, alpha0s, deltas)

    def test_frozen_lane_rings_stop_recording(self, small_problem):
        """capacity > iterations: each lane's ring holds exactly its own
        iterations — frozen lanes write nothing while the slowest lane
        keeps going, and the slots past a lane's freeze stay empty."""
        Xt, y, _ = small_problem
        res, _ = self._batched(Xt, y, capacity=400)
        iters = np.asarray(res.iterations)
        assert len(set(iters.tolist())) > 1  # lanes genuinely froze apart
        for lane in range(3):
            ring = jax.tree_util.tree_map(lambda a: a[lane], res.telemetry)
            it = int(iters[lane])
            assert int(ring.cursor) == it
            rec = ring_to_records(ring)
            np.testing.assert_array_equal(rec["k"], np.arange(it))
            assert np.all(np.asarray(ring.k)[it:] == -1)  # untouched slots

    def test_frozen_lane_wrapped_rings_keep_tail(self, small_problem):
        """capacity < iterations: a wrapped lane ring still reports the
        true per-lane count through ``cursor`` and surfaces the LAST
        ``capacity`` records of that lane — not the slowest lane's."""
        Xt, y, _ = small_problem
        res, _ = self._batched(Xt, y, capacity=32)
        iters = np.asarray(res.iterations)
        for lane in range(3):
            ring = jax.tree_util.tree_map(lambda a: a[lane], res.telemetry)
            it = int(iters[lane])
            assert int(ring.cursor) == it
            rec = ring_to_records(ring)
            n = min(it, 32)
            np.testing.assert_array_equal(rec["k"], np.arange(it - n, it))
            np.testing.assert_array_equal(
                rec["record_index"], np.arange(it - n, it)
            )
            assert np.all(np.diff(rec["n_dots"]) > 0)

    def test_fused_chunks_freeze_mid_chunk_exact_cursor(self, small_problem):
        """fuse_steps=K batched lanes stop on their own iteration, not a
        chunk boundary: in-chunk masking must keep cursor == iterations
        even when the freeze lands mid-chunk."""
        Xt, y, _ = small_problem
        mat = _sparse_mat(Xt)
        res, _ = self._batched(
            mat, y, capacity=64,
            backend="sparse", sparse_kernel=True, interpret=True,
            fuse_steps=8,
            telemetry=TelemetrySpec(capacity=64, record_objective=False),
        )
        iters = np.asarray(res.iterations)
        np.testing.assert_array_equal(
            np.asarray(res.telemetry.cursor), iters
        )
        for lane in range(3):
            ring = jax.tree_util.tree_map(lambda a: a[lane], res.telemetry)
            rec = ring_to_records(ring)
            it = int(iters[lane])
            n = min(it, 64)
            np.testing.assert_array_equal(rec["k"], np.arange(it - n, it))


class TestTracer:
    def test_spans_counters_and_validation(self):
        tr = Tracer("t")
        with tr.span("outer", cat="x", detail=1):
            with tr.span("inner"):
                pass
            tr.counter("widgets", 2)
            tr.counter("widgets", 3)
            tr.instant("mark", note="hi")
        assert tr.counter_table() == {"widgets": 5.0}
        table = tr.span_table()
        assert table["outer"]["count"] == 1 and table["inner"]["count"] == 1
        assert validate_chrome_trace(tr.to_chrome()) == []
        assert validate_chrome_trace(json.dumps(tr.to_chrome())) == []

    def test_use_tracer_stacks(self):
        tr = Tracer("scoped")
        default = get_tracer()
        with use_tracer(tr):
            assert get_tracer() is tr
        assert get_tracer() is default

    def test_validator_rejects_bad_traces(self):
        assert validate_chrome_trace("not json")
        assert validate_chrome_trace({"nope": 1})
        assert validate_chrome_trace(
            {"traceEvents": [{"ph": "Z", "name": "x", "ts": 0}]}
        )
        assert validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "name": "x", "ts": 0}]}
        )  # X without dur
        # unbalanced B/E on one track
        errs = validate_chrome_trace(
            {"traceEvents": [
                {"ph": "B", "name": "a", "ts": 0, "pid": 1, "tid": 1}
            ]}
        )
        assert any("unclosed" in e for e in errs)

    def test_save_roundtrip(self, tmp_path):
        tr = Tracer("t")
        with tr.span("s"):
            pass
        path = tr.save(tmp_path / "trace.json")
        with open(path) as fh:
            assert validate_chrome_trace(fh.read()) == []


class TestTimed:
    def test_no_stdout_by_default(self, capsys):
        tr = Tracer("t")
        with use_tracer(tr):
            with timed("quiet-block"):
                pass
        assert capsys.readouterr().out == ""
        assert tr.span_table()["quiet-block"]["count"] == 1

    def test_dict_and_timer_sinks(self):
        d = {}
        t = Timer()
        with use_tracer(Tracer("t")):
            with timed("x", sink=d):
                pass
            with timed("x", sink=d):
                pass
            with timed("y", sink=t):
                pass
        assert d["x"] > 0 and len(d) == 1
        assert t.count == 1 and t.total > 0

    def test_timer_merge(self):
        a = Timer(total=1.0, count=2)
        b = Timer(total=0.5, count=3)
        a.merge(b)
        assert a.total == 1.5 and a.count == 5
        assert a.mean == pytest.approx(0.3)

    def test_verbose_opt_in(self, capsys):
        with use_tracer(Tracer("t")):
            with timed("loud", verbose=True):
                pass
        assert "[timed] loud:" in capsys.readouterr().out


class TestMonitors:
    def test_straggler_detection_fake_clock(self):
        """Injected clock: steps of 1.0s with one 10x outlier — no real
        sleeps needed."""
        times = iter([0.0, 1.0,  # step 1 (seeds the EWMA)
                      2.0, 3.0,  # step 2
                      4.0, 14.0,  # step 3: 10s straggler
                      15.0, 16.0])  # step 4: recovered
        mon = StepMonitor(clock=lambda: next(times))
        flags = []
        for _ in range(4):
            mon.begin()
            flags.append(mon.end())
        assert flags == [False, False, True, False]
        assert mon.stragglers == [3]

    def test_heartbeat_json(self, tmp_path):
        times = iter([0.0, 1.0, 2.0, 12.0])
        hb = tmp_path / "hb.json"
        mon = StepMonitor(heartbeat_path=hb, clock=lambda: next(times))
        mon.begin(); mon.end()
        mon.begin()
        assert mon.end() is True
        data = json.loads(hb.read_text())
        assert data["step"] == 2
        assert data["straggler"] is True
        assert data["stragglers"] == [2]
        assert data["step_time"] == pytest.approx(10.0)

    def test_runtime_shim_is_gone(self):
        # PR 7's repro.runtime.monitor deprecation shim is retired:
        # the one import path is repro.obs.monitor
        with pytest.raises(ImportError):
            importlib.import_module("repro.runtime.monitor")


class TestReport:
    def test_build_and_render(self, small_problem, rng_key, tmp_path):
        Xt, y, _ = small_problem
        tr = Tracer("report-test")
        with use_tracer(tr):
            with tr.span("solve"):
                res = engine.solve(
                    LASSO, Xt, y,
                    _base_cfg(max_iters=40,
                              telemetry=TelemetrySpec(capacity=40)),
                    rng_key,
                )
                res.alpha.block_until_ready()
        report = build_report(
            meta={"git_sha": "deadbeef", "purpose": "test"},
            runs=[{
                "name": "lasso_xla", "backend": "xla",
                "iterations": int(res.iterations),
                "n_dots": int(res.n_dots),
                "objective": float(res.objective),
                "ring": res.telemetry,
            }],
            tracer=tr,
        )
        assert report["runs"][0]["event_counts"] == {"fw": 40}
        md = render_markdown(report)
        assert "deadbeef" in md
        assert "Convergence curve — lasso_xla" in md
        assert "| solve |" in md
        paths = write_report(tmp_path, report)
        with open(paths["json"]) as fh:
            loaded = json.load(fh)
        assert loaded["runs"][0]["records"]["k"][0] == 0
        assert (tmp_path / "solver_report.md").exists()
