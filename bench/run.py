"""Run one cell of the benchmark once, on the accelerator it starts on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's data on the device from ``--seed`` (the law its
configuration file names, ``bench/laws``; where the configuration states
a ``mesh``, over a (data, model) mesh of the cell's chips, each chip
drawing its own share), then compiles and warms up
every program of the window through one warm-up unit. The window runs
the cell's traffic (``bench/drive.py``) for ``--seconds``; with
``--trace 1`` it runs one path, or fits for a few seconds, under the
profiler instead, and reports the per-layer metrics. Once the window has
closed and its device memory is released, every answer of the window is
judged against the plain reference that the configuration names
(``bench/check.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), with
``checks`` last: each number compared, with its limit. The same numbers
end stderr. With no TPU, fewer chips than the cell asks for, a mesh that
is not the cell's chips, or no program beside the benchmark, it prints
no result and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# fits under the profiler in a traced run of a fit cell
TRACED_FIT_SECONDS = 3.0


class NoDevice(RuntimeError):
    pass


def _say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _device(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {info['platform']!r})")
    if info["count"] < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {info['count']}")
    return info


def seed_key(seed: int):
    """A PRNG key from every bit of ``seed`` (``jax.random.key`` keeps
    only the low 32 bits of a larger one)."""
    import jax

    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


def _memory_peak() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def _traced(driver, seconds: float, trace_dir: str):
    """One unit of work (a path) or a few seconds of fits under the
    profiler; returns the reduced trace and the window's length."""
    import jax

    from bench import trace

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            elapsed = driver.window(
                0.0 if driver.entry == "path" else min(seconds, TRACED_FIT_SECONDS))
    finally:
        jax.profiler.stop_trace()
    return trace.load(trace.find_xplane(trace_dir)), elapsed


def run(argv=None, *, root: Path = ROOT, require_tpu: bool = True,
        control_dtype=None, notes: dict = None) -> dict:
    """One run of one cell; returns the result line's object. Raises
    ``NoDevice`` where the cell cannot run here. ``control_dtype`` runs
    the program on the design and targets in that type (the control);
    ``notes``, where given, receives the run's own readings: units of
    work, window and reference seconds, iterations, the reference's gap."""
    args = _parse(argv)
    from bench import check, harness

    cell = harness.find_cell(root, args.workload)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise NoDevice(f"the program is not beside the benchmark ({src} is missing)")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import jax
    import jax.numpy as jnp

    from repro import devices

    devices.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    info = _device(cell.chips, require_tpu)

    from bench.drive import Driver

    law = harness.law(root, cell.spec)
    t0 = time.perf_counter()
    if "mesh" in cell.spec:
        from repro import distributed

        mesh = distributed.fw_mesh(n_data=int(cell.spec["mesh"]["data"]),
                                   n_model=int(cell.spec["mesh"]["model"]),
                                   devices=jax.devices()[: cell.chips])
        data = law.generate(cell.spec, seed_key(args.seed), mesh=mesh)
    else:
        data = law.generate(cell.spec, seed_key(args.seed))
    jax.block_until_ready(data)
    _say(f"data made on the device in {time.perf_counter() - t0:.3f} s")
    driver = Driver(data, cell.spec, cell.traffic, args.seed,
                    dtype=control_dtype or jnp.float32)
    t0 = time.perf_counter()
    driver.warm_up()
    setup_s = time.perf_counter() - T_START
    _say(f"warm-up {time.perf_counter() - t0:.3f} s; set-up {setup_s:.3f} s")

    tr = None
    if args.trace:
        with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
            tr, elapsed = _traced(driver, args.seconds, tdir)
    else:
        elapsed = driver.window(args.seconds)
    info["memory_peak_bytes"] = _memory_peak()
    answers, path_results = driver.answers, driver.path_results
    grid = driver.grid if driver.entry == "path" else None
    driver.release()
    _say(f"window {elapsed:.3f} s, {driver.units} units, {len(answers)} answers")

    t0 = time.perf_counter()
    verdict = check.judge(data, answers, cell.limits, args.seed,
                          harness.reference(root, cell.spec), grid)
    ref_s = time.perf_counter() - t0
    _say(f"reference check {ref_s:.3f} s; reference radii "
         f"{verdict['ref'].get('radii')}, its largest gap over f(0) "
         f"{verdict['ref'].get('ref_gap')!r}")
    if notes is not None:
        notes.update(verdict["ref"], units=driver.units, window_s=elapsed,
                     reference_s=ref_s, setup_s=setup_s,
                     iterations=sum(a["iterations"] for a in answers))
    ctx = SimpleNamespace(
        setup_s=setup_s, window_s=elapsed, units=driver.units,
        answers=answers, path_results=path_results, entry=driver.entry,
        trace=tr, data={k: v for k, v in data.items() if not hasattr(v, "shape")},
        shapes={k: tuple(v.shape) for k, v in data.items() if hasattr(v, "shape")},
        device_kind=info["kind"],
    )
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = harness.metric_reader(root, m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out = {"correct": verdict["correct"], "attempted": verdict["attempted"],
           "failed": verdict["failed"], "metrics": metrics, "device": info}
    if tr is not None:
        from bench import trace

        info["busy_s"] = trace.busy_s(tr)
        info["window_s"] = trace.window_s(tr)
        out["breakdown"] = trace.breakdown(tr)
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in verdict["numbers"].items()}
    return out


def main(argv=None) -> int:
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench.harness import CellError

    try:
        out = run(argv)
    except (NoDevice, CellError) as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 1
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    # a number that is not finite (no decrease at all) is printed by name:
    # strict JSON has no infinity
    for c in out["checks"].values():
        if not math.isfinite(c["value"]):
            c["value"] = repr(c["value"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
