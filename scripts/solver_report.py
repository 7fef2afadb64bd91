"""Solver run-report CLI (DESIGN.md §Observability).

Runs a small traced lasso solve per requested backend with the
telemetry ring on, then renders the artifacts:

    <out-dir>/solver_report.md      human-facing markdown report
    <out-dir>/solver_report.json    the same data, machine-readable
    <out-dir>/solver_trace.json     Chrome/Perfetto trace_event JSON

Usage (from the repo root):

    PYTHONPATH=src python scripts/solver_report.py --out-dir reports
    PYTHONPATH=src python scripts/solver_report.py --backends xla,sparse \
        --distributed --iters 300

``--distributed`` re-runs the solve on a forced 4-virtual-CPU-device
(1, 4) mesh in a subprocess (this process keeps its device count) and
adds the run — including the analytic per-iteration comm fraction — to
the same report.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_REPO_ROOT, os.path.join(_REPO_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def build_problem(m: int, p: int, seed: int = 0):
    import numpy as np

    from repro.data import make_regression, standardize

    ds = standardize(
        make_regression(m=m, p=p, n_informative=20, noise=0.5, seed=seed)
    )
    Xs = np.asarray(ds.X.T, np.float32).copy()
    y = np.asarray(ds.y, np.float32)
    return Xs, y


def _cfg(args, backend: str):
    from repro.core import FWConfig
    from repro.obs import TelemetrySpec

    return FWConfig(
        delta=args.delta,
        kappa=args.kappa,
        sampling="uniform",
        max_iters=args.iters,
        tol=0.0,
        patience=10**9,
        backend=backend,
        step_rule=args.rule,
        telemetry=TelemetrySpec(capacity=args.iters),
    )


def run_backend(backend: str, Xs, y, args) -> dict:
    """One traced, telemetry-on solve; returns a report ``runs`` entry."""
    import jax
    import jax.numpy as jnp

    from repro.core import LASSO, engine
    from repro.obs import ring_to_records, trace as obs_trace
    from repro.sparse.matrix import SparseBlockMatrix

    if backend == "sparse":
        import numpy as np

        Xsp = Xs.copy()
        Xsp[np.abs(Xsp) < 0.04] = 0.0
        A = SparseBlockMatrix.from_dense(Xsp, block_size=32)
    else:
        A = jnp.asarray(Xs)
    cfg = _cfg(args, backend)
    key = jax.random.PRNGKey(args.seed)
    yj = jnp.asarray(y)
    tracer = obs_trace.get_tracer()
    with tracer.span(f"report/compile_{backend}", cat="report"):
        res = engine.solve(LASSO, A, yj, cfg, key)
        res.alpha.block_until_ready()
    t0 = time.perf_counter()
    with tracer.span(f"report/solve_{backend}", cat="report"):
        res = engine.solve(LASSO, A, yj, cfg, key)
        res.alpha.block_until_ready()
    dt = time.perf_counter() - t0
    records = ring_to_records(res.telemetry)
    return {
        "name": f"lasso_{backend}",
        "backend": backend,
        "iterations": int(res.iterations),
        "n_dots": int(res.n_dots),
        "objective": float(res.objective),
        "seconds": dt,
        "ring": {k: v.tolist() for k, v in records.items()},
    }


# -- distributed subprocess -------------------------------------------------

_DIST_CHILD_FLAG = "--_dist-child"


def _dist_child(args) -> None:
    """Child body: forced 4-device mesh, one traced distributed solve,
    run entry printed as JSON on stdout (REPORTRESULT line)."""
    import jax
    import numpy as np

    from repro import distributed as dist
    from repro.core import LASSO
    from repro.devices import device_label
    from repro.obs import ring_to_records
    from repro.sparse.matrix import SparseBlockMatrix

    Xs, y = build_problem(args.m, args.p, args.seed)
    Xs[np.abs(Xs) < 0.04] = 0.0
    mat = SparseBlockMatrix.from_dense(Xs, block_size=32)
    mesh = dist.fw_mesh(1, 4)
    op = dist.shard_sparse(mat, y, mesh)
    cfg = _cfg(args, "xla")  # driver swaps in backend='distributed'
    key = jax.random.PRNGKey(args.seed)
    res = dist.solve(LASSO, op, cfg, key)
    res.alpha.block_until_ready()
    t0 = time.perf_counter()
    res = dist.solve(LASSO, op, cfg, key)
    res.alpha.block_until_ready()
    dt = time.perf_counter() - t0
    # analytic per-iteration comm budget (DESIGN.md §Distributed): the
    # |S| score psum over both axes, the (m_local,) column psum over
    # "model", and the O(1) scalar psums of the oracle recursions
    comm = 4 * (args.kappa + op.m_local + 8)
    local = 8 * args.kappa * op.nnz_max + 4 * 4 * op.m_local
    entry = {
        "name": "lasso_distributed_1x4",
        "backend": "distributed",
        # the child is pinned to 4 virtual CPU devices on any host
        "device": device_label(),
        "iterations": int(res.iterations),
        "n_dots": int(res.n_dots),
        "objective": float(res.objective),
        "seconds": dt,
        "comm_fraction": comm / (comm + local),
        "ring": {
            k: v.tolist() for k, v in ring_to_records(res.telemetry).items()
        },
    }
    print("REPORTRESULT" + json.dumps(entry), flush=True)


def run_distributed(args):
    env = {
        **os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.pathsep.join(sys.path),
    }
    cmd = [sys.executable, os.path.abspath(__file__), _DIST_CHILD_FLAG,
           "--m", str(args.m), "--p", str(args.p), "--iters", str(args.iters),
           "--kappa", str(args.kappa), "--delta", str(args.delta),
           "--rule", args.rule, "--seed", str(args.seed)]
    proc = subprocess.run(
        cmd, capture_output=True, text=True,
        timeout=int(os.environ.get("REPRO_SUBPROC_TIMEOUT", "900")), env=env,
    )
    lines = [l for l in proc.stdout.splitlines()
             if l.startswith("REPORTRESULT")]
    if proc.returncode != 0 or not lines:
        raise RuntimeError(
            f"distributed child failed (rc={proc.returncode}): "
            f"{proc.stderr[-800:]}"
        )
    return json.loads(lines[0][len("REPORTRESULT"):])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out-dir", default="reports")
    ap.add_argument("--backends", default="xla,pallas,sparse",
                    help="comma-separated: xla,pallas,sparse")
    ap.add_argument("--distributed", action="store_true",
                    help="add a 4-virtual-device (1,4)-mesh run (subprocess)")
    ap.add_argument("--m", type=int, default=128)
    ap.add_argument("--p", type=int, default=512)
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--kappa", type=int, default=48)
    ap.add_argument("--delta", type=float, default=100.0)
    ap.add_argument("--rule", default="classic")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(_DIST_CHILD_FLAG, action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if getattr(args, "_dist_child"):
        _dist_child(args)
        return 0

    from benchmarks.common import bench_provenance
    from repro.obs import build_report, trace as obs_trace, write_report

    tracer = obs_trace.Tracer("solver-report")
    runs = []
    with obs_trace.use_tracer(tracer):
        Xs, y = build_problem(args.m, args.p, args.seed)
        for backend in [b for b in args.backends.split(",") if b]:
            print(f"# running {backend} ...", flush=True)
            runs.append(run_backend(backend, Xs, y, args))
        if args.distributed:
            print("# running distributed (1,4) mesh ...", flush=True)
            runs.append(run_distributed(args))

    meta = bench_provenance()
    meta.update(m=args.m, p=args.p, iters=args.iters, kappa=args.kappa,
                rule=args.rule)
    report = build_report(meta=meta, runs=runs, tracer=tracer)
    paths = write_report(args.out_dir, report)
    trace_path = tracer.save(os.path.join(args.out_dir, "solver_trace.json"))
    errors = obs_trace.validate_chrome_trace(tracer.to_chrome())
    if errors:
        print("trace validation FAILED:", *errors, sep="\n  ")
        return 1
    print(f"# wrote {paths['markdown']}")
    print(f"# wrote {paths['json']}")
    print(f"# wrote {trace_path} (Perfetto-loadable)")
    return 0


if __name__ == "__main__":
    from repro.devices import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
