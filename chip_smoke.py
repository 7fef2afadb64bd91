"""Smoke run of the lasso regularization path on TPU chips.

Drives the solver's main path through its normal entry points
(``repro.core.path.fw_path`` / ``fw_path_batched`` -> ``engine``), with
the Pallas kernels compiled for the chip, on data made by the repo's
seeded generators at the paper's published sizes (Table 1), and checks
every kernel path against its XLA reference run on the same chip:

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # four chips: phase C only

Phase A  e2006-log1p, 16,087 x 4,272,227 at density 0.002, block-ELL
         sparse: the batched path with the sparse kernels on vs. off,
         the fused sparse megakernel (fuse_steps=8) vs. the XLA chunk
         executor, and block sampling through the sampled-score kernel.
Phase B  triazines, 186 x 635,376 dense: backend 'pallas' vs. backend
         'xla', unfused and fused (fuse_steps=8).
Phase C  phase A's batched path sharded on a (1, 4) mesh vs. the same
         path on device 0, with the bytes each device holds.

Earlier lines report set-up and path wall times, each labelled with the
device; the first point of every path includes its compile. The last
line of stdout is one JSON object naming the device. The script exits
non-zero, without that line, when JAX finds no TPU, when the ``repro``
package is not beside it, or when any check fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

SEED = 0
N_POINTS = 8
MAX_ITERS = 5000
FUSE = 8

# Agreement of a kernel path with its XLA reference, per grid point. Each
# pair replays one sampled index stream (uniform draws, or the same
# aligned blocks) under one stopping cadence (a fused path is compared
# with the XLA executor at the same fuse_steps), so they differ only in
# the rounding of the chip's reductions. Objectives agree within OBJ_RTOL
# of the larger of the point's objective and OBJ_FLOOR of the path's
# largest (a point that fits y almost exactly is not judged on the
# rounding of a near-zero); iteration counts within ITER_SLACK +
# ITER_RTOL; supports differ in at most SUPP_RTOL of their union. A
# support counts the atoms above SUPP_ATOL * delta: a step whose line
# search is at rounding level leaves an atom at exactly 0 on one path and
# at ~1e-7 * delta on the other.
OBJ_RTOL, OBJ_FLOOR = 1e-4, 1e-2
ITER_SLACK, ITER_RTOL = 10, 0.02
SUPP_RTOL, SUPP_ATOL = 0.05, 1e-5


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _label() -> str:
    from repro import devices

    return devices.device_label()


def _gib(nbytes) -> str:
    return f"{nbytes / 2**30:.3f} GiB"


def device_bytes(tag: str) -> None:
    """Bytes in use on every device, as the runtime reports them."""
    import jax

    for d in jax.devices():
        stats = d.memory_stats() or {}
        used = stats.get("bytes_in_use")
        peak = stats.get("peak_bytes_in_use")
        print(f"  [{tag}] device {d.id} ({d.device_kind}): in use "
              f"{_gib(used) if used is not None else 'n/a'}, peak "
              f"{_gib(peak) if peak is not None else 'n/a'}")


def check_kernel_config(cfg, *, sparse: bool) -> None:
    """The platform, not a flag, must pick native kernels here."""
    from repro.core import vertex

    _check(vertex.use_interpret(cfg) is False,
           f"{cfg.backend}: Pallas would run in interpret mode on this chip")
    if sparse:
        _check(vertex.use_sparse_kernel(cfg) is True,
               "sparse backend: the Pallas sparse kernels are off on this chip")


def report_fused(cfg) -> None:
    """Say which executor a fused chunk takes at this shape, and why."""
    from repro.core import vertex
    from repro.core.fw_lasso import LASSO
    from repro.kernels.fused_step import fused_step

    need = fused_step.prefetch_bytes(cfg.fuse_steps, cfg.kappa,
                                     LASSO.fused_needs_alpha)
    budget = fused_step.SMEM_PREFETCH_BUDGET
    if vertex.use_fused_kernel(LASSO, cfg):
        print(f"  fused chunk ({cfg.backend}, K={cfg.fuse_steps}, "
              f"kappa={cfg.kappa}): Pallas megakernel, SMEM prefetch "
              f"{need:,} B <= budget {budget:,} B")
    else:
        print(f"  fused chunk ({cfg.backend}, K={cfg.fuse_steps}, "
              f"kappa={cfg.kappa}): per-step XLA executor, chosen by shape "
              f"(SMEM prefetch {need:,} B > budget {budget:,} B)")


def run_path(name, fn, *args, **kw):
    t0 = time.perf_counter()
    res = fn(*args, **kw)
    wall = time.perf_counter() - t0
    pts = res.points
    print(f"  [{_label()}] {name}: {len(pts)} points in {wall:.3f} s "
          f"(first point {pts[0].seconds:.3f} s incl. compile), "
          f"{res.total_iters} iterations, {res.total_dots:,} dots")
    return res


def _support(pt) -> set:
    big = abs(pt.alpha_nnz_val) > SUPP_ATOL * pt.reg
    return set(pt.alpha_nnz_idx[big].tolist())


def compare(name, got, ref):
    """Per-point agreement of a path with its reference; raises after
    printing every point if any is outside the stated tolerances."""
    import math

    print(f"  {name} vs reference: objective rtol {OBJ_RTOL:g} (floor "
          f"{OBJ_FLOOR:g} of the path's largest); iterations within "
          f"{ITER_SLACK} + {ITER_RTOL:.0%}; supports (|alpha| > "
          f"{SUPP_ATOL:g} delta) differing in at most {SUPP_RTOL:.0%} of "
          f"their union")
    floor = OBJ_FLOOR * max(abs(r.objective) for r in ref.points)
    print("    delta        objective(ref)   rel.diff   iters ref/got  "
          "active ref/got  sym.diff  gap(got)")
    bad = []
    for g, r in zip(got.points, ref.points, strict=True):
        rel = abs(g.objective - r.objective) / max(abs(r.objective), floor)
        sg, sr = _support(g), _support(r)
        sym = len(sg ^ sr)
        ok = (
            math.isfinite(g.objective)
            and rel <= OBJ_RTOL
            and abs(g.iterations - r.iterations)
            <= ITER_SLACK + ITER_RTOL * r.iterations
            and sym <= SUPP_RTOL * len(sg | sr)
            and math.isfinite(g.gap)
            and g.l1 <= g.reg * (1 + 1e-4)
        )
        print(f"    {g.reg:<12.6g} {r.objective:<16.8g} {rel:<10.3e} "
              f"{r.iterations:>5}/{g.iterations:<5}   {r.active:>5}/{g.active:<5}    "
              f"{sym:<8}  {g.gap:.6g}{'' if ok else '   <-- FAIL'}")
        if not ok:
            bad.append(g.reg)
    _check(not bad, f"{name}: points outside tolerance at delta={bad}")


def make_e2006():
    from repro.data.proxies import PROXY_SPECS, make_sparse_proxy

    spec = PROXY_SPECS["e2006-log1p"]
    t0 = time.perf_counter()
    ds = make_sparse_proxy("e2006-log1p", scale=1.0, seed=SEED)
    ds.mat.values.block_until_ready()
    mat = ds.mat
    _check((mat.m, mat.p) == (spec.m, spec.p), "e2006-log1p not at published size")
    print(f"  [{_label()}] set-up: e2006-log1p generated and loaded in "
          f"{time.perf_counter() - t0:.3f} s: m={mat.m:,} p={mat.p:,} "
          f"nnz_max={mat.nnz_max} block_size={mat.block_size}; block-ELL "
          f"values+rows {_gib(mat.nbytes)} (p_padded*nnz_max*8 B)")
    return ds


def e2006_deltas(ds):
    from repro.core import path

    # as examples/lasso_fullpath_4m.py: half the generating l1 norm keeps
    # the path in the sparse regime; the paper's grid spans /100
    return path.delta_grid(0.5 * float(abs(ds.coef).sum()), n_points=N_POINTS)


def phase_a() -> None:
    import jax.numpy as jnp

    from repro.core import path
    from repro.core.solver_config import FWConfig

    print("phase A: e2006-log1p, block-ELL sparse, published size")
    ds = make_e2006()
    mat, y = ds.mat, jnp.asarray(ds.y)
    device_bytes("after load")
    deltas = e2006_deltas(ds)

    cfg = FWConfig(delta=1.0, backend="sparse", max_iters=MAX_ITERS,
                   report_gap=True)
    check_kernel_config(cfg, sparse=True)
    ref_cfg = dataclasses.replace(cfg, sparse_kernel=False)
    ref = run_path("batched path, XLA reference", path.fw_path_batched,
                   mat, y, deltas, ref_cfg, seed=SEED)
    got = run_path("batched path, sparse kernels", path.fw_path_batched,
                   mat, y, deltas, cfg, seed=SEED)
    compare("batched path, sparse kernels", got, ref)

    fcfg = dataclasses.replace(cfg, fuse_steps=FUSE)
    check_kernel_config(fcfg, sparse=True)
    report_fused(fcfg)
    fref = run_path("sequential fused path, XLA executor", path.fw_path,
                    mat, y, deltas, dataclasses.replace(fcfg, sparse_kernel=False),
                    seed=SEED)
    fused = run_path("sequential fused path, sparse megakernel", path.fw_path,
                     mat, y, deltas, fcfg, seed=SEED)
    compare("sequential fused path, sparse megakernel", fused, fref)

    bcfg = dataclasses.replace(cfg, sampling="block")
    check_kernel_config(bcfg, sparse=True)
    few = deltas[: N_POINTS // 2]
    bref = run_path("block-sampled path, XLA reference", path.fw_path,
                    mat, y, few, dataclasses.replace(bcfg, sparse_kernel=False),
                    seed=SEED)
    bgot = run_path("block-sampled path, sampled-score kernel", path.fw_path,
                    mat, y, few, bcfg, seed=SEED)
    compare("block-sampled path, sampled-score kernel", bgot, bref)
    device_bytes("end of phase A")


def phase_b() -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import path
    from repro.core.solver_config import FWConfig
    from repro.data.proxies import PROXY_SPECS, make_proxy

    print("phase B: triazines, dense, published size")
    spec = PROXY_SPECS["triazines"]
    t0 = time.perf_counter()
    ds = make_proxy("triazines", scale=1.0, seed=SEED)
    Xt = jnp.asarray(np.ascontiguousarray(ds.X.T))
    y = jnp.asarray(ds.y)
    Xt.block_until_ready()
    _check(Xt.shape == (spec.p, spec.m), "triazines not at published size")
    print(f"  [{_label()}] set-up: triazines generated and loaded in "
          f"{time.perf_counter() - t0:.3f} s: m={spec.m} p={spec.p:,}, "
          f"{_gib(Xt.nbytes)} dense f32")
    deltas = path.delta_grid(0.5 * float(np.abs(ds.coef).sum()), n_points=N_POINTS)

    base = FWConfig(delta=1.0, max_iters=MAX_ITERS, report_gap=True)
    ref = run_path("path, backend xla", path.fw_path, Xt, y, deltas,
                   dataclasses.replace(base, backend="xla"), seed=SEED)
    pcfg = dataclasses.replace(base, backend="pallas")
    check_kernel_config(pcfg, sparse=False)
    got = run_path("path, backend pallas", path.fw_path, Xt, y, deltas, pcfg,
                   seed=SEED)
    compare("path, backend pallas", got, ref)
    fref = run_path("fused path, backend xla", path.fw_path, Xt, y, deltas,
                    dataclasses.replace(base, backend="xla", fuse_steps=FUSE),
                    seed=SEED)
    fcfg = dataclasses.replace(pcfg, fuse_steps=FUSE)
    report_fused(fcfg)
    fused = run_path("fused path, backend pallas megakernel", path.fw_path,
                     Xt, y, deltas, fcfg, seed=SEED)
    compare("fused path, backend pallas megakernel", fused, fref)


def phase_c() -> None:
    import jax.numpy as jnp

    from repro import distributed
    from repro.core import path
    from repro.core.solver_config import FWConfig

    print("phase C: e2006-log1p sharded on a (1, 4) mesh vs. device 0")
    ds = make_e2006()
    mat, y = ds.mat, jnp.asarray(ds.y)
    deltas = e2006_deltas(ds)
    cfg = FWConfig(delta=1.0, backend="sparse", max_iters=MAX_ITERS,
                   report_gap=True)
    check_kernel_config(cfg, sparse=True)
    ref = run_path("batched path on device 0", path.fw_path_batched,
                   mat, y, deltas, cfg, seed=SEED)

    mesh = distributed.fw_mesh(n_data=1, n_model=4)
    t0 = time.perf_counter()
    op = distributed.shard_sparse(mat, ds.y, mesh)
    op.values.block_until_ready()
    print(f"  [{_label()}] set-up: sharded onto mesh {dict(mesh.shape)} in "
          f"{time.perf_counter() - t0:.3f} s")
    held = {}
    for arr in (op.values, op.rows):
        for sh in arr.addressable_shards:
            held[sh.device.id] = held.get(sh.device.id, 0) + sh.data.nbytes
    for dev_id in sorted(held):
        print(f"  matrix shard bytes on device {dev_id}: {_gib(held[dev_id])}")
    _check(len(held) == 4 and min(held.values()) > 0,
           f"matrix shards not spread over four devices: {held}")
    device_bytes("after sharding")
    got = run_path("batched path on the (1, 4) mesh",
                   distributed.fw_path_batched, op, deltas, cfg, seed=SEED)
    compare("batched path on the (1, 4) mesh", got, ref)
    device_bytes("end of phase C")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A and B on one chip; 4: phase C only")
    args = ap.parse_args(argv)

    from repro import devices

    import jax

    devices.enable_compile_cache()
    info = devices.device_info()
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}  (jax {jax.__version__})")
    if info["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU; nothing was run", file=sys.stderr)
        return 1
    if info["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {info['count']}", file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_c()
    else:
        phase_a()
        phase_b()
    print(f"all checks passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
