"""Kernel micro-benchmarks: kernel timing vs the jnp reference path. The
kernels compile natively on a TPU and run in interpret mode elsewhere
(the ``mode`` and ``device`` fields say which); a CPU wall time is no
speed evidence, and the BlockSpec geometry + VMEM footprint per grid
step are the TPU-relevant numbers reported there.

The sparse section times the block-ELL sampled-gradient against the dense
XLA gather at the paper's text-dataset densities — the acceptance number
for the sparse subsystem (sparse wins whenever col_density <= 0.01).

All rows are mirrored into BENCH_kernels.json (BenchJSON) so the perf
trajectory is machine-diffable across PRs.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import CSV, BenchJSON
from repro.devices import device_label, pallas_interpret
from repro.kernels import colstats, residual_update, sampled_scores, sparse_sampled_scores
from repro.kernels.fw_grad.ref import sampled_scores_ref
from repro.kernels.sparse_grad.ref import sparse_sampled_scores_ref
from repro.sparse import SparseBlockMatrix


def _time(fn, *args, n=5, **kw):
    fn(*args, **kw)  # warm/compile
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args, **kw))
    return (time.perf_counter() - t0) / n


def _sparse_rows(p, m, density, rng):
    """Feature-major matrix with exactly density*m nonzeros per feature."""
    k = max(1, int(density * m))
    Xt = np.zeros((p, m), np.float32)
    for i in range(p):
        idx = rng.choice(m, size=k, replace=False)
        Xt[i, idx] = rng.standard_normal(k).astype(np.float32)
    return Xt, k


def run(csv: CSV):
    js = BenchJSON("BENCH_kernels.json")
    interp = pallas_interpret()
    mode = "interpret" if interp else "native"
    csv.emit("kernel/device", 0.0, f"device={device_label()};mode={mode}")
    rng = np.random.default_rng(0)
    p, m, bs = 4096, 512, 256
    Xt = jnp.asarray(rng.standard_normal((p, m)).astype(np.float32))
    r = jnp.asarray(rng.standard_normal(m).astype(np.float32))
    blk = jnp.asarray([0, 5, 9, 2], jnp.int32)

    t_ref = _time(lambda: sampled_scores_ref(Xt, r, blk, bs)[0])
    t_int = _time(
        lambda: sampled_scores(Xt, r, blk, block_size=bs, m_tile=256, interpret=interp)
    )
    vmem_kb = (bs * 256 * 4 + 256 * 4 + bs * 4) / 1024  # per grid step
    csv.emit(
        "kernel/fw_grad", t_int * 1e6,
        f"ref_us={t_ref*1e6:.0f};kernel_us={t_int*1e6:.0f};"
        f"vmem_per_step_kb={vmem_kb:.0f};grid=(nb,m/mt)",
    )
    js.add("kernel/fw_grad", p=p, m=m, block_size=bs,
           ref_us=t_ref * 1e6, kernel_us=t_int * 1e6, vmem_per_step_kb=vmem_kb)

    y = jnp.asarray(rng.standard_normal(m).astype(np.float32))
    t_ref2 = _time(lambda: (Xt @ y, jnp.sum(Xt * Xt, axis=1)))
    t_int2 = _time(lambda: colstats(Xt, y, p_tile=256, m_tile=256, interpret=interp))
    csv.emit(
        "kernel/colstats", t_int2 * 1e6,
        f"ref_us={t_ref2*1e6:.0f};one_pass_fused=zty+znorm2",
    )
    js.add("kernel/colstats", p=p, m=m, ref_us=t_ref2 * 1e6, kernel_us=t_int2 * 1e6)

    z = jnp.asarray(rng.standard_normal(m).astype(np.float32))
    t_int3 = _time(
        lambda: residual_update(r, y, z, jnp.asarray(0.3), jnp.asarray(1.0), interpret=interp)
    )
    csv.emit("kernel/residual_update", t_int3 * 1e6, "fused_3read_1write")
    js.add("kernel/residual_update", m=m, kernel_us=t_int3 * 1e6)

    # padded-tail geometry (p % block_size != 0 — DESIGN.md §Padding);
    # the sampled blocks must include the partially-zero tail brick
    Xt_pad = jnp.asarray(rng.standard_normal((p + 100, m)).astype(np.float32))
    tail = -(-(p + 100) // bs) - 1
    blk_pad = jnp.asarray([0, 5, 9, tail], jnp.int32)
    t_pad = _time(
        lambda: sampled_scores(Xt_pad, r, blk_pad, block_size=bs, m_tile=256, interpret=interp)
    )
    csv.emit(
        "kernel/fw_grad_padded", t_pad * 1e6,
        f"p={p+100};pad_to={-(-(p+100)//bs)*bs};kernel_us={t_pad*1e6:.0f}",
    )
    js.add("kernel/fw_grad_padded", p=p + 100, m=m, block_size=bs,
           kernel_us=t_pad * 1e6)

    # -- sparse sampled-gradient vs dense XLA gather (ISSUE 2 acceptance) --
    # The dense gather reads nb*bs full length-m rows; the block-ELL op
    # reads nb*bs*nnz_max slots. At the paper's text densities the sparse
    # op must win on the same sampled blocks.
    ps, ms, bss = 4096, 2048, 256
    rng_s = np.random.default_rng(7)
    rs = jnp.asarray(rng_s.standard_normal(ms).astype(np.float32))
    blk_s = jnp.asarray([0, 3, 7, 11, 2, 9, 14, 5], jnp.int32)
    dense_gather = jax.jit(lambda X, r, b: sampled_scores_ref(X, r, b, bss)[0])
    sparse_ref = jax.jit(sparse_sampled_scores_ref)
    for density in (0.01, 0.002):
        Xts, k = _sparse_rows(ps, ms, density, rng_s)
        mat = SparseBlockMatrix.from_dense(Xts, block_size=bss)
        Xts_j = jnp.asarray(Xts)
        t_dense = _time(dense_gather, Xts_j, rs, blk_s, n=20)
        t_sparse = _time(sparse_ref, mat.values, mat.rows, rs, blk_s, n=20)
        t_kernel = _time(
            lambda: sparse_sampled_scores(mat.values, mat.rows, rs, blk_s, interpret=interp)
        )
        # correctness cross-check on the same draw
        np.testing.assert_allclose(
            np.asarray(sparse_ref(mat.values, mat.rows, rs, blk_s)),
            np.asarray(dense_gather(Xts_j, rs, blk_s)),
            rtol=2e-5, atol=2e-4,
        )
        tag = f"kernel/sparse_grad_density{density:g}"
        csv.emit(
            tag, t_sparse * 1e6,
            f"p={ps};m={ms};nnz_max={mat.nnz_max};dense_gather_us={t_dense*1e6:.0f};"
            f"sparse_xla_us={t_sparse*1e6:.0f};sparse_kernel_us={t_kernel*1e6:.0f};"
            f"speedup_vs_dense={t_dense/t_sparse:.1f}x",
        )
        js.add(tag, p=ps, m=ms, block_size=bss, col_density=density,
               nnz_max=mat.nnz_max, dense_gather_us=t_dense * 1e6,
               sparse_xla_us=t_sparse * 1e6, sparse_kernel_us=t_kernel * 1e6,
               speedup_vs_dense=t_dense / t_sparse)

    # end-to-end solver step: all three backends on the SAME fixed-iteration
    # run (sparse solves the block-ELL conversion of the same dense problem)
    from repro.core import FWConfig, fw_solve

    rng2 = np.random.default_rng(1)
    p2, m2 = 2048, 256
    Xt2_np = rng2.standard_normal((p2, m2)).astype(np.float32)
    Xt2_np[rng2.random((p2, m2)) > 0.01] = 0.0  # text-like density for sparse
    Xt2 = jnp.asarray(Xt2_np)
    mat2 = SparseBlockMatrix.from_dense(Xt2_np, block_size=128)
    y2 = jnp.asarray(rng2.standard_normal(m2).astype(np.float32))
    key = jax.random.PRNGKey(0)
    times = {}
    for backend in ("xla", "pallas", "sparse"):
        cfg = FWConfig(
            delta=25.0, sampling="block", kappa=256, block_size=128,
            max_iters=200, tol=0.0, patience=10**9, backend=backend,
        )
        A = mat2 if backend == "sparse" else Xt2
        times[backend] = _time(lambda cfg=cfg, A=A: fw_solve(A, y2, cfg, key).alpha, n=3)
        bmode = mode if backend == "pallas" else "native"
        csv.emit(
            f"solver/fw_solve_{backend}", times[backend] * 1e6 / 200,
            f"m={m2};p={p2};kappa=256;iters=200;mode={bmode}",
        )
        js.add(f"solver/fw_solve_{backend}", m=m2, p=p2, kappa=256, iters=200,
               backend=backend, us_per_iter=times[backend] * 1e6 / 200, mode=bmode)
    csv.emit(
        "solver/backend_ratio", times["pallas"] / times["xla"] * 100,
        f"pallas_over_xla_pct ({mode} kernels on {device_label()})",
    )
    csv.emit(
        "solver/sparse_vs_xla_ratio", times["sparse"] / times["xla"] * 100,
        "sparse_over_xla_pct (same block-sampled problem at density 0.01)",
    )
    js.add("solver/backend_ratios",
           pallas_over_xla=times["pallas"] / times["xla"],
           sparse_over_xla=times["sparse"] / times["xla"])

    # -- fused multi-step hot loop (ISSUE 5): iterations/sec at K=1/8/32 --
    # One fixed-iteration uniform-lasso run per (backend, fuse_steps):
    # K=1 is the per-dispatch baseline (dense-xla-ref / sparse-xla-ref),
    # K>1 the chunked driver (fori-of-step on these CPU executors; the
    # megakernel itself is TPU-targeted and timed by its parity tests in
    # interpret mode). Records land in BENCH_kernels.json as
    # hotloop/fused_k{K}_{backend} so the perf trajectory is diffable.
    pf, mf, kf, iters_f = 2048, 256, 128, 192
    rng_f = np.random.default_rng(3)
    Xf_np = rng_f.standard_normal((pf, mf)).astype(np.float32)
    Xf_sp = Xf_np.copy()
    Xf_sp[rng_f.random((pf, mf)) > 0.01] = 0.0
    arms_f = {
        "xla": jnp.asarray(Xf_np),
        "sparse": SparseBlockMatrix.from_dense(Xf_sp, block_size=128),
    }
    yf = jnp.asarray(rng_f.standard_normal(mf).astype(np.float32))
    keyf = jax.random.PRNGKey(2)
    base_f = {}
    for backend, A in arms_f.items():
        for K in (1, 8, 32):
            cfg = FWConfig(
                delta=25.0, sampling="uniform", kappa=kf, max_iters=iters_f,
                tol=0.0, patience=10**9, backend=backend, fuse_steps=K,
            )
            t = _time(lambda cfg=cfg, A=A: fw_solve(A, yf, cfg, keyf).alpha, n=3)
            ips = iters_f / t
            if K == 1:
                base_f[backend] = t
            tag = f"hotloop/fused_k{K}_{backend}"
            csv.emit(
                tag, t * 1e6 / iters_f,
                f"p={pf};m={mf};kappa={kf};iters={iters_f};"
                f"iters_per_sec={ips:.0f};speedup_vs_k1={base_f[backend]/t:.2f}x",
            )
            js.add(tag, p=pf, m=mf, kappa=kf, iters=iters_f, backend=backend,
                   fuse_steps=K, seconds=t, us_per_iter=t * 1e6 / iters_f,
                   iters_per_sec=ips, speedup_vs_k1=base_f[backend] / t)
    js.write()


if __name__ == "__main__":
    from repro.devices import enable_compile_cache

    enable_compile_cache()
    run(CSV())
