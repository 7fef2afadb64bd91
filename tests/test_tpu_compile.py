"""Compile-only checks of the main path for a TPU v5e chip.

Nothing runs: each test lowers a kernel, or one whole ``engine.solve``,
at the widths of the paper's published datasets and compiles it for a
described (not attached) v5e chip, so the chip's compiler refuses here
what interpret mode would accept (block shapes, SMEM and VMEM budgets,
in-kernel gathers). Shapes are passed as ``ShapeDtypeStruct``s; no data
is made.

    triazines     dense Xt 635,376 x 186
    e2006-log1p   block-ELL m = 16,087, p = 4,272,227, nnz_max = 67
                  (what ``make_sparse_proxy`` gives at density 0.002)
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.core.fw_lasso import LASSO
from repro.core.solver_config import FWConfig
from repro.kernels.colstats.colstats import colstats
from repro.kernels.fused_step import fused_step
from repro.kernels.fw_grad.fw_grad import row_scores, sampled_scores
from repro.kernels.residual_update.residual_update import residual_update
from repro.kernels.sparse_colstats.sparse_colstats import (
    sparse_colstats_fused,
    sparse_xtw,
)
from repro.kernels.sparse_grad.sparse_grad import sparse_sampled_scores
from repro.sparse.matrix import SparseBlockMatrix

P_TRI, M_TRI = 635_376, 186
M_E2006, P_E2006, NNZ_E2006, BS = 16_087, 4_272_227, 67, 256
NB_E2006 = -(-P_E2006 // BS)
F32, I32 = jnp.float32, jnp.int32
FUSED_KW = dict(oracle=LASSO, eps_den=1e-12, gap_rtol=1e-6, refresh_every=64,
                max_iters=10**6)


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """Shape builder on device 0 of the described chip, with JAX's
    persistent compile cache off: a compile for a described chip is
    written to the cache but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield lambda shape, dtype=F32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    """Compile for the chip; returns the compiled program's HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _has_kernel(hlo: str) -> bool:
    return "tpu_custom_call" in hlo


def _e2006(chip):
    return (chip((NB_E2006, BS, NNZ_E2006)), chip((NB_E2006, BS, NNZ_E2006), I32))


class TestKernels:
    @pytest.mark.parametrize("kappa", [194, 2048])
    def test_sampled_scores_block(self, chip, kappa):
        nb = max(kappa // 128, 1)
        hlo = _compile(lambda X, r, b: sampled_scores(X, r, b, block_size=128),
                       chip((P_TRI, M_TRI)), chip((M_TRI,)), chip((nb,), I32))
        assert _has_kernel(hlo)

    def test_row_scores_uniform(self, chip):
        hlo = _compile(lambda X, r, i: row_scores(X, r, i),
                       chip((P_TRI, M_TRI)), chip((M_TRI,)), chip((194,), I32))
        assert _has_kernel(hlo)

    def test_sparse_sampled_scores(self, chip):
        vals, rows = _e2006(chip)
        hlo = _compile(lambda v, r, w, b: sparse_sampled_scores(v, r, w, b),
                       vals, rows, chip((M_E2006,)), chip((8,), I32))
        assert _has_kernel(hlo)

    def test_sparse_colstats(self, chip):
        vals, rows = _e2006(chip)
        hlo = _compile(lambda v, r, y: sparse_colstats_fused(v, r, y),
                       vals, rows, chip((M_E2006,)))
        assert _has_kernel(hlo)

    def test_sparse_xtw(self, chip):
        vals, rows = _e2006(chip)
        hlo = _compile(lambda v, r, w: sparse_xtw(v, r, w),
                       vals, rows, chip((M_E2006,)))
        assert _has_kernel(hlo) and "fw_sparse_xtw" in hlo

    def test_colstats(self, chip):
        hlo = _compile(lambda X, y: colstats(X, y),
                       chip((P_TRI, M_TRI)), chip((M_TRI,)))
        assert _has_kernel(hlo)

    @pytest.mark.parametrize("m", [M_TRI, M_E2006])
    def test_residual_update(self, chip, m):
        hlo = _compile(lambda r, y, z, lam, d: residual_update(r, y, z, lam, d),
                       chip((m,)), chip((m,)), chip((m,)), chip(()), chip(()))
        assert _has_kernel(hlo)

    def test_dense_fused_step(self, chip):
        K, kappa = 8, 194

        def fn(X, y, r, i, zty, zn2, k0, d):
            scal = (jnp.float32(1.0), jnp.float32(0.5), jnp.float32(0.0))
            return fused_step.dense_fused_chunk(
                X, y, r, scal, i, zty, zn2, None, k0, d, **FUSED_KW
            )

        hlo = _compile(fn, chip((P_TRI, M_TRI)), chip((M_TRI,)), chip((M_TRI,)),
                       chip((K, kappa), I32), chip((K, kappa)), chip((K, kappa)),
                       chip((), I32), chip(()))
        assert _has_kernel(hlo)

    def test_sparse_fused_step(self, chip):
        K, kappa = 8, 194
        vals, rows = _e2006(chip)

        def fn(v, rw, y, r, i, zty, zn2, k0, d):
            scal = (jnp.float32(1.0), jnp.float32(0.5), jnp.float32(0.0))
            return fused_step.sparse_fused_chunk(
                v, rw, y, r, scal, i, zty, zn2, None, k0, d, **FUSED_KW
            )

        hlo = _compile(fn, vals, rows, chip((M_E2006,)), chip((M_E2006,)),
                       chip((K, kappa), I32), chip((K, kappa)), chip((K, kappa)),
                       chip((), I32), chip(()))
        assert _has_kernel(hlo)

    def test_fused_prefetch_over_budget_is_refused(self, chip):
        """The SMEM budget the engine uses is the chip's: a chunk just
        over it does not compile, which is why the engine takes the
        per-step executor there (``vertex.use_fused_kernel``)."""
        K = 8
        kappa = fused_step.SMEM_PREFETCH_BUDGET // (4 * K * 3) * 2
        assert not fused_step.fits_smem(K, kappa, needs_alpha=False)

        def fn(X, y, r, i, zty, zn2, k0, d):
            scal = (jnp.float32(1.0), jnp.float32(0.5), jnp.float32(0.0))
            return fused_step.dense_fused_chunk(
                X, y, r, scal, i, zty, zn2, None, k0, d, **FUSED_KW
            )

        with pytest.raises(Exception, match="(?i)smem|exceed"):
            _compile(fn, chip((P_TRI, M_TRI)), chip((M_TRI,)), chip((M_TRI,)),
                     chip((K, kappa), I32), chip((K, kappa)), chip((K, kappa)),
                     chip((), I32), chip(()))


class TestWholeSolve:
    """One ``engine.solve`` per kernel backend, as the chip would run it:
    native kernels (``interpret=False``), sparse kernels on."""

    @pytest.mark.parametrize("fuse_steps", [1, 8])
    def test_pallas_triazines(self, chip, fuse_steps):
        cfg = FWConfig(delta=1.0, backend="pallas", fuse_steps=fuse_steps,
                       interpret=False, report_gap=True)
        hlo = _compile(
            lambda X, y, k, d: engine.solve.__wrapped__(LASSO, X, y, cfg, k, None, d),
            chip((P_TRI, M_TRI)), chip((M_TRI,)), chip((2,), jnp.uint32), chip(()),
        )
        assert _has_kernel(hlo)

    @pytest.mark.parametrize("fuse_steps", [1, 8])
    def test_sparse_e2006(self, chip, fuse_steps):
        vals, rows = _e2006(chip)
        mat = SparseBlockMatrix(values=vals, rows=rows, p=P_E2006, m=M_E2006,
                                block_size=BS, nnz_max=NNZ_E2006)
        cfg = FWConfig(delta=1.0, backend="sparse", fuse_steps=fuse_steps,
                       sparse_kernel=True, interpret=False, report_gap=True)
        hlo = _compile(
            lambda X, y, k, d: engine.solve.__wrapped__(LASSO, X, y, cfg, k, None, d),
            mat, chip((M_E2006,)), chip((2,), jnp.uint32), chip(()),
        )
        assert _has_kernel(hlo)

    def test_sparse_batched_lanes_fit(self, chip):
        """A 100-point path's default lane width (13) at e2006-log1p's
        size fits one chip: the O(nnz) warm-start and gap passes run lane
        by lane."""
        vals, rows = _e2006(chip)
        mat = SparseBlockMatrix(values=vals, rows=rows, p=P_E2006, m=M_E2006,
                                block_size=BS, nnz_max=NNZ_E2006)
        cfg = FWConfig(delta=1.0, backend="sparse", sparse_kernel=True,
                       interpret=False, report_gap=True)
        L = 13
        fn = lambda X, y, k, a, d: engine.solve_batched.__wrapped__(
            LASSO, X, y, cfg, k, a, d
        )
        compiled = jax.jit(fn).lower(
            mat, chip((M_E2006,)), chip((L, 2), jnp.uint32), chip((L, P_E2006)),
            chip((L,)),
        ).compile()
        assert _has_kernel(compiled.as_text())
        mem = compiled.memory_analysis()
        used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        assert used < 16 * 2**30
