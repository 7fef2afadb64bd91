"""Hashable solver configs (static args to jitted solver entry points)."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

# obs.telemetry is import-clean of repro.core, so the spec can live with
# the observability layer while riding inside the static config key here
from repro.obs.telemetry import TelemetrySpec


@dataclass(frozen=True)
class DistSpec:
    """Static sharding vocabulary of the distributed backend (DESIGN.md
    §Distributed). Lives inside FWConfig so the jitted entry points see
    the mesh geometry as part of their static config key; the axis names
    are the shard_map axes the collectives reduce over.

    The convention shared by ``repro.distributed``: the design matrix is
    sharded feature-blocks over ``model_axis`` and samples over
    ``data_axis``; the residual/margin co-state and targets live as
    per-``data``-slice vectors; beta and the column statistics are
    REPLICATED (O(p) per host — ~17 MB at the paper's p = 4.2M, against
    the O(nnz)/O(p*m) matrix that sharding must split).
    """

    n_data: int = 1
    n_model: int = 1
    data_axis: str = "data"
    model_axis: str = "model"


VALID_BACKENDS = ("xla", "pallas", "sparse", "distributed")
VALID_STEP_RULES = ("classic", "away", "pairwise", "partan", "lazy")


@dataclass(frozen=True)
class FWConfig:
    """Configuration of the stochastic Frank-Wolfe Lasso solver.

    Attributes:
      delta: l1-ball radius (constrained formulation, paper eq. 1).
      kappa: sampling-set size |S| (paper §4.5).
      sampling: 'uniform' (paper), 'block' (TPU-native, DESIGN.md §4),
        or 'full' (deterministic FW).
      block_size: aligned block width for 'block' sampling.
      max_iters / tol: the paper's ||alpha^{k+1}-alpha^k||_inf <= eps rule.
      gap_rtol: a step whose sampled duality gap (the line-search numerator,
        DESIGN.md §Stopping) is below gap_rtol * the gap's own fp32 scale is
        counted as a stall — it is indistinguishable from rounding noise.
      backend: 'xla' (plain jnp gathers), 'pallas' (the fused kernels in
        repro.kernels drive the hot loop; interpret mode off-TPU), or
        'sparse' (block-ELL SparseBlockMatrix design matrix — the solver
        expects ``Xt`` to be a repro.sparse.SparseBlockMatrix and the
        three O(kappa*m) primitives drop to O(kappa*nnz_max); block
        geometry comes from the MATRIX, so ``block_size`` is ignored).
        'distributed' is the mesh-sharded variant of both layouts — it
        only runs inside ``repro.distributed.driver``'s shard_map (which
        sets it, together with ``dist``, from the operand's mesh); the
        plain entry points reject it.
      sparse_kernel: 'sparse' backend only — None = auto (Pallas
        kernels/sparse_grad on TPU, pure-XLA gather elsewhere), True/False
        forces the choice (tests force True + interpret).
      fuse_steps: K consecutive FW iterations per dispatch (DESIGN.md
        §Perf). 1 (default) is today's one-launch-per-iteration loop.
        K > 1 switches ``engine.run_loop``/``batched_loop`` to a chunked
        driver: the co-state and scalar recursions stay device-resident
        across K steps (the ``kernels/fused_step`` Pallas megakernel on
        the 'pallas' and kernel-dispatched 'sparse' backends, a fori_loop
        over the engine step elsewhere) and the §Stopping rule is checked
        BETWEEN chunks, so stall/patience stops may overshoot by at most
        K-1 iterations (max_iters is still exact — trailing chunk steps
        are masked). Fusion engages for the closed-form line-search
        oracles (lasso / elastic-net) under 'uniform' sampling, where the
        K x kappa index stream is a pure function of (key, cfg, p) and
        can be pregenerated; the logistic oracle's bisection and the
        other sampling modes fall back to fuse_steps=1 semantics, and the
        distributed driver forces fuse_steps=1 (single-device-only for
        now).
      report_gap: compute the certified FW duality gap
        g(alpha) = alpha^T grad + delta*||grad||_inf (oracle ``gap()``
        gradients) at the END of each solve — one O(nnz)/O(p*m) full
        gradient pass, surfaced as ``SolveResult.gap`` and
        ``PathPoint.gap``. Off by default: certification is not hot-loop
        work.
      m_tile: sample-dimension tile for the Pallas kernels.
      interpret: force Pallas interpret mode; None = auto (interpret
        everywhere except on real TPU devices).
      dist: static mesh vocabulary when ``backend == 'distributed'``
        (set by ``repro.distributed``; plain solves leave it None).
      step_rule: which FW step variant drives each iteration (DESIGN.md
        §StepRule). 'classic' (default) is the paper's Algorithm-2 step,
        bit-identical to the pre-refactor trajectory; 'away' adds
        away-steps over a tracked active set; 'pairwise' moves mass from
        the away atom straight onto the FW atom; 'partan' extrapolates
        each FW step against the previous iterate; 'lazy' re-scores a
        small cache of recent winners before paying a fresh sampled draw.
        All rules run on every backend, including 'distributed'.
      active_set_size: tracked active-set capacity for 'away'/'pairwise'
        (a fixed-size index buffer; weakest-|beta| slot is evicted when
        a new FW atom enters a full buffer).
      lazy_cache: winner-cache capacity for the 'lazy' LMO wrapper.
      telemetry: device-side metric-ring spec (DESIGN.md §Observability;
        ``repro.obs.TelemetrySpec``). None (default) means telemetry is
        OFF and every recording site is absent from the compiled
        program, so default trajectories stay bit-identical to the
        pre-telemetry engine. When set, ``EngineState`` carries a
        per-iteration ring surfaced on ``SolveResult.telemetry``; with
        ``record_objective`` the fused megakernel chunk falls back to
        the bit-identical fori-of-step executor (the kernel has no
        per-step objective output).
    """

    delta: float
    kappa: int = 194  # paper's top-2%/98% confidence default
    sampling: str = "uniform"
    block_size: int = 128
    max_iters: int = 50_000
    tol: float = 1e-3
    patience: int = 20  # consecutive sub-tol steps before stopping (stochastic)
    refresh_every: int = 64  # recompute S/F from residuals (fp32 drift control)
    eps_den: float = 1e-12
    renorm_threshold: float = 1e-6
    gap_rtol: float = 1e-6
    backend: str = "xla"
    fuse_steps: int = 1
    sparse_kernel: Optional[bool] = None
    report_gap: bool = False
    m_tile: int = 512
    interpret: Optional[bool] = None
    dist: Optional[DistSpec] = None
    step_rule: str = "classic"
    active_set_size: int = 32
    lazy_cache: int = 16
    telemetry: Optional[TelemetrySpec] = None

    def __post_init__(self):
        # fail at construction with the valid vocabulary, not deep in
        # backend dispatch with a KeyError-shaped stack
        if self.backend not in VALID_BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; valid choices: "
                f"{', '.join(VALID_BACKENDS)}"
            )
        if self.step_rule not in VALID_STEP_RULES:
            raise ValueError(
                f"unknown step_rule {self.step_rule!r}; valid choices: "
                f"{', '.join(VALID_STEP_RULES)}"
            )


@dataclass(frozen=True)
class CDConfig:
    """Cyclic / stochastic coordinate descent (penalized form, Glmnet-style)."""

    lam: float
    max_sweeps: int = 1000
    tol: float = 1e-3
    stochastic: bool = False


@dataclass(frozen=True)
class FISTAConfig:
    """FISTA on the penalized form; 'constrained' switches to l1-ball projection."""

    lam: float = 0.0
    delta: float = 0.0
    constrained: bool = False
    max_iters: int = 2000
    tol: float = 1e-3
    power_iters: int = 50  # Lipschitz estimation
