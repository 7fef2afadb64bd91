"""Offline proxies for the paper's real benchmark datasets (Table 1).

The container has no network access, so Pyrim / Triazines / E2006-tfidf /
E2006-log1p cannot be downloaded. We generate synthetic proxies that match
the published (m, p) and qualitative structure (sparse columns for the
text datasets, dense polynomial-feature-like correlated columns for the
QSAR ones) at a scale factor chosen for single-core CPU runtime. The scale
factor and true sizes are recorded in every benchmark output and in
EXPERIMENTS.md.

Two builders:

* ``make_proxy`` — dense (m, p) Dataset. Guarded by a memory budget:
  building E2006-log1p at scale 1.0 would allocate ~270 GB, so any build
  whose dense bytes exceed the budget raises with the estimate instead of
  silently densifying (or OOM-killing the host).
* ``make_sparse_proxy`` — sparse-native builder for the text datasets:
  generates COO triplets directly and assembles a feature-major
  SparseBlockMatrix (DESIGN.md §Sparse) without EVER materializing the
  dense matrix, so the published sizes fit in memory.
"""
from __future__ import annotations

import os
from typing import Dict, NamedTuple, Optional

import numpy as np

from repro.data.synthetic import Dataset, standardize
from repro.sparse.matrix import SparseBlockMatrix

# Default dense-build budget (bytes); override per call or via env.
DENSE_BUDGET_ENV = "REPRO_DENSE_BUDGET_BYTES"
DEFAULT_DENSE_BUDGET = 2 << 30  # 2 GiB
# host bytes of one band of the dense builder's mixing matrix
_MIX_BAND_BYTES = 256 << 20


class ProxySpec(NamedTuple):
    m: int
    t: int  # test examples
    p: int
    col_density: float  # fraction of nonzeros per predictor column
    n_relevant: int  # informative features in the generating model


# Published sizes (paper Table 1) with qualitative structure.
PROXY_SPECS: Dict[str, ProxySpec] = {
    "pyrim": ProxySpec(m=74, t=0, p=201_376, col_density=1.0, n_relevant=60),
    "triazines": ProxySpec(m=186, t=0, p=635_376, col_density=1.0, n_relevant=150),
    "e2006-tfidf": ProxySpec(m=16_087, t=3_308, p=150_360, col_density=0.01, n_relevant=150),
    "e2006-log1p": ProxySpec(m=16_087, t=3_308, p=4_272_227, col_density=0.002, n_relevant=300),
}


class SparseDataset(NamedTuple):
    """Sparse-native proxy: feature-major block-ELL matrix + targets.

    Columns are scaled to unit l2 norm (no centering — centering a sparse
    matrix densifies it; the paper's text datasets are used uncentered)
    and y is centered, so the solver sees the same conditioning contract
    as ``standardize`` gives the dense path.
    """

    mat: SparseBlockMatrix
    y: np.ndarray  # (m,) float32, centered
    coef: Optional[np.ndarray]  # generating coefficients (pre-scaling)
    name: str


def dense_proxy_bytes(name: str, scale: float = 1.0, dtype_bytes: int = 4) -> int:
    """Estimated bytes of the dense (m+t, p) build ``make_proxy`` performs."""
    spec = PROXY_SPECS[name]
    m = max(32, int(spec.m * scale))
    t = int(spec.t * scale)
    p = max(256, int(spec.p * scale))
    return (m + t) * p * dtype_bytes


def _dense_budget(max_dense_bytes: Optional[int]) -> int:
    if max_dense_bytes is not None:
        return int(max_dense_bytes)
    return int(os.environ.get(DENSE_BUDGET_ENV, DEFAULT_DENSE_BUDGET))


def make_proxy(
    name: str,
    scale: float = 1.0,
    seed: int = 0,
    max_dense_bytes: Optional[int] = None,
) -> Dataset:
    """Generate a dense proxy dataset. ``scale`` < 1 shrinks m, t and p
    uniformly (CPU-budget control); scale=1.0 reproduces the published
    sizes. Raises MemoryError (with the estimate) when the dense build
    would exceed ``max_dense_bytes`` (default $REPRO_DENSE_BUDGET_BYTES
    or 2 GiB) — route large text datasets through ``make_sparse_proxy``.
    """
    spec = PROXY_SPECS[name]
    budget = _dense_budget(max_dense_bytes)
    est = dense_proxy_bytes(name, scale)
    if est > budget:
        hint = (
            " Use make_sparse_proxy (sparse-native, no densification)."
            if spec.col_density < 1.0
            else " Lower `scale` or raise the budget."
        )
        raise MemoryError(
            f"dense build of {name!r} at scale={scale:g} needs ~{est:,} bytes "
            f"({est / 2**30:.2f} GiB) > budget {budget:,} bytes.{hint}"
        )
    m = max(32, int(spec.m * scale))
    t = int(spec.t * scale)
    p = max(256, int(spec.p * scale))
    n_rel = max(8, int(spec.n_relevant * min(1.0, scale * 2)))

    rng = np.random.default_rng(seed)
    n = m + t
    if spec.col_density >= 1.0:
        # QSAR-like: dense, mildly correlated columns (product features).
        q = max(16, p // 64)
        base = rng.standard_normal((n, q)).astype(np.float32)
        # the (q, p) mixing matrix is drawn a band of rows at a time (the
        # same stream as one draw) so the published sizes need no
        # q * p * 8-byte buffer: ~50 GB at triazines' p
        band = max(1, _MIX_BAND_BYTES // (8 * p))
        X = np.zeros((n, p))
        for r0 in range(0, q, band):
            rows = min(band, q - r0)
            mix = rng.standard_normal((rows, p)).astype(np.float32) / np.sqrt(q)
            X += base[:, r0 : r0 + rows] @ mix
        X = X + 0.5 * rng.standard_normal((n, p)).astype(np.float32)
    else:
        # Text-like: sparse nonnegative counts, heavy-tailed.
        X = np.zeros((n, p), np.float32)
        nnz_per_row = max(4, int(spec.col_density * p))
        for i in range(n):
            idx = rng.choice(p, size=nnz_per_row, replace=False)
            X[i, idx] = rng.exponential(1.0, size=nnz_per_row).astype(np.float32)

    coef = np.zeros(p, np.float32)
    support = rng.choice(p, size=n_rel, replace=False)
    coef[support] = rng.standard_normal(n_rel).astype(np.float32) * 10.0
    y = X @ coef + 0.5 * rng.standard_normal(n).astype(np.float32)

    ds = Dataset(
        X=X[:m],
        y=y[:m].astype(np.float32),
        X_test=X[m:] if t else None,
        y_test=y[m:].astype(np.float32) if t else None,
        coef=coef,
        name=f"{name}-scale{scale:g}",
    )
    return standardize(ds)


def make_sparse_coo(
    m: int,
    p: int,
    col_density: float,
    n_relevant: int,
    seed: int = 0,
):
    """Text-like sparse regression triplets, never densified.

    Per row, ~col_density*p feature slots are drawn with replacement and
    deduplicated (collisions are O(nnz^2/p) — negligible at the densities
    this serves), with heavy-tailed exponential values; the response is
    accumulated by scatter from a sparse generating coefficient vector.
    Returns (rows, cols, vals, y, coef) with UNIT-NORM columns and
    centered y.
    """
    rng = np.random.default_rng(seed)
    nnz_per_row = max(4, int(col_density * p))
    rows_l, cols_l = [], []
    for i in range(m):
        idx = np.unique(rng.integers(0, p, size=nnz_per_row))
        rows_l.append(np.full(idx.size, i, np.int64))
        cols_l.append(idx)
    rows = np.concatenate(rows_l)
    cols = np.concatenate(cols_l)
    vals = rng.exponential(1.0, size=rows.size).astype(np.float32)

    # unit l2 column norms (no centering — keeps the matrix sparse)
    norm2 = np.zeros(p, np.float64)
    np.add.at(norm2, cols, vals.astype(np.float64) ** 2)
    norms = np.sqrt(norm2)
    norms[norms < 1e-12] = 1.0
    vals = (vals / norms[cols]).astype(np.float32)

    coef = np.zeros(p, np.float32)
    support = rng.choice(p, size=min(n_relevant, p), replace=False)
    coef[support] = rng.standard_normal(support.size).astype(np.float32) * 10.0
    y = np.zeros(m, np.float64)
    np.add.at(y, rows, (vals * coef[cols]).astype(np.float64))
    y += 0.05 * rng.standard_normal(m)
    y -= y.mean()
    return rows, cols, vals, y.astype(np.float32), coef


def make_sparse_proxy(
    name: str,
    scale: float = 1.0,
    seed: int = 0,
    block_size: int = 256,
    nnz_max: Optional[int] = None,
) -> SparseDataset:
    """Sparse-native proxy for the text datasets (E2006-*): builds the
    block-ELL matrix straight from generated COO triplets — memory is
    O(nnz), so the published 4.2M-feature size fits where the dense build
    needs ~270 GB."""
    spec = PROXY_SPECS[name]
    if spec.col_density >= 1.0:
        raise ValueError(
            f"{name!r} is a dense (QSAR-like) dataset; use make_proxy"
        )
    m = max(32, int(spec.m * scale))
    p = max(256, int(spec.p * scale))
    n_rel = max(8, int(spec.n_relevant * min(1.0, scale * 2)))
    rows, cols, vals, y, coef = make_sparse_coo(
        m, p, spec.col_density, n_rel, seed=seed
    )
    mat = SparseBlockMatrix.from_coo(
        rows, cols, vals, (m, p), block_size=block_size, nnz_max=nnz_max
    )
    return SparseDataset(mat=mat, y=y, coef=coef, name=f"{name}-sparse-scale{scale:g}")
