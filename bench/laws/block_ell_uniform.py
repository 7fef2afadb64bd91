"""Text-like sparse design, generated on the device from the seed.

The law of ``repro.data.proxies.make_sparse_coo``, drawn per column so
that the block-ELL layout comes out directly:

* each column j < p holds c_j ~ Poisson(col_density * m) nonzeros, capped
  at ``nnz_max`` (the cap keeps every seed on one shape; at e2006-log1p's
  sizes a column passes it with probability ~1e-7);
* its rows are c_j distinct sample indices, drawn as sorted uniform
  draws from [0, m - c_j] shifted by their rank (a uniform multiset
  mapped one to one onto distinct rows);
* its values are Exp(1), scaled to unit l2 norm;
* ``n_relevant`` distinct columns, drawn the same way, carry
  coefficients of size coef_scale * |N(0, 1)| (the law's quantiles, the
  same sizes for every seed, in an order and with signs drawn from it),
  and y = X coef + noise * N(0, 1), centred.

Padded slots hold value 0 at row 0 and padded tail features are empty,
the layout ``repro.sparse.matrix.SparseBlockMatrix`` stores.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.draws import distinct_uniform, half_normal_scores

KIND = "block_ell"
# the sizes a CPU test run holds: the same kinds of width, far fewer rows
TINY = dict(m=300, p=6000, col_density=0.02, nnz_max=24, n_relevant=12)


@functools.partial(
    jax.jit,
    static_argnames=("m", "p", "block_size", "nnz_max", "col_density",
                     "n_relevant", "coef_scale", "noise"),
)
def _generate(key, *, m, p, block_size, nnz_max, col_density, n_relevant,
              coef_scale, noise):
    nblocks = -(-p // block_size)
    pp = nblocks * block_size
    k_cnt, k_rows, k_vals, k_supp, k_coef, k_noise = jax.random.split(key, 6)
    feat = jnp.arange(pp)
    counts = jax.random.poisson(k_cnt, col_density * m, (pp,))
    counts = jnp.where(feat < p, jnp.minimum(counts, nnz_max), 0).astype(jnp.int32)
    slot = jnp.arange(nnz_max, dtype=jnp.int32)[None, :]
    live = slot < counts[:, None]
    draws = jax.random.randint(
        k_rows, (pp, nnz_max), 0, (m - counts + 1)[:, None], dtype=jnp.int32
    )
    draws = jnp.sort(jnp.where(live, draws, m), axis=1)
    rows = jnp.where(live, draws + slot, 0).astype(jnp.int32)
    vals = jnp.where(live, jax.random.exponential(k_vals, (pp, nnz_max)), 0.0)
    norm = jnp.sqrt(jnp.sum(vals * vals, axis=1, keepdims=True))
    vals = (vals / jnp.where(norm > 0, norm, 1.0)).astype(jnp.float32)

    support = distinct_uniform(k_supp, p, n_relevant)
    coef_vals = coef_scale * half_normal_scores(k_coef, n_relevant)
    coef = jnp.zeros((p,), jnp.float32).at[support].set(coef_vals)
    contrib = vals[support] * coef_vals[:, None]
    y = jnp.zeros((m,), jnp.float32).at[rows[support]].add(contrib)
    y = y + noise * jax.random.normal(k_noise, (m,))
    y = y - jnp.mean(y)
    shape = (nblocks, block_size, nnz_max)
    return vals.reshape(shape), rows.reshape(shape), y, coef


def generate(spec: dict, key: jax.Array) -> dict:
    """The design and targets of ``spec`` (a configuration file's dict)
    on the default device, made from ``key``."""
    values, rows, y, coef = _generate(
        key,
        m=int(spec["m"]),
        p=int(spec["p"]),
        block_size=int(spec["block_size"]),
        nnz_max=int(spec["nnz_max"]),
        col_density=float(spec["col_density"]),
        n_relevant=int(spec["n_relevant"]),
        coef_scale=float(spec["coef_scale"]),
        noise=float(spec["noise"]),
    )
    return {"kind": KIND, "values": values, "rows": rows, "y": y, "coef": coef,
            "m": int(spec["m"]), "p": int(spec["p"])}
