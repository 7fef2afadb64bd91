"""``block_ell_uniform``'s law, drawn over a (data, model) mesh so that
each chip draws only its own feature blocks.

The draws are those of ``block_ell_uniform``: per column a count
c_j ~ Poisson(col_density * m) capped at ``nnz_max``, c_j distinct
uniform rows, Exp(1) values scaled to unit l2 norm, and ``n_relevant``
coefficients at the half-normal quantiles. They run under ``jit`` with
the outputs' shardings, and every per-feature array is held to the
"model" axis, so no chip makes the whole design (with
``jax_threefry_partitionable`` on, each chip draws its own features'
bits).

The layout is ``repro.distributed.ShardedOperand``'s: values and rows of
shape (1, n_model * nb_loc, block_size, nnz_max) under
P("data", "model", None, None), nb_loc = ceil(ceil(p / block_size) /
n_model), the features from p on empty (value 0 at row 0); y (m,) under
P("data"); coef (p,) replicated. A data axis above 1 (rows split over
chips, local row indices) is refused.

The targets follow the configuration's ``problem``:

    lasso     y = X coef + noise * N(0, 1), centred, as in
              ``block_ell_uniform``
    logistic  y = sign(X coef + noise * N(0, 1)) in {-1, +1} (+1 at 0:
              a label of 0 marks a padded row)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.draws import distinct_uniform, half_normal_scores

KIND = "block_ell"
PROBLEMS = ("lasso", "logistic")
# the sizes a CPU test run holds: the same kinds of width, far fewer rows
TINY = dict(m=200, p=2000, col_density=0.03, nnz_max=16, n_relevant=10)


def _draw(key, *, mesh, m, p, nb_total, block_size, nnz_max, col_density,
          n_relevant, coef_scale, noise, problem):
    pp = nb_total * block_size
    feat_sh = NamedSharding(mesh, P("model"))
    slot_sh = NamedSharding(mesh, P("model", None))
    k_cnt, k_rows, k_vals, k_supp, k_coef, k_noise = jax.random.split(key, 6)
    feat = jax.lax.with_sharding_constraint(jnp.arange(pp), feat_sh)
    counts = jax.random.poisson(k_cnt, col_density * m, (pp,))
    counts = jnp.where(feat < p, jnp.minimum(counts, nnz_max), 0).astype(jnp.int32)
    counts = jax.lax.with_sharding_constraint(counts, feat_sh)
    slot = jnp.arange(nnz_max, dtype=jnp.int32)[None, :]
    live = slot < counts[:, None]
    draws = jax.random.randint(
        k_rows, (pp, nnz_max), 0, (m - counts + 1)[:, None], dtype=jnp.int32
    )
    draws = jax.lax.with_sharding_constraint(draws, slot_sh)
    draws = jnp.sort(jnp.where(live, draws, m), axis=1)
    rows = jnp.where(live, draws + slot, 0).astype(jnp.int32)
    vals = jnp.where(live, jax.random.exponential(k_vals, (pp, nnz_max)), 0.0)
    vals = jax.lax.with_sharding_constraint(vals, slot_sh)
    norm = jnp.sqrt(jnp.sum(vals * vals, axis=1, keepdims=True))
    vals = (vals / jnp.where(norm > 0, norm, 1.0)).astype(jnp.float32)

    support = distinct_uniform(k_supp, p, n_relevant)
    coef_vals = coef_scale * half_normal_scores(k_coef, n_relevant)
    coef = jnp.zeros((p,), jnp.float32).at[support].set(coef_vals)
    contrib = vals[support] * coef_vals[:, None]
    t = jnp.zeros((m,), jnp.float32).at[rows[support]].add(contrib)
    t = t + noise * jax.random.normal(k_noise, (m,))
    if problem == "logistic":
        y = jnp.where(t >= 0, 1.0, -1.0).astype(jnp.float32)
    else:
        y = t - jnp.mean(t)
    shape = (1, nb_total, block_size, nnz_max)
    return vals.reshape(shape), rows.reshape(shape), y, coef


def program(spec: dict, mesh):
    """The jitted draw of ``spec`` over ``mesh``: a function of the key."""
    n_data, n_model = int(mesh.shape["data"]), int(mesh.shape["model"])
    if n_data != 1:
        raise ValueError(
            f"block_ell_sharded lays features over the model axis only; its "
            f"mesh has a data axis of {n_data}")
    problem = spec.get("problem", "lasso")
    if problem not in PROBLEMS:
        raise ValueError(f"block_ell_sharded makes no targets for {problem!r}")
    p, bs = int(spec["p"]), int(spec["block_size"])
    nb_loc = -(-(-(-p // bs)) // n_model)
    design = NamedSharding(mesh, P("data", "model", None, None))
    fn = functools.partial(
        _draw, mesh=mesh, m=int(spec["m"]), p=p, nb_total=n_model * nb_loc,
        block_size=bs, nnz_max=int(spec["nnz_max"]),
        col_density=float(spec["col_density"]),
        n_relevant=int(spec["n_relevant"]), coef_scale=float(spec["coef_scale"]),
        noise=float(spec["noise"]), problem=problem,
    )
    return jax.jit(fn, out_shardings=(design, design, NamedSharding(mesh, P("data")),
                                      NamedSharding(mesh, P())))


def generate(spec: dict, key: jax.Array, mesh=None) -> dict:
    """The design and targets of ``spec`` laid over ``mesh``, made from
    ``key`` on the mesh's devices."""
    if mesh is None:
        raise ValueError("block_ell_sharded needs the configuration's mesh")
    values, rows, y, coef = program(spec, mesh)(key)
    return {"kind": KIND, "values": values, "rows": rows, "y": y, "coef": coef,
            "m": int(spec["m"]), "p": int(spec["p"])}
