"""Random draws the data laws share."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def distinct_uniform(key, n: int, k: int) -> jax.Array:
    """k distinct integers of [0, n): sorted uniform draws from
    [0, n - k] shifted by their rank (a uniform multiset mapped one to
    one onto distinct integers)."""
    draws = jnp.sort(jax.random.randint(key, (k,), 0, n - k + 1))
    return draws + jnp.arange(k, dtype=draws.dtype)


def half_normal_scores(key, k: int) -> jax.Array:
    """k coefficients of one fixed set of sizes, the quantiles
    (i + 1/2) / k of |N(0, 1)|, in an order and with signs drawn from
    ``key``: every key gives the same sizes, so the same difficulty."""
    k_perm, k_sign = jax.random.split(key)
    sizes = jax.scipy.special.ndtri(0.5 + 0.5 * (jnp.arange(k) + 0.5) / k)
    signs = jnp.where(jax.random.bernoulli(k_sign, 0.5, (k,)), 1.0, -1.0)
    return jax.random.permutation(k_perm, sizes) * signs
