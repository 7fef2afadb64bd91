"""QSAR-like dense design, generated on the device from the seed.

The law of ``repro.data.proxies.make_proxy`` for a dense dataset:
X = B M / sqrt(q) + noise_x * N(0, 1) with B an (m, q) and M a (q, p)
standard normal matrix, q = p // mix_ratio, then
y = X coef + noise * N(0, 1) on ``n_relevant`` coefficients of size
coef_scale * |N(0, 1)| (the law's quantiles, the same sizes for every
seed, in an order and with signs drawn from it; on distinct columns:
sorted uniform draws shifted by their rank), and finally the columns
centred and scaled to unit l2 norm and y centred (``repro.data.synthetic.standardize``; coef is rescaled
with the columns).

Given B, each column of B M / sqrt(q) is N(0, B B^T / q), so it is drawn
here as L z with L the Cholesky factor of B B^T / q and z ~ N(0, I_m):
the same law, at m normal draws per column instead of q.
The design is returned feature-major, (p, m).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.draws import distinct_uniform, half_normal_scores

KIND = "dense"
# the sizes a CPU test run holds: the same kinds of width, far fewer rows
TINY = dict(m=40, p=3000, n_relevant=10)


@functools.partial(
    jax.jit,
    static_argnames=("m", "p", "mix_ratio", "noise_x", "n_relevant",
                     "coef_scale", "noise"),
)
def _generate(key, *, m, p, mix_ratio, noise_x, n_relevant, coef_scale, noise):
    q = max(16, p // mix_ratio)
    k_base, k_mix, k_noise_x, k_supp, k_coef, k_noise = jax.random.split(key, 6)
    hi = jax.lax.Precision.HIGHEST
    base = jax.random.normal(k_base, (m, q))
    chol = jnp.linalg.cholesky(jnp.matmul(base, base.T, precision=hi) / q)
    z = jax.random.normal(k_mix, (p, m))
    xt = jnp.matmul(z, chol.T, precision=hi) + noise_x * jax.random.normal(
        k_noise_x, (p, m)
    )
    support = distinct_uniform(k_supp, p, n_relevant)
    coef_vals = coef_scale * half_normal_scores(k_coef, n_relevant)
    y = jnp.matmul(coef_vals, xt[support], precision=hi)
    y = y + noise * jax.random.normal(k_noise, (m,))
    y = y - jnp.mean(y)
    xt = xt - jnp.mean(xt, axis=1, keepdims=True)
    norms = jnp.sqrt(jnp.sum(xt * xt, axis=1))
    norms = jnp.where(norms < 1e-12, 1.0, norms)
    xt = (xt / norms[:, None]).astype(jnp.float32)
    coef = jnp.zeros((p,), jnp.float32).at[support].set(coef_vals * norms[support])
    return xt, y.astype(jnp.float32), coef


def generate(spec: dict, key: jax.Array) -> dict:
    """The design and targets of ``spec`` (a configuration file's dict)
    on the default device, made from ``key``."""
    xt, y, coef = _generate(
        key,
        m=int(spec["m"]),
        p=int(spec["p"]),
        mix_ratio=int(spec["mix_ratio"]),
        noise_x=float(spec["noise_x"]),
        n_relevant=int(spec["n_relevant"]),
        coef_scale=float(spec["coef_scale"]),
        noise=float(spec["noise"]),
    )
    return {"kind": KIND, "xt": xt, "y": y, "coef": coef,
            "m": int(spec["m"]), "p": int(spec["p"])}
