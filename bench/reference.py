"""Plain lasso reference: objective, feasibility and certified duality gap.

For f(alpha) = 1/2 ||y - X alpha||^2 over ||alpha||_1 <= delta, with
r = y - X alpha and grad = -X^T r, the Frank-Wolfe duality gap

    g(alpha) = alpha^T grad + delta * ||grad||_inf
             = -(y - r)^T r + delta * max_j |z_j^T r|

bounds f(alpha) - min f from above for every feasible alpha. Everything
here is plain ``jax.numpy`` in float32 at HIGHEST matmul precision, on the
benchmark's own data (the dicts the laws in ``bench/laws`` return); it
imports nothing of the program. Points are evaluated in batches of
``BATCH`` coefficient vectors, and the O(nnz) pass over a block-ELL
design runs in chunks of feature blocks, so the reference fits beside
the data on one chip. A block-ELL design laid over a mesh with one
data shard, (1, NB, block_size, nnz_max), is read as (NB, block_size,
nnz_max).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
BATCH = 32
CHUNK_BLOCKS = 64


@jax.jit
def _resid_block_ell(values, rows, y, idx, val):
    """r = y - X alpha for each of B coefficient vectors given by their
    support ``idx`` (B, S) and values ``val`` (B, S) (0 on padding), one
    vector at a time."""
    bs = values.shape[1]

    def one(iv):
        i, v = iv
        cv = values[i // bs, i % bs] * v[:, None]  # (S, nnz)
        cr = rows[i // bs, i % bs]
        return y - jnp.zeros_like(y).at[cr].add(cv)

    return jax.lax.map(one, (idx, val))


@jax.jit
def _grad_inf_block_ell(values, rows, resid):
    """max_j |z_j^T r_b| for each residual r_b (rows of ``resid``)."""
    nblocks = values.shape[0]
    cb = min(CHUNK_BLOCKS, nblocks)
    n_chunks = -(-nblocks // cb)
    rt = resid.T  # (m, B): one gathered row per stored slot

    def chunk(c):
        # the last chunk is clamped back inside the array; re-reading a
        # few blocks leaves a maximum unchanged
        start = jnp.minimum(c * cb, nblocks - cb)
        v = jax.lax.dynamic_slice_in_dim(values, start, cb, axis=0)
        r = jax.lax.dynamic_slice_in_dim(rows, start, cb, axis=0)
        s = jnp.sum(v[..., None] * rt[r], axis=2)  # (cb, bs, B)
        return jnp.max(jnp.abs(s), axis=(0, 1))

    return jnp.max(jax.lax.map(chunk, jnp.arange(n_chunks)), axis=0)


@functools.partial(jax.jit, static_argnames=("p",))
def _resid_dense(xt, y, idx, val, p):
    dense = jnp.zeros((idx.shape[0], p), jnp.float32)
    b = jnp.broadcast_to(jnp.arange(idx.shape[0])[:, None], idx.shape)
    dense = dense.at[b, idx].add(val)
    return y[None, :] - jnp.matmul(dense, xt, precision=HI)


@jax.jit
def _grad_inf_dense(xt, resid):
    return jnp.max(jnp.abs(jnp.matmul(xt, resid.T, precision=HI)), axis=0)


def _pad(supports, width):
    idx = np.zeros((len(supports), width), np.int32)
    val = np.zeros((len(supports), width), np.float32)
    for i, (ix, vx) in enumerate(supports):
        idx[i, : ix.size] = ix
        val[i, : vx.size] = vx
    return idx, val


def _bucket(n: int) -> int:
    """Support widths compile in powers of two (at least 64)."""
    return max(64, 1 << int(np.ceil(np.log2(max(n, 1)))))


def _one_shard(data: dict) -> dict:
    """``data`` with a one-data-shard block-ELL layout seen as one design."""
    if data["kind"] != "block_ell" or data["values"].ndim == 3:
        return data
    if data["values"].shape[0] != 1:
        raise ValueError("the lasso reference reads designs with one data shard")
    return {**data, "values": data["values"][0], "rows": data["rows"][0]}


def f_zero(data: dict) -> float:
    """f(0) = ||y||^2 / 2, in float64."""
    return 0.5 * float(np.sum(np.square(np.asarray(data["y"], np.float64))))


def evaluate(data: dict, supports, deltas):
    """Objective, l1 norm and certified gap of each coefficient vector.

    ``supports`` is a list of (indices, values) host arrays, ``deltas``
    the radius of each. Returns three float64 arrays.
    """
    data = _one_shard(data)
    deltas = np.asarray(deltas, np.float64)
    objective, l1, gap = [], [], []
    for s0 in range(0, len(supports), BATCH):
        part = list(supports[s0 : s0 + BATCH])
        n_real = len(part)
        part += [(np.zeros(0, np.int32), np.zeros(0, np.float32))] * (BATCH - n_real)
        width = _bucket(max(ix.size for ix, _ in part))
        idx, val = _pad(part, width)
        idx, val = jnp.asarray(idx), jnp.asarray(val)
        if data["kind"] == "dense":
            resid = _resid_dense(data["xt"], data["y"], idx, val, data["p"])
            ginf = _grad_inf_dense(data["xt"], resid)
        else:
            resid = _resid_block_ell(data["values"], data["rows"], data["y"], idx, val)
            ginf = _grad_inf_block_ell(data["values"], data["rows"], resid)
        y = data["y"][None, :]
        f = 0.5 * jnp.sum(resid * resid, axis=1)
        a_grad = -jnp.sum((y - resid) * resid, axis=1)
        out = np.asarray(jnp.stack([f, a_grad, ginf]), np.float64)[:, :n_real]
        d = deltas[s0 : s0 + n_real]
        objective.append(out[0])
        gap.append(out[1] + d * out[2])
        l1.append(np.array([np.abs(v).sum(dtype=np.float64) for _, v in part[:n_real]]))
    return np.concatenate(objective), np.concatenate(l1), np.concatenate(gap)


# The reference solver: a working set of WORK_SET columns (the current
# support and the largest |gradient| entries over all p), the lasso on
# those columns solved by accelerated projected gradient, repeated
# OUTER times; then the certified gap over all p.
WORK_SET = 1024
OUTER = 4
INNER = 1500
POWER_ITERS = 40
# bytes of one chunk of gathered rows in the full-gradient pass
CHUNK_BYTES = 1 << 28


@jax.jit
def _xtr_block_ell(values, rows, resid):
    """X^T r_b over every stored feature, for each residual r_b (rows of
    ``resid``, (B, m)): returns (B, nblocks * block_size)."""
    nblocks, bs, nnz = values.shape
    nb = resid.shape[0]
    cb = int(max(1, min(nblocks, CHUNK_BYTES // (bs * nnz * nb * 4))))
    n_chunks = -(-nblocks // cb)
    rt = resid.T

    def chunk(c, out):
        # the last chunk is clamped back inside the array; the blocks it
        # reads twice are written twice with the same values
        start = jnp.minimum(c * cb, nblocks - cb)
        v = jax.lax.dynamic_slice_in_dim(values, start, cb, axis=0)
        r = jax.lax.dynamic_slice_in_dim(rows, start, cb, axis=0)
        s = jnp.sum(v[..., None] * rt[r], axis=2)  # (cb, bs, B)
        return jax.lax.dynamic_update_slice_in_dim(out, s, start, axis=0)

    out = jax.lax.fori_loop(0, n_chunks, chunk,
                            jnp.zeros((nblocks, bs, nb), jnp.float32))
    return out.reshape(nblocks * bs, nb).T


@jax.jit
def _xtr_dense(xt, resid):
    return jnp.matmul(resid, xt.T, precision=HI)


def _xtr(data, resid):
    """X^T r for each row of ``resid``: (B, p)."""
    if data["kind"] == "dense":
        return _xtr_dense(data["xt"], resid)
    return _xtr_block_ell(data["values"], data["rows"], resid)[:, : data["p"]]


def _columns(data, idx):
    """Dense columns (B, K, m) of the features ``idx`` (B, K)."""
    if data["kind"] == "dense":
        return data["xt"][idx]
    return _columns_block_ell(data["values"], data["rows"], idx, data["m"])


@functools.partial(jax.jit, static_argnames=("m",))
def _columns_block_ell(values, rows, idx, m):
    bs = values.shape[1]
    v = values[idx // bs, idx % bs]  # (B, K, nnz)
    r = rows[idx // bs, idx % bs]
    b = jnp.arange(idx.shape[0])[:, None, None]
    k = jnp.arange(idx.shape[1])[None, :, None]
    return jnp.zeros(idx.shape + (m,), jnp.float32).at[b, k, r].add(v)


def project_l1(v, radius):
    """Euclidean projection of ``v`` onto the l1 ball of ``radius``
    (sort and threshold)."""
    a = jnp.abs(v)
    u = jnp.sort(a)[::-1]
    css = jnp.cumsum(u)
    k = jnp.arange(1, v.shape[0] + 1, dtype=v.dtype)
    rho = jnp.max(jnp.where(u * k > css - radius, k, 1.0))
    theta = jnp.maximum((css[rho.astype(jnp.int32) - 1] - radius) / rho, 0.0)
    inside = jnp.sum(a) <= radius
    return jnp.where(inside, v, jnp.sign(v) * jnp.maximum(a - theta, 0.0))


def _apg(gram, c, a0, radius):
    """min 1/2 a^T G a - c^T a over ||a||_1 <= radius: projected gradient
    with Nesterov momentum and the gradient restart, from ``a0``."""
    def power(_, v):
        w = jnp.matmul(gram, v, precision=HI)
        return w / jnp.maximum(jnp.linalg.norm(w), 1e-30)

    v = jax.lax.fori_loop(0, POWER_ITERS, power, jnp.ones_like(c) / np.sqrt(c.size))
    lip = 1.05 * jnp.dot(v, jnp.matmul(gram, v, precision=HI)) + 1e-12

    def step(_, s):
        x, yv, t = s
        grad = jnp.matmul(gram, yv, precision=HI) - c
        x_new = project_l1(yv - grad / lip, radius)
        restart = jnp.dot(yv - x_new, x_new - x) > 0
        t_new = jnp.where(restart, 1.0, 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t)))
        beta = jnp.where(restart, 0.0, (t - 1.0) / t_new)
        return x_new, x_new + beta * (x_new - x), t_new

    x0 = project_l1(a0, radius)
    x, _, _ = jax.lax.fori_loop(0, INNER, step, (x0, x0, jnp.float32(1.0)))
    return x


@jax.jit
def _restricted(cols, y, a0, radius):
    """The lasso on each point's working-set columns: (B, K) coefficients
    and the (B, m) residuals they leave."""
    gram = jnp.einsum("bkm,bjm->bkj", cols, cols, precision=HI)
    c = jnp.einsum("bkm,m->bk", cols, y, precision=HI)
    a = jax.vmap(_apg)(gram, c, a0, radius)
    return a, y[None, :] - jnp.einsum("bkm,bk->bm", cols, a, precision=HI)


@functools.partial(jax.jit, static_argnames=("k",))
def _working_set(xtr, alpha, k):
    score = jnp.where(alpha != 0, jnp.inf, jnp.abs(xtr))
    _, idx = jax.lax.top_k(score, k)
    return idx, jnp.take_along_axis(alpha, idx, axis=1)


def optimum(data: dict, deltas) -> tuple:
    """The reference's objective and certified gap at each radius.

    Solves min 1/2 ||y - X alpha||^2 over ||alpha||_1 <= delta from
    alpha = 0 for each delta (in batches of BATCH // 4), independently of
    the program. f_ref - gap_ref is a lower bound on the optimum that
    holds whatever the solver's accuracy. Returns two float64 arrays.
    """
    data = _one_shard(data)
    deltas = np.asarray(deltas, np.float64)
    nb = max(1, BATCH // 4)
    p, y = data["p"], data["y"]
    k = min(WORK_SET, p)
    f_out, gap_out = [], []
    for s0 in range(0, len(deltas), nb):
        d = deltas[s0 : s0 + nb]
        n_real = len(d)
        d = np.concatenate([d, np.repeat(d[-1:], nb - n_real)])
        radius = jnp.asarray(d, jnp.float32)
        alpha = jnp.zeros((nb, p), jnp.float32)
        resid = jnp.broadcast_to(y, (nb, y.shape[0]))
        for _ in range(OUTER):
            idx, a0 = _working_set(_xtr(data, resid), alpha, k)
            a, resid = _restricted(_columns(data, idx), y, a0, radius)
            alpha = jnp.zeros((nb, p), jnp.float32).at[
                jnp.arange(nb)[:, None], idx].set(a)
        ginf = jnp.max(jnp.abs(_xtr(data, resid)), axis=1)
        f = 0.5 * jnp.sum(resid * resid, axis=1)
        a_grad = -jnp.sum((y[None, :] - resid) * resid, axis=1)
        out = np.asarray(jnp.stack([f, a_grad, ginf]), np.float64)[:, :n_real]
        f_out.append(out[0])
        gap_out.append(out[1] + d[:n_real] * out[2])
    return np.concatenate(f_out), np.concatenate(gap_out)
