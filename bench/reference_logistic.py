"""Plain l1-logistic reference: objective, feasibility and certified duality gap.

For f(a) = sum_{i: y_i != 0} log(1 + exp(-y_i x_i^T a)) over ||a||_1 <= delta
(labels +-1; a label of 0 marks a padded row, which adds nothing), with
the margins u = X a and w = -y sigmoid(-y u) the gradient in the margins,
grad f = X^T w and the Frank-Wolfe duality gap

    g(a) = a^T grad + delta ||grad||_inf = u^T w + delta max_j |z_j^T w|

bounds f(a) - min f from above for every feasible a; f(0) is the number
of labels != 0 times log 2.

Everything here is plain ``jax.numpy`` in float32 under HIGHEST matmul
precision, on the benchmark's own block-ELL data: values and rows of
shape (NB, block_size, nnz_max), or (1, NB, block_size, nnz_max) laid
over a (data, model) mesh with one data shard. It imports nothing of the
program. It is written over global arrays: each O(nnz) pass views the
block axis as (shards, blocks a shard), the shards read from the
array's own sharding, and walks the blocks a shard in chunks, so that
under ``jit`` GSPMD runs every chunk on the chip that holds it and
nothing of the design's size is made.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import _bucket, _pad, project_l1

BATCH = 32
# bytes of one chip's chunk of gathered margins in an O(nnz) pass
CHUNK_BYTES = 1 << 28


def _design(data: dict):
    """Values, rows and the number of shards along the block axis."""
    if data["kind"] != "block_ell":
        raise ValueError(f"the logistic reference reads block-ELL designs, not {data['kind']!r}")
    values = data["values"]
    if values.ndim == 4 and values.shape[0] != 1:
        raise ValueError("the logistic reference reads designs with one data shard")
    per_shard = values.sharding.shard_shape(values.shape)[-3]
    return values, data["rows"], values.shape[-3] // per_shard


def _flat(a):
    """(..., NB, bs, nnz) -> (NB, bs, nnz)."""
    return a.reshape((-1,) + a.shape[-2:])


def _cograd(y, u):
    """Gradient in the margins, -y sigmoid(-y u): 0 on padded rows."""
    return -y * jax.nn.sigmoid(-y * u)


def _loss(y, u):
    return jnp.sum(jnp.where(y != 0, jnp.logaddexp(0.0, -y * u), 0.0), axis=-1)


@functools.partial(jax.jit, static_argnames=("m",))
def _margins(values, rows, idx, val, m):
    """X a_b (B, m) for B coefficient vectors given by their support
    ``idx`` (B, S) and values ``val`` (B, S) (0 on padding)."""
    v, r = _flat(values), _flat(rows)
    bs = v.shape[1]
    cv = v[idx // bs, idx % bs] * val[..., None]  # (B, S, nnz)
    cr = r[idx // bs, idx % bs]
    b = jnp.arange(idx.shape[0])[:, None, None]
    return jnp.zeros((idx.shape[0], m), jnp.float32).at[b, cr].add(cv)


@functools.partial(jax.jit, static_argnames=("shards", "full"))
def _xtw(values, rows, w, shards, full):
    """X^T w_b for each row w_b of ``w`` (B, m): the whole (B, NB * bs)
    where ``full``, else max_j |z_j^T w_b| (B,)."""
    v = values.reshape((shards, -1) + values.shape[-2:])
    r = rows.reshape((shards, -1) + rows.shape[-2:])
    _, n, bs, nnz = v.shape
    nb = w.shape[0]
    cb = int(max(1, min(n, CHUNK_BYTES // (bs * nnz * nb * 4))))
    wt = w.T

    def part(c):
        # the last chunk is clamped back inside the array: the blocks it
        # reads twice are written twice, or maximised twice, alike
        start = jnp.minimum(c * cb, n - cb)
        vc = jax.lax.dynamic_slice_in_dim(v, start, cb, axis=1)
        rc = jax.lax.dynamic_slice_in_dim(r, start, cb, axis=1)
        return start, jnp.sum(vc[..., None] * wt[rc], axis=3)  # (shards, cb, bs, B)

    n_chunks = -(-n // cb)
    if full:
        def put(c, out):
            start, s = part(c)
            return jax.lax.dynamic_update_slice_in_dim(out, s, start, axis=1)

        out = jax.lax.fori_loop(0, n_chunks, put, jnp.zeros((shards, n, bs, nb), jnp.float32))
        return out.reshape(shards * n * bs, nb).T

    def top(c, best):
        return jnp.maximum(best, jnp.max(jnp.abs(part(c)[1]), axis=(0, 1, 2)))

    return jax.lax.fori_loop(0, n_chunks, top, jnp.zeros((nb,), jnp.float32))


@jax.jit
def _readings(y, u):
    """f(a) and a^T grad = u^T w for each row of the margins ``u``."""
    return _loss(y, u), jnp.sum(u * _cograd(y, u), axis=1)


def f_zero(data: dict) -> float:
    """f(0): log 2 for each real (nonzero) label."""
    return float(np.count_nonzero(np.asarray(data["y"])) * math.log(2.0))


def evaluate(data: dict, supports, deltas):
    """Objective, l1 norm and certified gap of each coefficient vector.

    ``supports`` is a list of (indices, values) host arrays, ``deltas``
    the radius of each. Returns three float64 arrays.
    """
    values, rows, shards = _design(data)
    y, m = data["y"], data["m"]
    deltas = np.asarray(deltas, np.float64)
    objective, l1, gap = [], [], []
    with jax.default_matmul_precision("highest"):
        for s0 in range(0, len(supports), BATCH):
            part = list(supports[s0 : s0 + BATCH])
            n_real = len(part)
            part += [(np.zeros(0, np.int32), np.zeros(0, np.float32))] * (BATCH - n_real)
            idx, val = _pad(part, _bucket(max(ix.size for ix, _ in part)))
            u = _margins(values, rows, jnp.asarray(idx), jnp.asarray(val), m)
            f, a_grad = _readings(y, u)
            ginf = _xtw(values, rows, _cograd(y, u), shards, False)
            out = np.asarray(jnp.stack([f, a_grad, ginf]), np.float64)[:, :n_real]
            objective.append(out[0])
            gap.append(out[1] + deltas[s0 : s0 + n_real] * out[2])
            l1.append(np.array([np.abs(v).sum(dtype=np.float64) for _, v in part[:n_real]]))
    return np.concatenate(objective), np.concatenate(l1), np.concatenate(gap)


# The reference solver, in rounds: the full gradient's certified gap over
# all p; then, on a working set of columns (the current support and the
# largest |gradient| entries, WORK_SET to start), accelerated projected
# gradient onto the l1 ball until the set's own Frank-Wolfe gap is under
# a quarter of the target, checked every CHECK steps, or MAX_STEPS have
# run. The next set holds the support and the columns whose |gradient|
# exceeds the largest on the set (each one breaks the l1 ball's
# optimality conditions), all of them where a power of two up to GROW
# times the last set's size holds them, so that each size compiles once.
# Rounds repeat until every radius certifies a gap under
# GAP_TARGET of f(0), or ROUNDS have run.
WORK_SET = 256
GROW = 8
CHECK = 50
MAX_STEPS = 3000
ROUNDS = 12
GAP_TARGET = 2e-7
POWER_ITERS = 40


def _apg(cv, cr, y, a0, radius, tol):
    """min f(C a) over ||a||_1 <= radius, C the working-set columns given
    by values ``cv`` and rows ``cr`` (K, nnz): projected gradient with
    Nesterov momentum and the gradient restart, from ``a0``, with the
    step 1 / L, L = ||C||_2^2 / 4 >= the logistic curvature; it stops
    once the set's Frank-Wolfe gap is under ``tol``. Returns the
    coefficients and the steps taken."""
    def matvec(a):
        return jnp.zeros(y.shape, jnp.float32).at[cr].add(cv * a[:, None])

    def rmatvec(w):
        return jnp.sum(cv * w[cr], axis=1)

    def power(_, x):
        z = rmatvec(matvec(x))
        return z / jnp.maximum(jnp.linalg.norm(z), 1e-30)

    x = jax.lax.fori_loop(0, POWER_ITERS, power, jnp.ones_like(a0) / np.sqrt(a0.size))
    lip = 0.25 * 1.05 * jnp.dot(x, rmatvec(matvec(x))) + 1e-12

    def gap(x):
        grad = rmatvec(_cograd(y, matvec(x)))
        return jnp.dot(x, grad) + radius * jnp.max(jnp.abs(grad))

    def step(_, s):
        x, v, t = s
        grad = rmatvec(_cograd(y, matvec(v)))
        x_new = project_l1(v - grad / lip, radius)
        restart = jnp.dot(v - x_new, x_new - x) > 0
        t_new = jnp.where(restart, 1.0, 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t)))
        beta = jnp.where(restart, 0.0, (t - 1.0) / t_new)
        return x_new, x_new + beta * (x_new - x), t_new

    def steps(s):
        x, v, t, _, n = s
        x, v, t = jax.lax.fori_loop(0, CHECK, step, (x, v, t))
        return x, v, t, gap(x), n + CHECK

    def going(s):
        return (s[3] > tol) & (s[4] < MAX_STEPS)

    x0 = project_l1(a0, radius)
    x, _, _, _, n = jax.lax.while_loop(
        going, steps, (x0, x0, jnp.float32(1.0), gap(x0), jnp.int32(0)))
    return x, n


@jax.jit
def _restricted(values, rows, y, idx, a0, radius, tol):
    """Each lane's working-set solve: coefficients (B, K) on ``idx``, and
    the APG steps each took."""
    v, r = _flat(values), _flat(rows)
    bs = v.shape[1]
    cv, cr = v[idx // bs, idx % bs], r[idx // bs, idx % bs]  # (B, K, nnz)
    return jax.vmap(_apg, in_axes=(0, 0, None, 0, 0, None))(cv, cr, y, a0, radius, tol)


@jax.jit
def _violations(grad, idx, alpha):
    """Per lane: the support's size plus the number of columns whose
    |gradient| exceeds the largest on the working set ``idx``."""
    g = jnp.abs(grad)
    top = jnp.max(jnp.take_along_axis(g, idx, axis=1), axis=1)
    return jnp.sum(alpha != 0, axis=1) + jnp.sum(g > top[:, None], axis=1)


@functools.partial(jax.jit, static_argnames=("k",))
def _working_set(grad, alpha, k):
    score = jnp.where(alpha != 0, jnp.inf, jnp.abs(grad))
    _, idx = jax.lax.top_k(score, k)
    return idx, jnp.take_along_axis(alpha, idx, axis=1)


def optimum(data: dict, deltas) -> tuple:
    """The reference's objective and certified gap at each radius.

    Solves min f(alpha) over ||alpha||_1 <= delta from alpha = 0 for
    each delta (in batches of BATCH // 4), independently of the program.
    f_ref - gap_ref is a lower bound on the optimum that holds whatever
    the solver's accuracy. Returns two float64 arrays.
    """
    values, rows, shards = _design(data)
    y, m, p = data["y"], data["m"], data["p"]
    deltas = np.asarray(deltas, np.float64)
    # 8 lanes: a row of 8 is gathered as fast as a row of 4, and at 4 the
    # TPU compiler spends over a minute on the gradient's pass
    nb = max(1, BATCH // 4)
    target = GAP_TARGET * f_zero(data)
    f_out, gap_out = [], []
    with jax.default_matmul_precision("highest"):
        for s0 in range(0, len(deltas), nb):
            d = deltas[s0 : s0 + nb]
            n_real = len(d)
            d = np.concatenate([d, np.repeat(d[-1:], nb - n_real)])
            radius = jnp.asarray(d, jnp.float32)
            lanes = jnp.arange(nb)[:, None]
            alpha = jnp.zeros((nb, p), jnp.float32)
            u = jnp.zeros((nb, m), jnp.float32)
            k, idx = min(WORK_SET, p), None
            for rnd in range(ROUNDS + 1):
                grad = _xtw(values, rows, _cograd(y, u), shards, True)[:, :p]
                f, a_grad = _readings(y, u)
                out = np.asarray(jnp.stack([f, a_grad, jnp.max(jnp.abs(grad), axis=1)]),
                                 np.float64)
                gap = out[1] + d * out[2]
                if gap.max() <= target or rnd == ROUNDS:
                    break
                if idx is not None:
                    need = int(jnp.max(_violations(grad, idx, alpha)))
                    k = min(max(k, _bucket(need)), GROW * k, p)
                idx, a0 = _working_set(grad, alpha, k)
                a, _ = _restricted(values, rows, y, idx, a0, radius, jnp.float32(target / 4))
                alpha = jnp.zeros((nb, p), jnp.float32).at[lanes, idx].set(a)
                u = _margins(values, rows, idx, a, m)
            f_out.append(out[0][:n_real])
            gap_out.append(gap[:n_real])
    return np.concatenate(f_out), np.concatenate(gap_out)
