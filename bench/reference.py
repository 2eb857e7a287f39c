"""Plain SODDA (Fang & Klabjan 2018, Algorithm 1), the yardstick for `correct`.

It imports nothing of the program and takes nothing the program made: it
gets the benchmark's own data, the configuration file and a fit's key, and
replays the fit in straightforward ``jax.numpy``:

* the iteration's sample (B^t, C^t, D^t, the block assignment pi and the
  inner rows J), drawn from ``fold_in(key, t)`` by the scheme the algorithm
  specifies (copied from ``repro.core.partition.sample_iteration``);
* the snapshot gradient mu = mask_C * X^T [l'(X (w * mask_B), y) * mask_D]
  / (P d), as one pass over X per product, tile by tile;
* the L-step inner chain of every (p, q) block,
  wbar <- wbar - gamma_t [(l'(x.wbar) - l'(x.w0)) x + mu_blk];
* the conflict-free concatenation of the updated sub-blocks;
* F(w) = mean hinge(X w, y) at the recorded ticks.

X is read where it lies, one device tile at a time, so the reference needs
no memory beyond the data and a few vectors. Every product and sum is an
elementwise multiply and a reduction in ``dtype``: float32 is the
reference, exact float32 arithmetic with no matrix-unit passes;
bfloat16 is the control, the same arithmetic one precision lower.
"""
from __future__ import annotations

import functools
import math
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

def record_ticks(iters: int, record_every: int) -> Tuple[int, ...]:
    """Every multiple of `record_every` below `iters`, and `iters`."""
    return tuple(range(0, iters, record_every)) + (iters,)


def counts(config: dict, d_frac_rows: int) -> Tuple[int, int, int]:
    """(|B|, |C|, |D| per observation partition)."""
    M = config["M"]
    b = max(1, int(round(config["b_frac"] * M)))
    c = max(1, min(b, int(round(config["c_frac"] * M))))
    d = max(1, int(round(config["d_frac"] * d_frac_rows)))
    return b, c, d


def _count_mask(u, count: int):
    """The `count` smallest entries of u, as a 0/1 mask (nested in count)."""
    if count >= u.shape[0]:
        return jnp.ones_like(u)
    return (u <= jnp.sort(u)[count - 1]).astype(u.dtype)


@functools.partial(jax.jit, static_argnames=("P", "Q", "n", "M", "L", "b",
                                             "c", "d"))
def sample(key, t, P, Q, n, M, L, b, c, d):
    """(mask_b (M,), mask_c (M,), mask_d (P*n,), pi (Q, P), J (P, Q, L))."""
    kb, kd, kp, kj = jax.random.split(jax.random.fold_in(key, t), 4)
    u = jax.random.uniform(kb, (M,))
    mask_d = jnp.concatenate([
        _count_mask(jax.random.uniform(jax.random.fold_in(kd, p), (n,)), d)
        for p in range(P)])
    pi = jnp.stack([jax.random.permutation(jax.random.fold_in(kp, q), P)
                    for q in range(Q)])
    J = jnp.stack([jnp.stack([
        jax.random.randint(jax.random.fold_in(kj, p * Q + q), (L,), 0, n)
        for q in range(Q)]) for p in range(P)])
    return _count_mask(u, b), _count_mask(u, c), mask_d, pi, J


def _hinge_deriv(z, y):
    return jnp.where(y * z < 1, -y, jnp.zeros_like(y))


def _hinge(z, y):
    return jnp.maximum(0, 1 - y * z)


LOSS = {"hinge": (_hinge, _hinge_deriv)}


@functools.partial(jax.jit, static_argnames=("dtype",))
def _matvec(X, v, dtype):
    """X v, each product and the sum in `dtype` (no matrix-unit passes)."""
    return jnp.sum(X.astype(dtype) * v.astype(dtype)[None, :], axis=1)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _rmatvec(X, s, dtype):
    """s X, each product and the sum in `dtype`."""
    return jnp.sum(X.astype(dtype) * s.astype(dtype)[:, None], axis=0)


@functools.partial(jax.jit, static_argnames=("mt",))
def _gather(X, rows, col0, mt):
    """X[rows[b], col0[b]:col0[b]+mt] for each block b of one tile.

    One gather of single elements, which reads the tile in whichever layout
    it lies: the TPU lays a tall tile out column-major, and a gather of whole
    rows or of (1, mt) slices first copies the whole tile to row-major.
    """
    cols = col0[:, None] + jnp.arange(mt, dtype=col0.dtype)
    return X[rows[:, :, None], cols[:, None, :]]


@functools.partial(jax.jit, static_argnames=("loss", "dtype"))
def _inner(w0, Xl, yl, mu, gamma, loss, dtype):
    """All chains at once: w0, mu (B, mt), Xl (B, L, mt), yl (B, L)."""
    deriv = LOSS[loss][1]
    w0, Xl, yl, mu = (a.astype(dtype) for a in (w0, Xl, yl, mu))
    gamma = gamma.astype(dtype)

    def chain(w0_, Xl_, yl_, mu_):
        def step(wbar, xy):
            x, yy = xy
            z1 = jnp.sum(x * wbar)
            z0 = jnp.sum(x * w0_)
            g = (deriv(z1, yy) - deriv(z0, yy)) * x + mu_
            return wbar - gamma * g, None

        return jax.lax.scan(step, w0_, (Xl_, yl_))[0]

    return jax.vmap(chain)(w0, Xl, yl, mu)


class Tiles:
    """X and y as the device tiles they lie in, for reading in place."""

    def __init__(self, X, y, dtype=jnp.float32, store=None):
        """`dtype` is the arithmetic's; tiles are kept in `store` (default
        `dtype`) and cast to `dtype` where they are read."""
        self.N, self.M = X.shape
        self.dtype = dtype
        store = dtype if store is None else store
        self.x = {}  # (row0, col0) -> tile on its device
        for s in X.addressable_shards:
            r, c = s.index
            key = (r.start or 0, c.start or 0)
            if key not in self.x:
                self.x[key] = s.data if store == X.dtype else \
                    s.data.astype(store)
        self.rows = sorted({r for r, _ in self.x})
        self.cols = sorted({c for _, c in self.x})
        self.nr = self.N // len(self.rows)
        self.nc = self.M // len(self.cols)
        self.home = next(iter(self.x.values())).devices().pop()
        self.y = jax.device_put(y, jax.sharding.SingleDeviceSharding(
            self.home))

    def matvec(self, v):
        """X v as (N,), summed over column tiles in column order."""
        parts = []
        for r in self.rows:
            acc = None
            for c in self.cols:
                t = self.x[(r, c)]
                dev = t.devices().pop()
                p = _matvec(t, jax.device_put(v[c:c + self.nc], dev),
                            self.dtype)
                p = jax.device_put(p, self.home)
                acc = p if acc is None else acc + p
            parts.append(acc)
        return jnp.concatenate(parts)

    def rmatvec(self, s):
        """s X as (M,), summed over row tiles in row order."""
        parts = []
        for c in self.cols:
            acc = None
            for r in self.rows:
                t = self.x[(r, c)]
                dev = t.devices().pop()
                p = _rmatvec(t, jax.device_put(s[r:r + self.nr], dev),
                             self.dtype)
                p = jax.device_put(p, self.home)
                acc = p if acc is None else acc + p
            parts.append(acc)
        return jnp.concatenate(parts)

    def blocks(self, rows: np.ndarray, col0: np.ndarray, mt: int):
        """X[rows[b], col0[b]:col0[b]+mt] for each block b: (B, L, mt).

        `rows` (B, L) and `col0` (B,) are global; each block lies in one
        tile, and each tile is read once, where it lies.
        """
        r0 = np.array([max(r for r in self.rows if r <= x)
                       for x in rows.min(axis=1)])
        c0 = np.array([max(c for c in self.cols if c <= x) for x in col0])
        order, got = [], []
        for key in sorted(set(zip(r0.tolist(), c0.tolist()))):
            bs = [b for b in range(len(col0)) if (r0[b], c0[b]) == key]
            t = self.x[key]
            dev = t.devices().pop()
            got.append(jax.device_put(_gather(
                t, jax.device_put(rows[bs] - key[0], dev),
                jax.device_put(col0[bs] - key[1], dev), mt), self.home))
            order += bs
        return jnp.concatenate(got)[np.argsort(order)]


def objective(tiles: Tiles, w, loss: str) -> float:
    z = tiles.matvec(w.astype(tiles.dtype))
    return float(jnp.mean(LOSS[loss][0](z, tiles.y.astype(tiles.dtype))
                          .astype(jnp.float32)))


def fit(tiles: Tiles, config: dict, key, iters: int,
        record_every: int) -> Tuple[List[float], np.ndarray]:
    """Replay one fit from w = 0: (objective at the ticks, final w)."""
    P, Q, N, M, L = (int(config[k]) for k in ("P", "Q", "N", "M", "L"))
    loss, dtype = config["loss"], tiles.dtype
    deriv = LOSS[loss][1]
    n, m, mt = N // P, M // Q, M // (P * Q)
    b, c, d = counts(config, n)
    key = jax.device_put(key, tiles.home)
    ticks = set(record_ticks(iters, record_every))
    w = jax.device_put(jnp.zeros((M,), dtype), tiles.home)
    hist = []
    for t in range(iters + 1):
        if t in ticks:
            hist.append(objective(tiles, w, loss))
        if t == iters:
            break
        mask_b, mask_c, mask_d, pi, J = sample(key, t + 1, P, Q, n, M, L,
                                               b, c, d)
        z = tiles.matvec(w * mask_b.astype(dtype))
        yv = tiles.y.astype(dtype)
        s = deriv(z, yv) * mask_d.astype(dtype) / jnp.asarray(P * d, dtype)
        mu = mask_c.astype(dtype) * tiles.rmatvec(s)
        pi_h, J_h = np.asarray(pi), np.asarray(J)
        # block b = p * Q + q: worker (p, q) updates sub-block pi_q(p)
        p_idx, q_idx = np.divmod(np.arange(P * Q), Q)
        rows = p_idx[:, None] * n + J_h.reshape(P * Q, L)
        col0 = q_idx * m + pi_h[q_idx, p_idx] * mt
        idx = jnp.asarray((col0[:, None] + np.arange(mt)).astype(np.int32))
        gamma = jnp.float32(config["lr0"]) / (1.0 + math.sqrt(t))
        wL = _inner(w[idx], tiles.blocks(rows, col0, mt),
                    yv[jnp.asarray(rows.astype(np.int32))], mu[idx],
                    jnp.asarray(gamma), loss, dtype)
        w = w.at[idx.reshape(-1)].set(wL.reshape(-1).astype(dtype))
    return hist, np.asarray(w.astype(jnp.float32))
