"""Plain reference for L2 kNN: brute force over every live vector.

The Euclidean distance sqrt(sum_i (q_i - x_i)^2) in plain ``jax.numpy``,
computed elementwise from the differences (the expansion |q|^2 - 2 q.x +
|x|^2 would lose the small distances to cancellation), with none of the
index's own code, scanning the pool in chunks with a running top-k.
``dtype`` is the precision the differences, squares and sums are computed
in: float32 for the reference, bfloat16 for the lower-precision control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _l2(a, b, dtype):
    """[..., dim] x [..., dim] -> [...] in ``dtype``, returned as f32."""
    d = a.astype(dtype) - b.astype(dtype)
    return jnp.sqrt(jnp.sum(d * d, axis=-1, dtype=dtype)).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("k", "chunk", "dtype"))
def _brute_knn(Q, X, live, *, k: int, chunk: int, dtype):
    """Exact kNN of Q [q, dim] over the live rows of X [n, dim] (n a
    multiple of ``chunk``)."""
    def body(carry, c):
        best_d, best_i = carry
        xc = jax.lax.dynamic_slice_in_dim(X, c * chunk, chunk)
        lc = jax.lax.dynamic_slice_in_dim(live, c * chunk, chunk)
        d = _l2(Q[:, None, :], xc[None, :, :], dtype)
        d = jnp.where(lc[None, :], d, jnp.inf)
        ids = jnp.broadcast_to(c * chunk + jnp.arange(chunk, dtype=jnp.int32),
                               d.shape)
        neg, sel = jax.lax.top_k(-jnp.concatenate([best_d, d], 1), k)
        return (-neg, jnp.take_along_axis(
            jnp.concatenate([best_i, ids], 1), sel, 1)), None

    init = (jnp.full((Q.shape[0], k), jnp.inf, jnp.float32),
            jnp.full((Q.shape[0], k), -1, jnp.int32))
    (d, i), _ = jax.lax.scan(body, init,
                             jnp.arange(X.shape[0] // chunk, dtype=jnp.int32))
    return d, jnp.where(jnp.isfinite(d), i, -1)


@jax.jit
def _rows_l2(Q, X, ids):
    """f32 distance from each Q row [q, dim] to the rows of X it names
    [q, k] (ids already in range)."""
    return _l2(Q[:, None, :], X[ids], jnp.float32)


def pairwise(Q: np.ndarray, X: np.ndarray, block: int = 256) -> np.ndarray:
    """[q, n] f32 distances on the host (numpy), ``block`` rows of X at a
    time (no [q, n, dim] temporary)."""
    d = np.empty((len(Q), len(X)), np.float32)
    for s in range(0, len(X), block):
        diff = Q[:, None, :] - X[None, s:s + block, :]
        d[:, s:s + block] = np.sqrt(np.sum(diff * diff, axis=-1))
    return d


class Reference:
    """Brute-force kNN over a fixed pool [n, dim] (row = object id); each
    call names the live subset by a boolean mask over the pool."""

    def __init__(self, pool: np.ndarray, *, dtype=jnp.float32,
                 chunk: int = 2048, q_chunk: int = 64):
        self.n_pool = len(pool)
        self.chunk = min(chunk, -(-len(pool) // 8) * 8)
        self.n = -(-len(pool) // self.chunk) * self.chunk
        padded = np.zeros((self.n, pool.shape[1]), np.float32)
        padded[:len(pool)] = pool
        self.dev = jnp.asarray(padded)
        self.dtype = dtype
        self.q_chunk = q_chunk

    def _chunks(self, Q: np.ndarray):
        for s in range(0, len(Q), self.q_chunk):
            part = Q[s:s + self.q_chunk]
            q = np.zeros((self.q_chunk, Q.shape[1]), np.float32)
            q[:len(part)] = part
            yield s, len(part), jnp.asarray(q)

    def knn(self, Q: np.ndarray, live: np.ndarray, k: int):
        """(dists [q, k] f32, ids [q, k] i32) over the rows where ``live``."""
        mask = np.zeros(self.n, bool)
        mask[:len(live)] = live
        mask = jnp.asarray(mask)
        ds, ids = [], []
        for _, m, q in self._chunks(Q):
            d, i = _brute_knn(q, self.dev, mask, k=k, chunk=self.chunk,
                              dtype=self.dtype)
            ds.append(np.asarray(d)[:m])
            ids.append(np.asarray(i)[:m])
        if not ds:
            return (np.zeros((0, k), np.float32), np.zeros((0, k), np.int32))
        return np.concatenate(ds), np.concatenate(ids)

    def dist(self, Q: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """f32 distance from each Q row to the pool rows it names [q, k];
        +inf where an id is out of the pool."""
        ok = (ids >= 0) & (ids < self.n_pool)
        safe = np.where(ok, ids, 0).astype(np.int32)
        out = np.empty(ids.shape, np.float32)
        for s, m, q in self._chunks(Q):
            i = np.zeros((self.q_chunk, ids.shape[1]), np.int32)
            i[:m] = safe[s:s + m]
            d = _rows_l2(q, self.dev, jnp.asarray(i))
            out[s:s + m] = np.asarray(d)[:m]
        return np.where(ok, out, np.inf).astype(np.float32)
