"""Plain reference for L-infinity kNN: brute force over every live vector.

Adapted from the served index's first chip smoke test: the Chebyshev
distance max_i |q_i - x_i| in plain ``jax.numpy``, with none of the
index's own code, scanning the pool in chunks with a running top-k.
``dtype`` is the precision the distances are computed in: float32 for the
reference, bfloat16 for the lower-precision control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("k", "chunk", "dtype"))
def _brute_knn(Q, X, live, *, k: int, chunk: int, dtype):
    """Exact kNN of Q [q, dim] over the live rows of X [n, dim] (n a
    multiple of ``chunk``)."""
    Qc = Q.astype(dtype)

    def body(carry, c):
        best_d, best_i = carry
        xc = jax.lax.dynamic_slice_in_dim(X, c * chunk, chunk).astype(dtype)
        lc = jax.lax.dynamic_slice_in_dim(live, c * chunk, chunk)
        d = jnp.max(jnp.abs(Qc[:, None, :] - xc[None, :, :]), axis=-1)
        d = jnp.where(lc[None, :], d.astype(jnp.float32), jnp.inf)
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


def pairwise(Q: np.ndarray, X: np.ndarray) -> np.ndarray:
    """[q, n] f32 distances on the host (numpy), one dimension at a time
    (no [q, n, dim] temporary)."""
    Xt = np.ascontiguousarray(X.T)
    d = np.zeros((len(Q), len(X)), np.float32)
    for j in range(Q.shape[1]):
        np.maximum(d, np.abs(Q[:, j:j + 1] - Xt[j][None, :]), out=d)
    return d


class Reference:
    """Brute-force kNN over a fixed pool [n, dim] (row = object id); each
    call names the live subset by a boolean mask over the pool."""

    def __init__(self, pool: np.ndarray, *, dtype=jnp.float32,
                 chunk: int = 8192, q_chunk: int = 256):
        self.host = np.asarray(pool, np.float32)
        self.chunk = min(chunk, -(-len(pool) // 8) * 8)
        self.n = -(-len(pool) // self.chunk) * self.chunk
        padded = np.zeros((self.n, pool.shape[1]), np.float32)
        padded[:len(pool)] = pool
        self.dev = jnp.asarray(padded)
        self.dtype = dtype
        self.q_chunk = q_chunk

    def knn(self, Q: np.ndarray, live: np.ndarray, k: int):
        """(dists [q, k] f32, ids [q, k] i32) over the rows where ``live``."""
        mask = np.zeros(self.n, bool)
        mask[:len(live)] = live
        mask = jnp.asarray(mask)
        ds, ids = [], []
        for s in range(0, len(Q), self.q_chunk):
            q = np.zeros((self.q_chunk, Q.shape[1]), np.float32)
            part = Q[s:s + self.q_chunk]
            q[:len(part)] = part
            d, i = _brute_knn(jnp.asarray(q), self.dev, mask, k=k,
                              chunk=self.chunk, dtype=self.dtype)
            ds.append(np.asarray(d)[:len(part)])
            ids.append(np.asarray(i)[:len(part)])
        if not ds:
            return (np.zeros((0, k), np.float32), np.zeros((0, k), np.int32))
        return np.concatenate(ds), np.concatenate(ids)

    def dist(self, Q: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """f32 distance from each Q row to the pool rows it names [q, k]
        (numpy, on the host); +inf where an id is out of the pool."""
        ok = (ids >= 0) & (ids < len(self.host))
        rows = self.host[np.where(ok, ids, 0)]
        d = np.max(np.abs(Q[:, None, :] - rows), axis=-1)
        return np.where(ok, d, np.inf).astype(np.float32)
