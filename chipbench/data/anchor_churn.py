"""Churn stream: mixed insert/delete batches concentrated around anchors.

Adapted from the served index's first chip smoke test.  Each batch is half
inserts and half deletes.  Every insert anchor (a live object) gets
``per_anchor`` fresh vectors in a tight cloud around it, so its leaf
overflows and splits; every delete anchor loses its ``per_anchor`` nearest
live objects, so its leaf underflows and merges.  Anchors and victims are
chosen among the objects live before the batch, fresh ones included, so
the live set stays at the corpus size however many batches a window
takes.

Fresh objects take oids ``n, n + 1, ...`` in stream order, so one pool
(the corpus followed by every fresh vector) serves the reference at every
epoch: pool row = oid.  Batches are drawn in order on first use, so a
stream is never exhausted and costs nothing before its first batch.
"""
from __future__ import annotations

import numpy as np

OP_INSERT, OP_DELETE = 1, 2     # the index's opcodes (core/smtree.py)


class Stream:
    """``batch(b)`` is batch b (0-based): (ops [B] i32, xs [B, dim] f32,
    oids [B] i32), the same for a seed whatever was asked before it.
    ``dist(Q, X)`` is the configuration's metric on the host ([q, n])."""

    def __init__(self, corpus: np.ndarray, traffic: dict, seed: int, dist):
        self.n, self.dim = corpus.shape
        self.seed = seed
        self.size = traffic["batch_ops"]
        self.per = traffic["per_anchor"]
        self.sigma = traffic["insert_sigma"]
        self.dist = dist
        self.pool_rows = corpus.astype(np.float32)
        self._live = np.ones(self.n, bool)   # after the batches drawn
        self.victims: list[np.ndarray] = []

    def prepare(self, n_batches: int) -> None:
        """Draw batches up to ``n_batches`` (each depends on the live set
        the ones before it leave)."""
        while len(self.victims) < n_batches:
            self.victims.append(self._draw(len(self.victims)))

    def _draw(self, b: int) -> np.ndarray:
        """Batch b's fresh vectors (onto the pool) and victims."""
        half = self.size // 2
        n_anchor = half // self.per
        rng = np.random.default_rng([self.seed, 3, b])
        live = np.concatenate([self._live, np.zeros(half, bool)])
        self.pool_rows = np.concatenate(
            [self.pool_rows, np.zeros((half, self.dim), np.float32)])
        ids = np.nonzero(live)[0]
        anchors = rng.choice(ids, 2 * n_anchor, replace=False)
        ins, dels = anchors[:n_anchor], anchors[n_anchor:]
        new = self.n + b * half + np.arange(half)
        self.pool_rows[new] = (
            np.repeat(self.pool_rows[ins], self.per, 0)
            + rng.normal(0, self.sigma, (half, self.dim))).astype(np.float32)
        # victims: the nearest live objects of each delete anchor, never an
        # insert anchor (its cloud is around it)
        free = live.copy()
        free[ins] = False
        cand = np.nonzero(free)[0]
        d = self.dist(self.pool_rows[dels], self.pool_rows[cand])
        part = np.argpartition(d, 2 * self.per - 1, axis=1)[:, :2 * self.per]
        order = np.take_along_axis(d, part, 1).argsort(1, kind="stable")
        near = np.take_along_axis(part, order, 1)
        v = []
        for row in near:
            pick = [o for o in cand[row] if free[o]][:self.per]
            free[pick] = False
            v += pick
        extra = rng.choice(np.nonzero(free)[0], half - len(v), replace=False)
        victims = np.concatenate([np.asarray(v, np.int64), extra])
        live[victims] = False
        live[new] = True
        self._live = live
        return victims

    def batch(self, b: int):
        self.prepare(b + 1)
        half = self.size // 2
        new = self.n + b * half + np.arange(half)
        oids = np.concatenate([new, self.victims[b]]).astype(np.int32)
        ops = np.concatenate([np.full(half, OP_INSERT, np.int32),
                              np.full(half, OP_DELETE, np.int32)])
        # a delete carries its object's vector, which the index descends by
        xs = self.pool_rows[oids]
        perm = np.random.default_rng([self.seed, 5, b]).permutation(self.size)
        return ops[perm], xs[perm], oids[perm]

    def pool(self, n_batches: int) -> np.ndarray:
        """Corpus followed by the fresh vectors of the first ``n_batches``
        batches: row = oid."""
        self.prepare(n_batches)
        return self.pool_rows[:self.n + n_batches * (self.size // 2)]

    def live(self, n_batches: int) -> list[np.ndarray]:
        """Live mask over ``pool(n_batches)`` after 0..n_batches batches."""
        self.prepare(n_batches)
        half = self.size // 2
        live = np.zeros(self.n + n_batches * half, bool)
        live[:self.n] = True
        out = [live.copy()]
        for b in range(n_batches):
            live[self.n + b * half:self.n + (b + 1) * half] = True
            live[self.victims[b]] = False
            out.append(live.copy())
        return out
