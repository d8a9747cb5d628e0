"""kNN-LM serving: the SM-forest as a first-class LM-serving datastore.

Khandelwal et al.-style interpolation: the datastore maps hidden states
h_t -> observed next token; at each decode step we retrieve the k nearest
stored states and mix

    p(w) = (1 - lam) * p_LM(w) + lam * p_kNN(w),
    p_kNN(w) ∝ Σ_{(h_i, w_i=w)} exp(-d(h, h_i) / T)

The SM-tree is what makes the datastore *dynamic*: ``evict`` uses the
paper's Delete to drop stale entries online (sliding-window memory) — the
operation the original M-tree family could not support.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.engine import SMTreeEngine
from repro.models import model as M


@dataclasses.dataclass
class KnnLmConfig:
    k: int = 8
    lam: float = 0.25
    temperature: float = 1.0
    metric: str = "l2"
    capacity: int = 32
    max_frontier: int = 128


class KnnLmDatastore:
    """Single-host datastore over the JAX SM-tree engine (the sharded-forest
    variant lives in core/distributed.py and examples/distributed_index.py).
    Keys: hidden states [n, D]; values: next-token ids [n].

    With ``mesh`` set, tree pages are replicated over the mesh and query
    cohorts are sharded over the data axes (``dist.sharding.query_pspecs``),
    so the cohort descent runs data-parallel inside the same GSPMD program
    as the sharded decode step (``launch/serve.py --mesh host --knn``)."""

    def __init__(self, cfg: KnnLmConfig, dim: int, mesh=None):
        self.cfg = cfg
        self.dim = dim
        self.mesh = mesh
        self.keys = np.zeros((0, dim), np.float32)
        self.values = np.zeros((0,), np.int32)
        self._keys_buf = self.keys    # growth buffers (_append_history)
        self._vals_buf = self.values
        self.engine: SMTreeEngine | None = None
        self.stream = None   # repro.stream.StreamingEngine when enabled
        self.frontend = None  # serve.frontend.ServeFrontend when enabled
        self.ship_server = None   # stream.transport.WalShipServer
        self.replicas = []        # stream.transport.ShippedReplica
        self.router = None        # serve.router.ReplicaRouter

    def _place(self):
        """Replicate tree pages over the mesh (queries shard, pages don't)."""
        if self.mesh is not None and self.engine is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self.engine.tree = jax.device_put(
                self.engine.tree, NamedSharding(self.mesh, P()))

    def shard_queries(self, h: jax.Array) -> jax.Array:
        """Place a [b, D] query cohort according to ``query_pspecs``."""
        if self.mesh is None:
            return h
        from jax.sharding import NamedSharding
        from repro.dist.sharding import query_pspecs
        return jax.device_put(
            h, NamedSharding(self.mesh, query_pspecs(self.mesh, h.shape[0])))

    def build(self, keys: np.ndarray, values: np.ndarray):
        self.keys = np.asarray(keys, np.float32)
        self.values = np.asarray(values, np.int32)
        # invalidate any growth buffer from a previous build
        self._keys_buf = self.keys
        self._vals_buf = self.values
        self.engine = SMTreeEngine.build(
            self.keys, ids=np.arange(len(values)),
            capacity=self.cfg.capacity, metric=self.cfg.metric)
        self._place()

    def add(self, key: np.ndarray, value: int):
        oid = len(self.values)
        self._append_history(np.asarray(key, np.float32)[None],
                             np.asarray([value], np.int32))
        self.engine.insert(key, oid)
        self._place()   # host-side split paths rebuild arrays off-mesh

    def evict(self, oid: int) -> bool:
        """Online deletion — the paper's contribution at work."""
        return self.engine.delete(self.keys[oid], oid)

    def evict_before(self, oid_bound: int) -> int:
        """Sliding-window eviction: drop all entries with id < bound."""
        n = 0
        for oid in range(oid_bound):
            if self.evict(oid):
                n += 1
        return n

    # -- batched online mutation (repro.stream) -------------------------
    def enable_stream(self, wal_dir: str | None = None, *, shards: int = 0,
                      **kw):
        """Route ``add_batch``/``evict_batch`` through the repro.stream
        write pipeline: conflict-free cohort batching (one device dispatch
        per batch instead of one per entry) with optional WAL durability.
        Call after ``build``.

        With ``shards`` > 1 the store is re-partitioned round-robin into a
        ``StreamingForest`` instead of a single-tree engine: queries merge
        per-shard descents, and ``maintenance()`` (offered by the
        front-end scheduler after every mutation batch) repairs delete
        skew — incrementally when ``rebalance_mode='incremental'`` is
        passed through ``kw``."""
        from repro.stream import (StreamingEngine, StreamingForest,
                                  WriteAheadLog)
        wal = WriteAheadLog(wal_dir) if wal_dir else None
        if shards and shards > 1:
            if self.mesh is not None:
                raise ValueError(
                    "sharded streaming store is host-side; it does not "
                    "compose with the mesh-replicated query path")
            from repro.core.distributed import build_forest_trees
            trees = build_forest_trees(self.keys, int(shards),
                                       capacity=self.cfg.capacity,
                                       metric=self.cfg.metric)
            self.stream = StreamingForest(trees, wal=wal, **kw)
        else:
            self.stream = StreamingEngine(self.engine.tree, wal=wal, **kw)
        return self.stream

    def enable_frontend(self, **cfg):
        """Serve retrieval through the async front-end: queries coalesce
        into epoch-pinned cohorts, ``add_batch``/``evict_batch`` ride the
        mutation scheduler (applied between epoch publishes) instead of
        stalling the decode loop.  Requires ``enable_stream`` first."""
        if self.stream is None:
            raise ValueError("enable_stream() before enable_frontend()")
        from repro.serve.frontend import FrontendConfig, ServeFrontend
        cfg.setdefault("k", self.cfg.k)
        cfg.setdefault("max_frontier", self.cfg.max_frontier)
        self.frontend = ServeFrontend(self.stream,
                                      FrontendConfig(**cfg)).start()
        return self.frontend

    def close_frontend(self) -> None:
        if self.frontend is not None:
            self.frontend.stop()
            self.frontend = None
            self._sync_engine_tree()

    def enable_replication(self, mirror_root: str, *, n_replicas: int = 1,
                           host: str = "127.0.0.1", seed: int = 0):
        """Fan reads out to ``n_replicas`` socket-fed followers: a
        ``WalShipServer`` serves the stream's WAL directory, each replica
        mirrors it locally and replays through the identical pipeline,
        and a ``ReplicaRouter`` in front of the front-end routes queries
        (leader-first; bounded-staleness degraded reads if the leader
        dies).  Requires ``enable_stream(wal_dir=...)`` — replication is
        log shipping, there must be a log — and ``enable_frontend``.
        Followers start from the leader's currently *published* epoch and
        tail from there, so enabling mid-stream is safe."""
        if self.stream is None or self.stream.wal is None:
            raise ValueError("enable_stream(wal_dir=...) before "
                             "enable_replication()")
        if not hasattr(self.stream, "batcher"):
            raise ValueError(
                "socket replication here follows single-tree engines; "
                "forest-sharded stores replicate through "
                "stream.replica.Replica over a StreamingForest follower")
        if self.frontend is None:
            raise ValueError("enable_frontend() before enable_replication()")
        import os

        from repro.serve.router import ReplicaRouter
        from repro.stream import StreamingEngine
        from repro.stream.transport import ShippedReplica, WalShipServer
        wal = self.stream.wal
        self.ship_server = WalShipServer(wal.directory, host=host,
                                         wal=wal).start()
        start_seq = wal.next_seq - 1
        _, tree = self.stream.epochs.current()
        for i in range(n_replicas):
            follower = StreamingEngine(
                tree, wal=None, max_batch=self.stream.batcher.max_batch,
                headroom_frac=self.stream.headroom_frac)
            rep = ShippedReplica(
                follower, self.ship_server.address,
                os.path.join(mirror_root, f"replica_{i:02d}"),
                start_seq=start_seq, seed=seed + i)
            self.replicas.append(rep.start())
        self.router = ReplicaRouter(self.frontend, self.replicas,
                                    k=self.cfg.k,
                                    max_frontier=self.cfg.max_frontier)
        return self.router.start()

    def close_replication(self) -> None:
        if self.router is not None:
            self.router.stop()
            self.router = None
        for rep in self.replicas:
            rep.stop()
        self.replicas = []
        if self.ship_server is not None:
            self.ship_server.stop()
            self.ship_server = None

    def _sync_engine_tree(self) -> None:
        """Resync ``engine.tree`` from the *published* epoch — never from
        ``stream.tree``, which is the batcher's live working reference and
        can be mid-churn (half-applied cohorts of the current batch) when a
        concurrent scheduler thread is applying.  Non-stream readers of
        ``engine.tree`` (engine.knn/validate, ``_place``) must only ever
        observe epoch-published versions, same as the ``knn_logits``
        pinned-read path.  Forest epochs publish shard *tuples* — there is
        no single engine tree to resync, and every read path goes through
        the pinned-epoch merge instead."""
        if self.stream is not None:
            _, tree = self.stream.epochs.current()
            if not isinstance(tree, tuple):
                self.engine.tree = tree

    def _append_history(self, keys: np.ndarray, values: np.ndarray) -> None:
        """Amortised-O(1) append to the oid-indexed key/value history.

        ``self.keys``/``self.values`` stay plain dense arrays (oid indexes
        directly into them — evicted rows keep their slot), but growth goes
        through capacity doubling: a per-step ``np.vstack`` over the full
        history would make sustained ``--knn-mutate`` serving quadratic."""
        n, b = len(self.values), len(values)
        cap = len(self._keys_buf)
        if n + b > cap:
            new_cap = max(2 * cap, n + b, 1024)
            kb = np.zeros((new_cap, self.dim), np.float32)
            vb = np.zeros((new_cap,), np.int32)
            kb[:n] = self.keys
            vb[:n] = self.values
            self._keys_buf, self._vals_buf = kb, vb
        self._keys_buf[n:n + b] = keys
        self._vals_buf[n:n + b] = values
        self.keys = self._keys_buf[:n + b]
        self.values = self._vals_buf[:n + b]

    def add_batch(self, keys: np.ndarray, values: np.ndarray) -> np.ndarray:
        """Insert a batch of (key, next-token) pairs; returns their oids.
        Under serving this is the live-growth path: each decode step's
        [b, D] hidden-state cohort lands in one batched apply."""
        keys = np.asarray(keys, np.float32)
        values = np.asarray(values, np.int32)
        oids = (len(self.values) + np.arange(len(values))).astype(np.int32)
        self._append_history(keys, values)
        if self.frontend is not None:
            from repro.core.smtree import OP_INSERT as _OP_I
            self.frontend.submit_mutations(
                np.full(len(oids), _OP_I, np.int32), keys, oids)
            self._sync_engine_tree()
        elif self.stream is not None:
            self.stream.insert_batch(keys, oids)
            self._sync_engine_tree()
        else:
            for k, o in zip(keys, oids):
                self.engine.insert(k, int(o))
        self._place()
        return oids

    def evict_batch(self, oids: np.ndarray) -> int:
        """Batched online eviction (sliding-window memory); returns the
        number of entries actually removed."""
        from repro.core.smtree import OP_DELETE as _OP_D, ST_APPLIED
        oids = np.asarray(oids, np.int32)
        if self.frontend is not None:
            # async: the scheduler applies between epoch publishes; the
            # count isn't known yet, so report the rows *submitted*
            self.frontend.submit_mutations(
                np.full(len(oids), _OP_D, np.int32), self.keys[oids], oids)
            self._sync_engine_tree()
            n = len(oids)
        elif self.stream is not None:
            res = self.stream.delete_batch(self.keys[oids], oids)
            self._sync_engine_tree()
            n = int((res.statuses == ST_APPLIED).sum())
        else:
            n = sum(self.evict(int(o)) for o in oids)
        self._place()
        return n

    def knn_logits(self, h: jax.Array, vocab: int) -> jax.Array:
        """h: [b, D] query hidden states -> kNN log-probs [b, vocab].

        With streaming enabled the descent runs against a *pinned* epoch
        (``EpochManager.reading``), so a concurrent ``add_batch`` /
        ``evict_batch`` writer can publish and retire versions without ever
        dropping the tree this query is descending."""
        if self.frontend is not None:
            # coalesced path: the decode step's [b, D] block is admitted
            # as b tickets and lands in one epoch-pinned cohort alongside
            # any other concurrent traffic
            d, ids = self.frontend.knn(np.asarray(h, np.float32))
        elif self.stream is not None:
            from repro import obs
            from repro.core import smtree
            with self.stream.epochs.reading() as tree:
                if isinstance(tree, tuple):
                    # forest epoch: per-shard cohort descent + host top-k
                    # merge, shared with the front-end read path
                    from repro.serve.frontend import pinned_knn
                    d, ids = pinned_knn(tree, np.asarray(h, np.float32),
                                        k=self.cfg.k,
                                        max_frontier=self.cfg.max_frontier)
                elif obs.want_level_stats():
                    res, pruned = smtree.knn(
                        tree, self.shard_queries(h), k=self.cfg.k,
                        max_frontier=self.cfg.max_frontier,
                        level_stats=True)
                    obs.observe_query_result(res, pruned)
                    d, ids = res.dists, np.asarray(res.ids)
                else:
                    res = smtree.knn(tree, self.shard_queries(h),
                                     k=self.cfg.k,
                                     max_frontier=self.cfg.max_frontier)
                    d, ids = res.dists, np.asarray(res.ids)
        else:
            from repro import obs
            res = self.engine.knn(self.shard_queries(h), k=self.cfg.k,
                                  max_frontier=self.cfg.max_frontier)
            if obs.want_level_stats():      # sampled, like the tree paths
                obs.observe_query_result(res)
            d, ids = res.dists, np.asarray(res.ids)       # [b, k]
        vals = jnp.asarray(np.where(ids >= 0, self.values[np.maximum(ids, 0)],
                                    0))
        w = jax.nn.softmax(jnp.where(jnp.isfinite(d),
                                     -d / self.cfg.temperature, -jnp.inf), -1)
        b = h.shape[0]
        probs = jnp.zeros((b, vocab), jnp.float32)
        probs = probs.at[jnp.arange(b)[:, None], vals].add(
            jnp.where(jnp.isfinite(d), w, 0.0))
        return jnp.log(jnp.maximum(probs, 1e-10))


def mix_logits(lm_logits: jax.Array, knn_logp: jax.Array, lam: float):
    """log((1-lam) p_LM + lam p_kNN) computed stably."""
    lm_logp = jax.nn.log_softmax(lm_logits.astype(jnp.float32), -1)
    return jnp.logaddexp(lm_logp + jnp.log1p(-lam), knn_logp + jnp.log(lam))


def datastore_entries(params, cfg: ArchConfig, tokens):
    """(keys [n, D] f32, values [n] i32) of token streams [b, s]: the
    pre-head hidden state at each position but the last, and the token
    that follows it (Khandelwal et al., section 3)."""
    # jitted, so the output head (whose logits nobody reads) is not run
    fwd = jax.jit(lambda p, t: M.forward(p, cfg, {"tokens": t},
                                         hidden=True)[2])
    h = np.asarray(fwd(params, jnp.asarray(tokens)), np.float32)
    keys = h[:, :-1].reshape(-1, h.shape[-1])
    return keys, np.asarray(tokens)[:, 1:].reshape(-1).astype(np.int32)
