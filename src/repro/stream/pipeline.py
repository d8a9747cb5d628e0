"""Serving-grade write pipelines: WAL ▸ batcher ▸ epochs ▸ snapshots.

Two orchestrators over the stream primitives:

  * ``StreamingEngine``  — one SM-tree (the kNN-LM datastore case): every
    mutation batch is framed into the WAL *before* it is applied (write-
    ahead), applied through the conflict-free-cohort batcher, and the
    resulting immutable tree version is published as the next epoch for
    concurrent readers.
  * ``StreamingForest``  — a sharded SM-forest: rows are routed to their
    owner shard (round-robin hash for new ids, ownership map — maintained
    across rebalances — for deletes), applied shard-at-a-time through the
    same batcher, with background ``maintenance()`` firing the rebalancer
    when delete skew builds up.

The forest's ``maintenance()`` runs in one of two rebalance modes:
``stop_world`` (the original one-shot ``rebalance_shards`` rebuild, kept
as the baseline and the replay path for old WALs) and ``incremental``
(a deterministic ``MigrationPlan`` executed one bounded step per call —
each step a delete-on-donor / insert-on-receiver cohort behind one epoch
publish, DESIGN.md §16).

Both support ``snapshot()`` (atomic checkpoint carrying the tree geometry
and the WAL high-water mark) and ``restore()`` = snapshot + WAL tail
replay.  Replay routes every record back through the identical code paths
— batch records through the batcher, control records (rebalance /
migration plan / migration step) through ``apply_control`` — so the
restored state is **bitwise identical** to the straight-line run, even
after a crash between migration steps (tests/test_stream_e2e.py,
tests/test_migration.py).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro import obs
from repro.core import smtree
from repro.core.smtree import OP_DELETE, OP_INSERT, TreeArrays, empty_tree
from repro.stream.batcher import (BatchResult, MutationBatcher, check_oids,
                                  cut_cohorts, escalate_rows, pad_to_bucket)
from repro.stream.epoch import EpochManager
from repro.stream.rebalance import (MigrationPlan, collect_stats,
                                    live_objects, needs_rebalance,
                                    plan_migration, rebalance_shards)
from repro.stream.wal import (KIND_BATCH, KIND_MIGRATION_PLAN,
                              KIND_MIGRATION_STEP, KIND_REBALANCE,
                              WriteAheadLog)

__all__ = ["StreamingEngine", "StreamingForest"]


def _mutation_log(xs, oids, op: int):
    xs = np.asarray(xs, np.float32)
    oids = np.asarray(oids, np.int32)
    return np.full(len(oids), op, np.int32), xs, oids


def _pad_cohort(ops, xs, oids, owner, max_batch: int):
    """Pad a cohort slice to its power-of-two bucket with NOP rows (oid -1,
    owner 0 — inert on every shard) so the collective jit cache stays one
    entry per bucket size, exactly like the batcher's host path."""
    n = len(ops)
    bucket = pad_to_bucket(n, max_batch)
    if bucket == n:
        return ops, xs, oids, owner
    pad = bucket - n
    return (np.concatenate([ops, np.full(pad, smtree.OP_NOP, np.int32)]),
            np.concatenate([xs, np.zeros((pad, xs.shape[1]), np.float32)]),
            np.concatenate([oids, np.full(pad, -1, np.int32)]),
            np.concatenate([owner, np.zeros(pad, np.int32)]))


class StreamingEngine:
    """WAL-backed batched mutation pipeline over a single SM-tree.

    ``headroom_frac`` arms ahead-of-time free-ring growth: after each
    batch — an epoch-publish point, never mid-pass — the node table is
    doubled (``smtree.grow_tree``) whenever the free ring sits below
    ``max(MAX_HEIGHT + 1, headroom_frac * max_nodes)``, so ring
    exhaustion (the one split-path host escalation left) stops being a
    mid-batch event.  Growth is deterministic in the mutation sequence,
    which the WAL replay contract requires.  ``None`` disables it (the
    PR-4 behaviour: exhaustion escalates so the host can ``_grow``)."""

    def __init__(self, tree: TreeArrays, *, wal: WriteAheadLog | None = None,
                 ckpt=None, max_batch: int = 4096, donate: bool = False,
                 device_splits: bool = True, device_merges: bool = True,
                 headroom_frac: float | None = 1 / 16):
        # donation would consume the buffers published as the previous
        # epoch out from under pinned readers — see MutationBatcher
        self.batcher = MutationBatcher(tree, max_batch=max_batch,
                                       donate=donate,
                                       device_splits=device_splits,
                                       device_merges=device_merges)
        self.wal = wal
        self.ckpt = ckpt          # dist.checkpoint.CheckpointManager
        self.headroom_frac = headroom_frac
        self.n_grows = 0
        self.epochs = EpochManager(tree)
        self._step = 0

    @property
    def tree(self) -> TreeArrays:
        return self.batcher.tree

    # -- mutations ---------------------------------------------------------
    def apply(self, ops, xs, oids, *, log: bool = True) -> BatchResult:
        """Apply one mutation batch; frames it into the WAL first so an
        acknowledged batch is always replayable.  Negative oids are rejected
        here — before the WAL append — so a bad batch can neither collide
        with the batcher's pad sentinel nor poison replay."""
        check_oids(oids)
        if log and self.wal is not None:
            with obs.span("mutation.wal_append", n=len(ops)):
                self.wal.append_batch(np.asarray(ops, np.int8), xs, oids)
        with obs.span("mutation.apply", n=len(ops)):
            res = self.batcher.apply(ops, xs, oids)
            if self.headroom_frac is not None:
                with obs.span("mutation.headroom"):
                    if smtree.needs_headroom(self.tree,
                                             frac=self.headroom_frac):
                        self.batcher.tree = smtree.grow_tree(self.tree)
                        self.n_grows += 1
                        obs.record_event("stream.tree_grow",
                                         n_grows=self.n_grows)
        with obs.span("mutation.publish"):
            self.epochs.publish(self.tree)
        if obs.enabled():
            obs.counter("stream.batches_total").inc()
            obs.counter("stream.rows_total").inc(len(ops))
            obs.counter("stream.escalated_rows_total").inc(res.n_escalated)
            obs.counter("stream.device_splits_total").inc(res.n_split)
            obs.counter("stream.device_merges_total").inc(res.n_merge)
        return res

    def insert_batch(self, xs, oids, **kw) -> BatchResult:
        ops, xs, oids = _mutation_log(xs, oids, OP_INSERT)
        return self.apply(ops, xs, oids, **kw)

    def delete_batch(self, xs, oids, **kw) -> BatchResult:
        ops, xs, oids = _mutation_log(xs, oids, OP_DELETE)
        return self.apply(ops, xs, oids, **kw)

    # -- snapshots ---------------------------------------------------------
    def _extra(self) -> dict:
        t = self.tree
        return {"kind": "smtree", "capacity": t.capacity, "dim": t.dim,
                "metric": t.metric, "max_nodes": t.max_nodes,
                "min_fill": t.min_fill,
                "wal_seq": (self.wal.next_seq - 1 if self.wal is not None
                            else -1)}

    def snapshot(self, step: int | None = None) -> int:
        """Checkpoint the current tree + WAL high-water mark."""
        if self.ckpt is None:
            raise ValueError("no CheckpointManager configured")
        step = self._step if step is None else step
        self.ckpt.save(step, {"tree": self.tree}, extra=self._extra())
        self._step = step + 1
        return step

    @classmethod
    def restore(cls, ckpt_dir: str, *, wal: WriteAheadLog | None = None,
                ckpt=None, **kw) -> "StreamingEngine":
        """Last snapshot + WAL tail replay (bitwise-deterministic)."""
        from repro.dist.checkpoint import read_manifest, restore_checkpoint
        manifest = read_manifest(ckpt_dir)
        extra = manifest["extra"]
        template = _tree_template(extra)
        state, _ = restore_checkpoint(ckpt_dir, {"tree": template},
                                      step=manifest["step"])
        eng = cls(state["tree"], wal=wal, ckpt=ckpt, **kw)
        eng._step = manifest["step"] + 1
        if wal is not None:
            for rec in wal.replay(after_seq=extra["wal_seq"]):
                if rec.kind == KIND_BATCH:
                    eng.apply(rec.ops.astype(np.int32), rec.xs, rec.oids,
                              log=False)
        return eng


def _tree_template(extra: dict, max_nodes: int | None = None) -> TreeArrays:
    t = empty_tree(dim=extra["dim"], capacity=extra["capacity"],
                   max_nodes=max_nodes or extra["max_nodes"],
                   metric=extra["metric"],
                   min_fill_frac=extra["min_fill"] / extra["capacity"])
    return t


class StreamingForest:
    """WAL-backed batched mutation pipeline over a sharded SM-forest.

    Two control-plane modes:

      * host-centric (``mesh=None``): shards are held as per-shard
        TreeArrays and mutated shard-at-a-time through per-shard batchers —
        each shard's cohorts still run the fused device scan + split pass.
      * mesh-resident (``mesh=`` a Mesh whose ``axis`` has one device per
        shard): the stacked forest lives on the mesh and every WAL batch is
        applied as cut-cohorts → one ``forest_apply_mutations`` collective →
        one ``forest_apply_splits`` collective over the compacted overflow
        rows → psum'd statuses.  Tree pages never leave HBM; the host sees
        only the per-row status vectors.  Residual escalations (multi-level
        or root splits, merges) unstack the affected shards to the host
        control plane — the rare path.

    Both modes produce bitwise-identical shards for conflict-free batches
    (tests/test_device_split.py): the collective is the same masked scan +
    split pass the batcher runs, and host escalation uses the same code in
    the same (overflow-first) order."""

    def __init__(self, trees: list[TreeArrays], *,
                 wal: WriteAheadLog | None = None, ckpt=None,
                 max_batch: int = 4096, max_skew: float = 1.5,
                 min_objects: int = 64, mesh=None, axis: str = "model",
                 device_splits: bool = True, device_merges: bool = True,
                 headroom_frac: float | None = 1 / 16,
                 rebalance_mode: str = "stop_world",
                 migration_step_objects: int = 64,
                 free_floor: float | None = None):
        if rebalance_mode not in ("stop_world", "incremental"):
            raise ValueError(f"unknown rebalance_mode {rebalance_mode!r} "
                             f"(expected 'stop_world' or 'incremental')")
        self.rebalance_mode = rebalance_mode
        self.migration_step_objects = int(migration_step_objects)
        self.free_floor = free_floor
        self._migration: dict | None = None   # {"plan": MigrationPlan,
        #                                        "next": step index}
        self.n_migration_steps = 0
        self.objects_migrated = 0
        self.device_splits = device_splits
        self.device_merges = device_merges
        self.headroom_frac = headroom_frac
        self.n_grows = 0
        self.batchers = [MutationBatcher(t, max_batch=max_batch,
                                         device_splits=device_splits,
                                         device_merges=device_merges)
                         for t in trees]
        self.wal = wal
        self.ckpt = ckpt
        self.max_batch = int(max_batch)
        self.max_skew = max_skew
        self.min_objects = min_objects
        self.mesh = mesh
        self.axis = axis
        if mesh is not None and mesh.shape[axis] != len(trees):
            raise ValueError(
                f"mesh axis {axis!r} has {mesh.shape[axis]} devices for "
                f"{len(trees)} shards (need exactly one per shard)")
        # mesh mode: the stacked forest is the source of truth between
        # rebalances; None = truth lives in the per-shard batchers
        self._stacked: TreeArrays | None = None
        self._unstack_cache: tuple | None = None   # (stacked, shard views)
        self._shard_nodes = [t.max_nodes for t in trees]
        self.epochs = EpochManager(tuple(self.trees))
        self.owner: dict[int, int] = {}
        self._step = 0
        self.n_rebalances = 0
        self._rebuild_ownership()

    @property
    def trees(self) -> list[TreeArrays]:
        if self._stacked is not None:
            # cache the unstacked view per stacked-forest identity: slicing
            # materialises per-shard copies on CPU, and epoch publication +
            # stats read this after every batch
            if (self._unstack_cache is None
                    or self._unstack_cache[0] is not self._stacked):
                from repro.core.distributed import unstack_forest
                self._unstack_cache = (self._stacked, unstack_forest(
                    self._stacked, max_nodes=self._shard_nodes))
            return self._unstack_cache[1]
        return [b.tree for b in self.batchers]

    @property
    def n_shards(self) -> int:
        return len(self.batchers)

    @property
    def n_objects(self) -> int:
        return sum(t.n_objects for t in self.trees)

    def _rebuild_ownership(self) -> None:
        self.owner = {}
        for s, t in enumerate(self.trees):
            _, oids = live_objects(t)
            for o in oids:
                self.owner[int(o)] = s

    # -- routing -----------------------------------------------------------
    def route(self, ops, oids) -> np.ndarray:
        """Owner shard per row.  Deletes follow the ownership map (objects
        migrate under rebalancing); new inserts hash round-robin
        (oid mod S, matching ``build_forest``'s initial partition).  The
        map is scanned in log order so same-batch insert→delete pairs
        route consistently."""
        S = self.n_shards
        pending = dict(self.owner)
        out = np.empty(len(oids), np.int32)
        for i, (op, oid) in enumerate(zip(ops, oids)):
            o = int(oid)
            s = pending.get(o, o % S)
            out[i] = s
            if op == OP_INSERT:
                pending[o] = s
            elif op == OP_DELETE:
                pending.pop(o, None)
        return out

    # -- mutations ---------------------------------------------------------
    def apply(self, ops, xs, oids, *, log: bool = True) -> BatchResult:
        ops = np.asarray(ops, np.int32)
        xs = np.asarray(xs, np.float32)
        oids = np.asarray(oids, np.int32)
        check_oids(oids)
        if log and self.wal is not None:
            with obs.span("mutation.wal_append", n=len(ops)):
                self.wal.append_batch(ops.astype(np.int8), xs, oids)
        owner = self.route(ops, oids)
        with obs.span("mutation.apply", n=len(ops),
                      plane="mesh" if self.mesh is not None else "host"):
            if self.mesh is not None:
                res = self._apply_mesh(ops, xs, oids, owner)
            else:
                res = self._apply_host(ops, xs, oids, owner)
        applied = res.statuses == smtree.ST_APPLIED
        for i in np.nonzero(applied)[0]:
            if ops[i] == OP_INSERT:
                self.owner[int(oids[i])] = int(owner[i])
            else:
                self.owner.pop(int(oids[i]), None)
        self._ensure_headroom()
        with obs.span("mutation.publish"):
            self.epochs.publish(tuple(self.trees))
        if obs.enabled():
            obs.counter("stream.batches_total").inc()
            obs.counter("stream.rows_total").inc(len(ops))
            obs.counter("stream.escalated_rows_total").inc(res.n_escalated)
            obs.counter("stream.device_splits_total").inc(res.n_split)
            obs.counter("stream.device_merges_total").inc(res.n_merge)
        return res

    def _ensure_headroom(self) -> None:
        """Ahead-of-time free-ring growth (epoch-publish point): double any
        shard whose ring fell below the watermark, so the next batch's
        split pass cannot exhaust it mid-collective.  Both control-plane
        modes read the same per-shard scalars and grow at the same points,
        which keeps mesh ≡ host bitwise (and WAL replay deterministic)."""
        if self.headroom_frac is None:
            return
        needy = [s for s, t in enumerate(self.trees)
                 if smtree.needs_headroom(t, frac=self.headroom_frac)]
        if not needy:
            return
        trees = list(self.trees)
        for s in needy:
            trees[s] = smtree.grow_tree(trees[s])
        for b, t in zip(self.batchers, trees):
            b.tree = t
        # growth is host-side: drop the mesh-resident stacked form, the
        # next collective apply restacks from the fresh shards
        self._stacked = None
        self._shard_nodes = [t.max_nodes for t in trees]
        self.n_grows += len(needy)

    def _apply_host(self, ops, xs, oids, owner) -> BatchResult:
        """Host-centric path: route rows to their shard's batcher.

        Cohorts are cut on the *global* log — the same boundaries the mesh
        path's collectives use — so escalation interleaves with the scans
        at identical points in every shard's op sequence and the two modes
        stay bitwise-interchangeable (a shard-local cut would let one
        shard's scan run ahead of another shard's repeat-induced
        boundary)."""
        statuses = np.zeros(len(ops), np.int32)
        n_fast = n_esc = n_split = n_merge = 0
        cohorts = cut_cohorts(oids)
        for start, end in cohorts:
            for cs in range(start, end, self.max_batch):
                ce = min(cs + self.max_batch, end)
                for s in range(self.n_shards):
                    rows = cs + np.nonzero(owner[cs:ce] == s)[0]
                    if not len(rows):
                        continue
                    r = self.batchers[s].apply(ops[rows], xs[rows],
                                               oids[rows])
                    statuses[rows] = r.statuses
                    n_fast += r.n_fast
                    n_esc += r.n_escalated
                    n_split += r.n_split
                    n_merge += r.n_merge
        return BatchResult(statuses, n_fast, n_esc, len(cohorts), n_split,
                           n_merge)

    def _apply_mesh(self, ops, xs, oids, owner) -> BatchResult:
        """Mesh-resident path: cut-cohorts → one collective apply + one
        collective split pass + one collective merge pass per cohort →
        psum'd statuses; host escalation only for the residual rows (a
        blocked split chain — ring exhaustion — which ahead-of-time
        headroom growth makes a cold assert-path)."""
        from repro.core import distributed as dist
        if self._stacked is None:
            self._stacked = dist.stack_trees([b.tree for b in self.batchers])
        forest = self._stacked
        statuses = np.zeros(len(ops), np.int32)
        n_fast = n_esc = n_split = n_merge = 0
        cohorts = cut_cohorts(oids)
        for start, end in cohorts:
            for cs in range(start, end, self.max_batch):
                ce = min(cs + self.max_batch, end)
                c_ops, c_xs, c_oids, c_owner = _pad_cohort(
                    ops[cs:ce], xs[cs:ce], oids[cs:ce], owner[cs:ce],
                    self.max_batch)
                forest, st = dist.forest_apply_mutations(
                    forest, self.mesh, c_ops, c_xs, c_oids, c_owner,
                    axis=self.axis)
                st = np.array(jax.device_get(st))[:ce - cs]
                ovf = (np.nonzero((st == smtree.ST_OVERFLOW)
                                  & (c_ops[:ce - cs] == OP_INSERT))[0]
                       if self.device_splits else np.array([], np.int64))
                # power-of-two-ladder split collectives (bounded jit cache
                # per forest geometry, no padded NOP steps — a pad costs
                # as much as a real split); stopping at the first
                # still-blocked chunk is conservative but bitwise-safe —
                # the host control plane produces the identical split for
                # any row the device would have absorbed
                c0 = 0
                for w in smtree.split_chunks(len(ovf)):
                    chunk = ovf[c0:c0 + w]
                    c0 += w
                    k = len(chunk)
                    k_ops = np.full(w, smtree.OP_NOP, np.int32)
                    k_ops[:k] = OP_INSERT
                    k_xs = np.zeros((w, xs.shape[1]), np.float32)
                    k_xs[:k] = c_xs[chunk]
                    k_oids = np.full(w, -1, np.int32)
                    k_oids[:k] = c_oids[chunk]
                    k_owner = np.zeros(w, np.int32)
                    k_owner[:k] = c_owner[chunk]
                    forest, k_st = dist.forest_apply_splits(
                        forest, self.mesh, k_ops, k_xs, k_oids, k_owner,
                        axis=self.axis)
                    k_st = np.asarray(jax.device_get(k_st))[:k]
                    st[chunk[k_st == smtree.ST_SPLIT]] = smtree.ST_SPLIT
                    if (k_st == smtree.ST_OVERFLOW).any():
                        break
                # merge collectives: underflow rows resolve on device only
                # once every overflow row has (the host reference resolves
                # all overflows before any underflow; a residual blocked
                # split must reach the host first to keep the structure-
                # edit order — and the bitwise tree — identical)
                unf = (np.nonzero((st == smtree.ST_UNDERFLOW)
                                  & (c_ops[:ce - cs] == OP_DELETE))[0]
                       if (self.device_merges
                           and not (st == smtree.ST_OVERFLOW).any())
                       else np.array([], np.int64))
                # unlike the split ladder there is no blocked-chunk
                # decision between merge dispatches (merges never
                # allocate), so every chunk is dispatched back-to-back
                # and the statuses sync once — one host round-trip per
                # cohort instead of one per chunk
                c0 = 0
                pending = []
                for w in smtree.merge_chunks(len(unf)):
                    chunk = unf[c0:c0 + w]
                    c0 += w
                    k = len(chunk)
                    k_ops = np.full(w, smtree.OP_NOP, np.int32)
                    k_ops[:k] = OP_DELETE
                    k_oids = np.full(w, -1, np.int32)
                    k_oids[:k] = c_oids[chunk]
                    k_owner = np.zeros(w, np.int32)
                    k_owner[:k] = c_owner[chunk]
                    forest, k_st = dist.forest_apply_merges(
                        forest, self.mesh, k_ops, k_oids, k_owner,
                        axis=self.axis)
                    pending.append((chunk, k, k_st))
                for chunk, k, k_st in pending:
                    st[chunk] = np.asarray(jax.device_get(k_st))[:k]
                esc = np.isin(st, (smtree.ST_OVERFLOW, smtree.ST_UNDERFLOW))
                n_esc += int(esc.sum())
                n_split += int((st == smtree.ST_SPLIT).sum())
                n_merge += int((st == smtree.ST_MERGE).sum())
                n_fast += int((st == smtree.ST_APPLIED).sum())
                st[np.isin(st, (smtree.ST_SPLIT, smtree.ST_MERGE))] = \
                    smtree.ST_APPLIED
                if esc.any():
                    forest = self._escalate_mesh(
                        forest, st, ops[cs:ce], xs[cs:ce], oids[cs:ce],
                        owner[cs:ce])
                statuses[cs:ce] = st
        self._stacked = forest
        return BatchResult(statuses, n_fast, n_esc, len(cohorts), n_split,
                           n_merge)

    def _escalate_mesh(self, forest, st, ops, xs, oids, owner):
        """Unstack only to run the host control plane on the shards that
        still hold unresolved rows, then restack (the rare path)."""
        from repro.core import distributed as dist
        trees = dist.unstack_forest(forest, max_nodes=self._shard_nodes)
        esc = np.nonzero(np.isin(st, (smtree.ST_OVERFLOW,
                                      smtree.ST_UNDERFLOW)))[0]
        obs.record_event("stream.host_escalation", n_rows=int(len(esc)))
        for s in sorted(set(int(owner[i]) for i in esc)):
            rows = np.array([i for i in esc if owner[i] == s])
            sub = st[rows].copy()
            trees[s] = escalate_rows(trees[s], sub, ops[rows], xs[rows],
                                     oids[rows])
            st[rows] = sub
        self._shard_nodes = [t.max_nodes for t in trees]
        return dist.stack_trees(trees)

    def insert_batch(self, xs, oids, **kw) -> BatchResult:
        ops, xs, oids = _mutation_log(xs, oids, OP_INSERT)
        return self.apply(ops, xs, oids, **kw)

    def delete_batch(self, xs, oids, **kw) -> BatchResult:
        ops, xs, oids = _mutation_log(xs, oids, OP_DELETE)
        return self.apply(ops, xs, oids, **kw)

    # -- queries (host-side scatter-gather; mesh serving uses forest_knn) --
    def knn(self, queries, *, k: int = 8, max_frontier: int = 64):
        """Global kNN over a *pinned* epoch's shards: per-shard cohort
        descent + host top-k merge.  Returns (dists [b, k], ids [b, k]).
        The pin (``EpochManager.reading``) keeps the version resident for
        the whole descent even if a concurrent writer publishes and retires
        epochs mid-query."""
        with self.epochs.reading() as trees:
            ds, ids = [], []
            on = obs.enabled()
            for t in trees:
                if on and obs.want_level_stats():
                    res, pruned = smtree.knn(t, queries, k=k,
                                             max_frontier=max_frontier,
                                             level_stats=True)
                    obs.observe_query_result(res, pruned)
                else:
                    res = smtree.knn(t, queries, k=k,
                                     max_frontier=max_frontier)
                ds.append(np.asarray(res.dists))
                ids.append(np.asarray(res.ids))
        d = np.concatenate(ds, axis=1)
        i = np.concatenate(ids, axis=1)
        order = np.argsort(d, axis=1, kind="stable")[:, :k]
        return np.take_along_axis(d, order, 1), np.take_along_axis(i, order, 1)

    # -- maintenance -------------------------------------------------------
    def maintenance(self, *, log: bool = True) -> bool:
        """Bounded background repair; returns True when repair work ran.

        ``stop_world`` mode: detect skew and rebuild the touched shards in
        one pass (the original behaviour).  ``incremental`` mode: when a
        migration plan is active, execute exactly one bounded step;
        otherwise consult the trigger and, when it fires, record the full
        deterministic plan in the WAL and execute its first step.  At most
        one step per call keeps the publish-time pause bounded regardless
        of how deep the skew is — callers (the front-end mutation daemon,
        the drill loops) invoke this once per mutation batch."""
        if self._migration is not None:
            self._migration_step(log=log)
            return True
        stats = collect_stats(self.trees)
        if obs.enabled():
            obs.gauge("rebalance.skew").set(stats.skew)
        if not needs_rebalance(stats, max_skew=self.max_skew,
                               min_objects=self.min_objects,
                               free_floor=self.free_floor):
            return False
        seed = (self.wal.next_seq if self.wal is not None
                else self.n_rebalances)
        if self.rebalance_mode == "stop_world":
            self._run_rebalance(int(seed), log=log)
            return True
        plan = plan_migration(self.trees, seed=int(seed),
                              step_objects=self.migration_step_objects)
        if not plan.steps:
            return False
        if log and self.wal is not None:
            self.wal.append_migration_plan(plan.to_params())
        self._install_migration(plan)
        self._migration_step(log=log)
        return True

    def _install_migration(self, plan: MigrationPlan, *,
                           next_step: int = 0) -> None:
        if self._migration is not None:
            raise ValueError("migration plan installed while another is "
                             "still active (corrupt WAL or snapshot?)")
        self._migration = {"plan": plan, "next": int(next_step)}
        obs.record_event("stream.migration_plan", seed=plan.seed,
                         steps=len(plan.steps), objects=plan.total)

    @property
    def migration_active(self) -> bool:
        return self._migration is not None

    def _extract(self, donor: int, oids: np.ndarray):
        """(vecs, found) for ids on the donor shard.  Mesh mode gathers
        through the owner-routed collective — tree pages stay device-
        resident, only the [m, dim] vectors come back — padded to the
        plan's step width so the jit cache holds one entry per forest
        geometry."""
        if self.mesh is not None:
            from repro.core import distributed as dist
            if self._stacked is None:
                self._stacked = dist.stack_trees(
                    [b.tree for b in self.batchers])
            w = max(self.migration_step_objects, len(oids))
            p_oids = np.full(w, -1, np.int32)
            p_oids[:len(oids)] = oids
            p_owner = np.full(w, -1, np.int32)
            p_owner[:len(oids)] = donor
            vecs, found = dist.forest_extract_objects(
                self._stacked, self.mesh, p_oids, p_owner, axis=self.axis)
            return (np.asarray(jax.device_get(vecs))[:len(oids)],
                    np.asarray(jax.device_get(found))[:len(oids)])
        vecs, found = smtree.extract_objects(self.batchers[donor].tree, oids)
        return np.asarray(vecs), np.asarray(found)

    def _migration_step(self, *, log: bool, expect: int | None = None) -> int:
        """Execute one bounded move from the active plan: extract the
        step's still-donor-owned objects and re-apply them as a normal
        delete-on-donor / insert-on-receiver conflict-free cohort pair
        through the standard apply path, then publish exactly one epoch.
        Readers pinned to the previous epoch see each object on the donor;
        the new epoch shows it on the receiver — never twice, never zero
        times.  Returns the number of objects re-homed."""
        mig = self._migration
        if mig is None:
            raise ValueError("no active migration plan")
        idx = mig["next"]
        if expect is not None and expect != idx:
            raise ValueError(
                f"WAL migration step {expect} does not match resume "
                f"position {idx} (truncated or reordered log)")
        plan: MigrationPlan = mig["plan"]
        step = plan.steps[idx]
        if log and self.wal is not None:
            self.wal.append_migration_step({"seed": plan.seed, "step": idx})
        t0 = time.perf_counter()
        # ids may have been deleted or re-routed since planning: move only
        # those still owned by the donor.  The owner map evolves
        # identically under replay, so the filter is deterministic.
        oids = np.asarray([o for o in step.oids
                           if self.owner.get(int(o)) == step.donor],
                          np.int32)
        moved = 0
        with obs.span("mutation.migration_step", n=len(oids), step=idx):
            n = 0
            if len(oids):
                vecs, found = self._extract(step.donor, oids)
                oids, vecs = oids[found], vecs[found]
                n = len(oids)
            if n:
                ops = np.concatenate([np.full(n, OP_DELETE, np.int32),
                                      np.full(n, OP_INSERT, np.int32)])
                xs = np.concatenate([vecs, vecs]).astype(np.float32)
                both = np.concatenate([oids, oids])
                owner = np.concatenate(
                    [np.full(n, step.donor, np.int32),
                     np.full(n, step.receiver, np.int32)])
                if self.mesh is not None:
                    res = self._apply_mesh(ops, xs, both, owner)
                else:
                    res = self._apply_host(ops, xs, both, owner)
                st = res.statuses
                for i, o in enumerate(oids):
                    o = int(o)
                    if st[n + i] == smtree.ST_APPLIED:
                        self.owner[o] = step.receiver
                        moved += 1
                    elif st[i] == smtree.ST_APPLIED:
                        # delete landed but the insert did not: the object
                        # is gone from both shards — drop it from the map
                        # rather than advertise a phantom owner
                        self.owner.pop(o, None)
        mig["next"] = idx + 1
        if mig["next"] >= len(plan.steps):
            self._migration = None
            self.n_rebalances += 1
            obs.record_event("stream.migration_done", seed=plan.seed,
                             steps=len(plan.steps))
        self._ensure_headroom()
        with obs.span("mutation.publish"):
            self.epochs.publish(tuple(self.trees),
                                meta={"migration": {"seed": plan.seed,
                                                    "step": idx}})
        self.n_migration_steps += 1
        self.objects_migrated += moved
        if obs.enabled():
            obs.counter("rebalance.migration_steps_total").inc()
            obs.counter("rebalance.objects_moved_total").inc(moved)
            obs.histogram("rebalance.step_pause_s").observe(
                time.perf_counter() - t0)
        return moved

    def apply_control(self, kind: str, params: dict) -> None:
        """Replay one WAL control record through the same state machine
        the live writer ran.  ``rebalance`` records re-run the stop-world
        rebuild with the recorded seed (also the path for WALs predating
        incremental mode); ``migration_plan`` records re-install the
        recorded schedule; ``migration_step`` records re-execute the next
        bounded move, asserting the recorded index so a truncated or
        reordered log fails loudly instead of silently diverging."""
        if kind == KIND_REBALANCE:
            self._run_rebalance(int(params["seed"]), log=False)
        elif kind == KIND_MIGRATION_PLAN:
            self._install_migration(MigrationPlan.from_params(params))
        elif kind == KIND_MIGRATION_STEP:
            self._migration_step(log=False, expect=int(params["step"]))
        else:
            raise ValueError(f"unknown WAL control record kind {kind!r}")

    def _run_rebalance(self, seed: int, *, log: bool) -> None:
        obs.record_event("stream.rebalance", seed=seed)
        if log and self.wal is not None:
            self.wal.append_rebalance({"seed": seed})
        trees, moved, _ = rebalance_shards(self.trees, seed=seed)
        for b, t in zip(self.batchers, trees):
            b.tree = t
        # rebuilds happen host-side: drop the mesh-resident stacked form,
        # the next collective apply restacks from the fresh shards
        self._stacked = None
        self._shard_nodes = [t.max_nodes for t in trees]
        self.n_rebalances += 1
        self._rebuild_ownership()
        self._ensure_headroom()   # rebalance is a headroom-growth point too
        self.epochs.publish(tuple(self.trees))

    # -- snapshots ---------------------------------------------------------
    def stacked(self) -> TreeArrays:
        if self._stacked is not None:
            return self._stacked
        from repro.core.distributed import stack_trees
        return stack_trees(self.trees)

    def _extra(self) -> dict:
        proto = self.trees[0]
        mig = self._migration
        return {"kind": "smforest", "n_shards": self.n_shards,
                "capacity": proto.capacity, "dim": proto.dim,
                "metric": proto.metric, "min_fill": proto.min_fill,
                "shard_max_nodes": [t.max_nodes for t in self.trees],
                "n_rebalances": self.n_rebalances,
                "rebalance_mode": self.rebalance_mode,
                "n_migration_steps": self.n_migration_steps,
                # a snapshot taken mid-plan must carry the remaining
                # schedule: the WAL tail after this point holds only step
                # records, and replaying them needs the installed plan
                "migration": (None if mig is None else
                              {"params": mig["plan"].to_params(),
                               "next": int(mig["next"])}),
                "wal_seq": (self.wal.next_seq - 1 if self.wal is not None
                            else -1)}

    def snapshot(self, step: int | None = None) -> int:
        if self.ckpt is None:
            raise ValueError("no CheckpointManager configured")
        step = self._step if step is None else step
        self.ckpt.save(step, {"forest": self.stacked()},
                       extra=self._extra())
        self._step = step + 1
        return step

    @classmethod
    def restore(cls, ckpt_dir: str, *, wal: WriteAheadLog | None = None,
                ckpt=None, **kw) -> "StreamingForest":
        """Last snapshot + WAL tail replay (bitwise-deterministic: batch
        records re-run the batcher, control records re-run through
        ``apply_control`` — a snapshot taken mid-migration re-installs the
        remaining plan from the manifest before the tail's step records
        resume it)."""
        from repro.core.distributed import stack_trees, unstack_forest
        from repro.dist.checkpoint import read_manifest, restore_checkpoint
        manifest = read_manifest(ckpt_dir)
        extra = manifest["extra"]
        shard_nodes = extra["shard_max_nodes"]
        template = stack_trees([_tree_template(extra, max_nodes=m)
                                for m in shard_nodes])
        state, _ = restore_checkpoint(ckpt_dir, {"forest": template},
                                      step=manifest["step"])
        trees = unstack_forest(state["forest"], max_nodes=shard_nodes)
        kw.setdefault("rebalance_mode",
                      extra.get("rebalance_mode", "stop_world"))
        forest = cls(trees, wal=wal, ckpt=ckpt, **kw)
        forest._step = manifest["step"] + 1
        forest.n_rebalances = extra.get("n_rebalances", 0)
        forest.n_migration_steps = extra.get("n_migration_steps", 0)
        mig = extra.get("migration")
        if mig:
            forest._install_migration(
                MigrationPlan.from_params(mig["params"]),
                next_step=int(mig["next"]))
        if wal is not None:
            for rec in wal.replay(after_seq=extra["wal_seq"]):
                if rec.kind == KIND_BATCH:
                    forest.apply(rec.ops.astype(np.int32), rec.xs, rec.oids,
                                 log=False)
                else:
                    forest.apply_control(rec.kind, rec.params or {})
        return forest
