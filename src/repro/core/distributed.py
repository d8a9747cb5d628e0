"""Distributed SM-forest: the paper's index sharded across a device mesh.

Design (DESIGN.md §2): objects are partitioned over the mesh's 'model' axis,
one independent SM-tree shard per device (a *forest*).  Under ``shard_map``:

  * ``forest_knn`` — queries are replicated to every shard (the sharded-in
    queries are all-gathered), each shard runs the jitted local kNN over its
    subtree, and the global top-k is a k-way merge: all_gather the per-shard
    candidate sets and ``lax.top_k`` them.  One collective round-trip per
    query batch — the classic scatter-gather search fan-out.
  * ``forest_delete`` / ``forest_insert_fast`` — updates broadcast; each
    shard applies the ones that belong to it (exact-match id test for
    delete, routing rule for insert).  The SM-tree's O(h) Delete — the
    paper's contribution — is what makes *online eviction* of a live
    distributed datastore possible without a stop-the-world rebuild.

The same code drives 8 host devices in tests and the production mesh's
'model' axis in serving (kNN-LM datastore, serve/knnlm.py).
"""
from __future__ import annotations

import dataclasses
import functools
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import smtree
from repro.core.smtree import TreeArrays, bulk_build
from repro.dist.sharding import shard_map

_DATA_FIELDS = ("vecs", "radius", "pdist", "child", "oid", "valid", "count",
                "is_leaf", "alive", "parent", "pslot", "root", "n_nodes",
                "height", "free_list", "free_head")


def stack_trees(trees: list[TreeArrays]) -> TreeArrays:
    """Stack per-shard SM-trees into one forest TreeArrays with a leading
    [n_shards] axis, padding every node table to the largest shard's size.
    Padded rows are dead (``alive`` False) so no traversal touches them.
    They are also *not* in the padded shard's free ring (``free_list`` keeps
    only its pre-padding ids), so the device allocator stays conservative:
    a shard never allocates into rows that ``unstack_forest`` would slice
    away again."""
    max_nodes = max(t.max_nodes for t in trees)

    def pad_leaf(leaf, axis0_pad):
        pad = [(0, axis0_pad)] + [(0, 0)] * (leaf.ndim - 1)
        return jnp.pad(leaf, pad)

    stacked = {}
    for name in _DATA_FIELDS:
        leaves = []
        for t in trees:
            leaf = getattr(t, name)
            if leaf.ndim and leaf.shape[:1] == (t.max_nodes,):
                leaf = pad_leaf(leaf, max_nodes - t.max_nodes)
            leaves.append(leaf)
        stacked[name] = jnp.stack(leaves)
    proto = trees[0]
    return TreeArrays(capacity=proto.capacity, dim=proto.dim,
                      metric=proto.metric, max_nodes=max_nodes,
                      min_fill=proto.min_fill, **stacked)


def unstack_forest(forest: TreeArrays,
                   max_nodes: list[int] | None = None) -> list[TreeArrays]:
    """Split a stacked forest back into per-shard trees (inverse of
    ``stack_trees``).  ``max_nodes`` optionally re-slices each shard's node
    table to its original, pre-padding size (stream snapshot restore needs
    this so replay reproduces the straight-line run bitwise)."""
    n_shards = forest.root.shape[0]
    out = []
    for s in range(n_shards):
        n = forest.max_nodes if max_nodes is None else int(max_nodes[s])
        fields = {}
        for name in _DATA_FIELDS:
            leaf = getattr(forest, name)[s]
            if leaf.ndim and leaf.shape[:1] == (forest.max_nodes,):
                leaf = leaf[:n]
            fields[name] = leaf
        out.append(TreeArrays(capacity=forest.capacity, dim=forest.dim,
                              metric=forest.metric, max_nodes=n,
                              min_fill=forest.min_fill, **fields))
    return out


def build_forest_trees(X: np.ndarray, n_shards: int, *, capacity: int = 32,
                       metric: str = "d_inf",
                       seed: int = 0) -> list[TreeArrays]:
    """Partition X round-robin over ``n_shards`` (object i -> shard i mod S,
    ids global) and bulk-build one SM-tree per shard.  Mesh-free: this is
    the host-side forest the stream subsystem mutates shard-at-a-time."""
    trees = []
    for s in range(n_shards):
        idx = np.arange(s, X.shape[0], n_shards)
        trees.append(bulk_build(X[idx], ids=idx, capacity=capacity,
                                metric=metric, seed=seed + s))
    return trees


def build_forest(X: np.ndarray, mesh: Mesh, *, axis: str = "model",
                 capacity: int = 32, metric: str = "d_inf",
                 seed: int = 0) -> TreeArrays:
    """Partition X round-robin over the mesh axis and bulk-build one SM-tree
    per shard.  Returns a TreeArrays whose leaves carry a leading [n_shards]
    axis sharded over ``axis`` (ids are global)."""
    forest = stack_trees(build_forest_trees(
        X, mesh.shape[axis], capacity=capacity, metric=metric, seed=seed))
    spec = jax.tree.map(lambda _: P(axis), forest)
    return jax.device_put(forest, NamedSharding(mesh, P(axis))), spec


def place_forest(trees_or_forest, mesh: Mesh, *,
                 axis: str = "model") -> TreeArrays:
    """Make a host-side forest mesh-resident: shards sharded one-per-device
    over ``axis`` so ``forest_knn`` serves straight from HBM.

    This is the read-replica fan-out step (stream/replica.py): a follower
    restores + tails the WAL entirely on host, then each published epoch's
    shard list is placed here and queried through the same collectives as
    the leader — identical bytes, different devices.  Accepts either a
    ``list[TreeArrays]`` (stacked and padded first) or an
    already-stacked forest."""
    forest = (trees_or_forest if isinstance(trees_or_forest, TreeArrays)
              else stack_trees(list(trees_or_forest)))
    n_shards = forest.root.shape[0]
    if mesh.shape[axis] != n_shards:
        raise ValueError(
            f"mesh axis {axis!r} has {mesh.shape[axis]} devices for "
            f"{n_shards} shards (need exactly one per shard)")
    return jax.device_put(forest, NamedSharding(mesh, P(axis)))


def promote_follower(replica, mesh: Mesh, *, axis: str = "model",
                     expect: tuple[int, str] | None = None,
                     timeout: float = 30.0):
    """Bring a replayed follower into the serving mesh: the failover
    endgame after ``stream.lease.promote`` hands it the WAL.

    ``replica`` is a ``stream.replica.Replica`` (or ``ShippedReplica``)
    whose follower is a ``StreamingForest``; ``expect`` is the leader's
    last ``(seq, digest)`` digest exchange when known — the follower must
    catch up through it and match bitwise before its shards are allowed
    to serve (``DigestMismatch`` otherwise; a diverged replica joining
    the mesh would silently answer queries from a different index).
    Returns ``(placed_forest, epoch)``: the pinned epoch's shard list
    made mesh-resident via :func:`place_forest`, and the epoch number it
    came from, for the router's session-token stamping."""
    if expect is not None:
        seq, digest = expect
        replica.verify(seq, digest, timeout=timeout)
    with replica.epochs.reading(with_epoch=True) as (epoch, pinned):
        shards = list(pinned) if isinstance(pinned, (tuple, list)) \
            else [pinned]
        placed = place_forest(shards, mesh, axis=axis)
    return placed, epoch


def _local_tree(forest_slice: TreeArrays) -> TreeArrays:
    """Strip the leading length-1 shard axis inside shard_map."""
    return dataclasses.replace(
        forest_slice, **{f: getattr(forest_slice, f)[0]
                         for f in _DATA_FIELDS})


def _restack(forest_slice: TreeArrays, tree: TreeArrays) -> TreeArrays:
    """Re-add the length-1 shard axis inside shard_map (inverse of
    ``_local_tree``)."""
    return dataclasses.replace(
        forest_slice, **{f: getattr(tree, f)[None] for f in _DATA_FIELDS})


def common_static_height(forest: TreeArrays) -> int | None:
    """Concrete tree height shared by every shard, or None when shards
    disagree (the cohort descent's static unroll needs one height; unequal
    shards fall back to the per-query engine)."""
    try:
        heights = np.asarray(jax.device_get(forest.height))
    except Exception:  # noqa: BLE001 — abstract/traced forest: no fast path
        return None
    if heights.size and (heights == heights.flat[0]).all():
        return int(heights.flat[0])
    return None


# The collective callables are built once per (mesh, axis, ...) and wrapped
# in jax.jit: a shard_map closure constructed per call would re-trace and
# re-lower the whole collective on EVERY invocation — seconds of compile on
# the mutation hot path (exactly the kind of host-side stall the
# mesh-resident control plane exists to avoid).
@functools.lru_cache(maxsize=None)
def _forest_knn_fn(mesh: Mesh, axis: str, batch_axis: str | None, k: int,
                   max_frontier: int, static_height: int | None,
                   parent_prune: bool):
    in_specs = (P(axis), P(batch_axis))
    out_specs = (P(batch_axis), P(batch_axis))

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs)
    def run(forest_slice, q):
        tree = _local_tree(forest_slice)
        res = smtree.knn(tree, q, k=k, max_frontier=max_frontier,
                         static_height=static_height,
                         parent_prune=parent_prune)
        # k-way merge across shards: gather candidates, top-k
        all_d = jax.lax.all_gather(res.dists, axis)            # [S, b, k]
        all_i = jax.lax.all_gather(res.ids, axis)
        S = all_d.shape[0]
        b = q.shape[0]
        flat_d = all_d.transpose(1, 0, 2).reshape(b, S * k)
        flat_i = all_i.transpose(1, 0, 2).reshape(b, S * k)
        neg, sel = jax.lax.top_k(-flat_d, k)
        return -neg, jnp.take_along_axis(flat_i, sel, axis=1)

    return run


def forest_knn(forest: TreeArrays, mesh: Mesh, queries: jax.Array, *,
               k: int = 8, axis: str = "model", max_frontier: int = 64,
               batch_axis: str | None = None,
               parent_prune: bool | None = None):
    """Batched global kNN over the sharded forest.

    queries: [b, dim] (replicated or sharded over ``batch_axis``).
    Returns (dists [b, k], ids [b, k]) with globally merged results.

    The concrete per-shard heights are read *before* entering shard_map and
    plumbed through as a static argument, so each shard runs the PR-2
    cohort fast path (fused frontier scoring) instead of the per-query
    fallback whenever all shards share one height — which balanced
    round-robin bulk builds guarantee in practice.  ``parent_prune`` is
    resolved here (None → ``REPRO_PARENT_PRUNE``) and baked into the
    cached collective, so the per-shard descents run the parent-distance
    pre-filter with bitwise-identical merged results either way
    (DESIGN.md §17).
    """
    static_height = common_static_height(forest)
    return _forest_knn_fn(mesh, axis, batch_axis, k, max_frontier,
                          static_height,
                          smtree._resolve_parent_prune(parent_prune)
                          )(forest, queries)


@functools.lru_cache(maxsize=None)
def _forest_delete_fn(mesh: Mesh, axis: str):
    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(axis), P(None), P(None)),
                       out_specs=(P(axis), P(None)))
    def run(forest_slice, xs, oids):
        tree = _local_tree(forest_slice)

        def body(carry, xo):
            tree = carry
            x, oid = xo
            new_tree, found, underflow, _ = smtree.delete_fast(tree, x, oid)
            # keep the pre-delete tree if underflow (host path resolves later)
            tree = jax.tree.map(
                lambda a, b: jnp.where(underflow, a, b), tree, new_tree)
            return tree, found & ~underflow

        tree, found = jax.lax.scan(body, tree, (xs, oids))
        found = jax.lax.psum(found.astype(jnp.int32), axis) > 0
        return _restack(forest_slice, tree), found

    return run


def forest_delete(forest: TreeArrays, mesh: Mesh, xs: jax.Array,
                  oids: jax.Array, *, axis: str = "model"):
    """Broadcast a delete batch; each shard applies the ids it owns via the
    jitted no-underflow fast path (underflow fallback is host-side per shard;
    eviction workloads delete recent bulk-built entries, so fast-path hit
    rate is high — measured in benchmarks/bench_engine.py).
    Returns (forest, found_mask [n])."""
    return _forest_delete_fn(mesh, axis)(forest, xs, oids)


def _validate_cohort(oids) -> None:
    """Host-side cohort-contract check: unique, non-negative oids.  Forces a
    device sync when ``oids`` lives on the mesh — which is exactly why it is
    opt-in (``validate=True``): the stream pipeline cuts cohorts host-side
    (``repro.stream.batcher.cut_cohorts``), where the contract holds by
    construction and the ids are still numpy."""
    oids_np = np.asarray(jax.device_get(oids))
    if len(np.unique(oids_np)) != len(oids_np):
        raise ValueError(
            "forest_apply_mutations requires unique oids per batch "
            "(conflict-free cohort); cut the log with "
            "repro.stream.batcher.cut_cohorts")
    if len(oids_np) and int(oids_np.min()) < 0:
        raise ValueError("negative object ids are reserved (NOP pad "
                         "sentinel)")


def forest_apply_mutations(forest: TreeArrays, mesh: Mesh, ops: jax.Array,
                           xs: jax.Array, oids: jax.Array,
                           owner: jax.Array, *, axis: str = "model",
                           validate: bool = False):
    """Broadcast a mixed insert/delete batch; each shard applies the rows it
    owns (``owner[i]`` = shard index) through the fused ``apply_mutations``
    scan in one collective step.  Non-owned rows become OP_NOP locally, so
    the psum of masked statuses reconstructs the global per-row outcome
    (ST_NOP is 0).  Returns (forest, statuses [B]).  ST_OVERFLOW rows are
    resolved by a follow-up ``forest_apply_splits`` collective (the stream
    control plane orchestrates it — repro.stream.pipeline); residual
    escalations go to the host.

    The batch must be a *conflict-free cohort* — no object id twice
    (``apply_mutations`` pre-locates delete targets against the pre-batch
    tree, which is unsound across same-id rows) and no negative ids.  Cut
    arbitrary logs with ``repro.stream.batcher.cut_cohorts`` first.
    ``validate=True`` re-checks the contract here at the price of a host
    round-trip per batch; it defaults off — and must stay off under jit —
    because the check syncs ``oids`` back to the host on the hot path."""
    if validate:
        _validate_cohort(oids)
    return _forest_apply_mutations_fn(mesh, axis)(
        forest, jnp.asarray(ops, jnp.int32), jnp.asarray(xs, jnp.float32),
        jnp.asarray(oids, jnp.int32), jnp.asarray(owner, jnp.int32))


@functools.lru_cache(maxsize=None)
def _forest_apply_mutations_fn(mesh: Mesh, axis: str):
    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(axis), P(None), P(None), P(None), P(None)),
                       out_specs=(P(axis), P(None)))
    def run(forest_slice, ops, xs, oids, owner):
        tree = _local_tree(forest_slice)
        me = jax.lax.axis_index(axis)
        mine = owner == me
        local_ops = jnp.where(mine, ops, smtree.OP_NOP)
        # splits/merges=False: statuses are abstract here; the split and
        # merge passes run as their own collectives (forest_apply_splits /
        # forest_apply_merges) over the compacted escalation rows
        tree, status = smtree.apply_mutations(tree, local_ops, xs, oids,
                                              donate=False, splits=False,
                                              merges=False)
        status = jax.lax.psum(jnp.where(mine, status, 0), axis)
        return _restack(forest_slice, tree), status

    return run


def forest_apply_splits(forest: TreeArrays, mesh: Mesh, ops: jax.Array,
                        xs: jax.Array, oids: jax.Array, owner: jax.Array, *,
                        axis: str = "model"):
    """On-mesh split collective: resolve a compacted batch of ST_OVERFLOW
    insert rows (in log order, owner-routed like ``forest_apply_mutations``)
    through each shard's device split pass (``smtree.apply_splits``).
    Returns (forest, statuses [K]): ST_SPLIT where a shard absorbed the row
    on device, ST_OVERFLOW where it still needs the host control plane.
    Tree pages never leave HBM; only the status vector does."""
    return _forest_apply_splits_fn(mesh, axis)(
        forest, jnp.asarray(ops, jnp.int32), jnp.asarray(xs, jnp.float32),
        jnp.asarray(oids, jnp.int32), jnp.asarray(owner, jnp.int32))


@functools.lru_cache(maxsize=None)
def _forest_apply_splits_fn(mesh: Mesh, axis: str):
    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(axis), P(None), P(None), P(None), P(None)),
                       out_specs=(P(axis), P(None)))
    def run(forest_slice, ops, xs, oids, owner):
        tree = _local_tree(forest_slice)
        me = jax.lax.axis_index(axis)
        mine = owner == me
        local_ops = jnp.where(mine, ops, smtree.OP_NOP)
        tree, status = smtree.apply_splits(tree, local_ops, xs, oids,
                                           donate=False)
        status = jax.lax.psum(jnp.where(mine, status, 0), axis)
        return _restack(forest_slice, tree), status

    return run


def forest_apply_merges(forest: TreeArrays, mesh: Mesh, ops: jax.Array,
                        oids: jax.Array, owner: jax.Array, *,
                        axis: str = "model"):
    """On-mesh merge collective: resolve a compacted batch of ST_UNDERFLOW
    delete rows (in log order, owner-routed like ``forest_apply_splits``)
    through each shard's device merge pass (``smtree.apply_merges``).
    Returns (forest, statuses [K]): ST_MERGE where a shard absorbed the
    row on device (merges never allocate, so no row ever blocks).  Tree
    pages never leave HBM; only the status vector does.  No ``xs``: the
    merge machinery locates targets by object id alone, exactly like the
    host's ``delete_with_merge``."""
    return _forest_apply_merges_fn(mesh, axis)(
        forest, jnp.asarray(ops, jnp.int32), jnp.asarray(oids, jnp.int32),
        jnp.asarray(owner, jnp.int32))


@functools.lru_cache(maxsize=None)
def _forest_apply_merges_fn(mesh: Mesh, axis: str):
    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(axis), P(None), P(None), P(None)),
                       out_specs=(P(axis), P(None)))
    def run(forest_slice, ops, oids, owner):
        tree = _local_tree(forest_slice)
        me = jax.lax.axis_index(axis)
        mine = owner == me
        local_ops = jnp.where(mine, ops, smtree.OP_NOP)
        tree, status = smtree.apply_merges(tree, local_ops, oids,
                                           donate=False)
        status = jax.lax.psum(jnp.where(mine, status, 0), axis)
        return _restack(forest_slice, tree), status

    return run


def forest_extract_objects(forest: TreeArrays, mesh: Mesh, oids: jax.Array,
                           owner: jax.Array, *, axis: str = "model"):
    """Owner-routed vector gather across the mesh forest: for each
    requested id, the shard named by ``owner[i]`` looks it up locally
    (``smtree.extract_objects``) and the psum of masked rows reconstructs
    the replicated result.  Returns (vecs [B, dim] f32, found [B] bool);
    rows absent from their owner shard (or with ``owner`` -1 pads) come
    back zero-filled with ``found`` False.

    This is the read half of a mesh migration step: tree pages stay
    device-resident — only the [B, dim] gather leaves the shards — so the
    streaming forest can re-emit the rows as a delete-on-donor /
    insert-on-receiver cohort without unstacking anything to the host."""
    return _forest_extract_objects_fn(mesh, axis)(
        forest, jnp.asarray(oids, jnp.int32), jnp.asarray(owner, jnp.int32))


@functools.lru_cache(maxsize=None)
def _forest_extract_objects_fn(mesh: Mesh, axis: str):
    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(axis), P(None), P(None)),
                       out_specs=(P(None), P(None)))
    def run(forest_slice, oids, owner):
        tree = _local_tree(forest_slice)
        me = jax.lax.axis_index(axis)
        mine = owner == me
        # non-owned rows become the -1 pad sentinel, which never matches
        local_oids = jnp.where(mine, oids, -1)
        vecs, found = smtree.extract_objects(tree, local_oids)
        found = found & mine
        vecs = jnp.where(found[:, None], vecs, 0.0)
        return (jax.lax.psum(vecs, axis),
                jax.lax.psum(found.astype(jnp.int32), axis) > 0)

    return run


def brute_force_knn(X: jax.Array, mesh: Mesh, queries: jax.Array, *,
                    k: int = 8, axis: str = "model", metric: str = "d_inf"):
    """Flat sharded scan baseline (the paper's 'sequential scan' line) using
    the Pallas distance kernel per shard."""
    from repro.kernels import ops

    @functools.partial(shard_map, mesh=mesh, in_specs=(P(axis), P(None)),
                       out_specs=(P(None), P(None)))
    def run(xs, q):
        d = ops.pairwise_distance(q, xs, metric=metric)       # [b, n_loc]
        neg, idx = jax.lax.top_k(-d, k)
        size = xs.shape[0]
        me = jax.lax.axis_index(axis)
        gids = idx + me * size
        all_d = jax.lax.all_gather(-neg, axis)                # [S, b, k]
        all_i = jax.lax.all_gather(gids, axis)
        S, b, _ = all_d.shape
        fd = all_d.transpose(1, 0, 2).reshape(b, S * k)
        fi = all_i.transpose(1, 0, 2).reshape(b, S * k)
        neg2, sel = jax.lax.top_k(-fd, k)
        return -neg2, jnp.take_along_axis(fi, sel, axis=1)

    return run(X, queries)
