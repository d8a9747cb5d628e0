"""TPU-native SM-tree engine in JAX.

The paper's pointer-machine structure is re-expressed as a fixed-capacity
structure-of-arrays (one row per node / one lane per entry) so traversal is
frontier-at-a-time: every level of the descent scores *all entries of all
frontier nodes* of *all queries in the cohort* in one batched metric
evaluation, prunes with the triangle inequality, and compacts the surviving
children into the next frontier with a fixed-size top-F selection.

On TPU the per-level scoring runs through the fused Pallas frontier kernel
(kernels/frontier.py): frontier node ids are scalar-prefetched, node pages
stream HBM→VMEM double-buffered, and distances + d_max bounds + prune scores
are emitted in one VMEM-resident pass.  ``REPRO_FRONTIER_IMPL=xla`` is the
escape hatch forcing the plain-XLA gather path (bitwise identical results —
the shared fixed-association metric in core/metric.py guarantees it);
``=perquery`` selects the legacy vmap(per-query) engine kept as a benchmark
baseline.  On non-TPU backends the default is the XLA path, and
``=pallas`` runs the kernel through the Pallas interpreter (CI parity).

Roles (mirrors production vector-store engines):
  * data plane  — ``knn``, ``range_search``, ``insert`` fast path, ``delete``
    fast path: pure jitted functions on the ``TreeArrays`` pytree
    (lax.while_loop / fori_loop control flow, donate-friendly).
  * control plane — node splits/merges (amortised-rare structure edits):
    host-side numpy on the same arrays, sharing the exact split policy of the
    paper-faithful reference implementation (core/split.py).

The SM-tree invariant r(entry) = max(pdist_child + r_child) is what makes the
functional formulation possible at all: radius maintenance is a *fold over
the descent path*, no subtree walks (DESIGN.md §2).

All arrays are padded to static bounds (max_nodes, capacity, max height, max
frontier) — required for jit and exactly analogous to page-file layout.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.metric import get_metric, make_metric

MAX_HEIGHT = 16          # supports capacity^15 objects; plenty
_INF = jnp.inf
# the SM radius is a sum of f32-rounded terms; a directly computed distance
# can exceed the folded bound by an ulp — pad the prune test so borderline
# subtrees are visited rather than (incorrectly) pruned
_EPS = 1e-5
# leaf-level chunk count in the cohort descent: the frontier is scored in
# this many sequential slices with a top-k merge between them, so r_q
# tightens toward the true kth-NN distance before the far leaves are
# scored (see _knn_cohort).  Purely a schedule knob — results are exact
# kNN for any value >= 1.
_LEAF_CHUNKS = 4
# named scope of the descent's top-k compactions (the per-level frontier
# cut and the leaf-chunk merges): it lands in the ops' op_name metadata,
# so a profile can total their device time
COMPACT_SCOPE = "descent.compact"
# named scope of the parent-distance bound a level computes before its
# metric evaluations (its gathers and its top-k, DESIGN.md §17)
BOUND_SCOPE = "descent.bound"


# --------------------------------------------------------------------------
# Tree state
# --------------------------------------------------------------------------
@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["vecs", "radius", "pdist", "child", "oid",
                                "valid", "count", "is_leaf", "alive",
                                "parent", "pslot", "root", "n_nodes",
                                "height", "free_list", "free_head"],
                   meta_fields=["capacity", "dim", "metric", "max_nodes",
                                "min_fill"])
@dataclasses.dataclass
class TreeArrays:
    vecs: jax.Array      # [N, cap, dim] f32 — entry reference values
    radius: jax.Array    # [N, cap] f32 — covering radii (0 at leaf entries)
    pdist: jax.Array     # [N, cap] f32 — d(entry, parent routing object)
    child: jax.Array     # [N, cap] i32 — child node id; -1 for leaf entries
    oid: jax.Array       # [N, cap] i32 — object id at leaf entries; -1 else
    valid: jax.Array     # [N, cap] bool
    count: jax.Array     # [N] i32
    is_leaf: jax.Array   # [N] bool
    alive: jax.Array     # [N] bool — allocated node slots (free-list support)
    parent: jax.Array    # [N] i32 — parent node id (-1 at root)
    pslot: jax.Array     # [N] i32 — slot within parent pointing here
    root: jax.Array      # [] i32
    n_nodes: jax.Array   # [] i32
    height: jax.Array    # [] i32
    free_list: jax.Array # [N] i32 — dead node ids, packed descending; -1 pad
    free_head: jax.Array # [] i32 — ring occupancy: free_list[:free_head] live
    capacity: int
    dim: int
    metric: str
    max_nodes: int
    min_fill: int

    @property
    def n_objects(self) -> int:
        # dead (freed) node slots may keep stale valid bits — e.g. a batched
        # merge that marks the donor dead on device without scrubbing its
        # rows — so the alive mask must gate the count
        live = self.alive[..., None] & self.is_leaf[..., None] & self.valid
        return int(jnp.sum(live))

    @property
    def n_free_nodes(self) -> int:
        """Unallocated node slots (free-list headroom for splits)."""
        return int(jnp.sum(~self.alive))


def packed_free_list(alive) -> tuple[np.ndarray, np.ndarray]:
    """Device free-ring representation of the dead node set.

    ``free_list[:free_head]`` holds the dead node ids in **descending**
    order, so the top of the stack (``free_list[free_head-1]``) is the
    *lowest* free id — popping on device allocates exactly the node the
    host control plane's ``_HostView.alloc`` (lowest free index) would
    pick, which is what keeps device splits bitwise-equal to host splits.
    Descending order is maintained on both ends: device pops scrub the
    top slot, device frees (the merge pass) insert at the sorted position
    (``_push_free``), and the rare host escalation recomputes the ring
    wholesale here via ``to_tree`` — all three leave the identical packed
    representation, so arbitrary push/pop interleavings keep device and
    host allocation choices aligned."""
    alive = np.asarray(alive)
    free = np.nonzero(~alive)[0][::-1].astype(np.int32)
    out = np.full(alive.shape[0], -1, np.int32)
    out[:len(free)] = free
    return out, np.int32(len(free))


def empty_tree(*, dim: int, capacity: int = 32, max_nodes: int = 1024,
               metric: str = "d_inf", min_fill_frac: float = 0.4) -> TreeArrays:
    cap, N = capacity, max_nodes
    alive = np.zeros((N,), bool)
    alive[0] = True
    free_list, free_head = packed_free_list(alive)
    return TreeArrays(
        vecs=jnp.zeros((N, cap, dim), jnp.float32),
        radius=jnp.zeros((N, cap), jnp.float32),
        pdist=jnp.zeros((N, cap), jnp.float32),
        child=jnp.full((N, cap), -1, jnp.int32),
        oid=jnp.full((N, cap), -1, jnp.int32),
        valid=jnp.zeros((N, cap), bool),
        count=jnp.zeros((N,), jnp.int32),
        is_leaf=jnp.ones((N,), bool),
        alive=jnp.asarray(alive),
        parent=jnp.full((N,), -1, jnp.int32),
        pslot=jnp.full((N,), -1, jnp.int32),
        root=jnp.int32(0), n_nodes=jnp.int32(1), height=jnp.int32(1),
        free_list=jnp.asarray(free_list), free_head=jnp.asarray(free_head),
        capacity=cap, dim=dim, metric=metric, max_nodes=N,
        min_fill=max(1, math.ceil(min_fill_frac * cap)))


def _metric_eval(metric: str, q, e):
    """q: [..., d]; e: [..., d] broadcast; returns distances [...].

    Thin shim over the core/metric.py registry — the single metric
    definition shared with the numpy reference implementation and the fused
    Pallas frontier kernel, so the three call sites cannot drift."""
    try:
        fn = get_metric(metric)
    except KeyError:
        raise ValueError(metric) from None
    return fn(q, e)


# --------------------------------------------------------------------------
# Bulk build: balanced bottom-up construction
# --------------------------------------------------------------------------
# Corpora of at least this many coordinates (n x dim) compute the build's
# distances on the default device; smaller ones in numpy on the host.  The
# shared metric's fixed fold gives the same values on either.
_DEVICE_BUILD_ELEMS = 2 ** 25
_BUILD_ROWS = 2 ** 24       # coordinates a distance call holds at once


class _BuildDists:
    """The build's metric evaluations over the rows of one point set: in
    numpy for a set of fewer than ``_DEVICE_BUILD_ELEMS`` coordinates, else
    in fixed-shape jitted chunks on the default device (one compile a
    shape)."""

    def __init__(self, X: np.ndarray, metric: str):
        self.X, self.dim = X, X.shape[1]
        self.mfn = make_metric(metric, None)
        device = X.size >= _DEVICE_BUILD_ELEMS
        self.dev = jax.device_put(X) if device else None
        if device:
            mfn = self.mfn
            self._rows = jax.jit(
                lambda X, a, b: mfn(X[a], X[b]))
            self._pairs = jax.jit(
                lambda X, g: mfn(X[g][:, :, None, :], X[g][:, None, :, :]))

    def rows(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        """[m] f32: d(X[a[i]], X[b[i]])."""
        if self.dev is None:
            return np.asarray(self.mfn(self.X[a], self.X[b]))
        c = max(1, _BUILD_ROWS // self.dim)
        c = 1 << (c.bit_length() - 1)
        out = np.empty(len(a), np.float32)
        for s in range(0, len(a), c):
            m = min(c, len(a) - s)
            ia = np.zeros(c, np.int32)
            ib = np.zeros(c, np.int32)
            ia[:m], ib[:m] = a[s:s + m], b[s:s + m]
            out[s:s + m] = np.asarray(self._rows(self.dev, ia, ib))[:m]
        return out

    def pairs(self, g: np.ndarray) -> np.ndarray:
        """[G, M, M] f32: d(X[g[i, j]], X[g[i, l]]) within each row of the
        index table ``g`` [G, M]."""
        G, M = g.shape
        c = max(1, _BUILD_ROWS // (M * M * self.dim))
        out = np.empty((G, M, M), np.float32)
        for s in range(0, G, c):
            part = g[s:s + c]
            if self.dev is None:
                P = self.X[part]
                out[s:s + c] = self.mfn(P[:, :, None, :], P[:, None, :, :])
                continue
            idx = np.zeros((c, M), np.int32)
            idx[:len(part)] = part
            out[s:s + c] = np.asarray(
                self._pairs(self.dev, idx))[:len(part)]
        return out


def _bisect(dists: _BuildDists, n: int, tgt: int, min_fill: int,
            rng) -> list[np.ndarray]:
    """Partition rows 0..n-1 into groups of near-equal size via recursive
    2-pivot bisection: a group of m rows splits into
    ``parts = min(ceil(m / tgt), m // min_fill)`` parts (none when parts
    <= 1), its rows ordered by d(a, .) - d(b, .) for a random row a and b
    the row farthest from a, and cut in proportion to the parts each side
    takes.  Sizes land in [floor(n/parts), ceil(n/parts)]; the cap at
    m // min_fill keeps every group at the min-fill floor (a group below
    it would violate the non-root invariant the engine's validate() and
    the cohort descent's d_max bound rely on — e.g. n=23 at capacity 32
    must stay one node, not split 11/12).  The cap can only force parts
    to 1 when m < 2*min_fill <= capacity, so every group fits a node.

    The recursion's shape depends on the sizes alone, so the pivots are
    drawn in its preorder first and the splits then run a depth at a
    time, every group of a depth in one batch of distance evaluations.
    Groups come back in the recursion's order."""
    splits = []             # (depth, start, size, a, cut), in preorder

    def walk(start, size, depth):
        parts = min(-(-size // tgt), size // min_fill)
        if parts <= 1:
            return
        a = int(rng.integers(size))
        cut = round(size * (parts // 2) / parts)
        splits.append((depth, start, size, a, cut))
        walk(start, cut, depth + 1)
        walk(start + cut, size - cut, depth + 1)

    walk(0, n, 0)
    perm = np.arange(n)
    cuts = [0, n]
    for depth in range(max((sp[0] for sp in splits), default=-1) + 1):
        level = [sp[1:] for sp in splits if sp[0] == depth]
        starts = np.array([st for st, _, _, _ in level])
        sizes = np.array([m for _, m, _, _ in level])
        seg = np.repeat(np.arange(len(level)), sizes)
        offs = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        pos = np.repeat(starts - offs, sizes) + np.arange(len(seg))
        rows = perm[pos]
        piv_a = perm[starts + np.array([a for _, _, a, _ in level])]
        da = dists.rows(piv_a[seg], rows)
        # b: the first row of its group at the largest d(a, .)
        top = np.maximum.reduceat(da, offs)
        hit = np.flatnonzero(da == top[seg])
        _, first = np.unique(seg[hit], return_index=True)
        db = dists.rows(rows[hit[first]][seg], rows)
        # rows in order of d(a, .) - d(b, .), closest-to-a first, within
        # each group (lexsort is stable)
        perm[pos] = rows[np.lexsort((da - db, seg))]
        cuts += [st + c for st, _, _, c in level]
    cuts = np.unique(cuts)
    return [perm[a:b] for a, b in zip(cuts[:-1], cuts[1:])]


def _medoids(dists: _BuildDists, groups: list[np.ndarray],
             extra: np.ndarray | None = None):
    """(medoid position, distances from it) of each group: the row whose
    largest d(row, .) (+ ``extra`` of the other row, over the same index
    space) is smallest, the first such."""
    M = max(len(g) for g in groups)
    table = np.zeros((len(groups), M), np.int64)
    valid = np.zeros((len(groups), M), bool)
    for i, g in enumerate(groups):
        table[i, :len(g)] = g
        valid[i, :len(g)] = True
    D = dists.pairs(table)
    E = D if extra is None else D + extra[table][:, None, :]
    far = np.where(valid[:, None, :], E, -np.inf).max(axis=2)
    mi = np.where(valid, far, np.inf).argmin(axis=1)
    return mi, [D[i, m, :len(g)] for i, (m, g) in
                enumerate(zip(mi, groups))]


def bulk_build(X: np.ndarray, ids: np.ndarray | None = None, *,
               capacity: int = 32, metric: str = "d_inf",
               fill_frac: float = 0.7, min_fill_frac: float = 0.4,
               seed: int = 0, slack: float = 1.5) -> TreeArrays:
    """Construct a valid SM-tree over X [n, d] (balanced recursive-bisection
    grouping, medoid routing objects, exact SM radii).  O(n log n) distance
    evaluations, batched a recursion depth (or a chunk of groups) at a
    time, on the default device for corpora of ``_DEVICE_BUILD_ELEMS``
    coordinates or more (the same values as on the host)."""
    X = np.asarray(X, np.float32)
    n, dim = X.shape
    ids = np.arange(n, dtype=np.int64) if ids is None else np.asarray(ids)
    target = max(2, int(capacity * fill_frac))
    min_fill = max(1, math.ceil(min_fill_frac * capacity))
    rng = np.random.default_rng(seed)

    # --- leaves ---
    dists = _BuildDists(X, metric)
    leaf_groups = _bisect(dists, n, target, min_fill, rng)

    # node table accumulators
    nodes: list[dict] = []

    # build leaf nodes
    level_nodes = []   # (node_id, routing_vec, covering_radius)
    for g, mi, d_to_m in zip(leaf_groups, *_medoids(dists, leaf_groups)):
        P = X[g]
        nid = len(nodes)
        nodes.append(dict(is_leaf=True, vecs=P, radius=np.zeros(len(g)),
                          pdist=d_to_m, oid=ids[g], child=np.full(len(g), -1)))
        level_nodes.append((nid, P[mi], float(d_to_m.max())))
    del dists

    height = 1
    while len(level_nodes) > 1:
        height += 1
        routing = np.stack([v for _, v, _ in level_nodes])
        radii = np.array([r for _, _, r in level_nodes])
        nids = np.array([i for i, _, _ in level_nodes])
        rdists = _BuildDists(routing, metric)
        parent_groups = _bisect(rdists, len(level_nodes), target, min_fill,
                                rng)
        next_level = []
        for g, mi, d_to_m in zip(parent_groups,
                                 *_medoids(rdists, parent_groups, radii)):
            P = routing[g]
            rg = radii[g]
            nid = len(nodes)
            nodes.append(dict(is_leaf=False, vecs=P, radius=rg, pdist=d_to_m,
                              oid=np.full(len(g), -1), child=nids[g]))
            next_level.append((nid, P[mi], float((d_to_m + rg).max())))
        level_nodes = next_level

    root = level_nodes[0][0]
    N = max(16, int(len(nodes) * slack))
    t = empty_tree(dim=dim, capacity=capacity, max_nodes=N, metric=metric,
                   min_fill_frac=min_fill_frac)
    vecs = np.zeros((N, capacity, dim), np.float32)
    radius = np.zeros((N, capacity), np.float32)
    pdist = np.zeros((N, capacity), np.float32)
    child = np.full((N, capacity), -1, np.int32)
    oid = np.full((N, capacity), -1, np.int32)
    valid = np.zeros((N, capacity), bool)
    count = np.zeros((N,), np.int32)
    is_leaf = np.ones((N,), bool)
    parent = np.full((N,), -1, np.int32)
    pslot = np.full((N,), -1, np.int32)
    alive = np.zeros((N,), bool)
    alive[:len(nodes)] = True
    for i, nd in enumerate(nodes):
        m = len(nd["oid"])
        assert m <= capacity, (m, capacity)
        vecs[i, :m] = nd["vecs"]
        radius[i, :m] = nd["radius"]
        pdist[i, :m] = nd["pdist"]
        child[i, :m] = nd["child"]
        oid[i, :m] = nd["oid"]
        valid[i, :m] = True
        count[i] = m
        is_leaf[i] = nd["is_leaf"]
        if not nd["is_leaf"]:
            for s, c in enumerate(nd["child"]):
                parent[c] = i
                pslot[c] = s
    free_list, free_head = packed_free_list(alive)
    return dataclasses.replace(
        t, vecs=jnp.asarray(vecs), radius=jnp.asarray(radius),
        pdist=jnp.asarray(pdist), child=jnp.asarray(child),
        oid=jnp.asarray(oid), valid=jnp.asarray(valid),
        count=jnp.asarray(count), is_leaf=jnp.asarray(is_leaf),
        alive=jnp.asarray(alive), parent=jnp.asarray(parent),
        pslot=jnp.asarray(pslot),
        root=jnp.int32(root), n_nodes=jnp.int32(len(nodes)),
        height=jnp.int32(height),
        free_list=jnp.asarray(free_list), free_head=jnp.asarray(free_head))


# --------------------------------------------------------------------------
# Batched queries (jitted data plane)
# --------------------------------------------------------------------------
@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["dists", "ids", "page_hits", "dist_evals",
                                "overflow"], meta_fields=[])
@dataclasses.dataclass
class QueryResult:
    dists: jax.Array     # [b, k] (inf-padded)
    ids: jax.Array       # [b, k] (-1-padded)
    page_hits: jax.Array # [b] nodes visited
    dist_evals: jax.Array# [b] metric evaluations
    overflow: jax.Array  # [b] bool — frontier truncated (result approximate)


_IMPLS = ("pallas", "xla", "perquery")


def _resolve_impl(impl: str | None) -> str:
    """Resolve the frontier-scoring implementation.

    None → the ``REPRO_FRONTIER_IMPL`` env var (default 'auto': the fused
    Pallas kernel on TPU, the XLA gather path elsewhere).  On non-TPU
    backends 'pallas' means the interpret-mode kernel — identical code,
    exercised by CPU CI."""
    if impl is None:
        impl = os.environ.get("REPRO_FRONTIER_IMPL", "auto")
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in _IMPLS:
        raise ValueError(
            f"impl must be one of {_IMPLS} or 'auto'; got {impl!r}")
    return impl


_PARENT_PRUNE_VALUES = ("auto", "0", "1")


def _resolve_parent_prune(parent_prune: bool | None) -> bool:
    """Resolve the parent-distance pre-filter toggle (DESIGN.md §17).

    None → the ``REPRO_PARENT_PRUNE`` env var ('auto'/'1' = on — the
    default, since results are bitwise identical either way; '0' = off,
    the A/B lever the benches and parity tests use).  Anything else raises
    rather than silently running unfiltered."""
    if parent_prune is not None:
        return bool(parent_prune)
    v = os.environ.get("REPRO_PARENT_PRUNE", "auto")
    if v not in _PARENT_PRUNE_VALUES:
        raise ValueError(
            f"REPRO_PARENT_PRUNE must be one of {_PARENT_PRUNE_VALUES}; "
            f"got {v!r}")
    return v != "0"


def knn(tree: TreeArrays, queries: jax.Array, *, k: int = 1,
        max_frontier: int = 64, impl: str | None = None,
        static_height: int | None = None, level_stats: bool = False,
        parent_prune: bool | None = None):
    """Batched k-NN: level-synchronous cohort descent with dynamic radius.

    queries: [b, dim].  Exact when ``overflow`` is False (frontier never
    truncated); otherwise best-effort (closest-first truncation).  ``impl``
    overrides the frontier-scoring backend (see ``_resolve_impl``).
    ``static_height`` supplies the concrete tree height in traced contexts
    (the sharded forest's shard_map) where ``tree.height`` is abstract, so
    the cohort fast path can unroll instead of falling back to the
    per-query engine.

    ``level_stats=True`` returns ``(QueryResult, pruned)`` where pruned is
    a ``(by_bound, by_parent, live_blocks)`` triple of int32 stacks —
    ``by_bound`` ``[n_internal_levels, b]`` counts entries whose d_min
    bound excluded their subtree; ``by_parent`` ``[height, b]`` counts
    entries the parent-distance pre-filter dropped *before* any metric
    eval (DESIGN.md §17; all-zero with ``parent_prune`` off, and at the
    root level, which has no parent); ``live_blocks`` ``[height, b]``
    counts the frontier kernel's grid steps whose block of slots held a
    live node (``kernels.frontier.live_blocks``; the leaf level sums its
    chunks).  It is a *static* flag: a separate jit
    cache entry that leaves the default geometry untouched
    (observability's paper counters; DESIGN.md §15).  ``pruned`` is None
    when the per-query fallback engine served the call.

    ``parent_prune`` toggles the triangle-inequality pre-filter
    ``|d(q,parent) − pdist| > r_q + r`` ahead of each level's metric eval
    (None → ``REPRO_PARENT_PRUNE`` env, default on).  Results are bitwise
    identical on or off; only ``dist_evals`` (which counts *performed*
    evaluations) changes.
    """
    queries = jnp.asarray(queries, jnp.float32)
    return _query(tree, queries, k, max_frontier, jnp.float32(_INF),
                  _resolve_impl(impl), static_height,
                  level_stats=level_stats,
                  parent_prune=_resolve_parent_prune(parent_prune))


def range_search(tree: TreeArrays, queries: jax.Array, radius: jax.Array, *,
                 max_results: int = 128, max_frontier: int = 64,
                 impl: str | None = None,
                 parent_prune: bool | None = None) -> QueryResult:
    """Batched range query: all objects within ``radius`` (per-query scalar or
    broadcast).  Returns the closest ``max_results`` matches.  The overflow
    flag is conservative: it is set whenever ``max_results`` rows are
    returned — at *exactly* ``max_results`` matches the engine cannot know no
    further object matched, so the flag reads "results may be truncated"."""
    queries = jnp.asarray(queries, jnp.float32)
    radius = jnp.broadcast_to(jnp.asarray(radius, jnp.float32),
                              (queries.shape[0],))
    res = _query(tree, queries, max_results, max_frontier, radius,
                 _resolve_impl(impl),
                 parent_prune=_resolve_parent_prune(parent_prune))
    return _range_filter(res, radius, max_results)


@functools.partial(jax.jit, static_argnames=("max_results",))
def _range_filter(res: QueryResult, radius, max_results: int) -> QueryResult:
    keep = res.dists <= radius[:, None]
    return QueryResult(jnp.where(keep, res.dists, _INF),
                       jnp.where(keep, res.ids, -1),
                       res.page_hits, res.dist_evals,
                       res.overflow | (jnp.sum(keep, 1) == max_results))


def _query(tree: TreeArrays, queries: jax.Array, k: int, F: int, r_cap,
           impl: str, static_height: int | None = None, *,
           level_stats: bool = False, parent_prune: bool = True):
    """Dispatch: the cohort engine unrolls the descent over the concrete tree
    height (leaves are all at one depth, so each level is statically either
    internal or leaf).  In traced contexts (e.g. the sharded forest's
    shard_map, where ``height`` is abstract) fall back to the per-query
    engine, which carries dynamic control flow — unless the caller plumbed
    the concrete height through as ``static_height``
    (core/distributed.py:forest_knn)."""
    if impl == "perquery":
        res = _knn_perquery(tree, queries, k, F, r_cap)
        return (res, None) if level_stats else res
    if static_height is not None:
        height = int(static_height)
    else:
        try:
            with obs.child_span("descent.height_read"):
                height = int(tree.height)
        except jax.errors.ConcretizationTypeError:
            res = _knn_perquery(tree, queries, k, F, r_cap)
            return (res, None) if level_stats else res
    interpret = jax.default_backend() != "tpu"
    return _knn_cohort(tree, queries, r_cap, k=k, F=F, height=height,
                       impl=impl, interpret=interpret,
                       level_stats=level_stats, prune=parent_prune)


def level_widths(height: int, capacity: int, F: int) -> list[int]:
    """Frontier width of each level of the cohort descent: ``w(0)=1,
    w(l+1)=min(F, w(l)*capacity)``.  A query row's kernel grid covers
    ``sum(widths)`` slots whatever the descent prunes."""
    widths = [1]
    for _ in range(height - 1):
        widths.append(min(F, widths[-1] * capacity))
    return widths


def leaf_chunks(w: int) -> list[tuple[int, int]]:
    """(start, width) of each slice the leaf level of width ``w`` is
    scored in: ``_LEAF_CHUNKS`` slices of equal width, the last one short."""
    chw = -(-w // min(_LEAF_CHUNKS, w))
    return [(c0, min(chw, w - c0)) for c0 in range(0, w, chw)]


def level_grid_steps(height: int, capacity: int, F: int,
                     dim: int) -> list[int]:
    """Frontier-kernel grid steps a query row runs at each level: one step
    a block of ``block_slots`` slots, summed over the leaf level's chunks
    (kernels/frontier.py)."""
    from repro.kernels.frontier import block_slots

    def steps(w):
        return -(-w // block_slots(w, capacity, dim))
    widths = level_widths(height, capacity, F)
    return ([steps(w) for w in widths[:-1]]
            + [sum(steps(wc) for _, wc in leaf_chunks(widths[-1]))])


def kernel_work(height: int, capacity: int, F: int, dim: int) -> dict:
    """What the frontier kernel does for a query row, as
    ``obs.observe_query_result`` counts it: the slots (``widths``) and
    grid steps (``grid_steps``) of each level, the bytes it copies for
    one live slot (``page_bytes``), and the halvings it folds a page's
    coordinates by along lanes before its transpose (``lane_stages``)."""
    from repro.kernels.frontier import lane_stages, page_bytes
    return dict(widths=level_widths(height, capacity, F),
                grid_steps=level_grid_steps(height, capacity, F, dim),
                page_bytes=page_bytes(capacity, dim),
                lane_stages=lane_stages(dim))


@functools.partial(jax.jit,
                   static_argnames=("k", "F", "height", "impl", "interpret",
                                    "level_stats", "prune"))
def _knn_cohort(tree: TreeArrays, queries: jax.Array, r_cap, *, k: int,
                F: int, height: int, impl: str, interpret: bool,
                level_stats: bool = False, prune: bool = True):
    """Level-synchronous query-cohort descent (the fast path).

    All ``b`` queries advance one level per step, sharing one fused frontier
    scoring (Pallas kernel or XLA gather) and one batched top-k compaction
    per level.  The loop is unrolled over the static tree height with
    per-level frontier widths ``w(0)=1, w(l+1)=min(F, w(l)*cap)`` — early
    levels touch only the pages that exist, and because every leaf sits at
    the same depth (balance invariant), each level is statically a pure
    internal level (bound + prune + compact) or the leaf level (candidate
    merge); the other phase's work is not emitted at all.

    Exactness argument under batched truncation: the d_max bound ``ub`` is
    the j-th smallest d + r seen so far (+_EPS, j = ceil(k / min_fill^rem),
    usually 1) — r covers the entry's whole disjoint subtree of >=
    min_fill^rem objects, so ub is a true upper bound on the kth-NN distance
    for *this* query regardless of which frontier slots other queries keep.
    Truncation to w_out slots
    keeps the w_out smallest d - r; a dropped subtree can only matter if its
    d - r exceeds every kept one AND ≤ r_q — exactly the case the per-query
    ``overflow`` flag reports (DESIGN.md §8).

    ``level_stats`` is static so the default (False) trace emits exactly
    the ops it always did; the True variant additionally stacks per-level
    pruned-by-bound, pruned-by-parent and live-block counts and only ever
    compiles when observability asks for it.

    ``prune`` (static) turns on the parent-distance pre-filter
    (DESIGN.md §17): each frontier slot carries ``qpd`` — the distance
    d(q, routing object) computed at the level that *admitted* the node —
    and the scorer drops entries with ``|qpd − pdist| > r_q + r`` before
    the metric eval.  The filter threshold pads by 2·_EPS (the prune
    test's _EPS plus f32 triangle rounding), so every filtered entry
    provably fails the d − r ≤ r_q + _EPS test and results stay bitwise
    identical; ``dist_evals`` counts evaluations actually performed, so
    it (alone) shrinks.  The root level has no parent — level 0 always
    scores unfiltered.

    The leaf level is *chunked*: the frontier arrives sorted by d − r
    (top_k compaction order), so scoring it in _LEAF_CHUNKS sequential
    slices and merging the top-k between slices tightens r_q toward the
    true kth-NN distance before the far leaves are touched — that is
    where the pre-filter earns its keep (DESIGN.md §17).  Chunking is
    emitted identically with the filter on and off (per-chunk r_q is the
    same value in both traces), so the bitwise-identity argument applies
    chunk by chunk, and the unpruned path still evaluates every valid
    entry — only wall-clock layout changes, not its dist_evals.
    """
    from repro.kernels.frontier import frontier_scores, live_blocks

    b = queries.shape[0]
    cap = tree.capacity
    r_cap = jnp.broadcast_to(jnp.asarray(r_cap, jnp.float32), (b,))

    widths = level_widths(height, cap, F)

    internal_valid = tree.valid & ~tree.is_leaf[:, None]
    leaf_valid = tree.valid & tree.is_leaf[:, None]

    frontier = jnp.full((b, 1), tree.root, jnp.int32)
    qpd = jnp.full((b, 1), _INF, jnp.float32)   # d(q, parent) per slot
    topk_d = jnp.full((b, k), _INF, jnp.float32)
    topk_i = jnp.full((b, k), -1, jnp.int32)
    ub = jnp.full((b,), _INF, jnp.float32)
    page_hits = jnp.zeros((b,), jnp.int32)
    dist_evals = jnp.zeros((b,), jnp.int32)
    overflow = jnp.zeros((b,), bool)
    pruned_levels = []          # level_stats only: [b] per internal level
    parent_levels = []          # level_stats only: [b] per level
    block_levels = []           # level_stats only: [b] per level

    for lvl in range(height):
        w = widths[lvl]
        fvalid = frontier >= 0                              # [b, w]
        nodes = jnp.maximum(frontier, 0)
        page_hits += jnp.sum(fvalid, axis=1, dtype=jnp.int32)

        # the root has no parent routing object: level 0 scores unfiltered
        use_filter = prune and lvl > 0
        if lvl > 0:
            # pre-eval kth-NN upper bound from parent distances alone
            # (DESIGN.md §17): two triangle hops give d(q, x) <= qpd +
            # pdist(e) + r(e) for every object x under entry e, and each
            # valid entry covers >= min_fill^rem disjoint objects, so the
            # j-th smallest such bound caps the kth-NN distance before
            # this level runs a single metric eval — exactly when the
            # pre-filter needs a tight r_q.  It feeds r_q in the pruned
            # AND unpruned traces (identical values), so on/off bitwise
            # identity is untouched.
            with jax.named_scope(BOUND_SCOPE):
                pd_ub = tree.pdist[nodes] + tree.radius[nodes]  # [b, w, cap]
                ok = tree.valid[nodes] & fvalid[:, :, None]
                ubnd = jnp.where(ok, qpd[:, :, None] + pd_ub,
                                 _INF).reshape(b, w * cap)
                j_pre = -(-k // max(1, tree.min_fill) ** (height - 1 - lvl))
                if j_pre == 1:
                    ub = jnp.minimum(ub, jnp.min(ubnd, axis=1) + _EPS)
                elif j_pre <= w * cap:
                    ub = jnp.minimum(
                        ub, -jax.lax.top_k(-ubnd, j_pre)[0][:, j_pre - 1]
                        + _EPS)

        if lvl < height - 1:
            if use_filter:
                # pre-level query radius — what the filter may assume.
                # The level body's r_q is computed after this level's ub
                # update and can only shrink, so filtering against the
                # pre-level value is conservative (never drops an entry
                # the prune test would have kept; DESIGN.md §17).
                rq_pre = jnp.minimum(jnp.minimum(topk_d[:, k - 1], r_cap),
                                     ub)
                filt = dict(pdist=tree.pdist, qpd=qpd, rq=rq_pre)
            else:
                filt = {}
            dmax, score, leaf_d, dq = frontier_scores(
                frontier, queries, tree.vecs, tree.radius, internal_valid,
                leaf_valid, metric=tree.metric, impl=impl,
                interpret=interpret, **filt)

            # entries the scorer left unmasked: finite outputs ⇔ valid, on
            # a live slot and not dropped by the parent-distance filter.
            # These are the evaluations the descent needs, not the work
            # the device did: both backends compute the metric for every
            # entry of a live page and the filter only masks outputs
            # (DESIGN.md §17).  With the filter off this equals the
            # valid-entry count.
            performed = jnp.isfinite(dmax) | jnp.isfinite(leaf_d)
            n_eval = jnp.sum(performed, axis=(1, 2), dtype=jnp.int32)
            dist_evals += n_eval
            if level_stats:
                evalid = tree.valid[nodes] & fvalid[:, :, None]
                parent_levels.append(
                    jnp.sum(evalid, axis=(1, 2), dtype=jnp.int32) - n_eval)
                block_levels.append(live_blocks(frontier, cap, tree.dim))
            # --- internal level: d_max bound, prune, compact the frontier
            # r covers the *whole* subtree, and every non-root node holds at
            # least min_fill entries, so an entry at this level covers >=
            # min_fill^rem objects — the j-th smallest d + r with
            # j = ceil(k / min_fill^rem) already bounds the kth-NN distance.
            # Usually j == 1: a plain min, no top_k (tighter than the
            # per-query engine's kth-smallest bound, and ~free).
            dmax = dmax.reshape(b, w * cap)
            rem = height - 1 - lvl
            cover = max(1, tree.min_fill) ** rem
            j = -(-k // cover)
            if j == 1:
                ub = jnp.minimum(ub, jnp.min(dmax, axis=1) + _EPS)
            elif j <= w * cap:
                jth_dmax = -jax.lax.top_k(-dmax, j)[0][:, j - 1] + _EPS
                ub = jnp.minimum(ub, jth_dmax)
            # (fewer than j subtree bounds visible: no update possible)
            r_q = jnp.minimum(jnp.minimum(topk_d[:, k - 1], r_cap), ub)
            score = score.reshape(b, w * cap)
            # score is +inf at masked entries; the explicit < _INF term keeps
            # them out of imask when r_q itself is still infinite
            imask = (score <= r_q[:, None] + _EPS) & (score < _INF)
            if level_stats:
                # scored entries whose d_min bound excluded their subtree
                # (isfinite(score) ⇔ the metric ran for this entry, so
                # parent-filtered entries are not double-counted here)
                pruned_levels.append(jnp.sum(
                    jnp.isfinite(score) & ~imask,
                    axis=1, dtype=jnp.int32))
            with jax.named_scope(COMPACT_SCOPE):
                sc = jnp.where(imask, score, _INF)
                childs = tree.child[nodes].reshape(b, w * cap)
                w_out = widths[lvl + 1]
                neg_s, order = jax.lax.top_k(-sc, w_out)
                sel_ok = -neg_s < _INF
                frontier = jnp.where(
                    sel_ok, jnp.take_along_axis(childs, order, axis=1), -1)
                overflow |= jnp.sum(imask, axis=1) > w_out
                # carry d(q, routing object) of each admitted entry: it
                # is the next level's d(q, parent), and the child's pdist
                # was computed against this exact routing object.
                # Selected slots always came through imask, so their dq
                # is finite.  Carried even with the filter off — the
                # pre-eval upper bound above consumes it in both traces.
                qpd = jnp.where(
                    sel_ok,
                    jnp.take_along_axis(dq.reshape(b, w * cap), order,
                                        axis=1),
                    _INF)
        else:
            # --- leaf level: merge candidates into the running top-k,
            # chunked over the (score-sorted) frontier so each chunk's
            # merge tightens r_q for the next.  Chunk 1 holds the closest
            # subtrees and usually drives topk_d[k-1] to near-final, so
            # the remaining chunks — most of the leaf entries — see a
            # near-oracle radius both in the candidate test and in the
            # parent-distance pre-filter.
            parent_acc = jnp.zeros((b,), jnp.int32)
            blocks_acc = jnp.zeros((b,), jnp.int32)
            for c0, wc in leaf_chunks(w):
                fr_c = frontier[:, c0:c0 + wc]
                nodes_c = nodes[:, c0:c0 + wc]
                # per-chunk query radius: identical formula (and value)
                # with the filter on or off — the bitwise-identity proof
                # applies per chunk
                r_q = jnp.minimum(jnp.minimum(topk_d[:, k - 1], r_cap), ub)
                filt = (dict(pdist=tree.pdist, qpd=qpd[:, c0:c0 + wc],
                             rq=r_q)
                        if use_filter else {})
                dmax_c, _, leaf_d, _ = frontier_scores(
                    fr_c, queries, tree.vecs, tree.radius, internal_valid,
                    leaf_valid, metric=tree.metric, impl=impl,
                    interpret=interpret, **filt)
                performed = jnp.isfinite(dmax_c) | jnp.isfinite(leaf_d)
                n_eval = jnp.sum(performed, axis=(1, 2), dtype=jnp.int32)
                dist_evals += n_eval
                if level_stats:
                    evalid = tree.valid[nodes_c] & (fr_c >= 0)[:, :, None]
                    parent_acc += jnp.sum(
                        evalid, axis=(1, 2), dtype=jnp.int32) - n_eval
                    blocks_acc += live_blocks(fr_c, cap, tree.dim)
                with jax.named_scope(COMPACT_SCOPE):
                    leaf_d = leaf_d.reshape(b, wc * cap)
                    cd = jnp.where(leaf_d <= r_q[:, None], leaf_d, _INF)
                    eoid = tree.oid[nodes_c].reshape(b, wc * cap)
                    ci = jnp.where(cd < _INF, eoid, -1)
                    all_d = jnp.concatenate([topk_d, cd], axis=1)
                    all_i = jnp.concatenate([topk_i, ci], axis=1)
                    neg, sel = jax.lax.top_k(-all_d, k)
                    topk_d = -neg
                    topk_i = jnp.take_along_axis(all_i, sel, axis=1)
            if level_stats:
                parent_levels.append(parent_acc)
                block_levels.append(blocks_acc)

    res = QueryResult(topk_d, topk_i, page_hits, dist_evals, overflow)
    if level_stats:
        by_bound = (jnp.stack(pruned_levels) if pruned_levels
                    else jnp.zeros((0, b), jnp.int32))
        by_parent = jnp.stack(parent_levels)
        return res, (by_bound, by_parent, jnp.stack(block_levels))
    return res


@functools.partial(jax.jit, static_argnames=("k", "F"))
def _knn_perquery(tree: TreeArrays, queries: jax.Array, k: int, F: int,
                  r_cap) -> QueryResult:
    """Legacy vmap(per-query) engine: dynamic while_loop descent.

    Kept as (a) the fallback for traced-height contexts (sharded forest)
    and (b) the benchmark baseline the cohort path is measured against
    (benchmarks/bench_engine.py)."""
    b = queries.shape[0]
    cap = tree.capacity
    r_cap = jnp.broadcast_to(jnp.asarray(r_cap, jnp.float32), (b,))

    def per_query(q, rc):
        frontier = jnp.full((F,), -1, jnp.int32).at[0].set(tree.root)
        topk_d = jnp.full((k,), _INF, jnp.float32)
        topk_i = jnp.full((k,), -1, jnp.int32)
        ub = jnp.float32(_INF)  # upper bound on kth-NN distance (d_max bound)
        stats = jnp.zeros((3,), jnp.int32)  # page_hits, dist_evals, overflow
        lvl = jnp.int32(0)

        def cond(state):
            frontier, *_, lvl = state
            return (lvl < tree.height) & jnp.any(frontier >= 0)

        def body(state):
            frontier, topk_d, topk_i, ub, stats, lvl = state
            fvalid = frontier >= 0
            nodes = jnp.maximum(frontier, 0)
            evalid = tree.valid[nodes] & fvalid[:, None]        # [F, cap]
            evecs = tree.vecs[nodes]                            # [F, cap, d]
            erad = tree.radius[nodes]
            echild = tree.child[nodes]
            eoid = tree.oid[nodes]
            leafy = tree.is_leaf[nodes][:, None]                # [F, 1]

            d = _metric_eval(tree.metric, q[None, None, :], evecs)  # [F, cap]
            stats = stats.at[0].add(jnp.sum(fvalid.astype(jnp.int32)))
            stats = stats.at[1].add(jnp.sum(evalid.astype(jnp.int32)))

            # d_max bound: each internal entry's (disjoint, non-empty) subtree
            # holds an object within d + r, so the kth smallest of all d + r
            # seen is an upper bound on the kth-NN distance.  This is what
            # lets level-synchronous descent prune before any leaf is seen.
            imask0 = evalid & ~leafy
            dmax = jnp.where(imask0, d + erad, _INF).reshape(-1)
            kth_dmax = -jax.lax.top_k(-dmax, k)[0][k - 1] + _EPS
            ub = jnp.minimum(ub, kth_dmax)

            r_q = jnp.minimum(jnp.minimum(topk_d[k - 1], rc), ub)
            # --- leaf candidates -> merge into running top-k
            lmask = evalid & leafy & (d <= r_q)
            cd = jnp.where(lmask, d, _INF).reshape(-1)
            ci = jnp.where(lmask, eoid, -1).reshape(-1)
            all_d = jnp.concatenate([topk_d, cd])
            all_i = jnp.concatenate([topk_i, ci])
            neg, sel = jax.lax.top_k(-all_d, k)
            topk_d, topk_i = -neg, all_i[sel]
            r_q = jnp.minimum(jnp.minimum(topk_d[k - 1], rc), ub)

            # --- surviving internal entries -> next frontier (closest-first)
            imask = imask0 & ((d - erad) <= r_q + _EPS)
            score = jnp.where(imask, d - erad, _INF).reshape(-1)
            childs = echild.reshape(-1)
            neg_s, order = jax.lax.top_k(-score, F)
            sel_ok = -neg_s < _INF
            frontier = jnp.where(sel_ok, childs[order], -1)
            stats = stats.at[2].max(
                (jnp.sum(imask) > F).astype(jnp.int32))
            return frontier, topk_d, topk_i, ub, stats, lvl + 1

        frontier, topk_d, topk_i, ub, stats, _ = jax.lax.while_loop(
            cond, body, (frontier, topk_d, topk_i, ub, stats, lvl))
        return topk_d, topk_i, stats

    topk_d, topk_i, stats = jax.vmap(per_query)(queries, r_cap)
    return QueryResult(topk_d, topk_i, stats[:, 0], stats[:, 1],
                       stats[:, 2].astype(bool))


# --------------------------------------------------------------------------
# Jitted insert fast path + host-side split fallback
# --------------------------------------------------------------------------
def _node_page(vecs: jax.Array, node: jax.Array) -> jax.Array:
    """``vecs[node]`` [cap, dim] (``node`` clipped into range).  On a TPU
    a Pallas copy reads it, which keeps a loop's pages in the tree's own
    layout (``kernels.frontier.node_page_pallas``); elsewhere plain
    indexing, with the same values."""
    from repro.kernels.frontier import node_page_pallas
    node = jnp.clip(node, 0, vecs.shape[0] - 1)
    return jax.lax.platform_dependent(
        vecs, node, tpu=node_page_pallas, default=lambda v, n: v[n])


def _descend_path(tree: TreeArrays, x: jax.Array):
    """SM-tree choose-subtree (closest entry) from root to leaf.
    Returns (path_nodes [MAX_HEIGHT], path_slots [MAX_HEIGHT], leaf_id)."""
    def body(state):
        node, lvl, pn, ps = state
        d = _metric_eval(tree.metric, x[None, :], _node_page(tree.vecs, node))
        d = jnp.where(tree.valid[node], d, _INF)
        slot = jnp.argmin(d)
        pn = pn.at[lvl].set(node)
        ps = ps.at[lvl].set(slot.astype(jnp.int32))
        return tree.child[node, slot], lvl + 1, pn, ps

    def cond(state):
        node, *_ = state
        return ~tree.is_leaf[node]

    pn = jnp.full((MAX_HEIGHT,), -1, jnp.int32)
    ps = jnp.full((MAX_HEIGHT,), -1, jnp.int32)
    leaf, _, pn, ps = jax.lax.while_loop(cond, body, (tree.root, 0, pn, ps))
    return pn, ps, leaf


_descend = jax.jit(_descend_path)


def _refresh_path_radii(tree: TreeArrays, pn: jax.Array, ps: jax.Array) -> TreeArrays:
    """Bottom-up radius fold along the descent path: the SM invariant.
    r(entry at (pn[i], ps[i])) = max over its child node's valid entries of
    (pdist [+ radius])."""
    def body(i, t):
        lvl = MAX_HEIGHT - 1 - i
        node = pn[lvl]
        slot = ps[lvl]
        ok = node >= 0
        n = jnp.maximum(node, 0)
        c = t.child[n, jnp.maximum(slot, 0)]
        cn = jnp.maximum(c, 0)
        contrib = t.pdist[cn] + jnp.where(t.is_leaf[cn], 0.0, t.radius[cn])
        r = jnp.max(jnp.where(t.valid[cn], contrib, -_INF))
        new_rad = t.radius.at[n, jnp.maximum(slot, 0)].set(
            jnp.where(ok, jnp.maximum(r, 0.0), t.radius[n, jnp.maximum(slot, 0)]))
        return dataclasses.replace(t, radius=new_rad)

    return jax.lax.fori_loop(0, MAX_HEIGHT, body, tree)


def _insert_fast_impl(tree: TreeArrays, x: jax.Array, obj_id: jax.Array):
    """No-split insert.  Returns (tree, fits: bool, leaf_id).  When the leaf
    is full the tree is returned UNCHANGED with fits=False — the caller runs
    the host-side split path."""
    pn, ps, leaf = _descend(tree, x)
    cnt = tree.count[leaf]
    fits = cnt < tree.capacity
    slot = jnp.minimum(cnt, tree.capacity - 1)
    # parent routing vec: entry pointing at `leaf`
    has_parent = pn[0] >= 0
    plast = jnp.argmax(jnp.where(pn >= 0, jnp.arange(MAX_HEIGHT), -1))
    pnode = pn[plast]
    pslot = ps[plast]
    pvec = tree.vecs[jnp.maximum(pnode, 0), jnp.maximum(pslot, 0)]
    pd = jnp.where(has_parent, _metric_eval(tree.metric, x, pvec), 0.0)

    def apply(t: TreeArrays) -> TreeArrays:
        t = dataclasses.replace(
            t,
            vecs=t.vecs.at[leaf, slot].set(x),
            radius=t.radius.at[leaf, slot].set(0.0),
            pdist=t.pdist.at[leaf, slot].set(pd),
            child=t.child.at[leaf, slot].set(-1),
            oid=t.oid.at[leaf, slot].set(obj_id.astype(jnp.int32)),
            valid=t.valid.at[leaf, slot].set(True),
            count=t.count.at[leaf].add(1))
        return _refresh_path_radii(t, pn, ps)

    new_tree = jax.lax.cond(fits, apply, lambda t: t, tree)
    return new_tree, fits, leaf


insert_fast = jax.jit(_insert_fast_impl)


@jax.jit
def path_to_root(tree: TreeArrays, leaf: jax.Array):
    """Climb parent pointers: returns (path_nodes, path_slots) root-first,
    padded with -1 — same layout as _descend's output."""
    def body(state):
        node, chain_n, chain_s, depth = state
        p = tree.parent[node]
        s = tree.pslot[node]
        chain_n = chain_n.at[depth].set(p)
        chain_s = chain_s.at[depth].set(s)
        return p, chain_n, chain_s, depth + 1

    def cond(state):
        node, *_ , _d = state
        return tree.parent[node] >= 0

    cn = jnp.full((MAX_HEIGHT,), -1, jnp.int32)
    cs = jnp.full((MAX_HEIGHT,), -1, jnp.int32)
    _, cn, cs, depth = jax.lax.while_loop(cond, body, (leaf, cn, cs, 0))
    # chain is leaf-first; reverse the filled prefix to be root-first
    idx = depth - 1 - jnp.arange(MAX_HEIGHT)
    ok = idx >= 0
    pn = jnp.where(ok, cn[jnp.maximum(idx, 0)], -1)
    ps = jnp.where(ok, cs[jnp.maximum(idx, 0)], -1)
    return pn, ps


def _delete_fast_impl(tree: TreeArrays, x: jax.Array, obj_id: jax.Array):
    """No-underflow delete.  Returns (tree, found, underflow, leaf_id).
    On underflow the tree is returned UNCHANGED with underflow=True — caller
    runs the host-side merge path.  Locates the object by exact id match and
    climbs parent pointers for the O(h) radius fold.  Negative ids (the NOP
    pad sentinel) never match."""
    hit = (tree.oid == obj_id) & tree.valid & (obj_id >= 0)
    found = jnp.any(hit)
    flat = jnp.argmax(hit.reshape(-1))
    leaf = (flat // tree.capacity).astype(jnp.int32)
    slot = (flat % tree.capacity).astype(jnp.int32)
    cnt = tree.count[leaf]
    # root never underflows
    underflow = found & (cnt - 1 < tree.min_fill) & (leaf != tree.root)

    pn, ps = path_to_root(tree, leaf)

    def apply(t: TreeArrays) -> TreeArrays:
        last = cnt - 1
        # swap-remove: move last entry into the hole
        t = dataclasses.replace(
            t,
            vecs=t.vecs.at[leaf, slot].set(t.vecs[leaf, last]),
            radius=t.radius.at[leaf, slot].set(t.radius[leaf, last]),
            pdist=t.pdist.at[leaf, slot].set(t.pdist[leaf, last]),
            child=t.child.at[leaf, slot].set(t.child[leaf, last]),
            oid=t.oid.at[leaf, slot].set(t.oid[leaf, last]))
        t = dataclasses.replace(
            t,
            valid=t.valid.at[leaf, last].set(False),
            oid=t.oid.at[leaf, last].set(-1),
            count=t.count.at[leaf].add(-1))
        return _refresh_path_radii(t, pn, ps)

    ok = found & ~underflow
    new_tree = jax.lax.cond(ok, apply, lambda t: t, tree)
    return new_tree, found, underflow, leaf


delete_fast = jax.jit(_delete_fast_impl)


# --------------------------------------------------------------------------
# Batched mutation apply (the repro.stream data plane)
# --------------------------------------------------------------------------
# Mutation opcodes for ``apply_mutations`` / the stream batcher.  OP_NOP is 0
# so padding rows are all-zeros and masked statuses psum cleanly in the
# sharded forest (core/distributed.py).
OP_NOP, OP_INSERT, OP_DELETE = 0, 1, 2
# Per-row outcomes.  ST_NOP must stay 0 (same psum argument).
ST_NOP, ST_APPLIED, ST_OVERFLOW, ST_UNDERFLOW, ST_NOTFOUND = 0, 1, 2, 3, 4
# Resolved by the on-device split pass (apply_splits): either a single-level
# leaf split or an escalation-time re-check that found room.  Callers
# (stream/batcher.py) normalise it to ST_APPLIED after counting.
ST_SPLIT = 5
# Resolved by the on-device merge pass (apply_merges): an underflow delete
# absorbed without leaving HBM.  Normalised to ST_APPLIED like ST_SPLIT.
ST_MERGE = 6


def _apply_row(t: TreeArrays, vecs0: jax.Array, op, x, oid, leaf0, found0):
    """One mutation as a branch-free masked update (the scan body of
    ``apply_mutations``).

    Semantically identical to dispatching to ``insert_fast``/``delete_fast``
    per row, but shaped so XLA:CPU keeps the scan carry **in place** and
    each step's work stays O(h·cap); every deviation below is load-bearing
    for that (each was worth 2-4x on the batch throughput at n=100k):

      * no ``lax.cond``/``switch`` on tree state — branches returning whole
        trees materialise both versions of every array per step.  Rows that
        do not apply redirect their scatters out of bounds instead
        (``mode="drop"``), so no masking read of the current cell is needed.
      * the choose-subtree descent and the parent-routing-vector gather read
        ``vecs0`` — the *loop-invariant* pre-batch vecs.  Both only ever
        touch internal-node rows, which the fast path never writes, so the
        values are identical; reading the carried ``t.vecs`` instead would
        put a gather and a scatter on the same buffer in one fusion, which
        XLA resolves by copying all of ``vecs`` every step.  The descent
        reads each page through ``_node_page``: on a TPU, plain indexing
        there makes XLA copy all of ``vecs0`` into a capacity-minor layout
        on loop entry (four times the pages at 32 x 3,072).
      * the delete target's leaf (``leaf0``/``found0``) is located once,
        vectorised, before the scan (``_locate_oids``): within a
        conflict-free batch nothing moves an object across leaves, so only
        the *slot* must be re-derived per step — an O(cap) row probe
        instead of an O(N·cap) table scan.
      * leaf ``child`` rows are always -1 and leaf ``radius`` rows always
        0.0 (bulk build, host splits and this fast path all maintain that),
        so the insert/swap writes to them are dropped outright.
    """
    cap = t.capacity
    is_ins_op = op == OP_INSERT
    is_del_op = op == OP_DELETE
    N = t.max_nodes   # out-of-bounds scatter target for inactive rows

    # --- insert probe: choose-subtree descent (invariant routing pages)
    t_inv = dataclasses.replace(t, vecs=vecs0)
    pn_i, ps_i, leaf_i = _descend_path(t_inv, x)
    cnt_i = t.count[leaf_i]
    fits = cnt_i < cap
    slot_i = jnp.minimum(cnt_i, cap - 1)
    has_parent = pn_i[0] >= 0
    plast = jnp.argmax(jnp.where(pn_i >= 0, jnp.arange(MAX_HEIGHT), -1))
    pvec = vecs0[jnp.maximum(pn_i[plast], 0), jnp.maximum(ps_i[plast], 0)]
    pd = jnp.where(has_parent, _metric_eval(t.metric, x, pvec), 0.0)

    # --- delete probe: pre-located leaf, slot re-derived from the live row
    # (earlier swap-removes may have moved the target within its leaf)
    found = found0 & is_del_op
    leaf_d = jnp.maximum(leaf0, 0)
    row_hit = (t.oid[leaf_d] == oid) & t.valid[leaf_d]      # [cap]
    slot_d = jnp.argmax(row_hit).astype(jnp.int32)
    cnt_d = t.count[leaf_d]
    underflow = found & (cnt_d - 1 < t.min_fill) & (leaf_d != t.root)
    last_d = jnp.maximum(cnt_d - 1, 0)
    pn_d, ps_d = path_to_root(t, leaf_d)

    do_ins = is_ins_op & fits
    do_del = found & ~underflow
    act = do_ins | do_del

    # --- write 1: the edited slot (insert target / swap-remove fill);
    # inactive rows scatter out of bounds and are dropped
    n1 = jnp.where(act, jnp.where(do_ins, leaf_i, leaf_d), N)
    s1 = jnp.where(do_ins, slot_i, slot_d)

    _flags = dict(mode="drop", unique_indices=True, indices_are_sorted=True)

    def w1(arr, ins_val):
        src = arr[leaf_d, last_d]
        return arr.at[n1, s1].set(jnp.where(do_ins, ins_val, src), **_flags)

    vecs = w1(t.vecs, x)
    pdist = w1(t.pdist, pd)
    oid_a = w1(t.oid, oid.astype(jnp.int32))
    valid = t.valid.at[n1, s1].set(True, **_flags)

    # --- write 2: clear the delete tail slot (after write 1, matching the
    # swap-remove order — handles slot == last)
    n2 = jnp.where(do_del, leaf_d, N)
    valid = valid.at[n2, last_d].set(False, **_flags)
    oid_a = oid_a.at[n2, last_d].set(-1, **_flags)

    delta = jnp.where(do_ins, 1, -1).astype(jnp.int32)
    count = t.count.at[n1].add(delta, **_flags)

    t = dataclasses.replace(t, vecs=vecs, pdist=pdist, oid=oid_a,
                            valid=valid, count=count)

    # --- radius fold along the touched path (no-op rows fold nothing)
    pn = jnp.where(do_ins, pn_i, jnp.where(do_del, pn_d, -1))
    ps = jnp.where(do_ins, ps_i, jnp.where(do_del, ps_d, -1))
    t = _refresh_path_radii(t, pn, ps)

    status = jnp.where(
        is_ins_op, jnp.where(fits, ST_APPLIED, ST_OVERFLOW),
        jnp.where(is_del_op,
                  jnp.where(found, jnp.where(underflow, ST_UNDERFLOW,
                                             ST_APPLIED), ST_NOTFOUND),
                  ST_NOP)).astype(jnp.int32)
    return t, status


def _locate_slots(tree: TreeArrays, oids: jax.Array):
    """Vectorised exact-id lookup at slot granularity: for each requested
    oid, the flat slot index ``node * cap + slot`` holding it (``N * cap``
    when absent) and a found mask.  One O(N·cap·log B) sorted-join pass
    replaces B sequential O(N·cap) table scans; first-hit semantics (lowest
    flat slot wins) match the scan the fast path used to do.  Requires the
    batch's oids to be unique (the conflict-free-cohort contract)."""
    B = oids.shape[0]
    N, cap = tree.oid.shape
    order = jnp.argsort(oids)
    sorted_oids = oids[order]
    pos = jnp.searchsorted(sorted_oids, tree.oid)            # [N, cap]
    pos_c = jnp.minimum(pos, B - 1)
    # negative requested oids never match: they are the NOP pad sentinel
    # (stream/batcher.py pads cohorts with oid = -1), and pads repeat — so
    # without this guard a sentinel-colliding stored id would break both the
    # uniqueness contract and the pad-rows-are-inert one
    match = ((sorted_oids[pos_c] == tree.oid) & tree.valid
             & (sorted_oids[pos_c] >= 0))
    row = jnp.where(match, order[pos_c], B)                  # B → dropped
    flat = jnp.arange(N * cap, dtype=jnp.int32).reshape(N, cap)
    first = jnp.full((B,), N * cap, jnp.int32).at[row].min(flat, mode="drop")
    return first, first < N * cap


def _locate_oids(tree: TreeArrays, oids: jax.Array):
    """Node-granularity wrapper over ``_locate_slots``: the node holding
    each requested oid, or -1 when absent."""
    _, cap = tree.oid.shape
    first, found = _locate_slots(tree, oids)
    return jnp.where(found, first // cap, -1).astype(jnp.int32), found


@jax.jit
def _extract_objects_impl(tree: TreeArrays, oids: jax.Array):
    N, cap = tree.oid.shape
    first, found = _locate_slots(tree, oids)
    flat_vecs = tree.vecs.reshape(N * cap, -1)
    idx = jnp.minimum(first, N * cap - 1)
    vecs = jnp.where(found[:, None], flat_vecs[idx], 0.0)
    return vecs.astype(jnp.float32), found


def extract_objects(tree: TreeArrays, oids):
    """Gather the stored vectors for a batch of object ids.

    oids: [B] int32, unique (conflict-free-cohort contract; -1 pads never
    match).  Returns (vecs [B, dim] f32, found [B] bool); rows whose id is
    not live in ``tree`` come back zero-filled with ``found`` False.  This
    is the read half of a migration step: the stream layer re-emits the
    extracted rows as a delete-on-donor / insert-on-receiver cohort, so a
    move rides the same jitted apply scan as any other mutation batch."""
    return _extract_objects_impl(tree, jnp.asarray(oids, jnp.int32))


def move_objects(donor: TreeArrays, receiver: TreeArrays, oids, *,
                 splits: bool = True, merges: bool = True):
    """Host reference for a batch move: re-home ``oids`` from ``donor``
    into ``receiver``.  Returns (donor, receiver, moved [B] bool).

    Order is insert-before-delete so a structural failure can only leave
    an object visible twice across the pair, never zero times; ids absent
    from the donor, or whose insert/delete escalation did not complete,
    report ``moved`` False and leave both trees consistent.  The streaming
    forest's migration steps use the same extract + cohort-apply shape but
    route through its batcher/mesh plumbing (stream/pipeline.py)."""
    oids = jnp.asarray(oids, jnp.int32)
    vecs, found = extract_objects(donor, oids)
    found = np.asarray(found)
    ins_ops = jnp.where(found, OP_INSERT, OP_NOP)
    ins_oids = jnp.where(found, oids, -1)
    receiver, st_i = apply_mutations(receiver, ins_ops, vecs, ins_oids,
                                     splits=splits, merges=merges)
    placed = found & np.isin(np.asarray(st_i), (ST_APPLIED, ST_SPLIT))
    del_ops = jnp.where(jnp.asarray(placed), OP_DELETE, OP_NOP)
    del_oids = jnp.where(jnp.asarray(placed), oids, -1)
    donor, st_d = apply_mutations(donor, del_ops, vecs, del_oids,
                                  splits=splits, merges=merges)
    moved = placed & np.isin(np.asarray(st_d), (ST_APPLIED, ST_MERGE))
    return donor, receiver, jnp.asarray(moved)


def _apply_mutations_impl(tree: TreeArrays, ops: jax.Array, xs: jax.Array,
                          oids: jax.Array):
    """One fused ``lax.scan`` over a mutation log: per row the branch-free
    insert/delete fast path (``_apply_row``) plus a status.

    The log must be a *conflict-free cohort* — no object id appears twice
    (deletes are pre-located against the pre-batch tree, which is only
    sound when no earlier row in the same batch touches the same id).  The
    stream batcher (repro.stream.batcher) cuts arbitrary logs into such
    cohorts.

    Rows the fast path cannot absorb leave the tree untouched and report
    ST_OVERFLOW / ST_UNDERFLOW / ST_NOTFOUND — the stream batcher escalates
    them to the host control plane.  The whole batch is one device dispatch
    with an in-place carry, which is where the throughput over a Python
    insert_fast/delete_fast loop comes from (benchmarks/bench_stream.py).
    """
    vecs0 = tree.vecs   # invariant routing pages (see _apply_row)
    leaf0, found0 = _locate_oids(tree, oids)

    def step(t, row):
        op, x, oid, l0, f0 = row
        return _apply_row(t, vecs0, op, x, oid, l0, f0)

    return jax.lax.scan(step, tree, (ops, xs, oids, leaf0, found0),
                        unroll=2)


@functools.cache
def _apply_mutations_jit(donate: bool):
    return jax.jit(_apply_mutations_impl,
                   donate_argnums=(0,) if donate else ())


def apply_mutations(tree: TreeArrays, ops, xs, oids, *,
                    donate: bool = False, splits: bool = True,
                    merges: bool = True):
    """Batched insert/delete apply.  Returns (tree, statuses [B] int32).

    ops: [B] int32 opcodes, xs: [B, dim] f32, oids: [B] int32.  Ops apply in
    log order; see ``_apply_mutations_impl`` for escalation statuses.  With
    ``donate`` (default off, on every backend) the input tree's buffers are
    donated — callers must treat the argument as consumed.

    With ``splits`` (default), overflow rows are resolved by the on-device
    split pass (``apply_splits``) before returning: the common single-level
    leaf split never leaves HBM, and such rows come back as ``ST_SPLIT``.
    With ``merges`` (default), underflow rows are then resolved by the
    on-device merge pass (``apply_merges``, rows come back ``ST_MERGE``) —
    but only when *no* ST_OVERFLOW row survived the split pass: the host
    reference (``escalate_rows``) resolves all overflows before any
    underflow, so a residual blocked overflow must reach the host first or
    the structure-edit order (and hence the bitwise tree) would diverge.
    The orchestration reads the status vector (a [B]-int sync the stream
    batcher pays anyway); in traced contexts (shard_map — where statuses
    are abstract) both flags are no-ops and the caller runs the
    collectives itself (``core.distributed.forest_apply_splits`` /
    ``forest_apply_merges``)."""
    ops = jnp.asarray(ops, jnp.int32)
    xs = jnp.asarray(xs, jnp.float32)
    oids = jnp.asarray(oids, jnp.int32)
    with obs.child_span("mutation.scan"):
        tree, status = _apply_mutations_jit(donate)(tree, ops, xs, oids)
        if not (splits or merges):
            return tree, status
        try:
            st_host = np.asarray(status)
        except (jax.errors.ConcretizationTypeError,
                jax.errors.TracerArrayConversionError):
            return tree, status
        count_host_sync()
    dirty = 0
    # the post-scan tree is an exclusively-owned intermediate (callers
    # only ever see the final return), so the split/merge chain can
    # donate its buffers even where the scan itself must not (the scan
    # input is the caller's live tree, typically pinned by an epoch)
    if splits:
        tree, st_host, n_split = resolve_overflows(
            tree, ops, xs, oids, st_host, donate=True)
        dirty += n_split
    if merges and not (st_host == ST_OVERFLOW).any():
        tree, st_host, n_merge = resolve_underflows(
            tree, ops, oids, st_host, donate=True)
        dirty += n_merge
    if dirty:
        status = jnp.asarray(st_host)
    return tree, status


def count_host_sync() -> None:
    """Count one device-to-host status or scalar read on the mutation
    path (``mutation.host_syncs_total``): each waits for every program
    queued on the device before it, query cohorts included."""
    if obs.enabled():
        obs.counter("mutation.host_syncs_total").inc()


# --------------------------------------------------------------------------
# On-device node splits (the mesh-resident mutation control plane)
# --------------------------------------------------------------------------
def _promote_and_partition(t: TreeArrays, D, Radd, uvalid, n, min_side,
                           max_moves: int):
    """mM_RAD promotion + generalized-hyperplane partition of one pending
    entry set, decision-for-decision equal to core/split.py:minmax_split.

    D: [m, m] pairwise distances between the pending reference values;
    Radd: [m] the per-entry radius term of the radius scoring matrix
    C = D + Radd[None, :] (zeros for leaf sets); uvalid: [m] member mask —
    the split pass passes all-true (its pending set is exactly cap + 1
    rows), the merge pass's re-split passes ``arange(2*cap) < n`` for the
    dynamically-sized union of two nodes.  ``n``/``min_side`` may be
    traced; ``max_moves`` is a static upper bound on rebalance moves (the
    loop body no-ops once both sides meet min_side, so a loose bound only
    costs dead iterations).  Returns the slot layout both halves will be
    written with: (pi, pj, sel_i, sel_j, pres_i, pres_j, n_i, n_j, r_i,
    r_j), where sel_*/pres_* are [cap] member indices / occupancy masks in
    the exact member order the host's sequential ``_rebalance`` produces.
    """
    cap = t.capacity
    m = D.shape[0]
    # all ordered pairs in one fused 3-D reduction ([P, m] gather forms
    # cost ~25x more per scan step on XLA:CPU); the row-major argmin over
    # the masked upper triangle keeps the first minimal pair, matching
    # np.argmin over triu_indices exactly (padding sits at indices >= n,
    # so masking the j axis of the triangle drops every invalid pair).
    # Values are f32-identical to the host's f64-cast copies, so every
    # comparison agrees.
    Cmat = D + Radd[None, :]                                     # [m, m]
    toi3 = D[:, None, :] <= D[None, :, :]                        # [m, m, m]
    kval = uvalid[None, None, :]
    cand_ri = jnp.max(jnp.where(toi3 & kval, Cmat[:, None, :], -_INF),
                      axis=-1)
    cand_rj = jnp.max(jnp.where(~toi3 & kval, Cmat[None, :, :], -_INF),
                      axis=-1)
    cand_ri = jnp.where(jnp.isfinite(cand_ri), cand_ri, 0.0)
    cand_rj = jnp.where(jnp.isfinite(cand_rj), cand_rj, 0.0)
    triu = jnp.asarray(np.triu(np.ones((m, m), bool), k=1))
    best = jnp.argmin(jnp.where(
        triu & uvalid[None, :], jnp.maximum(cand_ri, cand_rj),
        _INF).reshape(-1))
    pi = (best // m).astype(jnp.int32)
    pj = (best % m).astype(jnp.int32)
    mask_i = (D[pi] <= D[pj]) & uvalid                           # [m]

    # sequential min-fill rebalance, order-exactly: host side lists are the
    # ascending initial members plus moved entries in move order (only one
    # of the two while-loops can run, so the donating side stays ascending
    # and argmin's first-minimal == Python min's first-minimal).  ``stamp``
    # encodes that order so argsort reproduces the host's slot layout.
    # (fori rather than unrolled: same runtime, ~1s less compile — and the
    # split scan's compile is the one-time cost every new tree geometry
    # pays.)
    Dpi = D[pi]
    Dpj = D[pj]

    def _rb(k, carry):
        mask, stamp = carry
        n_i = jnp.sum(mask)
        need_i = n_i < min_side
        need_j = (n - n_i) < min_side
        cand_i = jnp.argmin(
            jnp.where(mask | ~uvalid, _INF, Dpi)).astype(jnp.int32)
        cand_j = jnp.argmin(jnp.where(mask, Dpj, _INF)).astype(jnp.int32)
        mv = jnp.where(need_i, cand_i, cand_j)
        do = need_i | need_j
        mask = jnp.where(do, mask.at[mv].set(need_i), mask)
        stamp = jnp.where(do, stamp.at[mv].set(m + k), stamp)
        return mask, stamp

    mask_i, stamp = jax.lax.fori_loop(
        0, max_moves, _rb, (mask_i, jnp.arange(m, dtype=jnp.int32)))
    n_i = jnp.sum(mask_i).astype(jnp.int32)
    n_j = n - n_i
    BIG = jnp.int32(2 * m + 2)
    ord_i = jnp.argsort(jnp.where(mask_i, stamp, BIG))
    ord_j = jnp.argsort(jnp.where(mask_i | ~uvalid, BIG, stamp))
    slots = jnp.arange(cap, dtype=jnp.int32)
    sel_i = ord_i[:cap]      # n_i, n_j <= cap (min_side >= m - cap)
    sel_j = ord_j[:cap]
    pres_i = slots < n_i
    pres_j = slots < n_j
    r_i = jnp.max(jnp.where(pres_i, (Dpi + Radd)[sel_i], -_INF))
    r_j = jnp.max(jnp.where(pres_j, (Dpj + Radd)[sel_j], -_INF))
    return pi, pj, sel_i, sel_j, pres_i, pres_j, n_i, n_j, r_i, r_j


def _write_half(t: TreeArrays, row, V, R, C, O, Dp, sel, pres, n):
    """write_node equivalent: rewrite node ``row`` with the ``sel``-ordered
    members of the pending set.  Slots beyond the new count keep their
    stale vecs/radius/pdist exactly as the host's write_node leaves them
    (oid/child/valid tails are scrubbed), and each member child's
    parent/pslot pointers are re-aimed (leaf members have child -1 and
    drop out).  ``row`` may be out of bounds (masked no-op)."""
    N = t.max_nodes
    cap = t.capacity
    rc = jnp.minimum(row, N - 1)     # clamped gather source for stale keeps
    slots = jnp.arange(cap, dtype=jnp.int32)
    vecs = t.vecs.at[row].set(
        jnp.where(pres[:, None], V[sel], t.vecs[rc]), mode="drop")
    radius = t.radius.at[row].set(
        jnp.where(pres, R[sel], t.radius[rc]), mode="drop")
    pdist = t.pdist.at[row].set(
        jnp.where(pres, Dp[sel], t.pdist[rc]), mode="drop")
    child = t.child.at[row].set(jnp.where(pres, C[sel], -1), mode="drop")
    oid = t.oid.at[row].set(jnp.where(pres, O[sel], -1), mode="drop")
    valid = t.valid.at[row].set(pres, mode="drop")
    count = t.count.at[row].set(n, mode="drop")
    kids = jnp.where(pres & (row < N), C[sel], -1)
    kid_rows = jnp.where(kids >= 0, kids, N)
    parent = t.parent.at[kid_rows].set(jnp.minimum(row, N - 1), mode="drop",
                                       unique_indices=True)
    pslot = t.pslot.at[kid_rows].set(slots, mode="drop",
                                     unique_indices=True)
    return dataclasses.replace(t, vecs=vecs, radius=radius, pdist=pdist,
                               child=child, oid=oid, valid=valid,
                               count=count, parent=parent, pslot=pslot)


def _pop_free(t: TreeArrays, do):
    """Masked free-ring pop: allocates the same lowest free id the host's
    ``alloc`` picks (the ring is packed descending), scrubbing the popped
    slot so the packed representation matches the host recompute.  When
    ``do`` is False the ring is untouched (the returned id is garbage and
    must be dropped by the caller's masked writes)."""
    top = jnp.maximum(t.free_head - 1, 0)
    n2 = t.free_list[top]
    pos = jnp.where(do, top, t.max_nodes)
    free_list = t.free_list.at[pos].set(-1, mode="drop")
    inc = do.astype(jnp.int32)
    return dataclasses.replace(
        t, free_list=free_list, free_head=t.free_head - inc,
        n_nodes=jnp.where(do, jnp.maximum(t.n_nodes, n2 + 1),
                          t.n_nodes)), n2


def _push_free(t: TreeArrays, f, do):
    """Masked free-ring push: insert node id ``f`` at its *descending-
    sorted* position, not on top of the stack.  The ring's contract is
    ``free_list[:free_head] == packed_free_list(alive)`` — the dead ids in
    descending order, so the top of the stack is the lowest free id and a
    device pop allocates exactly what the host's ``alloc`` would.  A plain
    LIFO push of a freed id would break that the moment a later split pops
    it back while a lower id sits buried below; the sorted insert keeps the
    packed representation bitwise-equal to the host's wholesale recompute
    in ``to_tree``.  O(N) masked shift per push — merges free at most
    O(height) nodes per row, and N-sized masked moves are exactly what the
    rest of the pass does anyway."""
    N = t.max_nodes
    fl = t.free_list
    idx = jnp.arange(N, dtype=jnp.int32)
    live = idx < t.free_head
    pos = jnp.sum((live & (fl > f)).astype(jnp.int32))
    shifted = fl[jnp.maximum(idx - 1, 0)]
    newfl = jnp.where(idx < pos, fl,
                      jnp.where(idx == pos, f, shifted))
    inc = do.astype(jnp.int32)
    return dataclasses.replace(
        t, free_list=jnp.where(do, newfl, fl), free_head=t.free_head + inc)


def _split_row(t: TreeArrays, op, x, oid, blocked):
    """One overflow insert resolved on device: the scan body of
    ``apply_splits``.

    Bitwise-faithful to the host escalation
    (``_HostView.insert_with_split``) in every case:

      * re-descend from the root on the *live* tree and re-check occupancy
        — earlier rows in this pass may have freed space or changed
        routing — and plain-append when the leaf has room;
      * otherwise run the full multi-level split loop: mM_RAD promotion
        with minmax_split's exact tie-breaks and member order, free-ring
        allocation (the same lowest-free-id the host's alloc picks),
        parent entry replacement + append, pending-set splice on parent
        overflow, and on-device root growth.

    Escalation ladder: only a near-empty free ring (the host would have to
    ``_grow`` the node table, a resize no fixed-shape kernel can do)
    blocks the row — and, to preserve log order, every later overflow row
    in the pass; merges (delete underflow) remain host-side.

    Shaped like ``_apply_row``: straight-line masked updates, no
    cond/switch on tree state — on XLA:CPU a conditional returning the
    tree copies every array at the branch boundary, which at production
    node counts costs more than the split itself.  Inactive rows enter the
    split loop with ``done`` already set, so they pay zero iterations.
    """
    cap = t.capacity
    N = t.max_nodes
    want = (op == OP_INSERT) & ~blocked
    pn, ps, leaf = _descend_path(t, x)
    cnt = t.count[leaf]
    has_room = cnt < cap
    # worst case allocs: one split per level + a root growth
    can_split = (~has_room) & (t.free_head >= t.height + 1)
    do_append = want & has_room
    do_split = want & can_split
    ok = do_append | do_split
    blocked = blocked | (want & ~ok)

    # --- append case: the host's re-check branch (append_entry + fold_up)
    parentL = t.parent[leaf]
    has_parent = parentL >= 0
    pvec = t.vecs[jnp.maximum(parentL, 0), jnp.maximum(t.pslot[leaf], 0)]
    pd_app = jnp.where(has_parent, _metric_eval(t.metric, x, pvec), 0.0)
    na = jnp.where(do_append, leaf, N)
    sa = jnp.minimum(cnt, cap - 1)
    _fl = dict(mode="drop", unique_indices=True)
    t = dataclasses.replace(
        t,
        vecs=t.vecs.at[na, sa].set(x, **_fl),
        # explicit 0.0 (not elided as in _apply_row): a leaf reusing an
        # ex-internal freed slot can carry stale nonzero radius beyond its
        # count, and the host path writes the zero
        radius=t.radius.at[na, sa].set(0.0, **_fl),
        pdist=t.pdist.at[na, sa].set(pd_app, **_fl),
        oid=t.oid.at[na, sa].set(oid.astype(jnp.int32), **_fl),
        valid=t.valid.at[na, sa].set(True, **_fl),
        count=t.count.at[na].add(1, **_fl))

    # --- split case: the host's overflow loop as a bounded while_loop.
    # Each iteration splits the pending set across the reused node and a
    # fresh allocation, then installs the promoted pair in the parent
    # (done), splices the full parent and ascends, or grows a new root
    # (done).  R carries the node's *stored* radius row — semantically
    # zero at leaves, but _apply_row elides leaf radius writes, so stale
    # nonzero values survive there and the host's write_node permutes
    # them; copying the row keeps the split bitwise-faithful.
    state = dict(
        t=t,
        V=jnp.concatenate([t.vecs[leaf], x[None, :]], axis=0),
        R=jnp.concatenate([t.radius[leaf], jnp.zeros((1,), jnp.float32)]),
        C=jnp.concatenate([t.child[leaf],
                           jnp.full((1,), -1, jnp.int32)]),
        O=jnp.concatenate([t.oid[leaf],
                           jnp.reshape(oid.astype(jnp.int32), (1,))]),
        pend_leaf=jnp.asarray(True),
        cur=leaf,
        done=~do_split,
        grew_root=jnp.asarray(False),
    )

    def cond_fn(s):
        return ~s["done"]

    def body(s):
        t = s["t"]
        V, R, C, O = s["V"], s["R"], s["C"], s["O"]
        cur = s["cur"]
        D = _metric_eval(t.metric, V[:, None, :], V[None, :, :])
        Radd = jnp.where(s["pend_leaf"], jnp.zeros_like(R), R)
        from repro.core.split import min_side_for
        ms = min_side_for(cap + 1, cap, t.min_fill)
        (pi, pj, sel_i, sel_j, pres_i, pres_j, n_i, n_j, r_i,
         r_j) = _promote_and_partition(
            t, D, Radd, jnp.ones((cap + 1,), bool), cap + 1, ms,
            max_moves=ms)

        parent = t.parent[cur]          # read before any pointer writes
        pslot_c = jnp.maximum(t.pslot[cur], 0)
        is_root = parent < 0
        p_n = jnp.maximum(parent, 0)

        t, n2 = _pop_free(t, jnp.asarray(True))
        t = _write_half(t, cur, V, R, C, O, D[pi], sel_i, pres_i, n_i)
        t = _write_half(t, n2, V, R, C, O, D[pj], sel_j, pres_j, n_j)
        t = dataclasses.replace(
            t, alive=t.alive.at[n2].set(True),
            is_leaf=t.is_leaf.at[n2].set(s["pend_leaf"]))

        # --- parent present: replace the entry pointing at cur with
        # promoted i (the pending splice below must see this write)
        gp = t.parent[p_n]
        gv = t.vecs[jnp.maximum(gp, 0), jnp.maximum(t.pslot[p_n], 0)]
        has_gp = gp >= 0
        pd_i = jnp.where(has_gp, _metric_eval(t.metric, V[pi], gv), 0.0)
        pd_j = jnp.where(has_gp, _metric_eval(t.metric, V[pj], gv), 0.0)
        rowP = jnp.where(is_root, N, p_n)
        t = dataclasses.replace(
            t,
            vecs=t.vecs.at[rowP, pslot_c].set(V[pi], **_fl),
            radius=t.radius.at[rowP, pslot_c].set(r_i, **_fl),
            pdist=t.pdist.at[rowP, pslot_c].set(pd_i, **_fl),
            child=t.child.at[rowP, pslot_c].set(cur, **_fl))

        # --- parent has room: append promoted j, terminal
        parent_room = t.count[p_n] < cap
        app = ~is_root & parent_room
        ap = t.count[p_n]
        apc = jnp.minimum(ap, cap - 1)
        rowA = jnp.where(app, p_n, N)
        rowA2 = jnp.where(app, n2, N)
        t = dataclasses.replace(
            t,
            vecs=t.vecs.at[rowA, apc].set(V[pj], **_fl),
            radius=t.radius.at[rowA, apc].set(r_j, **_fl),
            pdist=t.pdist.at[rowA, apc].set(pd_j, **_fl),
            child=t.child.at[rowA, apc].set(n2, **_fl),
            oid=t.oid.at[rowA, apc].set(-1, **_fl),
            valid=t.valid.at[rowA, apc].set(True, **_fl),
            count=t.count.at[rowA].add(1, **_fl),
            parent=t.parent.at[rowA2].set(p_n, **_fl),
            pslot=t.pslot.at[rowA2].set(ap, **_fl))

        # --- no parent: grow a new root (host: alloc + two append_entry
        # calls — slots 0/1 written, vecs/radius/pdist beyond stay stale)
        t, nr = _pop_free(t, is_root)
        nrc = jnp.minimum(nr, N - 1)
        rowR = jnp.where(is_root, nrc, N)
        two = jnp.arange(cap) < 2
        slot01 = jnp.where(jnp.arange(cap) == 0, cur, n2)
        rowRc = jnp.where(is_root, cur, N)
        rowRn = jnp.where(is_root, n2, N)
        t = dataclasses.replace(
            t,
            vecs=t.vecs.at[rowR, 0].set(V[pi], **_fl),
            radius=t.radius.at[rowR, 0].set(r_i, **_fl),
            pdist=t.pdist.at[rowR, 0].set(0.0, **_fl))
        t = dataclasses.replace(
            t,
            vecs=t.vecs.at[rowR, 1].set(V[pj], **_fl),
            radius=t.radius.at[rowR, 1].set(r_j, **_fl),
            pdist=t.pdist.at[rowR, 1].set(0.0, **_fl),
            child=t.child.at[rowR].set(jnp.where(two, slot01, -1),
                                       mode="drop"),
            oid=t.oid.at[rowR].set(jnp.full((cap,), -1, jnp.int32),
                                   mode="drop"),
            valid=t.valid.at[rowR].set(two, mode="drop"),
            count=t.count.at[rowR].set(2, mode="drop"),
            is_leaf=t.is_leaf.at[rowR].set(False, mode="drop"),
            alive=t.alive.at[rowR].set(True, mode="drop"),
            parent=(t.parent.at[rowR].set(-1, mode="drop")
                    .at[rowRc].set(nrc, **_fl).at[rowRn].set(nrc, **_fl)),
            pslot=(t.pslot.at[rowR].set(-1, mode="drop")
                   .at[rowRc].set(0, **_fl).at[rowRn].set(1, **_fl)),
            root=jnp.where(is_root, nrc, t.root),
            height=t.height + is_root.astype(jnp.int32))

        # --- parent full: splice its (post-replacement) entries + promoted
        # j as the next pending set and ascend; n2's parent pointer is
        # fixed by the next level's _write_half, exactly like the host
        splice = ~is_root & ~parent_room
        V2 = jnp.concatenate([t.vecs[p_n], V[pj][None, :]], axis=0)
        R2 = jnp.concatenate([t.radius[p_n], r_j[None]])
        C2 = jnp.concatenate([t.child[p_n], n2[None]])
        O2 = jnp.concatenate([t.oid[p_n], jnp.full((1,), -1, jnp.int32)])
        return dict(
            t=t,
            V=jnp.where(splice, V2, V),
            R=jnp.where(splice, R2, R),
            C=jnp.where(splice, C2, C),
            O=jnp.where(splice, O2, O),
            pend_leaf=s["pend_leaf"] & ~splice,
            cur=jnp.where(splice, p_n, cur),
            done=~splice,
            grew_root=is_root,
        )

    s = jax.lax.while_loop(cond_fn, body, state)
    t = s["t"]

    # --- radius fold: the append case folds the descent path; a split that
    # ended in a parent append folds from the last split node (the host's
    # fold_up(cur)); root growth folds nothing (promoted radii are exact).
    # Non-fold rows climb from the root so the walk exits immediately.
    fold_split = do_split & ~s["grew_root"]
    pn2, ps2 = path_to_root(t, jnp.where(fold_split, s["cur"], t.root))
    pn_f = jnp.where(do_append, pn, jnp.where(fold_split, pn2, -1))
    ps_f = jnp.where(do_append, ps, jnp.where(fold_split, ps2, -1))
    t = _refresh_path_radii(t, pn_f, ps_f)

    status = jnp.where(ok, ST_SPLIT,
                       jnp.where(op == OP_INSERT, ST_OVERFLOW, ST_NOP))
    return t, status.astype(jnp.int32), blocked


def _apply_splits_impl(tree: TreeArrays, ops: jax.Array, xs: jax.Array,
                       oids: jax.Array):
    def step(carry, row):
        t, blocked = carry
        op, x, oid = row
        t, st, blocked = _split_row(t, op, x, oid, blocked)
        return (t, blocked), st

    (tree, _), st = jax.lax.scan(step, (tree, jnp.zeros((), bool)),
                                 (ops, xs, oids))
    return tree, st


@functools.cache
def _apply_splits_jit(donate: bool):
    return jax.jit(_apply_splits_impl,
                   donate_argnums=(0,) if donate else ())


def apply_splits(tree: TreeArrays, ops, xs, oids, *,
                 donate: bool = False):
    """On-device split pass over a compacted batch of overflow inserts.

    ops/xs/oids: [K] rows previously reported ST_OVERFLOW by
    ``apply_mutations`` (pad with OP_NOP / oid -1 / zero vecs), in log
    order.  Returns (tree, statuses [K]): ST_SPLIT for rows resolved on
    device, ST_OVERFLOW for rows needing the host control plane (multi-level
    or root splits, or an empty free ring — and, to preserve log order,
    every row after the first such failure), ST_NOP for pads."""
    ops = jnp.asarray(ops, jnp.int32)
    xs = jnp.asarray(xs, jnp.float32)
    oids = jnp.asarray(oids, jnp.int32)
    return _apply_splits_jit(donate)(tree, ops, xs, oids)


# Fixed dispatch width for the split pass: exactly ONE jit entry per tree
# geometry.  A per-count bucket ladder halves the padded-NOP waste but
# multiplies the (seconds-scale) split-scan compile by the ladder depth,
# which dominates every realistic serving window.
SPLIT_CHUNK = 8


def split_chunks(n: int):
    """Fixed-width cover of ``n`` rows (the last chunk padded by the
    dispatcher)."""
    return [SPLIT_CHUNK] * ((n + SPLIT_CHUNK - 1) // SPLIT_CHUNK)


def resolve_overflows(tree: TreeArrays, ops, xs, oids, statuses, *,
                      donate: bool = False):
    """Compact a batch's ST_OVERFLOW rows and run the device split pass.

    statuses: [B] int32 on the host.  Returns (tree, statuses, n_resolved)
    with resolved rows re-marked ST_SPLIT.  The compaction keeps log order
    and dispatches power-of-two-ladder scans (``split_chunks``); a chunk
    reporting a blocked row stops the chunk loop, so the residual rows
    reach the host in log order exactly as if a single scan had processed
    the whole set.  Tree data never leaves the device — only the tiny
    status vector does, and callers (the stream batcher) sync that anyway
    to drive escalation."""
    statuses = np.asarray(statuses)
    ops_np = np.asarray(ops)
    idx = np.nonzero((statuses == ST_OVERFLOW) & (ops_np == OP_INSERT))[0]
    with obs.child_span("mutation.split_pass", rows=len(idx)):
        if not len(idx):
            return tree, statuses, 0
        xs_np = np.asarray(xs, np.float32)
        oids_np = np.asarray(oids, np.int32)
        out = statuses.copy()
        n_resolved = 0
        c0 = 0
        for w in split_chunks(len(idx)):
            chunk = idx[c0:c0 + w]
            c0 += w
            k = len(chunk)
            with obs.child_span("mutation.split_chunk"):
                ops_k = np.full(w, OP_NOP, np.int32)
                ops_k[:k] = OP_INSERT
                xs_k = np.zeros((w, xs_np.shape[1]), np.float32)
                xs_k[:k] = xs_np[chunk]
                oids_k = np.full(w, -1, np.int32)
                oids_k[:k] = oids_np[chunk]
                tree, st = apply_splits(tree, ops_k, xs_k, oids_k,
                                        donate=donate)
                st = np.asarray(jax.device_get(st))[:k]
                count_host_sync()
            out[chunk[st == ST_SPLIT]] = ST_SPLIT
            n_resolved += int((st == ST_SPLIT).sum())
            if (st == ST_OVERFLOW).any():
                break   # blocked: the rest goes to the host in log order
    return tree, out, n_resolved


# --------------------------------------------------------------------------
# On-device node merges (delete underflow — the symmetric half of the
# mesh-resident mutation control plane)
# --------------------------------------------------------------------------
def _remove_entry_masked(t: TreeArrays, node, s, do):
    """Host ``remove_entry`` as masked writes: swap-remove slot ``s`` of
    ``node`` (the last entry fills the hole; a swapped *internal* child's
    pslot is re-aimed), clear the tail slot, decrement count.  Write
    ordering handles ``s == last`` exactly like ``_apply_row``'s delete;
    everything drops when ``do`` is False."""
    N = t.max_nodes
    _fl = dict(mode="drop", unique_indices=True)
    nc = jnp.minimum(jnp.maximum(node, 0), N - 1)
    last = jnp.maximum(t.count[nc] - 1, 0)
    row = jnp.where(do, nc, N)
    t = dataclasses.replace(
        t,
        vecs=t.vecs.at[row, s].set(t.vecs[nc, last], **_fl),
        radius=t.radius.at[row, s].set(t.radius[nc, last], **_fl),
        pdist=t.pdist.at[row, s].set(t.pdist[nc, last], **_fl),
        child=t.child.at[row, s].set(t.child[nc, last], **_fl),
        oid=t.oid.at[row, s].set(t.oid[nc, last], **_fl))
    # swapped child's pslot: host skips when s == last (the entry at s IS
    # the removed one) and at leaves (child -1 drops the write anyway)
    c_sw = t.child[nc, last]
    sw_do = do & (s != last) & ~t.is_leaf[nc] & (c_sw >= 0)
    t = dataclasses.replace(
        t, pslot=t.pslot.at[jnp.where(sw_do, c_sw, N)].set(s, **_fl))
    return dataclasses.replace(
        t,
        valid=t.valid.at[row, last].set(False, **_fl),
        child=t.child.at[row, last].set(-1, **_fl),
        oid=t.oid.at[row, last].set(-1, **_fl),
        count=t.count.at[row].add(-1, **_fl))


def _free_node_masked(t: TreeArrays, node, do):
    """Host ``free`` as masked writes: alive/valid cleared, count zeroed,
    parent/pslot detached — vecs/radius/pdist/child/oid stay *stale*,
    exactly as the host leaves them (``alloc`` scrubs on reuse) — plus the
    sorted free-ring push."""
    N = t.max_nodes
    cap = t.capacity
    row = jnp.where(do, node, N)
    t = dataclasses.replace(
        t,
        alive=t.alive.at[row].set(False, mode="drop"),
        valid=t.valid.at[row].set(jnp.zeros((cap,), bool), mode="drop"),
        count=t.count.at[row].set(0, mode="drop"),
        parent=t.parent.at[row].set(-1, mode="drop"),
        pslot=t.pslot.at[row].set(-1, mode="drop"))
    return _push_free(t, node, do)


def _merge_row(t: TreeArrays, op, oid):
    """One underflow delete resolved on device: the scan body of
    ``apply_merges``, bitwise-faithful to ``_HostView.delete_with_merge``:

      * re-locate the object on the *live* tree (earlier rows in this pass
        may have moved entries across nodes) with the host's first-hit
        (row-major) semantics, and swap-remove it from its leaf;
      * propagate underflow as a bounded while_loop: pick the nearest
        sibling by routing-object distance (first-minimal, self excluded),
        then either **merge** into it (total fits: ordered appends, free
        the donor onto the ring at its sorted position, swap-remove the
        parent entry, refresh the sibling entry's radius) or
        **redistribute** (re-split the union with minmax_split's exact
        promotion/member order across the same two nodes, parent entries
        rewritten in place);
      * fold radii up the final node's parent chain and collapse
        single-entry internal roots on device, freeing each onto the ring.

    Merges only ever *free* nodes, so — unlike the split pass — no row can
    block on ring exhaustion: the device absorbs every underflow.  Same
    shape discipline as ``_split_row``: straight-line masked ``mode="drop"``
    writes, no cond/switch on tree state."""
    cap = t.capacity
    N = t.max_nodes
    _fl = dict(mode="drop", unique_indices=True)
    want = op == OP_DELETE
    # negative oids (the NOP pad sentinel) never match, mirroring
    # delete_fast — a pad row in a merge chunk must be inert even against
    # a (boundary-rejected, but defence-in-depth) planted -1 entry
    hit = (t.oid == oid) & t.valid & (oid >= 0)
    found = want & jnp.any(hit)
    flat = jnp.argmax(hit.reshape(-1))
    leaf = (flat // cap).astype(jnp.int32)
    slot = (flat % cap).astype(jnp.int32)
    t = _remove_entry_masked(t, leaf, slot, found)

    def cond_fn(s):
        return s["go"]

    def body(s):
        t = s["t"]
        cur = s["cur"]
        parent = t.parent[cur]          # >= 0: the loop excludes the root
        p = jnp.maximum(parent, 0)
        islot = jnp.maximum(t.pslot[cur], 0)
        m = t.count[p]
        slots = jnp.arange(cap, dtype=jnp.int32)
        # nearest sibling entry by routing-object distance; invalid slots
        # and self are +inf, so argmin's first-minimal matches the host's
        # argmin over d[:m] with d[islot] = inf (f64 casts of f32 values
        # compare identically)
        d = _metric_eval(t.metric, t.vecs[p, islot][None, :], t.vecs[p])
        d = jnp.where((slots < m) & (slots != islot), d, _INF)
        j = jnp.argmin(d).astype(jnp.int32)
        sib = t.child[p, j]
        sb = jnp.maximum(sib, 0)
        cm = t.count[cur]
        ns = t.count[sb]
        total = ns + cm
        do_merge = total <= cap

        # ---- merge branch: append cur's entries to sib in slot order
        # (the host's append_entry loop), free cur, swap-remove the parent
        # entry, refresh the sibling entry's covering radius
        sv = t.vecs[p, j]
        pd_m = _metric_eval(t.metric, t.vecs[cur], sv[None, :])   # [cap]
        rowM = jnp.where(do_merge, sb, N)
        # targets of valid members stay < cap (total <= cap here); masked
        # rows land at cap + k — distinct and all dropped
        tgt = jnp.where(slots < cm, ns + slots, cap + slots)
        kids = t.child[cur]
        t = dataclasses.replace(
            t,
            vecs=t.vecs.at[rowM, tgt].set(t.vecs[cur], **_fl),
            radius=t.radius.at[rowM, tgt].set(t.radius[cur], **_fl),
            pdist=t.pdist.at[rowM, tgt].set(pd_m, **_fl),
            child=t.child.at[rowM, tgt].set(kids, **_fl),
            oid=t.oid.at[rowM, tgt].set(t.oid[cur], **_fl),
            valid=t.valid.at[rowM, tgt].set(True, **_fl),
            count=t.count.at[rowM].add(cm, **_fl))
        kidrow = jnp.where(do_merge & (slots < cm) & (kids >= 0), kids, N)
        t = dataclasses.replace(
            t,
            parent=t.parent.at[kidrow].set(sb, **_fl),
            pslot=t.pslot.at[kidrow].set(ns + slots, **_fl))
        t = _free_node_masked(t, cur, do_merge)
        t = _remove_entry_masked(t, p, islot, do_merge)
        # islot removal may have moved entry j — re-read sib's live pslot
        jj = jnp.maximum(t.pslot[sb], 0)
        contrib = t.pdist[sb] + jnp.where(t.is_leaf[sb], 0.0, t.radius[sb])
        fr = jnp.max(jnp.where(t.valid[sb], contrib, -_INF))
        t = dataclasses.replace(
            t, radius=t.radius.at[jnp.where(do_merge, p, N), jj].set(
                fr, **_fl))

        # ---- redistribute branch: re-split the union of sib + cur across
        # the same two nodes (no alloc, no free).  All merge-branch writes
        # above dropped in this case, so the reads below see the pre-branch
        # state.  The union is dynamically sized (cap < total <= 2*cap):
        # sib's entries first, then cur's — the host's vstack order.
        do_rs = ~do_merge
        M = 2 * cap
        ks = jnp.arange(M, dtype=jnp.int32)
        in_sib = ks < ns
        src_row = jnp.where(in_sib, sb, cur)
        src_slot = jnp.clip(jnp.where(in_sib, ks, ks - ns), 0, cap - 1)
        V = t.vecs[src_row, src_slot]
        R = t.radius[src_row, src_slot]
        C = t.child[src_row, src_slot]
        O = t.oid[src_row, src_slot]
        uvalid = ks < total
        D = _metric_eval(t.metric, V[:, None, :], V[None, :, :])
        Radd = jnp.where(t.is_leaf[cur], jnp.zeros_like(R), R)
        # min_side_for, with the dynamic member count: the total - cap
        # term guarantees neither side overflows
        min_side = jnp.maximum(
            2, jnp.maximum(jnp.minimum(t.min_fill, total // 2),
                           total - cap))
        (pi, pj, sel_i, sel_j, pres_i, pres_j, n_i, n_j, r_i,
         r_j) = _promote_and_partition(t, D, Radd, uvalid, total, min_side,
                                       max_moves=cap)
        t = _write_half(t, jnp.where(do_rs, sb, N), V, R, C, O, D[pi],
                        sel_i, pres_i, n_i)
        t = _write_half(t, jnp.where(do_rs, cur, N), V, R, C, O, D[pj],
                        sel_j, pres_j, n_j)
        gp = t.parent[p]
        gv = t.vecs[jnp.maximum(gp, 0), jnp.maximum(t.pslot[p], 0)]
        has_gp = gp >= 0
        pd_i = jnp.where(has_gp, _metric_eval(t.metric, V[pi], gv), 0.0)
        pd_j = jnp.where(has_gp, _metric_eval(t.metric, V[pj], gv), 0.0)
        rowP = jnp.where(do_rs, p, N)
        rowPs = jnp.where(do_rs, sb, N)
        rowPc = jnp.where(do_rs, cur, N)
        t = dataclasses.replace(
            t,
            vecs=t.vecs.at[rowP, j].set(V[pi], **_fl)
                       .at[rowP, islot].set(V[pj], **_fl),
            radius=t.radius.at[rowP, j].set(r_i, **_fl)
                           .at[rowP, islot].set(r_j, **_fl),
            pdist=t.pdist.at[rowP, j].set(pd_i, **_fl)
                         .at[rowP, islot].set(pd_j, **_fl),
            child=t.child.at[rowP, j].set(sb, **_fl)
                         .at[rowP, islot].set(cur, **_fl),
            parent=t.parent.at[rowPs].set(p, **_fl)
                           .at[rowPc].set(p, **_fl),
            pslot=t.pslot.at[rowPs].set(j, **_fl)
                         .at[rowPc].set(islot, **_fl))

        go = (p != t.root) & (t.count[p] < t.min_fill)
        return dict(t=t, cur=p, go=go)

    s = jax.lax.while_loop(
        cond_fn, body,
        dict(t=t, cur=leaf,
             go=found & (leaf != t.root) & (t.count[leaf] < t.min_fill)))
    t = s["t"]

    # fold_up(cur): recompute radii along the final node's parent chain
    # (not-found rows climb from the root, an empty chain)
    pnF, psF = path_to_root(t, jnp.where(found, s["cur"], t.root))
    t = _refresh_path_radii(t, pnF, psF)

    # root collapse: free single-entry internal roots onto the ring (the
    # host loop, including multi-level collapse after deep cascades)
    def rc_cond(s2):
        return s2["go"]

    def rc_body(s2):
        t = s2["t"]
        old = t.root
        newr = t.child[old, 0]
        t = dataclasses.replace(
            t, root=newr, height=t.height - 1,
            parent=t.parent.at[newr].set(-1),
            pslot=t.pslot.at[newr].set(-1))
        t = _free_node_masked(t, old, jnp.asarray(True))
        return dict(t=t, go=~t.is_leaf[t.root] & (t.count[t.root] == 1))

    s2 = jax.lax.while_loop(
        rc_cond, rc_body,
        dict(t=t, go=found & ~t.is_leaf[t.root] & (t.count[t.root] == 1)))
    t = s2["t"]

    status = jnp.where(want, jnp.where(found, ST_MERGE, ST_NOTFOUND),
                       ST_NOP).astype(jnp.int32)
    return t, status


def _apply_merges_impl(tree: TreeArrays, ops: jax.Array, oids: jax.Array):
    def step(t, row):
        op, oid = row
        return _merge_row(t, op, oid)

    return jax.lax.scan(step, tree, (ops, oids))


@functools.cache
def _apply_merges_jit(donate: bool):
    return jax.jit(_apply_merges_impl,
                   donate_argnums=(0,) if donate else ())


def apply_merges(tree: TreeArrays, ops, oids, *,
                 donate: bool = False):
    """On-device merge pass over a compacted batch of underflow deletes.

    ops/oids: [K] rows previously reported ST_UNDERFLOW by
    ``apply_mutations`` (pad with OP_NOP / oid -1), in log order.  Returns
    (tree, statuses [K]): ST_MERGE for resolved rows, ST_NOTFOUND for
    targets that vanished (cannot happen inside a conflict-free cohort,
    kept for the host path's semantics), ST_NOP for pads.  Merges never
    allocate, so — unlike ``apply_splits`` — no row ever blocks."""
    ops = jnp.asarray(ops, jnp.int32)
    oids = jnp.asarray(oids, jnp.int32)
    return _apply_merges_jit(donate)(tree, ops, oids)


# Dispatch widths for the merge pass.  Unlike the split ladder (one fixed
# SPLIT_CHUNK entry, because a blocked row forces a host decision between
# chunks), merge chunks dispatch back-to-back with no intervening sync —
# so per-dispatch overhead, not padded-NOP waste, dominates bulk
# underflow batches (delete-heavy streams routinely underflow ~25% of a
# 256-row cohort).  Two widths bound the jit cache at two entries per
# geometry: the bulk width swallows big runs in one dispatch, the small
# width keeps sparse batches (the common case) from paying 56 NOP rows.
MERGE_CHUNK = 8
MERGE_CHUNK_MAX = 64


def merge_chunks(n: int):
    """Dispatch-width cover of ``n`` rows (each chunk padded by the
    dispatcher).  Full MERGE_CHUNK_MAX chunks, then either one more MAX
    chunk (when the remainder would need >2 small dispatches — overhead
    beats pad waste) or small chunks."""
    out = []
    while n >= MERGE_CHUNK_MAX:
        out.append(MERGE_CHUNK_MAX)
        n -= MERGE_CHUNK_MAX
    if n > 2 * MERGE_CHUNK:
        out.append(MERGE_CHUNK_MAX)
        n = 0
    while n > 0:
        out.append(MERGE_CHUNK)
        n -= MERGE_CHUNK
    return out


def resolve_underflows(tree: TreeArrays, ops, oids, statuses, *,
                       donate: bool = False):
    """Compact a batch's ST_UNDERFLOW rows and run the device merge pass.

    statuses: [B] int32 on the host.  Returns (tree, statuses, n_resolved)
    with resolved rows re-marked ST_MERGE.  Callers must only invoke this
    once no ST_OVERFLOW rows remain (the host reference resolves *all*
    overflows before *any* underflow — ``escalate_rows`` — and the device
    path must replay the same structure-edit order to stay bitwise-
    transparent); ``apply_mutations``/the stream pipeline enforce that."""
    statuses = np.asarray(statuses)
    ops_np = np.asarray(ops)
    idx = np.nonzero((statuses == ST_UNDERFLOW) & (ops_np == OP_DELETE))[0]
    with obs.child_span("mutation.merge_pass", rows=len(idx)):
        if not len(idx):
            return tree, statuses, 0
        oids_np = np.asarray(oids, np.int32)
        out = statuses.copy()
        c0 = 0
        pending = []
        # dispatch every chunk back-to-back and sync the statuses once at
        # the end: merges never block (unlike the split ladder, which must
        # stop at the first blocked chunk), so there is no decision to
        # make between chunks and no reason to stall the dispatch queue
        # on a host round-trip per chunk
        for w in merge_chunks(len(idx)):
            chunk = idx[c0:c0 + w]
            c0 += w
            k = len(chunk)
            ops_k = np.full(w, OP_NOP, np.int32)
            ops_k[:k] = OP_DELETE
            oids_k = np.full(w, -1, np.int32)
            oids_k[:k] = oids_np[chunk]
            tree, st = apply_merges(tree, ops_k, oids_k, donate=donate)
            pending.append((chunk, k, st))
        for chunk, k, st in pending:
            out[chunk] = np.asarray(jax.device_get(st))[:k]
            count_host_sync()
    return tree, out, len(idx)


# --------------------------------------------------------------------------
# Ahead-of-time free-ring headroom (node-table growth off the hot path)
# --------------------------------------------------------------------------
def needs_headroom(tree: TreeArrays, *, frac: float = 1 / 16) -> bool:
    """True when the free ring is low enough that a mutation batch could
    plausibly exhaust it mid-pass (the one split-path escalation left).
    The watermark is ``frac`` of the node table, floored at MAX_HEIGHT + 1
    — the worst case a *single* overflow row can allocate — so growth
    always fires before a row can block.  Syncs one scalar."""
    wm = max(MAX_HEIGHT + 1, int(tree.max_nodes * frac))
    free_head = int(jax.device_get(tree.free_head))
    count_host_sync()
    return free_head < wm


def grow_tree(tree: TreeArrays, *, factor: int = 2) -> TreeArrays:
    """Host-side node-table growth: pad every [N, ...] leaf to
    ``factor * max_nodes`` dead rows (the host ``_HostView._grow`` layout:
    child/oid/parent/pslot pad to -1, is_leaf to True) and recompute the
    packed free ring.  The new ids are the *highest*, so they join the
    descending ring at the bottom and every pre-growth allocation decision
    is unchanged — growth is behaviour-transparent to the mutation order.

    This is the ahead-of-time escape from the last host escalation: the
    stream pipelines call it at snapshot/rebalance/epoch-publish points
    when ``needs_headroom`` fires, so ring exhaustion stops being a
    mid-batch event at all.  Changes array shapes (one recompile per new
    geometry — the cost doubling amortises away)."""
    if factor < 2:
        raise ValueError(f"factor must be >= 2, got {factor}")
    N = tree.max_nodes
    pad_n = N * (factor - 1)
    fields = {}
    alive_np = None
    for name in ("vecs", "radius", "pdist", "child", "oid", "valid",
                 "count", "is_leaf", "alive", "parent", "pslot"):
        a = np.asarray(jax.device_get(getattr(tree, name)))
        pad = np.zeros((pad_n,) + a.shape[1:], a.dtype)
        if name in ("child", "oid", "parent", "pslot"):
            pad -= 1
        if name == "is_leaf":
            pad |= True
        a = np.concatenate([a, pad], axis=0)
        if name == "alive":
            alive_np = a
        fields[name] = jnp.asarray(a)
    free_list, free_head = packed_free_list(alive_np)
    return dataclasses.replace(
        tree, **fields, free_list=jnp.asarray(free_list),
        free_head=jnp.asarray(free_head), max_nodes=N * factor)
