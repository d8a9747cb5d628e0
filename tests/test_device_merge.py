"""PR-5 device merge pass + free-ring push + headroom growth tests.

The contract under test mirrors tests/test_device_split.py for the Delete
side: underflow deletes resolved on device (``smtree.apply_merges`` / the
``forest_apply_merges`` collective) are **bitwise-transparent** — applying
a mutation log with device merges on yields exactly the tree the host
escalation path produces, because the device pass replays
``_HostView.delete_with_merge`` decision-for-decision (same first-hit
relocation, same nearest-sibling tie-breaks, same merge-vs-redistribute
choice with minmax_split's member order, same root collapse) and pushes
freed node ids back onto the packed free ring at their *sorted* position,
so interleaved pops keep matching host allocs.

Also covered: ring push/pop interleavings, pad-sentinel inertness in merge
chunks, and ahead-of-time free-ring headroom growth (``grow_tree`` +
``StreamingEngine``/``StreamingForest`` watermarks).
"""
import dataclasses

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import smtree
from repro.core.engine import SMTreeEngine
from repro.core.smtree import (MAX_HEIGHT, OP_DELETE, OP_INSERT, ST_APPLIED,
                               ST_NOP, ST_NOTFOUND, bulk_build, grow_tree,
                               needs_headroom, packed_free_list)
from repro.data.datagen import clustered, uniform
from repro.dist.sharding import make_mesh
from repro.stream import StreamingEngine, StreamingForest
from repro.stream.batcher import MutationBatcher

DIM = 5


def _trees_equal(a, b, msg=""):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=msg)


def _check_ring(tree):
    fl = np.asarray(tree.free_list)
    fh = int(tree.free_head)
    want = np.nonzero(~np.asarray(tree.alive))[0][::-1]
    assert fh == len(want)
    np.testing.assert_array_equal(fl[:fh], want)
    assert (fl[fh:] == -1).all()


def _random_stream(rng, live, vec, nid, n, del_frac=0.6):
    ops, xs, oids = [], [], []
    for _ in range(n):
        if live and rng.random() < del_frac:
            v = int(sorted(live)[rng.integers(len(live))])
            live.discard(v)
            ops.append(OP_DELETE)
            oids.append(v)
            xs.append(vec[v])
        else:
            v = rng.random(DIM).astype(np.float32)
            ops.append(OP_INSERT)
            oids.append(nid)
            xs.append(v)
            vec[nid] = v
            live.add(nid)
            nid += 1
    return (np.array(ops, np.int32), np.stack(xs).astype(np.float32),
            np.array(oids, np.int32), nid)


# ---------------------------------------------------------------------------
# free-ring push invariant
# ---------------------------------------------------------------------------
def test_device_merge_repacks_ring_sorted():
    """Device merges free nodes; the ring must stay equal to the host's
    wholesale recompute (descending ids, -1 beyond) — a LIFO push would
    diverge the moment a lower id sits buried below the top."""
    X = uniform(300, dims=DIM, seed=1)
    tree = bulk_build(X, capacity=8)
    b = MutationBatcher(tree)
    r = b.apply(np.full(220, OP_DELETE, np.int32), X[:220],
                np.arange(220, dtype=np.int32))
    assert (r.statuses == ST_APPLIED).all()
    assert r.n_merge > 0, "workload never exercised a device merge"
    assert r.n_escalated == 0, "device merges must absorb every underflow"
    _check_ring(b.tree)
    SMTreeEngine(b.tree).validate()


def test_ring_push_pop_interleaving_matches_host():
    """Alternating delete-heavy and insert-heavy batches: device merges
    push freed ids, device splits pop them back — allocation choices must
    keep matching the host control plane bitwise throughout."""
    rng = np.random.default_rng(7)
    X = clustered(300, dims=DIM, seed=7)
    tree = bulk_build(X, capacity=8, fill_frac=0.9)
    bd = MutationBatcher(tree)                       # device splits+merges
    bh = MutationBatcher(tree, device_splits=False,
                         device_merges=False)        # all-host reference
    live = set(range(300))
    vec = {i: X[i] for i in range(300)}
    nid = 1000
    n_merge = n_split = 0
    for phase in range(4):
        frac = 0.85 if phase % 2 == 0 else 0.15
        ops, xs, oids, nid = _random_stream(rng, live, vec, nid, 64,
                                            del_frac=frac)
        rd = bd.apply(ops, xs, oids)
        rh = bh.apply(ops, xs, oids)
        np.testing.assert_array_equal(rd.statuses, rh.statuses)
        n_merge += rd.n_merge
        n_split += rd.n_split
        _trees_equal(bd.tree, bh.tree, f"phase {phase}")
        _check_ring(bd.tree)
    assert n_merge > 0 and n_split > 0, (n_merge, n_split)
    SMTreeEngine(bd.tree).validate()


# ---------------------------------------------------------------------------
# device merge == host merge, bitwise
# ---------------------------------------------------------------------------
def test_single_underflow_delete_bitwise():
    """Single deletes aimed at min-fill leaves: batcher (device merge) vs
    SMTreeEngine.delete (host merge) must agree bitwise op-for-op, and at
    least one op must resolve as a device merge."""
    X = uniform(280, dims=DIM, seed=2)
    tree = bulk_build(X, capacity=8)
    b = MutationBatcher(tree)
    eng = SMTreeEngine(tree)
    n_merge = 0
    for i in range(140):
        r = b.apply(np.array([OP_DELETE], np.int32), X[i][None],
                    np.array([i], np.int32))
        assert (r.statuses == ST_APPLIED).all()
        n_merge += r.n_merge
        assert eng.delete(X[i], i)
        _trees_equal(b.tree, eng.tree, f"device merge != host merge at {i}")
    assert n_merge > 0, "no delete resolved as a device merge"
    SMTreeEngine(b.tree).validate()


def test_redistribute_branch_bitwise():
    """Force the re-split (total > capacity) branch: a near-capacity build
    makes the nearest sibling too full to merge into, so underflow must
    redistribute — and stay bitwise-equal to the host's minmax re-split."""
    X = clustered(300, dims=DIM, seed=3)
    tree = bulk_build(X, capacity=8, fill_frac=0.95)
    bd = MutationBatcher(tree)
    bh = MutationBatcher(tree, device_merges=False)
    order = np.random.default_rng(3).permutation(300)
    n_merge = 0
    for c in range(0, 160, 16):
        idx = order[c:c + 16].astype(np.int32)
        rd = bd.apply(np.full(16, OP_DELETE, np.int32), X[idx], idx)
        rh = bh.apply(np.full(16, OP_DELETE, np.int32), X[idx], idx)
        np.testing.assert_array_equal(rd.statuses, rh.statuses)
        n_merge += rd.n_merge
        _trees_equal(bd.tree, bh.tree, f"chunk at {c}")
    assert n_merge > 0
    SMTreeEngine(bd.tree).validate()


def test_cascade_to_root_collapse_and_singleton_root():
    """Delete down to a handful of objects: multi-level underflow cascades,
    merge-into-singleton-root and repeated on-device root collapse (height
    shrinks) — bitwise vs the engine's host path the whole way down."""
    X = uniform(260, dims=DIM, seed=4)
    tree = bulk_build(X, capacity=8)
    assert int(tree.height) >= 3, "need a deep tree for cascades"
    b = MutationBatcher(tree)
    eng = SMTreeEngine(tree)
    for i in range(254):
        r = b.apply(np.array([OP_DELETE], np.int32), X[i][None],
                    np.array([i], np.int32))
        assert (r.statuses == ST_APPLIED).all()
        assert eng.delete(X[i], i)
        _trees_equal(b.tree, eng.tree, f"delete {i}")
    assert int(b.tree.height) == 1, "root should have collapsed to a leaf"
    assert b.tree.n_objects == 6
    _check_ring(b.tree)
    SMTreeEngine(b.tree).validate()


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_interleaved_stream_device_merges_bitwise_transparent(seed):
    """Property: a delete-heavy mixed stream applied with device merges on
    == device merges off (host escalation), bitwise, with the live set
    exactly matching the log semantics."""
    rng = np.random.default_rng(seed)
    X = clustered(320, dims=DIM, seed=seed % 97)
    tree = bulk_build(X, capacity=8, seed=seed % 13)
    bd = MutationBatcher(tree, device_merges=True)
    bh = MutationBatcher(tree, device_merges=False)
    live = set(range(320))
    vec = {i: X[i] for i in range(320)}
    nid = 1000
    n_merge = 0
    for _ in range(3):
        ops, xs, oids, nid = _random_stream(rng, live, vec, nid, 48)
        rd = bd.apply(ops, xs, oids)
        rh = bh.apply(ops, xs, oids)
        np.testing.assert_array_equal(rd.statuses, rh.statuses)
        n_merge += rd.n_merge
        _trees_equal(bd.tree, bh.tree, f"seed {seed}")
    live_oids = sorted(
        int(o) for o in np.asarray(bd.tree.oid)[
            np.asarray(bd.tree.valid)
            & np.asarray(bd.tree.is_leaf)[:, None]
            & np.asarray(bd.tree.alive)[:, None]])
    assert live_oids == sorted(live)
    SMTreeEngine(bd.tree).validate()
    assert n_merge > 0, "delete-heavy workload never exercised the pass"


# ---------------------------------------------------------------------------
# pad-sentinel rows in merge chunks
# ---------------------------------------------------------------------------
def test_merge_chunk_pad_rows_inert():
    """Merge chunks pad with OP_NOP / oid -1; a planted sentinel-colliding
    entry must never be located, removed, or merged by a pad row."""
    X = uniform(200, dims=DIM, seed=5)
    tree = bulk_build(X, capacity=8)
    leaf = int(np.nonzero(np.asarray(tree.is_leaf)
                          & np.asarray(tree.alive))[0][0])
    lost = int(np.asarray(tree.oid)[leaf, 0])   # overwritten below
    tree = dataclasses.replace(tree, oid=tree.oid.at[leaf, 0].set(-1))
    planted_vec = np.asarray(tree.vecs)[leaf, 0].copy()
    # underflow deletes -> MERGE_CHUNK dispatches whose tails are pads
    b = MutationBatcher(tree)
    n_merge = 0
    for i in range(120):
        if i == lost:
            continue
        r = b.apply(np.array([OP_DELETE], np.int32),
                    X[i][None].astype(np.float32),
                    np.array([i], np.int32))
        assert (r.statuses == ST_APPLIED).all()
        n_merge += r.n_merge
    assert n_merge > 0, "no merge chunk (with pad rows) ever dispatched"
    # the planted entry survives wherever merges moved it (internal
    # entries carry oid -1 by design; only leaf rows can hold the plant)
    mask = ((np.asarray(b.tree.oid) == -1) & np.asarray(b.tree.valid)
            & np.asarray(b.tree.is_leaf)[:, None]
            & np.asarray(b.tree.alive)[:, None])
    assert mask.sum() == 1, "pad rows touched the sentinel-colliding entry"
    where = np.argwhere(mask)[0]
    np.testing.assert_array_equal(
        np.asarray(b.tree.vecs)[where[0], where[1]], planted_vec)
    # direct pad-shaped rows through apply_merges are pure NOPs
    t2, st = smtree.apply_merges(
        b.tree, np.full(smtree.MERGE_CHUNK, smtree.OP_NOP, np.int32),
        np.full(smtree.MERGE_CHUNK, -1, np.int32), donate=False)
    assert (np.asarray(st) == ST_NOP).all()
    _trees_equal(b.tree, t2, "NOP merge chunk mutated the tree")
    # an explicit OP_DELETE of oid -1 reports NOTFOUND, tree untouched
    t3, st3 = smtree.apply_merges(
        b.tree, np.array([OP_DELETE], np.int32),
        np.array([-1], np.int32), donate=False)
    assert int(np.asarray(st3)[0]) == ST_NOTFOUND
    _trees_equal(b.tree, t3, "oid -1 merge row mutated the tree")


# ---------------------------------------------------------------------------
# mesh collective parity (single-device main process; 8-shard drill lives
# in tests/_dist_worker.py::scenario_forest_device_merges)
# ---------------------------------------------------------------------------
@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10_000))
def test_forest_mesh_merges_match_host(seed):
    """Property: the mesh-resident StreamingForest (apply + split + merge
    collectives under shard_map) stays bitwise-equal to the host-centric
    batcher path on delete-heavy streams."""
    mesh = make_mesh((jax.device_count(),), ("model",))
    if mesh.shape["model"] != 1:
        pytest.skip("main-process test assumes a single host device")
    rng = np.random.default_rng(seed)
    X = clustered(260, dims=DIM, seed=seed % 89)
    sf_mesh = StreamingForest(
        [bulk_build(X, capacity=8, fill_frac=0.9, seed=1)], mesh=mesh)
    sf_host = StreamingForest(
        [bulk_build(X, capacity=8, fill_frac=0.9, seed=1)])
    live = set(range(260))
    vec = {i: X[i] for i in range(260)}
    nid = 5000
    n_merge = 0
    for _ in range(3):
        ops, xs, oids, nid = _random_stream(rng, live, vec, nid, 40)
        rm = sf_mesh.apply(ops, xs, oids)
        rh = sf_host.apply(ops, xs, oids)
        np.testing.assert_array_equal(rm.statuses, rh.statuses)
        assert (rm.statuses == ST_APPLIED).all()
        assert rm.n_merge == rh.n_merge
        n_merge += rm.n_merge
        for a, b in zip(sf_mesh.trees, sf_host.trees):
            _trees_equal(a, b, f"seed {seed}")
    assert sf_mesh.owner == sf_host.owner
    for t in sf_mesh.trees:
        SMTreeEngine(t).validate()


# ---------------------------------------------------------------------------
# ahead-of-time headroom growth
# ---------------------------------------------------------------------------
def test_grow_tree_ring_and_transparency():
    X = clustered(200, dims=DIM, seed=6)
    t0 = bulk_build(X, capacity=8)
    tg = grow_tree(t0)
    assert tg.max_nodes == 2 * t0.max_nodes
    _check_ring(tg)
    # new rows are dead, detached and leaf-typed (the host _grow layout)
    N = t0.max_nodes
    assert not np.asarray(tg.alive)[N:].any()
    assert (np.asarray(tg.parent)[N:] == -1).all()
    assert (np.asarray(tg.child)[N:] == -1).all()
    assert np.asarray(tg.is_leaf)[N:].all()
    # growth is behaviour-transparent: the same mutation stream lands
    # identically on the original (where it fits) and the grown tree
    bg = MutationBatcher(tg)
    bo = MutationBatcher(t0)
    ops = np.full(64, OP_INSERT, np.int32)
    xs = uniform(64, dims=DIM, seed=7)
    oids = np.arange(5000, 5064, dtype=np.int32)
    rg = bg.apply(ops, xs, oids)
    ro = bo.apply(ops, xs, oids)
    np.testing.assert_array_equal(rg.statuses, ro.statuses)
    for f in ("root", "height", "count", "oid", "valid"):
        a = np.asarray(getattr(bg.tree, f))
        b = np.asarray(getattr(bo.tree, f))
        np.testing.assert_array_equal(a[:N] if a.ndim else a,
                                      b[:N] if b.ndim else b, err_msg=f)
    SMTreeEngine(bg.tree).validate()


def test_streaming_engine_headroom_growth_preempts_exhaustion():
    """A tiny node table under sustained inserts: the watermark fires at a
    publish point, the table doubles, and no host escalation for ring
    exhaustion ever happens mid-batch."""
    X = clustered(120, dims=DIM, seed=8)
    tree = bulk_build(X, capacity=8, slack=1.1)
    eng = StreamingEngine(tree)
    n0 = eng.tree.max_nodes
    fresh = uniform(640, dims=DIM, seed=9)
    for c in range(0, 640, 64):
        r = eng.insert_batch(fresh[c:c + 64],
                             np.arange(1000 + c, 1064 + c, dtype=np.int32))
        assert (r.statuses == ST_APPLIED).all()
    assert eng.n_grows >= 1, "watermark never fired"
    assert eng.tree.max_nodes > n0
    assert not needs_headroom(eng.tree)
    assert eng.tree.n_objects == 120 + 640
    _check_ring(eng.tree)
    SMTreeEngine(eng.tree).validate()


def test_headroom_watermark_floor():
    # the floor (MAX_HEIGHT + 1, the worst case one overflow row can
    # allocate) applies even at frac=0: a 16-row table can never hold it
    t = bulk_build(uniform(60, dims=DIM, seed=10), capacity=8, slack=1.05)
    assert t.max_nodes - int(t.free_head) >= 0
    assert int(t.free_head) < MAX_HEIGHT + 1 <= t.max_nodes + 1
    assert needs_headroom(t, frac=0.0)


def test_streaming_forest_growth_bitwise_across_modes(tmp_path):
    """Host-mode and mesh-mode StreamingForests grow at identical points
    (same watermark reads), so they stay bitwise-interchangeable; WAL
    replay after a snapshot reproduces the grown geometry exactly."""
    from repro.dist.checkpoint import CheckpointManager
    from repro.stream import WriteAheadLog
    mesh = make_mesh((jax.device_count(),), ("model",))
    if mesh.shape["model"] != 1:
        pytest.skip("main-process test assumes a single host device")
    X = clustered(100, dims=DIM, seed=11)

    def build():
        return [bulk_build(X, capacity=8, slack=1.1)]

    sf_mesh = StreamingForest(build(), mesh=mesh)
    sf_host = StreamingForest(build())
    wal = WriteAheadLog(str(tmp_path / "wal"))
    ckpt = CheckpointManager(str(tmp_path / "ckpt"), async_write=False)
    sf_wal = StreamingForest(build(), wal=wal, ckpt=ckpt)
    fresh = uniform(512, dims=DIM, seed=12)
    for c in range(0, 512, 64):
        oids = np.arange(2000 + c, 2064 + c, dtype=np.int32)
        rm = sf_mesh.insert_batch(fresh[c:c + 64], oids)
        rh = sf_host.insert_batch(fresh[c:c + 64], oids)
        sf_wal.insert_batch(fresh[c:c + 64], oids)
        np.testing.assert_array_equal(rm.statuses, rh.statuses)
    assert sf_mesh.n_grows == sf_host.n_grows >= 1
    for a, b in zip(sf_mesh.trees, sf_host.trees):
        _trees_equal(a, b, "growth diverged across control-plane modes")
    sf_wal.snapshot()
    restored = StreamingForest.restore(str(tmp_path / "ckpt"), wal=wal)
    for a, b in zip(sf_wal.trees, restored.trees):
        _trees_equal(a, b, "snapshot restore lost grown geometry")


def test_packed_free_list_roundtrip_after_push():
    """_push_free inserts at the sorted position (property, pure jit)."""
    alive = np.ones(32, bool)
    dead = [3, 7, 19, 28]
    for d in dead:
        alive[d] = False
    fl, fh = packed_free_list(alive)
    t = smtree.empty_tree(dim=2, capacity=4, max_nodes=32)
    t = dataclasses.replace(
        t, free_list=jax.numpy.asarray(fl), free_head=jax.numpy.asarray(fh))
    for f in (12, 1, 30, 5):
        t = smtree._push_free(t, jax.numpy.int32(f), jax.numpy.asarray(True))
        alive[f] = False
        want_fl, want_fh = packed_free_list(alive)
        np.testing.assert_array_equal(np.asarray(t.free_list), want_fl)
        assert int(t.free_head) == want_fh
