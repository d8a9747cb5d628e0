"""PR-4 device split pass + free-list allocator tests.

The contract under test: single-level leaf splits resolved on device
(``smtree.apply_splits`` / the ``forest_apply_splits`` collective) are
**bitwise-transparent** — applying a mutation log with device splits on
yields exactly the tree the host escalation path produces, because the
device pass replays ``_HostView.insert_with_split`` decision-for-decision
(same mM_RAD promotion tie-breaks, same sequential-rebalance member order,
same lowest-free-id allocation) and the escalation ladder preserves log
order around the rows it cannot absorb.

Also covered: the packed free-ring invariants, negative-oid boundary
rejection, and the pad-row sentinel hardening (a stored sentinel-colliding
id can never be touched by a pad row).
"""
import dataclasses

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import smtree
from repro.core.engine import SMTreeEngine
from repro.core.metric import pairwise
from repro.dist.sharding import make_mesh
from repro.core.smtree import (OP_DELETE, OP_INSERT, ST_APPLIED, ST_NOTFOUND,
                               bulk_build, empty_tree, packed_free_list)
from repro.data.datagen import clustered, uniform
from repro.stream import StreamingEngine, StreamingForest
from repro.stream.batcher import MutationBatcher

DIM = 5


def _trees_equal(a, b, msg=""):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=msg)


def _live_oids(tree):
    mask = (np.asarray(tree.valid) & np.asarray(tree.is_leaf)[:, None]
            & np.asarray(tree.alive)[:, None])
    return sorted(int(o) for o in np.asarray(tree.oid)[mask])


def _random_stream(rng, live, vec, nid, n, del_frac=0.4):
    """Mixed log over the mutable live-set bookkeeping (log order applies
    insert-then-delete of the same id correctly)."""
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
# free-ring invariants
# ---------------------------------------------------------------------------
def _check_ring(tree):
    fl = np.asarray(tree.free_list)
    fh = int(tree.free_head)
    want = np.nonzero(~np.asarray(tree.alive))[0][::-1]
    assert fh == len(want)
    np.testing.assert_array_equal(fl[:fh], want)
    assert (fl[fh:] == -1).all()


def test_free_ring_empty_and_bulk():
    _check_ring(empty_tree(dim=DIM, capacity=8, max_nodes=64))
    _check_ring(bulk_build(uniform(300, dims=DIM, seed=1), capacity=8))


def test_free_ring_after_host_edits():
    """Host merges free nodes; to_tree must repack the ring (descending,
    -1 beyond) so subsequent device pops keep matching host allocs."""
    X = uniform(250, dims=DIM, seed=2)
    eng = SMTreeEngine.build(X, capacity=8)
    for i in range(200):
        assert eng.delete(X[i], i)
    assert eng.tree.n_free_nodes > 0
    _check_ring(eng.tree)
    # refill through splits (device + host) and re-check
    b = MutationBatcher(eng.tree)
    fresh = uniform(200, dims=DIM, seed=3)
    r = b.apply(np.full(200, OP_INSERT, np.int32), fresh,
                np.arange(1000, 1200, dtype=np.int32))
    assert (r.statuses == ST_APPLIED).all()
    _check_ring(b.tree)
    SMTreeEngine(b.tree).validate()


def test_device_split_pops_lowest_free_id():
    """The ring is descending, so the device allocates the same node id the
    host's lowest-free-index alloc would — pinned here directly."""
    X = clustered(300, dims=DIM, seed=4)
    tree = bulk_build(X, capacity=8, fill_frac=0.95)
    lowest_free = int(np.nonzero(~np.asarray(tree.alive))[0][0])
    assert int(tree.free_list[tree.free_head - 1]) == lowest_free


# ---------------------------------------------------------------------------
# device split == host split, bitwise
# ---------------------------------------------------------------------------
def test_single_overflow_insert_bitwise():
    """Single inserts aimed at full leaves: batcher (device split) vs
    SMTreeEngine.insert (host split) must agree bitwise op-for-op, and at
    least one op must resolve as a device split."""
    X = clustered(300, dims=DIM, seed=5)
    tree = bulk_build(X, capacity=8, fill_frac=0.95)
    near_full = np.nonzero((np.asarray(tree.count) >= 7)
                           & np.asarray(tree.is_leaf)
                           & np.asarray(tree.alive))[0]
    assert len(near_full), "build produced no near-full leaf"
    b = MutationBatcher(tree)
    eng = SMTreeEngine(tree)
    n_split = 0
    oid = 9000
    for leaf in near_full[:4]:
        for j in range(3):   # fill the leaf, then overflow it
            x = np.asarray(tree.vecs)[leaf, 0] + 1e-4 * (j + 1)
            r = b.apply(np.array([OP_INSERT], np.int32), x[None],
                        np.array([oid], np.int32))
            assert (r.statuses == ST_APPLIED).all()
            n_split += r.n_split
            eng.insert(x, oid)
            _trees_equal(b.tree, eng.tree, "device split != host split")
            oid += 1
    assert n_split > 0, "no insert resolved as a device split"
    SMTreeEngine(b.tree).validate()


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10_000))
def test_interleaved_stream_device_splits_bitwise_transparent(seed):
    """Property: a mixed insert/delete stream (near-capacity tree, heavy
    split pressure) applied with device splits on == device splits off,
    bitwise, with the live set exactly matching the log semantics."""
    rng = np.random.default_rng(seed)
    X = clustered(350, dims=DIM, seed=seed % 97)
    tree = bulk_build(X, capacity=8, fill_frac=0.95, seed=seed % 13)
    bd = MutationBatcher(tree, device_splits=True)
    bh = MutationBatcher(tree, device_splits=False)
    live = set(range(350))
    vec = {i: X[i] for i in range(350)}
    nid = 1000
    n_split = 0
    for _ in range(3):
        ops, xs, oids, nid = _random_stream(rng, live, vec, nid, 48)
        rd = bd.apply(ops, xs, oids)
        rh = bh.apply(ops, xs, oids)
        np.testing.assert_array_equal(rd.statuses, rh.statuses)
        n_split += rd.n_split
        _trees_equal(bd.tree, bh.tree, f"seed {seed}")
    assert _live_oids(bd.tree) == sorted(live)
    SMTreeEngine(bd.tree).validate()
    # the workload is near-capacity: the device pass must actually fire
    assert n_split > 0


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000))
def test_forest_mesh_matches_host_and_reference(seed):
    """Property: the mesh-resident StreamingForest (collective apply +
    device-split collective under shard_map) stays bitwise-equal to the
    host-centric batcher path, and both match brute force over the live
    set — exact queries, correct semantics vs the one-at-a-time log."""
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
    for _ in range(3):
        ops, xs, oids, nid = _random_stream(rng, live, vec, nid, 40)
        rm = sf_mesh.apply(ops, xs, oids)
        rh = sf_host.apply(ops, xs, oids)
        np.testing.assert_array_equal(rm.statuses, rh.statuses)
        assert (rm.statuses == ST_APPLIED).all()
        for a, b in zip(sf_mesh.trees, sf_host.trees):
            _trees_equal(a, b, f"seed {seed}")
    assert sf_mesh.owner == sf_host.owner
    for t in sf_mesh.trees:
        SMTreeEngine(t).validate()
    assert sorted(sf_mesh.owner) == sorted(live)
    # exact retrieval over the final live set
    lv = np.stack([vec[o] for o in sorted(live)])
    Q = lv[rng.integers(0, len(lv), 8)] + 0.002
    d, _ = sf_mesh.knn(Q, k=3, max_frontier=512)
    want = np.sort(pairwise("d_inf", Q, lv), axis=1)[:, :3]
    np.testing.assert_allclose(d, want, atol=1e-5)


# ---------------------------------------------------------------------------
# negative oids + pad-row sentinel hardening
# ---------------------------------------------------------------------------
def test_negative_oid_rejected_at_boundaries(tmp_path):
    X = uniform(100, dims=DIM, seed=6)
    tree = bulk_build(X, capacity=8)
    xs = np.zeros((1, DIM), np.float32)
    bad = np.array([-3], np.int32)
    with pytest.raises(ValueError, match="negative"):
        MutationBatcher(tree).apply(np.array([OP_INSERT], np.int32), xs, bad)
    eng = StreamingEngine(tree)
    with pytest.raises(ValueError, match="negative"):
        eng.insert_batch(xs, bad)
    sf = StreamingForest([tree])
    with pytest.raises(ValueError, match="negative"):
        sf.delete_batch(xs, bad)
    # a rejected batch must not have been WAL-framed
    from repro.stream import WriteAheadLog, iter_wal
    wal = WriteAheadLog(str(tmp_path / "wal"))
    eng2 = StreamingEngine(tree, wal=wal)
    with pytest.raises(ValueError, match="negative"):
        eng2.insert_batch(xs, bad)
    wal.close()
    assert list(iter_wal(str(tmp_path / "wal"))) == []


def test_forest_apply_mutations_validate_flag():
    from repro.core.distributed import forest_apply_mutations, stack_trees
    mesh = make_mesh((jax.device_count(),), ("model",))
    if mesh.shape["model"] != 1:
        pytest.skip("main-process test assumes a single host device")
    X = uniform(120, dims=DIM, seed=7)
    forest = stack_trees([bulk_build(X, capacity=8)])
    xs = np.zeros((2, DIM), np.float32)
    owner = np.zeros(2, np.int32)
    dup = np.array([5, 5], np.int32)
    neg = np.array([3, -1], np.int32)
    ops = np.full(2, OP_DELETE, np.int32)
    with pytest.raises(ValueError, match="unique"):
        forest_apply_mutations(forest, mesh, ops, xs, dup, owner,
                               validate=True)
    with pytest.raises(ValueError, match="negative"):
        forest_apply_mutations(forest, mesh, ops, xs, neg, owner,
                               validate=True)
    # default: no validation, duplicate-free batch applies fine
    out, st = forest_apply_mutations(forest, mesh, ops, xs,
                                     np.array([5, 6], np.int32), owner)
    assert (np.asarray(st) == ST_APPLIED).all()


def test_pad_rows_cannot_touch_sentinel_colliding_entry():
    """Plant an oid == -1 entry (bypassing the boundary check, as a
    corrupted upstream could) and verify NOP pad rows — which carry the -1
    sentinel — never locate, delete, or swap it."""
    X = uniform(90, dims=DIM, seed=8)
    tree = bulk_build(X, capacity=8)
    leaf = int(np.nonzero(np.asarray(tree.is_leaf)
                          & np.asarray(tree.alive))[0][0])
    tree = dataclasses.replace(tree, oid=tree.oid.at[leaf, 0].set(-1))
    n_before = tree.n_objects
    b = MutationBatcher(tree)
    # 3 rows pad to a 4-bucket: one pad row with oid -1 rides along
    ops = np.full(3, OP_INSERT, np.int32)
    r = b.apply(ops, uniform(3, dims=DIM, seed=9),
                np.array([500, 501, 502], np.int32))
    assert (r.statuses == ST_APPLIED).all()
    assert b.tree.n_objects == n_before + 3
    assert int(np.asarray(b.tree.oid)[leaf, 0]) == -1, \
        "pad row clobbered the sentinel-colliding entry"
    # an explicit delete of -1 through the jitted path reports NOTFOUND
    t2, st = smtree.apply_mutations(b.tree, np.array([OP_DELETE], np.int32),
                                    np.zeros((1, DIM), np.float32),
                                    np.array([-1], np.int32))
    assert int(np.asarray(st)[0]) == ST_NOTFOUND
    assert int(np.asarray(t2.oid)[leaf, 0]) == -1


def test_delete_fast_ignores_negative_ids():
    from repro.core.smtree import delete_fast
    X = uniform(80, dims=DIM, seed=10)
    tree = bulk_build(X, capacity=8)
    leaf = int(np.nonzero(np.asarray(tree.is_leaf)
                          & np.asarray(tree.alive))[0][0])
    tree = dataclasses.replace(tree, oid=tree.oid.at[leaf, 0].set(-1))
    _, found, _, _ = delete_fast(tree, np.zeros(DIM, np.float32),
                                 np.int32(-1))
    assert not bool(found)


# ---------------------------------------------------------------------------
# epoch read-path pinning
# ---------------------------------------------------------------------------
def test_reading_context_manager_pins_and_releases():
    from repro.stream import EpochManager
    mgr = EpochManager("v0")
    with mgr.reading() as t:
        assert t == "v0"
        mgr.publish("v1")
        mgr.publish("v2")
        # the pinned version survives both publishes
        assert 0 in mgr.resident
    # released on exit: superseded version retired
    assert mgr.resident == [2]
    with pytest.raises(RuntimeError):
        with mgr.reading():
            raise RuntimeError("reader crashed")
    assert mgr.resident == [2]   # pin released despite the exception


def test_packed_free_list_helper():
    alive = np.array([True, False, True, False, False])
    fl, fh = packed_free_list(alive)
    assert fh == 3
    np.testing.assert_array_equal(fl, [4, 3, 1, -1, -1])
