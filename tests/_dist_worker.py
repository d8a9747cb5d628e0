"""Multi-device scenarios run in a subprocess with 8 host CPU devices.
Invoked by tests/test_distributed.py: python _dist_worker.py <scenario>.
Prints 'PASS <scenario>' on success."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import use_mesh as _use_mesh  # noqa: E402
from repro.dist.sharding import make_mesh  # noqa: E402


def scenario_forest_knn():
    from repro.core.distributed import build_forest, brute_force_knn, forest_knn
    from repro.core.metric import pairwise
    mesh = make_mesh((2, 4), ("data", "model"))
    X = np.random.default_rng(0).random((4000, 8)).astype(np.float32)
    Q = np.random.default_rng(1).random((16, 8)).astype(np.float32)
    forest, _ = build_forest(X, mesh, capacity=16)
    with _use_mesh(mesh):
        d, ids = forest_knn(forest, mesh, jnp.asarray(Q), k=5,
                            max_frontier=256)
    D = pairwise("d_inf", Q, X)
    want = np.sort(D, axis=1)[:, :5]
    np.testing.assert_allclose(np.asarray(d), want, atol=1e-5)
    # ids must point at actual matching-distance objects
    got_d = np.take_along_axis(D, np.asarray(ids), axis=1)
    np.testing.assert_allclose(got_d, want, atol=1e-5)


def scenario_forest_brute_matches_tree():
    from repro.core.distributed import build_forest, brute_force_knn, forest_knn
    mesh = make_mesh((2, 4), ("data", "model"))
    X = np.random.default_rng(3).random((2048, 16)).astype(np.float32)
    Q = np.random.default_rng(4).random((8, 16)).astype(np.float32)
    forest, _ = build_forest(X, mesh, capacity=16)
    with _use_mesh(mesh):
        d1, _ = forest_knn(forest, mesh, jnp.asarray(Q), k=3, max_frontier=256)
        Xs = jax.device_put(jnp.asarray(X), jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("model")))
        d2, _ = brute_force_knn(Xs, mesh, jnp.asarray(Q), k=3)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-5)


def scenario_forest_delete():
    from repro.core.distributed import build_forest, forest_delete, forest_knn
    mesh = make_mesh((1, 8), ("data", "model"))
    X = np.random.default_rng(5).random((4096, 8)).astype(np.float32)
    forest, _ = build_forest(X, mesh, capacity=16)
    victims = np.arange(0, 256)
    with _use_mesh(mesh):
        forest, found = forest_delete(
            forest, mesh, jnp.asarray(X[victims]),
            jnp.asarray(victims, jnp.int32))
        d, ids = forest_knn(forest, mesh, jnp.asarray(X[victims][:16]), k=1,
                            max_frontier=256)
    assert np.asarray(found).mean() > 0.9, "most deletes should hit fast path"
    # deleted points must no longer be their own nearest neighbour at d=0
    ids = np.asarray(ids)[:, 0]
    found_np = np.asarray(found)[:16]
    for i in range(16):
        if found_np[i]:
            assert ids[i] != victims[i], f"victim {victims[i]} still present"


def scenario_forest_stream():
    """Batched mutation hook under shard_map: owner-routed insert/delete
    batches through the fused apply_mutations scan, then exact kNN via the
    static-height cohort fast path."""
    from repro.core.distributed import (build_forest, common_static_height,
                                        forest_apply_mutations, forest_knn)
    from repro.core.metric import pairwise
    from repro.core.smtree import OP_DELETE, OP_INSERT, ST_APPLIED
    mesh = make_mesh((1, 8), ("data", "model"))
    rng = np.random.default_rng(9)
    X = rng.random((4096, 8)).astype(np.float32)
    forest, _ = build_forest(X, mesh, capacity=16)
    assert common_static_height(forest) is not None, \
        "balanced round-robin build should give equal shard heights"
    # mixed batch: delete 128 existing (owner = oid % 8), insert 64 new
    victims = np.arange(0, 896, 7)           # 128 ids covering all 8 shards
    new_ids = 4096 + np.arange(64)
    ops = np.concatenate([np.full(128, OP_DELETE), np.full(64, OP_INSERT)])
    oids = np.concatenate([victims, new_ids]).astype(np.int32)
    xs = np.concatenate([X[victims],
                         rng.random((64, 8)).astype(np.float32)])
    owner = oids % 8
    with _use_mesh(mesh):
        forest, status = forest_apply_mutations(
            forest, mesh, jnp.asarray(ops, jnp.int32), jnp.asarray(xs),
            jnp.asarray(oids), jnp.asarray(owner, jnp.int32))
        status = np.asarray(status)
        assert (status == ST_APPLIED).mean() > 0.9, np.bincount(status)
        d, ids = forest_knn(forest, mesh, jnp.asarray(xs[-64:]), k=1,
                            max_frontier=256)
    # the fresh inserts that applied must be findable at distance 0
    ok = status[128:] == ST_APPLIED
    d = np.asarray(d)[:, 0]
    ids0 = np.asarray(ids)[:, 0]
    assert ok.any()
    np.testing.assert_allclose(d[ok], 0.0, atol=1e-6)
    assert (ids0[ok] == new_ids[ok]).all()


def scenario_forest_device_splits():
    """Mesh-resident mutation control plane on 8 shards: near-capacity
    bulk builds force leaf splits, the StreamingForest mesh path resolves
    them through the forest_apply_splits collective, and every shard stays
    bitwise-equal to the host-centric batcher path."""
    from repro.core.distributed import build_forest_trees
    from repro.core.engine import SMTreeEngine
    from repro.core.smtree import OP_DELETE, OP_INSERT, ST_APPLIED
    from repro.stream import StreamingForest
    mesh = make_mesh((8,), ("model",))
    rng = np.random.default_rng(17)
    X = rng.random((2048, 6)).astype(np.float32)

    def build():
        return [t for t in build_forest_trees(X, 8, capacity=8)]

    sf_mesh = StreamingForest(build(), mesh=mesh)
    sf_host = StreamingForest(build())
    live = set(range(2048))
    vec = {i: X[i] for i in range(2048)}
    nid = 10_000
    n_split = 0
    with _use_mesh(mesh):
        for step in range(5):
            ops, xs, oids = [], [], []
            for _ in range(128):
                if live and rng.random() < 0.2:
                    v = int(sorted(live)[rng.integers(len(live))])
                    live.discard(v)
                    ops.append(OP_DELETE)
                    oids.append(v)
                    xs.append(vec[v])
                else:
                    ops.append(OP_INSERT)
                    oids.append(nid)
                    v = rng.random(6).astype(np.float32)
                    xs.append(v)
                    vec[nid] = v
                    live.add(nid)
                    nid += 1
            ops = np.array(ops, np.int32)
            xs = np.stack(xs).astype(np.float32)
            oids = np.array(oids, np.int32)
            rm = sf_mesh.apply(ops, xs, oids)
            rh = sf_host.apply(ops, xs, oids)
            assert (rm.statuses == rh.statuses).all(), step
            assert (rm.statuses == ST_APPLIED).all(), np.bincount(rm.statuses)
            n_split += rm.n_split
            assert rm.n_split == rh.n_split, (rm.n_split, rh.n_split)
            for s, (a, b) in enumerate(zip(sf_mesh.trees, sf_host.trees)):
                for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                    np.testing.assert_array_equal(
                        np.asarray(la), np.asarray(lb),
                        err_msg=f"shard {s} diverged at step {step}")
    assert n_split > 0, "workload never exercised a device split"
    assert sf_mesh.owner == sf_host.owner
    for t in sf_mesh.trees:
        SMTreeEngine(t).validate()


def scenario_forest_device_merges():
    """Delete-heavy mesh drill on 8 shards: underflow merges resolve
    through the forest_apply_merges collective (zero host escalations),
    every shard stays bitwise-equal to the host-centric batcher path, and
    the packed free ring keeps matching the wholesale recompute after
    device pushes."""
    from repro.core.distributed import build_forest_trees
    from repro.core.engine import SMTreeEngine
    from repro.core.smtree import OP_DELETE, OP_INSERT, ST_APPLIED
    from repro.core.smtree import packed_free_list
    from repro.stream import StreamingForest
    mesh = make_mesh((8,), ("model",))
    rng = np.random.default_rng(23)
    X = rng.random((2048, 6)).astype(np.float32)

    def build():
        return [t for t in build_forest_trees(X, 8, capacity=8)]

    sf_mesh = StreamingForest(build(), mesh=mesh)
    sf_host = StreamingForest(build())
    live = set(range(2048))
    vec = {i: X[i] for i in range(2048)}
    nid = 10_000
    n_merge = 0
    with _use_mesh(mesh):
        for step in range(5):
            ops, xs, oids = [], [], []
            for _ in range(128):
                if live and rng.random() < 0.75:
                    v = int(sorted(live)[rng.integers(len(live))])
                    live.discard(v)
                    ops.append(OP_DELETE)
                    oids.append(v)
                    xs.append(vec[v])
                else:
                    ops.append(OP_INSERT)
                    oids.append(nid)
                    v = rng.random(6).astype(np.float32)
                    xs.append(v)
                    vec[nid] = v
                    live.add(nid)
                    nid += 1
            ops = np.array(ops, np.int32)
            xs = np.stack(xs).astype(np.float32)
            oids = np.array(oids, np.int32)
            rm = sf_mesh.apply(ops, xs, oids)
            rh = sf_host.apply(ops, xs, oids)
            assert (rm.statuses == rh.statuses).all(), step
            assert (rm.statuses == ST_APPLIED).all(), np.bincount(rm.statuses)
            assert rm.n_escalated == 0, \
                f"device merges must absorb all underflows, step {step}"
            assert rm.n_merge == rh.n_merge, (rm.n_merge, rh.n_merge)
            n_merge += rm.n_merge
            for s, (a, b) in enumerate(zip(sf_mesh.trees, sf_host.trees)):
                for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                    np.testing.assert_array_equal(
                        np.asarray(la), np.asarray(lb),
                        err_msg=f"shard {s} diverged at step {step}")
    assert n_merge > 0, "workload never exercised a device merge"
    for t in sf_mesh.trees:
        fl, fh = packed_free_list(np.asarray(t.alive))
        np.testing.assert_array_equal(np.asarray(t.free_list), fl)
        assert int(t.free_head) == int(fh)
        SMTreeEngine(t).validate()


def scenario_forest_knn_cohort_parity():
    """forest_knn static-height cohort path == per-query fallback."""
    from repro.core.distributed import build_forest, forest_knn
    mesh = make_mesh((2, 4), ("data", "model"))
    X = np.random.default_rng(12).random((2048, 8)).astype(np.float32)
    Q = np.random.default_rng(13).random((16, 8)).astype(np.float32)
    forest, _ = build_forest(X, mesh, capacity=16)
    with _use_mesh(mesh):
        d1, i1 = forest_knn(forest, mesh, jnp.asarray(Q), k=4,
                            max_frontier=256)
        os.environ["REPRO_FRONTIER_IMPL"] = "perquery"
        try:
            d2, i2 = forest_knn(forest, mesh, jnp.asarray(Q), k=4,
                                max_frontier=256)
        finally:
            del os.environ["REPRO_FRONTIER_IMPL"]
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-6)


def scenario_forest_parent_prune_parity():
    """8-shard mesh forest: kNN with the parent-distance pre-filter on is
    bitwise identical to the unpruned collective — both via the explicit
    kwarg and via the REPRO_PARENT_PRUNE env toggle."""
    from repro.core.distributed import build_forest, forest_knn
    mesh = make_mesh((1, 8), ("data", "model"))
    X = np.random.default_rng(41).random((4096, 8)).astype(np.float32)
    # near-data queries (the regime where the filter actually fires)
    Q = (X[:32] + np.random.default_rng(42)
         .normal(0, 0.01, (32, 8))).astype(np.float32)
    forest, _ = build_forest(X, mesh, capacity=16)
    with _use_mesh(mesh):
        d_on, i_on = forest_knn(forest, mesh, jnp.asarray(Q), k=5,
                                max_frontier=64, parent_prune=True)
        d_off, i_off = forest_knn(forest, mesh, jnp.asarray(Q), k=5,
                                  max_frontier=64, parent_prune=False)
        os.environ["REPRO_PARENT_PRUNE"] = "0"
        try:
            d_env, i_env = forest_knn(forest, mesh, jnp.asarray(Q), k=5,
                                      max_frontier=64)
        finally:
            del os.environ["REPRO_PARENT_PRUNE"]
    np.testing.assert_array_equal(np.asarray(d_on), np.asarray(d_off))
    np.testing.assert_array_equal(np.asarray(i_on), np.asarray(i_off))
    np.testing.assert_array_equal(np.asarray(d_env), np.asarray(d_off))
    np.testing.assert_array_equal(np.asarray(i_env), np.asarray(i_off))


def scenario_replica_forest_mesh():
    """WAL-shipping follower of a StreamingForest: tails the leader's
    segments on host, verifies bitwise equality by digest exchange, then
    places its shards on the mesh (place_forest) and serves exact kNN
    through the same forest_knn collectives as the leader."""
    import tempfile
    from repro.core.distributed import (build_forest_trees, forest_knn,
                                        place_forest)
    from repro.core.metric import pairwise
    from repro.core.smtree import OP_DELETE, OP_INSERT, ST_APPLIED
    from repro.stream import (Replica, StreamingForest, WriteAheadLog,
                              ledger_digest)
    mesh = make_mesh((8,), ("model",))
    rng = np.random.default_rng(29)
    X = rng.random((2048, 8)).astype(np.float32)
    live = set(range(2048))
    vec = {i: X[i] for i in range(2048)}
    nid = 10_000
    with tempfile.TemporaryDirectory() as d:
        wal_dir = os.path.join(d, "wal")
        leader = StreamingForest(build_forest_trees(X, 8, capacity=8),
                                 wal=WriteAheadLog(wal_dir,
                                                   segment_max_records=2))
        rep = Replica(StreamingForest(build_forest_trees(X, 8, capacity=8)),
                      wal_dir)
        for _ in range(3):
            ops, xs, oids = [], [], []
            for _ in range(128):
                if live and rng.random() < 0.4:
                    v = int(sorted(live)[rng.integers(len(live))])
                    live.discard(v)
                    ops.append(OP_DELETE)
                    oids.append(v)
                    xs.append(vec[v])
                else:
                    x = rng.random(8).astype(np.float32)
                    vec[nid] = x
                    live.add(nid)
                    ops.append(OP_INSERT)
                    oids.append(nid)
                    xs.append(x)
                    nid += 1
            res = leader.apply(np.array(ops, np.int32),
                               np.stack(xs).astype(np.float32),
                               np.array(oids, np.int32))
            assert (res.statuses == ST_APPLIED).all()
        seq, dg = ledger_digest(leader)
        rep.verify(seq, dg)                # bitwise, or DigestMismatch
        # read fan-out: the follower's published epoch goes mesh-resident
        with rep.epochs.reading() as shards:
            forest = place_forest(list(shards), mesh)
            Q = np.stack([vec[o] for o in sorted(live)[:16]]) + 0.003
            with _use_mesh(mesh):
                d_got, ids = forest_knn(forest, mesh,
                                        jnp.asarray(Q, jnp.float32), k=3,
                                        max_frontier=256)
        keys = np.stack([vec[o] for o in sorted(live)])
        want = np.sort(pairwise(shards[0].metric, Q, keys), axis=1)[:, :3]
        np.testing.assert_allclose(np.asarray(d_got), want, atol=1e-5)


def scenario_promote_follower_mesh():
    """Full failover into the mesh: a socket-shipped forest follower
    drains a dead leader's tail, is promoted under a new fencing token
    (stream.lease), and its verified epoch goes mesh-resident via
    core.distributed.promote_follower — then serves exact kNN through
    the same collectives, and accepts fenced appends as the new leader."""
    import tempfile
    from repro.core.distributed import (build_forest_trees, forest_knn,
                                        promote_follower)
    from repro.core.metric import pairwise
    from repro.core.smtree import ST_APPLIED
    from repro.stream import (StreamingForest, WriteAheadLog, ledger_digest)
    from repro.stream.lease import FenceGuard, LeaseStore, promote
    from repro.stream.transport import ShippedReplica, WalShipServer

    class _Clock:
        t = 0.0

        def __call__(self):
            return self.t

    mesh = make_mesh((8,), ("model",))
    rng = np.random.default_rng(31)
    X = rng.random((2048, 8)).astype(np.float32)
    vec = {i: X[i] for i in range(2048)}
    with tempfile.TemporaryDirectory() as d:
        clock = _Clock()
        store = LeaseStore(os.path.join(d, "lease"), ttl_s=5.0, clock=clock)
        grant = store.try_acquire("leader")
        wal_dir = os.path.join(d, "wal")
        wal = WriteAheadLog(wal_dir, segment_max_records=2,
                            fence=FenceGuard(store, "leader", grant.token))
        leader = StreamingForest(build_forest_trees(X, 8, capacity=8),
                                 wal=wal)
        srv = WalShipServer(wal_dir, wal=wal).start()
        rep = ShippedReplica(
            StreamingForest(build_forest_trees(X, 8, capacity=8)),
            srv.address, os.path.join(d, "mirror"))
        nid = 10_000
        for i in range(3):
            xs = rng.random((64, 8)).astype(np.float32)
            oids = np.arange(nid, nid + 64, dtype=np.int32)
            for o, x in zip(oids, xs):
                vec[int(o)] = x
            nid += 64
            res = leader.insert_batch(xs, oids)
            assert (res.statuses == ST_APPLIED).all()
        seq, dg = ledger_digest(leader)
        wal.close()                      # leader dies; disk + server live
        clock.t = 6.0
        promo = promote(rep, store, "follower-1", target=(seq, dg))
        assert promo.lease.token > grant.token
        forest, epoch = promote_follower(rep, mesh, expect=(seq, dg))
        live = sorted(vec)
        Q = np.stack([vec[o] for o in live[:16]]) + 0.003
        with _use_mesh(mesh):
            d_got, ids = forest_knn(forest, mesh,
                                    jnp.asarray(Q, jnp.float32), k=3,
                                    max_frontier=256)
        keys = np.stack([vec[o] for o in live])
        with rep.epochs.reading() as shards:
            metric = shards[0].metric
        want = np.sort(pairwise(metric, Q, keys), axis=1)[:, :3]
        np.testing.assert_allclose(np.asarray(d_got), want, atol=1e-5)
        # the promoted follower leads: appends land under the new fence
        rep.follower.insert_batch(rng.random((4, 8)).astype(np.float32),
                                  np.arange(90_000, 90_004, dtype=np.int32))
        assert promo.wal.next_seq == seq + 2
        rep.stop()
        srv.stop()


def scenario_train_step_sharded():
    """2x4 mesh end-to-end: sharded train step runs and loss decreases."""
    import dataclasses
    from repro.configs.all_archs import smoke_config
    from repro.data.pipeline import DataConfig, synth_batch
    from repro.models import model as M
    from repro.train.train_step import TrainSettings, make_train_step, init_all
    from repro.train.optimizer import AdamWConfig

    cfg = dataclasses.replace(smoke_config("qwen2.5-3b"), n_layers=2,
                              block_pattern=("attn",))
    mesh = make_mesh((2, 4), ("data", "model"))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8)
    batch0 = synth_batch(dc, 0)
    inputs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
              for k, v in batch0.items()}
    settings = TrainSettings(opt=AdamWConfig(lr=1e-2, warmup_steps=2,
                                             total_steps=50))
    with _use_mesh(mesh):
        step_fn, sh = make_train_step(cfg, mesh, inputs, settings)
        params, opt = init_all(cfg, jax.random.PRNGKey(0))
        params = jax.device_put(params, sh["params"])
        opt = jax.device_put(opt, sh["opt"])
        jitted = jax.jit(step_fn,
                         in_shardings=(sh["params"], sh["opt"], sh["batch"]),
                         out_shardings=(sh["params"], sh["opt"], sh["metrics"]),
                         donate_argnums=(0, 1))
        losses = []
        for step in range(8):
            batch = jax.device_put(synth_batch(dc, step), sh["batch"])
            params, opt, metrics = jitted(params, opt, batch)
            losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], f"no learning: {losses}"


def scenario_elastic_reshard():
    """Checkpoint written under a 2x4 mesh restores onto 1x8 and 4x2."""
    import dataclasses, tempfile
    from repro.configs.all_archs import smoke_config
    from repro.dist.checkpoint import restore_checkpoint, save_checkpoint
    from repro.dist import sharding as shd
    from repro.models import model as M

    cfg = smoke_config("qwen2.5-3b")
    params = M.init_params(cfg, jax.random.PRNGKey(7))
    mesh_a = make_mesh((2, 4), ("data", "model"))
    spec_a = shd.param_pspecs(cfg, params, mesh_a)
    pa = jax.device_put(params, shd.to_named(spec_a, mesh_a))
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 3, {"params": pa})
        for shape in [(1, 8), (4, 2)]:
            mesh_b = make_mesh(shape, ("data", "model"))
            spec_b = shd.param_pspecs(cfg, params, mesh_b)
            out, manifest = restore_checkpoint(
                d, {"params": params},
                shardings={"params": shd.to_named(spec_b, mesh_b)})
            assert manifest["step"] == 3
            for a, b in zip(jax.tree.leaves(params),
                            jax.tree.leaves(out["params"])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def scenario_compressed_psum():
    """int8 compressed gradient all-reduce: mean within quantisation error,
    error feedback captures the residual."""
    from jax.sharding import PartitionSpec as P
    from repro.dist.compression import compressed_psum_mean
    from repro.dist.sharding import shard_map
    import functools
    mesh = make_mesh((8,), ("data",))
    g = np.random.default_rng(11).normal(size=(8, 4096)).astype(np.float32)

    @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=(P("data"), P("data")))
    def run(gs):
        mean, err = compressed_psum_mean({"g": gs}, "data")
        return mean["g"], err["g"]

    with _use_mesh(mesh):
        mean, err = run(jnp.asarray(g))
    true_mean = g.mean(0, keepdims=True)
    got = np.asarray(mean)[0:1]
    scale = np.abs(g).max() / 127
    assert np.abs(got - true_mean).max() < 4 * scale, \
        (np.abs(got - true_mean).max(), scale)
    # error feedback residual is bounded by one quantisation step
    assert np.abs(np.asarray(err)).max() <= scale * 1.01




def scenario_moe_ep_equivalence():
    """shard_map expert-parallel MoE == single-device dense-dispatch MoE
    (same routing, dropless capacity)."""
    import dataclasses
    from repro.configs.all_archs import smoke_config
    from repro.models import moe as moe_mod
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = dataclasses.replace(smoke_config("grok-1-314b"),
                              n_experts=8, experts_per_token=2,
                              expert_pad_to=0, capacity_factor=64.0)
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model))
    y_ref, aux_ref = moe_mod.moe_apply(p, cfg, x)           # dense dispatch
    cfg_ep = dataclasses.replace(cfg, moe_ep=True)
    with _use_mesh(mesh):
        y_ep, aux_ep = jax.jit(
            lambda p, x: moe_mod.moe_apply(p, cfg_ep, x))(p, x)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)


def scenario_forest_migration_mesh():
    """Incremental migration on an 8-shard mesh forest: a skewed delete
    drill trips the planner, bounded migration steps run through the
    mesh extract + cohort-apply collectives with the stacked forest
    staying device-resident throughout, and every shard stays bitwise
    equal to the host-path forest after each step."""
    from repro.core.distributed import build_forest_trees
    from repro.core.engine import SMTreeEngine
    from repro.stream import StreamingForest, collect_stats
    mesh = make_mesh((8,), ("model",))
    rng = np.random.default_rng(23)
    X = rng.random((4096, 6)).astype(np.float32)

    def build():
        return StreamingForest(
            [t for t in build_forest_trees(X, 8, capacity=8)],
            mesh=mesh if build.on_mesh else None,
            max_skew=1.3, min_objects=64, rebalance_mode="incremental",
            migration_step_objects=48)

    build.on_mesh = True
    sf_mesh = build()
    build.on_mesh = False
    sf_host = build()
    victims = np.asarray([o for o in range(4096) if o % 8 < 3], np.int32)
    with _use_mesh(mesh):
        for c in range(0, len(victims), 512):
            chunk = victims[c:c + 512]
            sf_mesh.delete_batch(X[chunk], chunk)
            sf_host.delete_batch(X[chunk], chunk)
        assert collect_stats(sf_mesh.trees).skew >= 2.0
        steps = 0
        while sf_mesh.maintenance():
            assert sf_host.maintenance()
            steps += 1
            # mesh steps must not bounce the forest off the devices
            assert sf_mesh._stacked is not None, \
                f"stacked forest left the mesh at step {steps}"
            for s, (a, b) in enumerate(zip(sf_mesh.trees, sf_host.trees)):
                for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                    np.testing.assert_array_equal(
                        np.asarray(la), np.asarray(lb),
                        err_msg=f"shard {s} diverged at step {steps}")
        assert not sf_host.maintenance()
    assert steps >= 2, "drill completed without incremental steps"
    assert sf_mesh.owner == sf_host.owner
    assert sf_mesh.objects_migrated == sf_host.objects_migrated > 0
    assert collect_stats(sf_mesh.trees).skew <= 1.3
    for t in sf_mesh.trees:
        SMTreeEngine(t).validate()


if __name__ == "__main__":
    name = sys.argv[1]
    globals()[f"scenario_{name}"]()
    print(f"PASS {name}")
