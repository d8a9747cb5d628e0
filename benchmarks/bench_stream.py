"""Stream-subsystem benchmarks: sustained mutation throughput.

The matrix the PR-3 acceptance tracks: ops/sec through the WAL-backed
cohort batcher for insert-only, delete-only and 90/10-skewed streams at
batch >= 256, against the one-at-a-time ``insert_fast``/``delete_fast``
Python loop (the pre-stream write path, kept as the baseline).  PR 4/5
add the structure-edit rows: split-heavy (device split pass vs host
escalation), delete-heavy (device merge pass vs host escalation),
mixed churn, and the mesh-resident forest collectives with absorption
counters.  Also records WAL append cost (buffered, fsync'd, and
group-commit under concurrent appenders), the checkpoint ``fsync_dir``
durability premium (ROADMAP/DESIGN.md §9 satellite), the rebalance
pass, and the evict-while-serving composite (queries against a pinned
epoch while the writer streams mutations).

Scale envs: REPRO_BENCH_SMOKE=1 (tiny, CI) / REPRO_BENCH_FULL=1.
"""
from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np

from repro.core.engine import SMTreeEngine
from repro.core.smtree import OP_DELETE, OP_INSERT, bulk_build
from repro.data.datagen import make_dataset

FULL = os.environ.get("REPRO_BENCH_FULL") == "1"
SMOKE = os.environ.get("REPRO_BENCH_SMOKE") == "1"


def jnp_copy(a):
    import jax.numpy as jnp
    return jnp.array(a, copy=True)

if SMOKE:
    N = 2_000
    N_OPS = 1_024
    BATCHES = [256]
    N_LOOP = 192
elif FULL:
    N = 100_000
    N_OPS = 16_384
    BATCHES = [256, 1024, 4096]
    N_LOOP = 2_048
else:
    N = 20_000
    N_OPS = 8_192
    BATCHES = [256, 1024]
    N_LOOP = 1_024
DIM = 10
CAPACITY = 32
MESH_SHARDS = 4   # the mesh-forest rows: one shard per device


def _make_stream(rng, kind: str, n_ops: int, n_live: int, base_id: int):
    """(ops, xs, oids) with unique ids per stream (single cohort)."""
    X = make_dataset("clustered", n_live, seed=7)[:, :DIM].copy()
    if kind == "insert":
        ops = np.full(n_ops, OP_INSERT, np.int32)
        oids = base_id + np.arange(n_ops)
        xs = make_dataset("uniform", n_ops, seed=11)[:, :DIM].copy()
    elif kind == "delete":
        ops = np.full(n_ops, OP_DELETE, np.int32)
        oids = rng.permutation(n_live)[:n_ops]
        xs = X[oids]
    else:   # mixed: frac deletes, rest inserts
        frac = float(kind)
        n_del = int(n_ops * frac)
        victims = rng.permutation(n_live)[:n_del]
        ins_ids = base_id + np.arange(n_ops - n_del)
        ops = np.concatenate([np.full(n_del, OP_DELETE, np.int32),
                              np.full(n_ops - n_del, OP_INSERT, np.int32)])
        oids = np.concatenate([victims, ins_ids])
        xs = np.concatenate([X[victims],
                             make_dataset("uniform", n_ops - n_del,
                                          seed=13)[:, :DIM]])
        perm = rng.permutation(n_ops)
        ops, oids, xs = ops[perm], oids[perm], xs[perm]
    return (ops.astype(np.int32), np.asarray(xs, np.float32),
            oids.astype(np.int32))


def _fresh_tree():
    X = make_dataset("clustered", N, seed=7)[:, :DIM].copy()
    return bulk_build(X, capacity=CAPACITY)


def _time_stream(tree, ops, xs, oids, batch: int,
                 device_splits: bool = True,
                 device_merges: bool = True) -> float:
    """ops/sec through the batched pipeline (first batch warms the jit).

    Headroom growth is disabled for the timed rows: a mid-run doubling
    recompiles every jit entry for the new geometry, which at smoke scale
    swamps the op window — the same once-per-resize cost the split-heavy
    row already provisions slack to keep out of the measurement (and the
    pre-growth behaviour, host ``_grow`` on exhaustion, paid identically).
    Growth itself is covered by tests/test_device_merge.py."""
    from repro.core import smtree
    from repro.stream import StreamingEngine
    import jax
    eng = StreamingEngine(tree, device_splits=device_splits,
                          device_merges=device_merges,
                          headroom_frac=None)
    if device_merges:
        # warm the merge-scan compiles (both ladder widths) for this tree
        # geometry (donate=True matches resolve_underflows' jit entry)
        for w in (smtree.MERGE_CHUNK, smtree.MERGE_CHUNK_MAX):
            scratch = jax.tree.map(lambda a: jnp_copy(a), eng.tree)
            smtree.apply_merges(scratch,
                                np.full(w, smtree.OP_NOP, np.int32),
                                np.full(w, -1, np.int32), donate=True)
    if device_splits:
        # warm the split-scan compile for this tree geometry (the warm
        # batch below only reaches it when it happens to overflow a leaf).
        # donate=True matches the hot path's jit entry (resolve_overflows
        # always donates its intermediates), so feed it a throwaway copy
        scratch = jax.tree.map(lambda a: jnp_copy(a), eng.tree)
        smtree.apply_splits(scratch,
                            np.full(smtree.SPLIT_CHUNK, smtree.OP_NOP,
                                    np.int32),
                            np.zeros((smtree.SPLIT_CHUNK, xs.shape[1]),
                                     np.float32),
                            np.full(smtree.SPLIT_CHUNK, -1, np.int32),
                            donate=True)
    eng.apply(ops[:batch], xs[:batch], oids[:batch])   # compile + warm
    n = (len(ops) - batch) // batch * batch
    t0 = time.perf_counter()
    for s in range(batch, batch + n, batch):
        eng.apply(ops[s:s + batch], xs[s:s + batch], oids[s:s + batch])
    dt = time.perf_counter() - t0
    return n / dt


def _split_rows(report, rng):
    """Split-heavy workload: a near-capacity bulk build (fill 0.9, with
    free-ring headroom as a mutation-heavy deployment would provision —
    without it every few batches exhaust the node table, and the host
    ``_grow`` resize forces a full recompile that swamps both paths) makes
    insert streams overflow leaves constantly — the device split pass vs
    the PR-3 host-escalation path, plus the split count actually exercised
    (PR-4 acceptance row)."""
    from repro.stream.batcher import MutationBatcher

    def _tree():
        return bulk_build(X, capacity=CAPACITY, fill_frac=0.9, slack=4.0)

    n = min(N, 20_000)
    X = make_dataset("clustered", n, seed=7)[:, :DIM].copy()
    ops, xs, oids = _make_stream(rng, "insert", N_OPS, n, base_id=8 * n)
    rates = {}
    for dev, name in ((True, "stream_split_heavy_b256_ops_per_s"),
                      (False, "stream_split_heavy_host_b256_ops_per_s")):
        rates[dev] = _time_stream(_tree(), ops, xs, oids, 256,
                                  device_splits=dev)
        report(name, round(rates[dev], 0))
    report("split_device_vs_host_speedup",
           round(rates[True] / rates[False], 2))
    # observability: how many rows the device pass actually absorbed
    b = MutationBatcher(_tree())
    r = b.apply(ops[:1024], xs[:1024], oids[:1024])
    report("split_heavy_n_device_splits_per_1k", int(r.n_split))
    report("split_heavy_n_host_escalations_per_1k", int(r.n_escalated))


def _merge_rows(report, rng):
    """Delete-heavy workload (the PR-5 acceptance row): sustained deletes
    on a near-min-fill build underflow leaves steadily — the device merge
    pass vs the PR-4 escalate-to-host path, plus a mixed-churn row (60/40
    delete/insert on the same build: eviction pressure with concurrent
    ingest) and the absorption counters."""
    from repro.stream.batcher import MutationBatcher

    n = min(N, 20_000)
    X = make_dataset("clustered", n, seed=7)[:, :DIM].copy()

    def _tree():
        # leaves a couple of entries above min-fill so sustained
        # deletes underflow steadily (~8% of ops) — the long-lived
        # steady state of a delete-heavy deployment
        return bulk_build(X, capacity=CAPACITY, fill_frac=0.48)

    ops, xs, oids = _make_stream(rng, "delete", min(N_OPS, n - 256), n,
                                 base_id=0)
    rates = {}
    for dev, name in ((True, "stream_merge_heavy_b256_ops_per_s"),
                      (False, "stream_merge_heavy_host_b256_ops_per_s")):
        rates[dev] = _time_stream(_tree(), ops, xs, oids, 256,
                                  device_merges=dev)
        report(name, round(rates[dev], 0))
    report("merge_device_vs_host_speedup",
           round(rates[True] / rates[False], 2))
    # absorption counters: every underflow must resolve on device
    b = MutationBatcher(_tree())
    r = b.apply(ops[:1024], xs[:1024], oids[:1024])
    report("merge_heavy_n_device_merges_per_1k", int(r.n_merge))
    report("merge_heavy_n_host_escalations_per_1k", int(r.n_escalated))

    # mixed churn: 60/40 delete/insert on the same near-min-fill build —
    # eviction pressure with concurrent ingest, the sliding-window shape
    ops, xs, oids = _make_stream(rng, "0.6", N_OPS, n, base_id=16 * n)
    churn = _time_stream(_tree(), ops, xs, oids, 256)
    report("stream_churn60d_b256_ops_per_s", round(churn, 0))
    b = MutationBatcher(_tree())
    r = b.apply(ops[:1024], xs[:1024], oids[:1024])
    report("churn_n_device_splits_per_1k", int(r.n_split))
    report("churn_n_device_merges_per_1k", int(r.n_merge))
    report("churn_n_host_escalations_per_1k", int(r.n_escalated))


def _time_loop(tree, ops, xs, oids) -> float:
    """ops/sec through the pre-stream write path: one jitted fast-path call
    + host sync per mutation, engine escalation on overflow/underflow."""
    eng = SMTreeEngine(tree)
    n = min(N_LOOP, len(ops))
    # warm both fast-path compilations outside the timed window
    eng.insert(xs[0] + 17.0, 1 << 30)
    eng.delete(xs[0] + 17.0, 1 << 30)
    t0 = time.perf_counter()
    for i in range(n):
        if ops[i] == OP_INSERT:
            eng.insert(xs[i], int(oids[i]))
        else:
            eng.delete(xs[i], int(oids[i]))
    return n / (time.perf_counter() - t0)


# Both legs (device collectives vs escalate-to-host) run INTERLEAVED —
# dev/host/dev/host, best-of-2 per leg — because on a shared CI/container
# host, runs minutes apart see ±30% machine drift, which is larger than
# the effect under test.
def _mesh_forest_case(kind: str, n: int, n_ops: int):
    """Best-of-2 (device, host) ops/s of one mesh-forest workload, plus the
    device leg's absorption counters.  Runs in this process (a child
    process could not open a chip this process already holds), one shard
    on each of MESH_SHARDS devices; fewer visible devices is an error."""
    import jax

    from repro.core import distributed as dist
    from repro.core import smtree as smt
    from repro.dist.sharding import make_mesh
    from repro.stream import StreamingForest

    S = MESH_SHARDS
    if jax.device_count() < S:
        raise RuntimeError(f"needs {S} devices, {jax.device_count()} "
                           f"visible (benchmarks/run.py forces {S} host "
                           f"devices on a CPU host)")
    batch = 256
    mesh = make_mesh((S,), ("model",), devices=jax.devices()[:S])
    X = make_dataset("clustered", n, seed=7)[:, :10].copy()
    # insert streams need near-full leaves (split pressure) and free-ring
    # slack for sustained splits; delete streams need leaves near min-fill
    # (underflow pressure) and never allocate
    fill = 0.9 if kind == "insert" else 0.48
    slack = 4.0 if kind == "insert" else 1.5
    trees0 = [bulk_build(X[np.arange(s, n, S)], ids=np.arange(s, n, S),
                         capacity=32, fill_frac=fill, slack=slack)
              for s in range(S)]
    if kind == "insert":
        xs = make_dataset("uniform", n_ops + batch, seed=11)[:, :10].copy()
        oids = (10 * n + np.arange(n_ops + batch)).astype(np.int32)
        ops_all = np.full(n_ops + batch, OP_INSERT, np.int32)
    else:   # delete-heavy mix: 90% deletes of live ids, 10% fresh inserts
        rng = np.random.default_rng(13)
        victims = rng.permutation(n)[:int((n_ops + batch) * 0.9)]
        n_ins = n_ops + batch - len(victims)
        ops_all = np.concatenate([np.full(len(victims), OP_DELETE, np.int32),
                                  np.full(n_ins, OP_INSERT, np.int32)])
        oids = np.concatenate([victims,
                               10 * n + np.arange(n_ins)]).astype(np.int32)
        xs = np.concatenate([X[victims],
                             make_dataset("uniform", n_ins,
                                          seed=11)[:, :10]]).astype(np.float32)
        perm = rng.permutation(n_ops + batch)
        ops_all, oids, xs = ops_all[perm], oids[perm], xs[perm]

    def run_leg(dev):
        trees = [jax.tree.map(lambda a: a.copy(), t) for t in trees0]
        sf = StreamingForest(trees, mesh=mesh, device_splits=dev,
                             device_merges=dev)
        stats = {"esc": 0, "dev": 0}

        def step(s0):
            r = sf.apply(ops_all[s0:s0 + batch],
                         xs[s0:s0 + batch].astype(np.float32),
                         oids[s0:s0 + batch])
            stats["esc"] += r.n_escalated
            stats["dev"] += r.n_split + r.n_merge

        step(0)   # warm the apply collective (and stack the forest)
        if dev:
            # warm the split/merge collectives explicitly: the warm batch
            # only reaches them when it happens to over/underflow a leaf,
            # and their seconds-scale scan compile must not land in the
            # timed loop.  NOP chunks compile the exact jit entries the
            # hot path dispatches; the returned (unchanged) forest is
            # discarded.
            w = smt.SPLIT_CHUNK
            dist.forest_apply_splits(
                sf._stacked, mesh, np.full(w, smt.OP_NOP, np.int32),
                np.zeros((w, 10), np.float32), np.full(w, -1, np.int32),
                np.zeros(w, np.int32))
            for w in (smt.MERGE_CHUNK, smt.MERGE_CHUNK_MAX):
                dist.forest_apply_merges(
                    sf._stacked, mesh, np.full(w, smt.OP_NOP, np.int32),
                    np.full(w, -1, np.int32), np.zeros(w, np.int32))
        stats["esc"] = stats["dev"] = 0
        t0 = time.perf_counter()
        for s0 in range(batch, batch + n_ops, batch):
            step(s0)
        return n_ops / (time.perf_counter() - t0), stats

    best = {True: 0.0, False: 0.0}
    counts = {}
    for rep in range(2):
        for dev in (True, False):
            rate, stats = run_leg(dev)
            best[dev] = max(best[dev], rate)
            if dev:
                counts = stats
    return best[True], best[False], counts


def _mesh_forest_rows(report):
    """The tentpole measurements: a mesh-resident 4-shard StreamingForest
    (one shard per device), device structure-edit collectives vs the
    escalate-to-host path (which must unstack + restack the whole stacked
    forest around every host edit).  Two workloads: the PR-4 split-heavy
    insert stream, and the PR-5 delete-heavy mix (90% deletes) whose
    underflows run the forest_apply_merges collective — with the
    absorption counters proving zero host escalations on the device
    path."""
    # shards must be big enough that the host path's whole-forest
    # unstack/restack cost is visible over collective dispatch overhead
    n, n_ops = (2_000, 768) if SMOKE else (32_000, 2_048)
    for kind, label in (("insert", "split"), ("delete", "merge")):
        d_rate = h_rate = float("nan")
        try:
            d_rate, h_rate, counts = _mesh_forest_case(kind, n, n_ops)
            report(f"mesh_forest_{label}_heavy_host_escalations",
                   counts["esc"])
            report(f"mesh_forest_{label}_heavy_device_edits", counts["dev"])
        except Exception as exc:  # noqa: BLE001 — a bench row
            print(f"# mesh forest case {label} failed: {exc}", flush=True)
        report(f"mesh_forest_{label}_heavy_ops_per_s", d_rate)
        report(f"mesh_forest_{label}_heavy_host_ops_per_s", h_rate)
        if np.isfinite(d_rate) and np.isfinite(h_rate):
            report(f"mesh_forest_{label}_device_vs_host_speedup",
                   round(d_rate / h_rate, 2))


def _wal_rows(report):
    from repro.stream import WriteAheadLog
    rng = np.random.default_rng(3)
    ops, xs, oids = _make_stream(rng, "0.5", 2048, N, base_id=10 * N)
    for sync, name in ((False, "wal_append_us_per_batch_b256"),
                       (True, "wal_fsync_append_us_per_batch_b256")):
        d = tempfile.mkdtemp(prefix="walbench")
        try:
            wal = WriteAheadLog(d, segment_max_records=256, sync=sync)
            t0 = time.perf_counter()
            n_batches = len(ops) // 256
            for s in range(0, n_batches * 256, 256):
                wal.append_batch(ops[s:s + 256].astype(np.int8),
                                 xs[s:s + 256], oids[s:s + 256])
            dt = time.perf_counter() - t0
            wal.close()
            report(name, round(dt / n_batches * 1e6, 1))
        finally:
            shutil.rmtree(d, ignore_errors=True)

    # group commit under concurrent appenders: the fsync amortises across
    # the burst (the ROADMAP ~14x fsync-vs-buffered gap, recovered)
    import threading
    T = 4
    per = max(1, len(ops) // 256 // T)
    for group, name in (
            (False, "wal_fsync_4thread_us_per_batch_b256"),
            (True, "wal_group_fsync_4thread_us_per_batch_b256")):
        d = tempfile.mkdtemp(prefix="walbench")
        try:
            wal = WriteAheadLog(d, segment_max_records=1024, sync=True,
                                group_commit=group)

            def worker():
                for s in range(0, per * 256, 256):
                    wal.append_batch(ops[s:s + 256].astype(np.int8),
                                     xs[s:s + 256], oids[s:s + 256])

            threads = [threading.Thread(target=worker) for _ in range(T)]
            t0 = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            dt = time.perf_counter() - t0
            wal.close()
            report(name, round(dt / (T * per) * 1e6, 1))
        finally:
            shutil.rmtree(d, ignore_errors=True)


def _ckpt_rows(report, tree):
    """The fsync_dir durability premium (DESIGN.md §9)."""
    from repro.dist.checkpoint import save_checkpoint
    for fsync, name in ((False, "ckpt_ms"), (True, "ckpt_fsync_dir_ms")):
        d = tempfile.mkdtemp(prefix="ckbench")
        try:
            save_checkpoint(d, 0, {"tree": tree}, fsync_dir=fsync)  # warm fs
            iters = 3
            t0 = time.perf_counter()
            for i in range(1, 1 + iters):
                save_checkpoint(d, i, {"tree": tree}, fsync_dir=fsync)
            report(name,
                   round((time.perf_counter() - t0) / iters * 1e3, 2))
        finally:
            shutil.rmtree(d, ignore_errors=True)


def _rebalance_rows(report):
    from repro.core.distributed import build_forest_trees
    from repro.stream import StreamingForest, collect_stats
    n = min(N, 8_192)
    X = make_dataset("clustered", n, seed=7)[:, :DIM].copy()
    sf = StreamingForest(build_forest_trees(X, 4, capacity=CAPACITY),
                         min_objects=64)
    # drain shards 0/1: the heavily-skewed delete stream (80% of their
    # objects — skew lands well above the 1.5x trigger)
    victims = np.array([o for o in range(n) if o % 4 < 2][:2 * n // 5])
    sf.delete_batch(X[victims], victims)
    before = collect_stats(sf.trees).skew
    t0 = time.perf_counter()
    fired = sf.maintenance()
    dt = time.perf_counter() - t0
    after = collect_stats(sf.trees).skew
    report("rebalance_skew_before", round(before, 3))
    report("rebalance_fired", int(fired))
    report("rebalance_skew_after", round(after, 3))
    report("rebalance_ms", round(dt * 1e3, 1))


def _skew_drain_rows(report):
    """Skew-drain drill, incremental vs stop-the-world (PR 9): worst and
    p99 publish-time pause per maintenance call, plus steady-state
    mutation ops/s sustained *while the drain is in progress*.  Legs are
    interleaved and run twice (PR 5 methodology): the first pair pays
    one-time jit compilation, only the second pair is reported."""
    from repro.core.distributed import build_forest_trees
    from repro.stream import StreamingForest, collect_stats
    n = min(N, 8_192)
    X = make_dataset("clustered", n, seed=7)[:, :DIM].copy()
    trees = build_forest_trees(X, 4, capacity=CAPACITY)
    victims = np.array([o for o in range(n) if o % 4 < 2][:2 * n // 5])
    B = 64
    fresh = make_dataset("uniform", 200 * B, seed=41)[:, :DIM].copy()

    def leg(mode, base_id):
        sf = StreamingForest([t for t in trees], max_skew=1.3,
                             min_objects=64, rebalance_mode=mode,
                             migration_step_objects=B)
        sf.delete_batch(X[victims], victims)
        skew0 = collect_stats(sf.trees).skew
        pauses, mut_ops, mut_t, nid = [], 0, 0.0, base_id
        for r in range(200):
            t0 = time.perf_counter()
            fired = sf.maintenance()
            dt = time.perf_counter() - t0
            if not fired:
                break
            pauses.append(dt)
            oids = nid + np.arange(B)
            t0 = time.perf_counter()
            sf.insert_batch(fresh[(r % 200) * B:(r % 200) * B + B], oids)
            mut_t += time.perf_counter() - t0
            mut_ops += B
            nid += B
        return {"skew0": skew0, "pauses": pauses, "steps": len(pauses),
                "ops_per_s": mut_ops / mut_t if mut_t else 0.0,
                "skew1": collect_stats(sf.trees).skew}

    out = {}
    for rep in range(2):
        for mode in ("incremental", "stop_world"):
            out[mode] = leg(mode, base_id=(10 + 4 * rep) * n)
    report("skew_drain_skew_before", round(out["incremental"]["skew0"], 3))
    report("skew_drain_steps_incremental", out["incremental"]["steps"])
    for mode, r in out.items():
        p = np.asarray(r["pauses"]) * 1e3
        report(f"rebalance_p99_pause_ms_{mode}",
               round(float(np.percentile(p, 99)), 2))
        report(f"rebalance_max_pause_ms_{mode}", round(float(p.max()), 2))
        report(f"skew_drain_ops_per_s_{mode}", round(r["ops_per_s"], 0))
        report(f"skew_drain_final_skew_{mode}", round(r["skew1"], 3))


def _serve_rows(report):
    """Evict-while-serving: queries pinned to an epoch while the writer
    applies sliding-window add/evict batches."""
    from repro.core import smtree
    from repro.stream import StreamingEngine
    import jax
    rng = np.random.default_rng(5)
    n = min(N, 8_192)
    X = make_dataset("clustered", n, seed=7)[:, :DIM].copy()
    eng = StreamingEngine(bulk_build(X, capacity=CAPACITY))
    Q = X[rng.integers(0, n, 64)] + 0.01
    B = 128
    rounds = 4 if SMOKE else 12
    # warm compiles
    jax.block_until_ready(smtree.knn(eng.tree, Q, k=8).dists)
    cursor, nid = 0, n
    t_q = t_m = 0.0
    fresh = make_dataset("uniform", rounds * B, seed=100)[:, :DIM].copy()
    for r in range(rounds):
        e, tree = eng.epochs.acquire()
        t0 = time.perf_counter()
        res = smtree.knn(tree, Q, k=8, max_frontier=64)
        jax.block_until_ready(res.dists)
        t_q += time.perf_counter() - t0
        t0 = time.perf_counter()
        eng.insert_batch(fresh[r * B:(r + 1) * B], nid + np.arange(B))
        eng.delete_batch(X[cursor:cursor + B],
                         np.arange(cursor, cursor + B))
        t_m += time.perf_counter() - t0
        cursor += B
        nid += B
        eng.epochs.release(e)
    report("serve_knn_qps_under_mutation", round(rounds * 64 / t_q, 0))
    report("serve_mutation_ops_per_s", round(rounds * 2 * B / t_m, 0))


def run(report):
    import gc
    rng = np.random.default_rng(1)
    tree = _fresh_tree()

    # -- headline speedup first, in a clean process state: timing the loop
    # after the stream stages understates it ~3x (allocator/cache pressure
    # from the earlier stages' buffers), which would flatter the speedup
    ops, xs, oids = _make_stream(rng, "0.5", N_OPS, N, base_id=4 * N)
    loop_rate = _time_loop(tree, ops, xs, oids)
    report("loop_mixed_ops_per_s", round(loop_rate, 0))
    gc.collect()
    mixed_rate = _time_stream(tree, ops, xs, oids, 256)
    report("stream_mixed50_b256_ops_per_s", round(mixed_rate, 0))
    report("speedup_batched_vs_loop_b256", round(mixed_rate / loop_rate, 2))

    # -- mutation-throughput matrix --------------------------------------
    for kind, label in (("insert", "insert"), ("delete", "delete"),
                        ("0.9", "mixed90d")):
        ops, xs, oids = _make_stream(rng, kind, N_OPS, N, base_id=2 * N)
        for b in BATCHES:
            gc.collect()
            rate = _time_stream(tree, ops, xs, oids, b)
            report(f"stream_{label}_b{b}_ops_per_s", round(rate, 0))

    _split_rows(report, rng)
    _merge_rows(report, rng)
    _mesh_forest_rows(report)
    _wal_rows(report)
    _ckpt_rows(report, tree)
    _rebalance_rows(report)
    _skew_drain_rows(report)
    _serve_rows(report)
