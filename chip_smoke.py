#!/usr/bin/env python3
"""Chip smoke test: the served SM-tree index, end to end, on a TPU.

    python chip_smoke.py              # one chip: index + kNN-LM phases
    python chip_smoke.py --chips 4    # four chips: the mesh-resident forest

One chip, two phases:

* ``index`` — an ANN-Benchmarks SIFT-1M-shaped deployment: 1M clustered
  128-d vectors made from ``--seed``, L2, node capacity 32.  The index is
  bulk-built, wrapped in a WAL-backed ``StreamingEngine`` and served through
  ``ServeFrontend`` (cohort width 1024, SLO 5 ms): waves of k=10 queries
  interleaved with 4 mutation batches of 4,096 ops (half inserts of fresh
  vectors, half deletes of live ids, placed so that leaves both overflow
  and underflow: device splits and merges fire).  Every answer is checked
  against exact brute-force kNN in ``jax.numpy`` over the live set at the
  answer's epoch.  One b=256 cohort also runs on the XLA path and is
  compared with the Pallas kernel.
* ``knnlm`` — ``repro.launch.serve.main`` with qwen2.5-3b at its published
  width (random weights), ``--knn --frontend``; every retrieval is checked
  against brute force over the datastore.

``--chips 4`` runs only the mesh-resident ``StreamingForest`` (one shard
per chip) and its references: ``forest_knn`` against brute force, and the
shards' digests against a host-mode forest that replays the same batches.

Exits non-zero, printing no result, when no TPU is visible or any check
fails.  The last stdout line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

K, CAP, DIM, F = 10, 32, 128, 64
N_CORPUS, N_QUERIES = 1_000_000, 768     # SIFT-1M: 1M base vectors
# at F=64 a 128-d query's frontier is truncated (an approximate answer,
# flagged ``overflow``); a wide-frontier probe, served at every epoch,
# checks exact answers too
F_EXACT, N_EXACT = 2048, 64
WIDTH, SLO_MS = 1024, 5.0
N_BATCHES, BATCH = 4, 4096
# mutations are concentrated around anchors: each insert anchor takes 32
# fresh neighbours (its leaf overflows), each delete anchor loses its 32
# nearest live objects (its leaf underflows)
PER_ANCHOR = 32
# distance agreement, relative above 1 (the reference sums in its own
# order); ids may differ only between distances that agree (ties)
TOL = 1e-5


class CheckFailed(AssertionError):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(cond, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


# ---------------------------------------------------------------- reference
@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def _brute_knn(Q, X, live, *, k: int, chunk: int):
    """Exact kNN of Q [q, dim] over the live rows of X [n, dim] (n a
    multiple of ``chunk``): f32 L2 in plain jax.numpy, none of the index's
    own code, scanning X in chunks with a running top-k."""

    def body(carry, c):
        best_d, best_i = carry
        xc = jax.lax.dynamic_slice_in_dim(X, c * chunk, chunk)
        lc = jax.lax.dynamic_slice_in_dim(live, c * chunk, chunk)
        d2 = jnp.sum(jnp.square(Q[:, None, :] - xc[None, :, :]), axis=-1)
        d = jnp.where(lc[None, :], jnp.sqrt(d2), jnp.inf)
        ids = jnp.broadcast_to(c * chunk + jnp.arange(chunk, dtype=jnp.int32),
                               d.shape)
        neg, sel = jax.lax.top_k(-jnp.concatenate([best_d, d], 1), k)
        return (-neg, jnp.take_along_axis(
            jnp.concatenate([best_i, ids], 1), sel, 1)), None

    init = (jnp.full((Q.shape[0], k), jnp.inf, jnp.float32),
            jnp.full((Q.shape[0], k), -1, jnp.int32))
    (d, i), _ = jax.lax.scan(body, init,
                             jnp.arange(X.shape[0] // chunk, dtype=jnp.int32))
    return d, i


class Reference:
    """Brute-force kNN over a fixed pool of vectors; a query names the live
    subset by a boolean mask over the pool."""

    def __init__(self, pool: np.ndarray, *, chunk: int = 16_384,
                 q_chunk: int = 32):
        self.host = pool
        self.chunk = min(chunk, -(-len(pool) // 8) * 8)
        n = -(-len(pool) // self.chunk) * self.chunk
        padded = np.zeros((n, pool.shape[1]), np.float32)
        padded[:len(pool)] = pool
        self.dev = jnp.asarray(padded)
        self.n = n
        self.q_chunk = q_chunk

    def knn(self, Q: np.ndarray, live: np.ndarray, k: int):
        mask = np.zeros(self.n, bool)
        mask[:len(live)] = live
        mask = jnp.asarray(mask)
        ds, ids = [], []
        for s in range(0, len(Q), self.q_chunk):
            q = np.zeros((self.q_chunk, Q.shape[1]), np.float32)
            part = Q[s:s + self.q_chunk]
            q[:len(part)] = part
            d, i = _brute_knn(jnp.asarray(q), self.dev, mask, k=k,
                              chunk=self.chunk)
            ds.append(np.asarray(d)[:len(part)])
            ids.append(np.asarray(i)[:len(part)])
        return np.concatenate(ds), np.concatenate(ids)


def _l2_host(q, X):
    """f32 L2 from q [dim] to the rows of X, in numpy."""
    return np.sqrt(np.sum(np.square(q[None, :] - X), axis=-1))


def _agree(a, b) -> bool:
    return bool(np.all(np.abs(np.asarray(a) - np.asarray(b))
                       <= TOL * np.maximum(1.0, np.abs(b))))


def check_answers(name: str, Q, d, ids, overflow, ref: Reference, live,
                  k: int) -> dict:
    """Hold each answer to the exact kNN over ``live``.  Where ``overflow``
    is False the k distances must agree with brute force and the ids may
    differ only between equidistant objects; every answer's ids must be
    live and carry their true distance."""
    ref_d, ref_i = ref.knn(Q, live, k)
    bad = []
    recall = []
    for r in range(len(Q)):
        got_i, got_d = np.asarray(ids[r]), np.asarray(d[r])
        ok = (got_i >= 0).all() and live[got_i].all()
        if ok:
            ok = _agree(got_d, _l2_host(Q[r], ref.host[got_i]))
        recall.append(len(set(got_i) & set(ref_i[r])) / k)
        if ok and not overflow[r]:
            ok = _agree(got_d, ref_d[r])
            for o in set(got_i) ^ set(ref_i[r]):
                # a swapped id must be a tie at the k-th distance
                ok = ok and _agree(_l2_host(Q[r], ref.host[[o]])[0],
                                   ref_d[r][-1])
        if not ok:
            bad.append(r)
    n_over = int(np.sum(overflow))
    stats = {"checked": len(Q), "mismatches": len(bad),
             "overflowed": n_over,
             "mean_recall_at_k": float(np.mean(recall))}
    log(f"{name}: {json.dumps(stats)}")
    require(not bad, f"{name}: {len(bad)} answers disagree with brute force "
                     f"(first rows {bad[:5]})")
    return stats


# ---------------------------------------------------------------- workload
def make_workload(n: int, n_queries: int, seed: int):
    """Corpus, held-out queries and N_BATCHES mixed mutation batches.

    Returns (pool, n, Q, batches): ``pool`` holds the corpus (oids 0..n-1)
    followed by every fresh insert (oid = row), so one array serves as the
    brute-force pool at every epoch; ``batches`` lists (ops, xs, oids)."""
    from repro.core.smtree import OP_DELETE, OP_INSERT
    from repro.data.datagen import make_dataset

    rng = np.random.default_rng(seed)
    data = make_dataset("clustered", n + n_queries, dims=DIM, seed=seed)
    data = data[rng.permutation(len(data))]
    X, Q = data[:n], data[n:]
    half = BATCH // 2
    n_anchor = half // PER_ANCHOR
    anchors = rng.choice(n, 2 * N_BATCHES * n_anchor, replace=False)
    ins_anchor = anchors[:N_BATCHES * n_anchor]
    del_anchor = anchors[N_BATCHES * n_anchor:]
    # fresh vectors: tight clouds around the insert anchors
    fresh = (np.repeat(X[ins_anchor], PER_ANCHOR, 0)
             + rng.normal(0, 0.01, (len(ins_anchor) * PER_ANCHOR, DIM))
             ).astype(np.float32)
    pool = np.concatenate([X, fresh])
    # victims: the nearest live corpus objects of each delete anchor
    ref = Reference(X)
    _, nn = ref.knn(X[del_anchor], np.ones(n, bool), 2 * PER_ANCHOR)
    used = np.zeros(n, bool)
    used[ins_anchor] = True          # keep insert anchors alive
    batches = []
    for b in range(N_BATCHES):
        victims = []
        for a in range(b * n_anchor, (b + 1) * n_anchor):
            cand = [o for o in nn[a] if not used[o]][:PER_ANCHOR]
            used[cand] = True
            victims += cand
        spare = np.nonzero(~used)[0]
        extra = rng.choice(spare, half - len(victims), replace=False)
        used[extra] = True
        victims = np.concatenate([np.asarray(victims, np.int64), extra])
        new = n + np.arange(b * half, (b + 1) * half)
        oids = np.concatenate([new, victims]).astype(np.int32)
        ops = np.concatenate([np.full(half, OP_INSERT, np.int32),
                              np.full(half, OP_DELETE, np.int32)])
        perm = rng.permutation(BATCH)
        ops, oids = ops[perm], oids[perm]
        batches.append((ops, pool[oids], oids))
    return pool, n, Q, batches


def live_after(pool_size: int, n: int, batches) -> list[np.ndarray]:
    """Live mask over the pool after 0..len(batches) batches."""
    from repro.core.smtree import OP_INSERT
    live = np.zeros(pool_size, bool)
    live[:n] = True
    out = [live.copy()]
    for ops, _, oids in batches:
        live[oids] = ops == OP_INSERT
        out.append(live.copy())
    return out


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def check_no_faults(name: str) -> None:
    from repro import obs
    n = obs.RECORDER.stats()["n_dumps"]
    require(n == 0, f"{name}: {n} faults recorded "
                    f"(last dump {obs.RECORDER.last_dump_path})")


# ---------------------------------------------------------------- phases
def check_kernel(tree, Q) -> None:
    """The query path must run the compiled Pallas frontier kernel."""
    from repro.core import smtree
    impl = smtree._resolve_impl(None)
    require(jax.default_backend() == "tpu" and impl == "pallas",
            f"query path resolved to {impl!r} on {jax.default_backend()}")
    hlo = smtree._knn_cohort.lower(
        tree, jnp.asarray(Q), jnp.float32(np.inf), k=K, F=F,
        height=int(tree.height), impl=impl, interpret=False,
        level_stats=False, prune=True).as_text()
    require("tpu_custom_call" in hlo, "no tpu_custom_call in the descent")
    log("kernel: impl=pallas, descent HLO holds tpu_custom_call")


def index_phase(args, out: Path, dev) -> dict:
    from repro.core import smtree
    from repro.serve.frontend import FrontendConfig, ServeFrontend
    from repro.stream import StreamingEngine, WriteAheadLog

    t0 = time.perf_counter()
    pool, n, Q, batches = make_workload(N_CORPUS, N_QUERIES, args.seed)
    log(f"index: workload {n} x {DIM} corpus, {len(Q)} queries, "
        f"{N_BATCHES} batches x {BATCH} ops in "
        f"{time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    tree = smtree.bulk_build(pool[:n], capacity=CAP, metric="l2",
                             seed=args.seed)
    jax.block_until_ready(tree.vecs)
    log(f"index: build {time.perf_counter() - t0:.1f}s (set-up), "
        f"{tree.max_nodes} node slots, height {int(tree.height)}, "
        f"pages {tree.vecs.nbytes / 2**30:.2f} GiB")

    warm = np.zeros((WIDTH, DIM), np.float32)
    warm[:min(WIDTH, len(Q))] = Q[:WIDTH]
    check_kernel(tree, warm)
    t0 = time.perf_counter()
    res = smtree.knn(tree, warm, k=K, max_frontier=F)
    jax.block_until_ready(res.dists)
    log(f"index: query compile + first cohort "
        f"{time.perf_counter() - t0:.1f}s (set-up)")

    def recording_knn(f: int, overflow: dict[bytes, bool]):
        """The front end's cohort call at max_frontier ``f``, recording
        each query's ``overflow`` flag."""
        def knn_fn(pinned, q):
            r = smtree.knn(pinned, q, k=K, max_frontier=f)
            for row, flag in zip(q, np.asarray(r.overflow)):
                overflow[row.tobytes()] = bool(flag)
            return r.dists, r.ids
        return knn_fn

    wal_dir = out / "wal"
    shutil.rmtree(wal_dir, ignore_errors=True)
    eng = StreamingEngine(tree, wal=WriteAheadLog(str(wal_dir)),
                          max_batch=BATCH)
    del tree
    overflow: dict[bytes, bool] = {}
    fe = ServeFrontend(eng, FrontendConfig(cohort_width=WIDTH, slo_ms=SLO_MS,
                                           k=K, max_frontier=F),
                       knn_fn=recording_knn(F, overflow))
    # the exact probe: a second front end on the same engine, one cohort
    # of N_EXACT queries at max_frontier F_EXACT, served at every epoch
    overflow_exact: dict[bytes, bool] = {}
    fe_exact = ServeFrontend(eng, FrontendConfig(
        cohort_width=N_EXACT, slo_ms=SLO_MS, k=K, max_frontier=F_EXACT,
        maintenance=False), knn_fn=recording_knn(F_EXACT, overflow_exact))
    waves = np.array_split(np.arange(len(Q)), N_BATCHES + 1)
    tickets, probes, epochs_after, results = [], [], [], []
    t0 = time.perf_counter()
    with fe, fe_exact:
        for w, rows in enumerate(waves):
            tickets += [(r, fe.submit(Q[r])) for r in rows]
            # answered before the next batch is submitted: one probe
            # cohort at each epoch
            wave_probes = [(r, fe_exact.submit(Q[r])) for r in range(N_EXACT)]
            probes += [(r, tk.result(900), tk.epoch) for r, tk in wave_probes]
            if w < N_BATCHES:
                tb = time.perf_counter()
                results.append(fe.submit_mutations(*batches[w]).result(900))
                epochs_after.append(eng.epochs.epoch)
                r = results[-1]
                log(f"index: batch {w}: {time.perf_counter() - tb:.2f}s, "
                    f"splits {r.n_split}, merges {r.n_merge}, "
                    f"escalated {r.n_escalated}, epoch {epochs_after[-1]}")
        answers = [(r, tk.result(900), tk.epoch) for r, tk in tickets]
        stats = fe.stats.snapshot()
    log(f"index: served in {time.perf_counter() - t0:.1f}s: "
        f"{json.dumps(stats)}")
    for name, front in (("served", fe), ("exact probe", fe_exact)):
        require(front.stats.n_maintenance_faults == 0,
                f"{name} front end: maintenance faulted")
    require(sum(r.n_split for r in results) > 0, "no device split fired")
    require(sum(r.n_merge for r in results) > 0, "no device merge fired")
    from repro.core.smtree import ST_APPLIED
    require(all((r.statuses == ST_APPLIED).all() for r in results),
            "a mutation was not applied")

    # the tree state at epoch e is the corpus after every batch whose
    # publish came at or before e
    lives = live_after(len(pool), n, batches)
    ref = Reference(pool)

    def check_by_epoch(name, answered, flags) -> dict:
        epochs = np.asarray([e for _, _, e in answered])
        totals = {"checked": 0, "mismatches": 0, "overflowed": 0}
        states = set()     # tree states seen, by batches applied
        for e in np.unique(epochs):
            sel = [answered[i] for i in np.nonzero(epochs == e)[0]]
            n_applied = int(np.sum(np.asarray(epochs_after) <= e))
            rows = np.asarray([r for r, _, _ in sel])
            ov = np.asarray([flags[Q[r].tobytes()] for r in rows])
            s = check_answers(
                f"index {name}, epoch {e} ({n_applied} batches applied)",
                Q[rows], np.stack([a[0] for _, a, _ in sel]),
                np.stack([a[1] for _, a, _ in sel]), ov, ref,
                lives[n_applied], K)
            for key in ("checked", "mismatches", "overflowed"):
                totals[key] += s[key]
            states.add(n_applied)
        require(totals["checked"] == len(answered),
                f"{name}: not every ticket was checked")
        totals["states"] = len(states)
        return totals

    totals = check_by_epoch(f"served (max_frontier {F})", answers, overflow)
    exact = check_by_epoch(f"exact probe (max_frontier {F_EXACT})", probes,
                           overflow_exact)
    require(exact["states"] == N_BATCHES + 1 and exact["overflowed"] == 0,
            f"the exact probe must answer exactly at each of the "
            f"{N_BATCHES + 1} tree states: {exact}")
    totals["exact_answers"] = exact["checked"]

    # the XLA path against the kernel, one b=256 cohort
    _, final = eng.epochs.current()
    qb = Q[:256]
    rp = smtree.knn(final, qb, k=K, max_frontier=F, impl="pallas")
    rx = smtree.knn(final, qb, k=K, max_frontier=F, impl="xla")
    dp, dx = np.asarray(rp.dists), np.asarray(rx.dists)
    bitwise = (np.array_equal(dp, dx)
               and np.array_equal(np.asarray(rp.ids), np.asarray(rx.ids)))
    fin = np.isfinite(dp) & np.isfinite(dx)
    max_diff = float(np.max(np.abs(dp[fin] - dx[fin]), initial=0.0))
    log(f"index: xla vs pallas at b=256: bitwise_equal={bitwise}, "
        f"max |d_pallas - d_xla| = {max_diff!r}, ids equal in "
        f"{float(np.mean(np.asarray(rp.ids) == np.asarray(rx.ids))):.6f} "
        f"of slots")
    check_no_faults("index")
    log(f"index: peak_bytes_in_use {peak_bytes(dev)}")
    return totals


def knnlm_phase(serve_argv: list[str], dev) -> dict:
    """``repro.launch.serve.main`` in this process; every front-end
    retrieval it makes is recorded and held to brute force over the keys
    the datastore was built from (key i is oid i)."""
    from repro.launch import serve
    from repro.serve.frontend import ServeFrontend
    from repro.serve.knnlm import KnnLmDatastore

    built, calls = [], []
    orig_build, orig_knn = KnnLmDatastore.build, ServeFrontend.knn

    def recording_build(self, keys, values):
        built.append(np.array(keys, np.float32))
        return orig_build(self, keys, values)

    def recording_knn(self, qs, timeout=60.0):
        d, i = orig_knn(self, qs, timeout)
        calls.append((self, self.engine.epochs.current(), np.array(qs), d, i))
        return d, i

    KnnLmDatastore.build, ServeFrontend.knn = recording_build, recording_knn
    t0 = time.perf_counter()
    try:
        serve.main(serve_argv)
    finally:
        KnnLmDatastore.build, ServeFrontend.knn = orig_build, orig_knn
    log(f"knnlm: serve.main {time.perf_counter() - t0:.1f}s, "
        f"{len(calls)} retrieval calls")
    require(len(built) == 1, f"{len(built)} datastore builds, expected 1")
    require(calls, "the decode made no retrieval")
    fe = calls[0][0]
    require(fe.stats.n_maintenance_faults == 0, "maintenance faulted")
    require(len({id(c[1][1]) for c in calls}) == 1,
            "the datastore changed during a read-only decode")
    (_, tree) = calls[0][1]
    pool = built[0]
    require(int(tree.n_objects) == len(pool),
            f"the datastore holds {int(tree.n_objects)} of {len(pool)} keys")
    # exact answers need every leaf to fit the frontier
    n_leaves = int(np.sum(np.asarray(tree.alive) & np.asarray(tree.is_leaf)))
    k = fe.cfg.k
    require(n_leaves <= fe.cfg.max_frontier,
            f"{n_leaves} leaves exceed max_frontier {fe.cfg.max_frontier}")
    Q = np.concatenate([c[2] for c in calls])
    d = np.concatenate([c[3] for c in calls])
    i = np.concatenate([c[4] for c in calls])
    stats = check_answers(f"knnlm ({len(pool)} keys, dim {pool.shape[1]})",
                          Q, d, i, np.zeros(len(Q), bool), Reference(pool),
                          np.ones(len(pool), bool), k)
    check_no_faults("knnlm")
    log(f"knnlm: peak_bytes_in_use {peak_bytes(dev)}")
    return stats


def forest_phase(args, out: Path, devices) -> dict:
    from repro.core import distributed as dist
    from repro.core import smtree
    from repro.dist.sharding import make_mesh, use_mesh
    from repro.stream import StreamingForest
    from repro.stream.replica import tree_digest

    S = len(devices)
    mesh = make_mesh((S,), ("model",), devices=devices)
    t0 = time.perf_counter()
    pool, n, Q, batches = make_workload(N_CORPUS, N_QUERIES, args.seed)
    trees =dist.build_forest_trees(pool[:n], S, capacity=CAP, metric="l2",
                                    seed=args.seed)
    log(f"forest: {S} shards of {n} x {DIM} built in "
        f"{time.perf_counter() - t0:.1f}s (set-up)")
    sf_mesh = StreamingForest(trees, mesh=mesh)
    sf_host = StreamingForest(trees)
    del trees
    n_split = n_merge = 0
    with use_mesh(mesh):
        for b, (ops, xs, oids) in enumerate(batches):
            tb = time.perf_counter()
            rm = sf_mesh.apply(ops, xs, oids)
            tm = time.perf_counter() - tb
            rh = sf_host.apply(ops, xs, oids)
            require(np.array_equal(rm.statuses, rh.statuses),
                    f"batch {b}: mesh and host statuses differ")
            n_split += rm.n_split
            n_merge += rm.n_merge
            log(f"forest: batch {b}: mesh {tm:.2f}s, splits {rm.n_split}, "
                f"merges {rm.n_merge}, escalated {rm.n_escalated}")
        stacked = sf_mesh._stacked
        require(stacked is not None, "the mesh forest is not device-resident")
        shard_devs = [s.device for s in stacked.vecs.addressable_shards]
        require(len(set(shard_devs)) == S
                and all(s.data.shape[0] == 1
                        for s in stacked.vecs.addressable_shards),
                f"shards are not one per chip: {shard_devs}")
        log(f"forest: one shard per chip on {sorted(d.id for d in shard_devs)}")
        require(n_split > 0 and n_merge > 0,
                f"device splits {n_split}, merges {n_merge}: both must fire")
        dm = [tree_digest(t) for t in sf_mesh.trees]
        dh = [tree_digest(t) for t in sf_host.trees]
        log(f"forest: shard digests mesh == host: {dm == dh} "
            f"({', '.join(x[:12] for x in dm)})")
        require(dm == dh, "mesh shards differ from the host-mode forest")

        _, shards = sf_mesh.epochs.current()
        forest = dist.place_forest(shards, mesh)
        answers = {}
        for name, q, f in (("forest", Q, F),
                           (f"forest exact probe (max_frontier {F_EXACT})",
                            Q[:N_EXACT], F_EXACT)):
            t0 = time.perf_counter()
            d, i = dist.forest_knn(forest, mesh, q, k=K, max_frontier=f)
            answers[name] = (q, f, np.asarray(d), np.asarray(i))
            log(f"{name}: forest_knn over {len(q)} queries (compile "
                f"included) {time.perf_counter() - t0:.1f}s")
    live = live_after(len(pool), n, batches)[-1]
    ref = Reference(pool)
    for name, (q, f, d, i) in answers.items():
        # a shard's frontier truncation makes the merged answer best-effort
        ov = np.zeros(len(q), bool)
        for t in sf_host.trees:
            ov |= np.asarray(smtree.knn(t, q, k=K, max_frontier=f).overflow)
        stats = check_answers(name, q, d, i, ov, ref, live, K)
    check_no_faults("forest")
    log(f"forest: peak_bytes_in_use per chip "
        f"{[peak_bytes(x) for x in devices]}")
    return stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the mesh-resident forest phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"))
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU visible (JAX found "
              f"{devices[0].platform}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"visible", file=sys.stderr)
        return 2
    try:
        from repro import obs
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is missing ({e}); run from "
              f"a checkout", file=sys.stderr)
        return 2

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    os.environ["REPRO_OBS_DUMP_DIR"] = str(out)
    log(f"compile cache: {enable_compile_cache()}")
    obs.enable()
    used = devices[:args.chips]
    log(f"devices: {[str(d) for d in used]}")
    t_all = time.perf_counter()
    try:
        if args.chips == 4:
            forest_phase(args, out, used)
        else:
            index_phase(args, out, used[0])
            gc.collect()
            knnlm_phase(["--arch", "qwen2.5-3b", "--knn", "--frontend",
                         "--batch", "8", "--prompt-len", "16",
                         "--steps", "8"], used[0])
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": used[0].platform, "kind": used[0].device_kind,
        "count": len(used)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
