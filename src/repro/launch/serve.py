"""Batched serving driver: prefill + cached greedy decode, optional kNN-LM
mixing from an SM-tree datastore.

    python -m repro.launch.serve --arch qwen2.5-3b --smoke --batch 4 \
        --prompt-len 32 --steps 16 [--knn --lam 0.3] [--mesh host]

``--mesh host`` runs the GSPMD-sharded decode step (serve/serve_step.py
builders + dist/sharding policy) over all host devices — the same code path
the decode_32k / long_500k dry-run cells lower for the production mesh.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.all_archs import smoke_config
from repro.configs.base import get_config
from repro.data.pipeline import DataConfig, synth_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M


def _dump_obs(args) -> None:
    """With ``--obs``: print the final metrics snapshot as one parseable
    ``[obs] {...}`` line (and write it to ``--obs-out`` when given) so CI
    smoke jobs can assert on coverage without scraping the summary."""
    if not getattr(args, "obs", False):
        return
    import json

    from repro.obs.export import metrics_snapshot
    snap = metrics_snapshot()
    body = json.dumps(snap, sort_keys=True, default=repr)
    if getattr(args, "obs_out", None):
        with open(args.obs_out, "w") as f:
            f.write(body + "\n")
    print(f"[obs] {body}", flush=True)


# the datastore's token stream: 16 sequences of 129 tokens give 2,048
# (key, next token) entries
STORE_SEQS, STORE_SEQ_LEN = 16, 129


def _build_store(args, cfg, params, mesh=None):
    """kNN-LM datastore keyed as the method publishes it: the model's own
    pre-head hidden states over a synthetic token stream, each valued by
    the token that follows it, searched by ``KnnLmConfig``'s metric and
    ``k``.  With a mesh the tree pages replicate and query cohorts shard
    over 'data'."""
    from repro.serve.knnlm import (KnnLmConfig, KnnLmDatastore,
                                   datastore_entries)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=STORE_SEQ_LEN,
                    global_batch=STORE_SEQS)
    keys, vals = datastore_entries(
        params, cfg, synth_batch(dc, 1, with_labels=False)["tokens"])
    store = KnnLmDatastore(KnnLmConfig(lam=args.lam), cfg.d_model, mesh=mesh)
    store.build(keys, vals)
    replicas = getattr(args, "replicas", 0)
    if getattr(args, "knn_mutate", False) or getattr(args, "frontend", False):
        wal_dir = None
        if replicas:
            # replication is log shipping: the stream needs a real WAL
            import tempfile
            args._repl_root = tempfile.mkdtemp(prefix="serve-repl-")
            wal_dir = f"{args._repl_root}/wal"
        shards = getattr(args, "knn_shards", 0)
        kw = {}
        if shards and shards > 1:
            # sharded store: maintenance (offered by the front-end
            # scheduler after each mutation batch) repairs delete skew in
            # the configured mode — incremental migration steps by
            # default, stop-the-world rebuilds as the baseline
            kw = {"shards": shards,
                  "rebalance_mode": getattr(args, "rebalance_mode",
                                            "incremental"),
                  "max_skew": 1.3, "min_objects": 256}
        store.enable_stream(wal_dir=wal_dir, **kw)  # batched add/evict
    if getattr(args, "frontend", False):
        # async serving front-end: retrieval coalesces into epoch-pinned
        # cohorts, mutations ride the scheduler between epoch publishes —
        # this replaces the old alternating query/mutate decode loop
        store.enable_frontend(cohort_width=args.cohort_width or args.batch,
                              slo_ms=args.slo_ms)
        if replicas:
            # socket-fed read replicas + replica-aware router in front of
            # the front-end (stream/transport.py, serve/router.py)
            store.enable_replication(f"{args._repl_root}/mirrors",
                                     n_replicas=replicas)
    return store


def _finish_frontend(store) -> str:
    """Drain the scheduler (all submitted mutations applied) and format
    the serving counters for the run summary."""
    if store is None or store.frontend is None:
        return ""
    store.frontend.drain()
    s = store.frontend.stats.snapshot()
    repl = ""
    if store.router is not None:
        # let the followers drain the tail the drain() above appended,
        # then report how far behind they ended
        seq = store.stream.wal.next_seq - 1
        for rep in store.replicas:
            try:
                rep.catch_up(seq, timeout=10.0)
            except TimeoutError:
                pass                      # lag reported honestly below
        r = store.router.snapshot()
        repl = (f", {len(store.replicas)} replicas "
                f"(max lag {r['max_replica_lag']} records)")
        store.close_replication()
    store.close_frontend()
    return (f", frontend: {s['n_cohorts']} cohorts "
            f"(fill {s['mean_cohort_fill']}, "
            f"{s['n_mutation_batches']} mutation batches, "
            f"p50 {s['p50_ms']}ms p99 {s['p99_ms']}ms){repl}")


class _WindowMutator:
    """Sliding-window live mutation under serving: every decode step adds
    the step's (hidden-state, next-token) pairs to the datastore and evicts
    the same number of oldest entries — the evict-while-serving workload
    the paper's O(h) Delete makes possible, batched through the stream
    pipeline (one WAL-able apply per step instead of per entry)."""

    def __init__(self, store):
        self.store = store
        self.evict_cursor = 0
        self.n_ops = 0

    def step(self, h, toks):
        h = np.asarray(h, np.float32)
        toks = np.asarray(toks, np.int32)
        self.store.add_batch(h, toks)
        b = len(toks)
        self.store.evict_batch(np.arange(self.evict_cursor,
                                         self.evict_cursor + b))
        self.evict_cursor += b
        self.n_ops += 2 * b


def serve_sharded(args, cfg):
    """GSPMD-sharded greedy decode on a {data, model} mesh over all host
    devices, using the exact serve_step builders the dry-run lowers.  With
    ``--knn`` the SM-tree datastore rides along: the query cohort shards
    over 'data' (dist.sharding.query_pspecs) and retrieval runs the fused
    frontier fast path against replicated tree pages."""
    from repro.configs.base import ShapeSpec
    from repro.dist import sharding as shd
    from repro.serve.serve_step import (ServeSettings, make_decode_step,
                                        make_knnlm_mixer)

    n_dev = len(jax.devices())
    nm = 2 if n_dev % 2 == 0 else 1
    mesh = shd.make_mesh((n_dev // nm, nm), ("data", "model"))
    total = args.prompt_len + args.steps + 1
    shape = ShapeSpec("serve", total, args.batch, "decode")
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.prompt_len,
                    global_batch=args.batch)
    prompt = jnp.asarray(synth_batch(dc, 0, with_labels=False)["tokens"])

    with shd.use_mesh(mesh):
        # with --knn the step also returns its pre-head hidden state: the
        # datastore's query
        fn, sh = make_decode_step(cfg, mesh, shape,
                                  ServeSettings(hidden=args.knn))
        jitted = jax.jit(fn,
                         in_shardings=(sh["params"], sh["token"],
                                       sh["cache"], sh["pos"]),
                         out_shardings=(sh["token"], sh["logits"],
                                        sh["cache"])
                         + ((sh["hidden"],) if args.knn else ()),
                         donate_argnums=(2,))
        params = jax.jit(M.init_params, static_argnums=0,
                         out_shardings=sh["params"])(
            cfg, jax.random.PRNGKey(0))
        cache = jax.device_put(M.init_cache(cfg, args.batch, total),
                               sh["cache"])
        mix_fn = None
        mutator = None
        store = None
        if args.knn:
            store = _build_store(args, cfg, params, mesh=mesh)
            mix_fn, _ = make_knnlm_mixer(cfg, mesh, shape, store,
                                         lam=args.lam)
            if args.knn_mutate:
                mutator = _WindowMutator(store)
        t0 = time.time()
        for pos in range(args.prompt_len):
            tok, logits, cache, *_ = jitted(params, prompt[:, pos], cache,
                                            jnp.int32(pos))
        prefill_s = time.time() - t0
        out = [tok]
        t0 = time.time()
        for step in range(args.steps):
            tok, logits, cache, *hs = jitted(
                params, tok, cache, jnp.int32(args.prompt_len + step))
            if mix_fn is not None:
                h = hs[0]
                tok = jnp.argmax(mix_fn(logits, h), -1).astype(jnp.int32)
                if mutator is not None:
                    mutator.step(h, tok)
            out.append(tok)
        jax.block_until_ready(tok)
        decode_s = time.time() - t0
        fe = _finish_frontend(store)
    toks = np.stack([np.asarray(t) for t in out], axis=1)
    mut = (f", {mutator.n_ops} live mutations "
           f"({mutator.n_ops / decode_s:.0f} ops/s)" if mutator else "")
    print(f"[serve] mesh {dict(mesh.shape)} batch {args.batch}: "
          f"prefill {prefill_s:.2f}s, decode {args.steps} steps in "
          f"{decode_s:.2f}s ({decode_s / args.steps * 1e3:.1f} ms/step"
          f"{', kNN-LM mixed' if mix_fn else ''}{mut}{fe})")
    print("[serve] sample:", toks[0][:12])
    _dump_obs(args)
    return toks


def serve_single(args, cfg):
    """Greedy decode on one device, optionally kNN-LM mixed."""
    # jitted so the random init fuses into the parameter buffers: eager
    # init would hold each op's full-size temporaries beside the weights
    params = jax.jit(M.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.prompt_len,
                    global_batch=args.batch)
    prompt = jnp.asarray(synth_batch(dc, 0, with_labels=False)["tokens"])

    store = _build_store(args, cfg, params) if args.knn else None
    mutator = (_WindowMutator(store)
               if store is not None and args.knn_mutate else None)

    cache = M.init_cache(cfg, args.batch, args.prompt_len + args.steps + 1)
    # each step also returns its pre-head hidden state: the datastore's
    # query with --knn
    step_fn = jax.jit(functools.partial(M.decode_step, hidden=True),
                      static_argnums=1)

    t0 = time.time()
    for pos in range(args.prompt_len):
        logits, cache, _ = step_fn(params, cfg, prompt[:, pos], cache,
                                   jnp.int32(pos))
    prefill_s = time.time() - t0

    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for step in range(args.steps):
        pos = args.prompt_len + step
        logits, cache, h = step_fn(params, cfg, tok, cache, jnp.int32(pos))
        if store is not None:
            from repro.serve.knnlm import mix_logits
            h = h.astype(jnp.float32)
            logits = mix_logits(logits, store.knn_logits(
                h, logits.shape[-1]), args.lam)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        if store is not None and mutator is not None:
            mutator.step(h, tok)
        out.append(tok)
    jax.block_until_ready(tok)   # async dispatch: sync before timing
    decode_s = time.time() - t0
    fe = _finish_frontend(store)
    toks = np.stack([np.asarray(t) for t in out], axis=1)
    mut = (f", {mutator.n_ops} live mutations "
           f"({mutator.n_ops / decode_s:.0f} ops/s)" if mutator else "")
    print(f"[serve] batch {args.batch}: prefill {prefill_s:.2f}s, "
          f"decode {args.steps} steps in {decode_s:.2f}s "
          f"({decode_s / args.steps * 1e3:.1f} ms/step"
          f"{', kNN-LM mixed' if store else ''}{mut}{fe})")
    print("[serve] sample:", toks[0][:12])
    _dump_obs(args)
    return toks


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--knn", action="store_true",
                    help="mix with an SM-tree kNN-LM datastore")
    ap.add_argument("--knn-mutate", action="store_true",
                    help="with --knn: live sliding-window add/evict of "
                         "datastore entries each decode step (batched "
                         "through the repro.stream pipeline)")
    ap.add_argument("--frontend", action="store_true",
                    help="with --knn: route retrieval through the async "
                         "serving front-end (admission queue -> epoch-"
                         "pinned cohorts; mutations ride the scheduler "
                         "between epoch publishes)")
    ap.add_argument("--slo-ms", type=float, default=5.0,
                    help="front-end admission SLO: a partial cohort "
                         "dispatches once its oldest request is this old")
    ap.add_argument("--cohort-width", type=int, default=0,
                    help="front-end cohort width (0: use --batch); one "
                         "jitted kNN geometry per width")
    ap.add_argument("--replicas", type=int, default=0,
                    help="with --frontend: ship the WAL over a socket to "
                         "N read replicas and route queries through the "
                         "replica-aware router (stream/transport.py)")
    ap.add_argument("--knn-shards", type=int, default=0,
                    help="with --knn-mutate/--frontend: shard the "
                         "datastore into a streaming forest of N SM-trees "
                         "(host-side; per-shard descent + top-k merge) so "
                         "background rebalancing exercises under serving")
    ap.add_argument("--rebalance-mode", default="incremental",
                    choices=["stop_world", "incremental"],
                    help="with --knn-shards: skew repair strategy — "
                         "'incremental' drains skew one bounded, WAL-"
                         "replayable migration step per mutation batch "
                         "behind the epoch mechanism; 'stop_world' keeps "
                         "the one-shot rebuild baseline (also the replay "
                         "path for old WALs)")
    ap.add_argument("--obs", action="store_true",
                    help="enable the observability plane (repro.obs): "
                         "metrics registry, trace spans, flight recorder; "
                         "prints a final '[obs] {...}' JSON snapshot line")
    ap.add_argument("--obs-out", default=None, metavar="PATH",
                    help="with --obs: also write the final snapshot JSON "
                         "to PATH (for CI assertions)")
    ap.add_argument("--lam", type=float, default=0.3)
    ap.add_argument("--mesh", default="single", choices=["single", "host"],
                    help="'host': sharded decode over all visible devices "
                         "(a 1x1 mesh on one device)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.prompt_len < 1:
        ap.error("--prompt-len must be >= 1 (decode needs a seed token)")
    if args.replicas and not args.frontend:
        ap.error("--replicas requires --frontend (the router fronts the "
                 "admission queue)")
    if args.knn_shards > 1:
        if not (args.knn_mutate or args.frontend):
            ap.error("--knn-shards requires --knn-mutate or --frontend "
                     "(the forest lives in the stream pipeline)")
        if args.replicas:
            ap.error("--knn-shards does not compose with --replicas "
                     "(socket replication follows single-tree engines)")
        if args.mesh == "host":
            ap.error("--knn-shards is the host-side forest; it does not "
                     "compose with --mesh host")
    if args.obs:
        from repro import obs
        obs.enable()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    # an f32 model's matmuls run in f32.  At default precision XLA:TPU
    # feeds the MXU bf16 copies of the f32 weights and hoists those
    # converts out of the layer scan: a bf16 copy of every stacked weight
    # at once (5.2 GB beside qwen2.5-3b's 12.4 GB, more than a 16 GB chip)
    precision = (jax.default_matmul_precision("highest")
                 if cfg.compute_dtype == "float32"
                 else contextlib.nullcontext())
    with precision:
        if args.mesh == "host":
            # on one device this is a 1x1 mesh: the same sharded program
            return serve_sharded(args, cfg)
        return serve_single(args, cfg)


if __name__ == "__main__":
    main()
