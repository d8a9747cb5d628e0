"""Serving step builders: prefill and cached decode, with GSPMD shardings.

``make_decode_step`` / ``make_prefill_step`` mirror train_step's builder
pattern; the dry-run lowers these for the decode_*/prefill_* shape cells.
The kNN-LM datastore mixing (core SM-tree feature) hooks in via
serve/knnlm.py and is exercised by examples/knnlm_serve.py.
"""
from __future__ import annotations

import dataclasses
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec
from repro.dist import sharding as shd
from repro.models import model as M


@dataclasses.dataclass(frozen=True)
class ServeSettings:
    attn_impl: str | None = None
    temperature: float = 1.0
    greedy: bool = True
    seq_shard_cache: bool = False   # long-context: shard KV cache over seq
    hidden: bool = False   # also return the step's pre-head hidden state


def make_decode_step(cfg: ArchConfig, mesh, shape: ShapeSpec,
                     settings: ServeSettings = ServeSettings()):
    """Returns (decode_fn, shardings).  decode_fn(params, token, cache, pos)
    -> (next_token, logits, cache), and with ``settings.hidden`` the step's
    pre-head hidden state [b, D] (a kNN-LM datastore's query) as a fourth
    element (sharding ``shardings["hidden"]``)."""

    def decode_fn(params, token, cache, pos):
        logits, cache, *h = M.decode_step(params, cfg, token, cache, pos,
                                          hidden=settings.hidden)
        if settings.greedy:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            nxt = jnp.argmax(logits / settings.temperature, -1).astype(jnp.int32)
        return (nxt, logits, cache, *h)

    pspecs = shd.param_pspecs(cfg, M.param_specs(cfg), mesh)
    param_sh = shd.to_named(pspecs, mesh)
    cache_tree = M.cache_specs(cfg, shape)
    cache_specs_tree = shd.cache_pspecs(cfg, cache_tree, mesh,
                                        seq_shard=settings.seq_shard_cache)
    cache_sh = shd.to_named(cache_specs_tree, mesh)
    dp = shd.batch_dp(mesh)
    import numpy as np
    dsize = int(np.prod([mesh.shape[a] for a in
                         (dp if isinstance(dp, tuple) else (dp,))]))
    tok_spec = P(dp) if shape.global_batch % dsize == 0 \
        and shape.global_batch >= dsize else P(None)
    token_sh = NamedSharding(mesh, tok_spec)
    logits_sh = NamedSharding(mesh, P(tok_spec[0] if tok_spec else None,
                                      "model"))
    shardings = dict(params=param_sh, cache=cache_sh, token=token_sh,
                     logits=logits_sh, pos=NamedSharding(mesh, P()),
                     hidden=NamedSharding(mesh, P(tok_spec[0] if tok_spec
                                                  else None, None)),
                     pspecs=pspecs)
    return decode_fn, shardings


def make_knnlm_mixer(cfg: ArchConfig, mesh, shape: ShapeSpec, store,
                     lam: float | None = None):
    """Returns (mix_fn, query_sharding) wiring an SM-tree kNN-LM datastore
    into the sharded decode loop.

    ``mix_fn(logits, h)`` runs the [b, D] hidden-state cohort through the
    datastore's kNN — the store itself shards queries over the data axes
    (``KnnLmDatastore.shard_queries`` / ``shd.query_pspecs``, the same
    sharding the token batch carries) against replicated tree pages — and
    returns the interpolated logits.  Pairs with ``make_decode_step``; the
    returned query sharding is for wiring into jit in/out shardings."""
    from repro.serve.knnlm import mix_logits

    query_sh = NamedSharding(mesh, shd.query_pspecs(mesh, shape.global_batch))
    store.mesh = mesh          # ensure the store shards its query cohorts
    store._place()             # ...and replicates tree pages on this mesh
    lam = store.cfg.lam if lam is None else lam

    def mix_fn(logits, h):
        knn_logp = store.knn_logits(h.astype(jnp.float32), logits.shape[-1])
        return mix_logits(logits, knn_logp, lam)

    return mix_fn, query_sh


def make_prefill_step(cfg: ArchConfig, mesh, shape: ShapeSpec,
                      settings: ServeSettings = ServeSettings()):
    """Full-sequence forward producing logits (inference, no labels)."""

    def prefill_fn(params, batch):
        logits, _ = M.forward(params, cfg, batch, remat=False,
                              attn_impl=settings.attn_impl)
        return logits

    pspecs = shd.param_pspecs(cfg, M.param_specs(cfg), mesh)
    inputs = M.input_specs(cfg, shape)
    shardings = dict(
        params=shd.to_named(pspecs, mesh),
        batch=shd.to_named(shd.input_pspecs(cfg, "prefill", inputs, mesh), mesh),
        logits=NamedSharding(mesh, shd.logits_pspec(mesh)),
        pspecs=pspecs)
    return prefill_fn, shardings
