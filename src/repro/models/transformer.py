"""Unified decoder-LM assembly for all block families.

The layer stack is a `lax.scan` over ``n_periods`` stacked copies of the
config's ``block_pattern`` (one period = one pytree level, e.g. jamba's
(mamba, mamba_moe, ..., attn, ...) 8-layer period).  Scanning keeps HLO size
and compile time flat in depth — essential for the 40-cell dry-run — and the
period is the remat (activation-checkpoint) unit.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import constrain, constrain_batch_seq
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import apply_norm, dense, dense_init, mlp_apply, \
    mlp_init, norm_init, truncated_normal


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _block_init(kind: str, key, cfg: ArchConfig, dtype):
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {"norm1": norm_init(cfg.d_model, cfg.norm, dtype)}
    if kind in ("attn", "attn_moe"):
        p["attn"] = attn_mod.attn_init(ks[0], cfg, dtype)
    elif kind in ("mamba", "mamba_moe"):
        p["mamba"] = ssm_mod.mamba_init(ks[0], cfg, dtype)
    elif kind == "mlstm":
        p["mlstm"] = xlstm_mod.mlstm_init(ks[0], cfg, dtype)
        return p                      # xLSTM blocks carry no separate FFN
    elif kind == "slstm":
        p["slstm"] = xlstm_mod.slstm_init(ks[0], cfg, dtype)
        return p
    else:
        raise ValueError(kind)
    p["norm2"] = norm_init(cfg.d_model, cfg.norm, dtype)
    if kind.endswith("_moe"):
        p["moe"] = moe_mod.moe_init(ks[1], cfg, dtype)
    elif cfg.d_ff:
        p["ffn"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, dtype,
                            gated=cfg.gated_mlp)
    return p


def init_lm(cfg: ArchConfig, rng) -> dict:
    dtype = _dtype(cfg)
    k_embed, k_blocks, k_head = jax.random.split(rng, 3)
    params: dict[str, Any] = {
        "embed": truncated_normal(k_embed, (cfg.padded_vocab, cfg.d_model),
                                  1.0, dtype),
        "final_norm": norm_init(cfg.d_model, cfg.norm, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.padded_vocab,
                                       dtype)
    blocks = []
    keys = jax.random.split(k_blocks, len(cfg.block_pattern))
    for j, kind in enumerate(cfg.block_pattern):
        pkeys = jax.random.split(keys[j], cfg.n_periods)
        blocks.append(jax.vmap(
            lambda k: _block_init(kind, k, cfg, dtype))(pkeys))
    params["blocks"] = blocks
    if cfg.pos_embedding == "learned":
        params["pos_embed"] = truncated_normal(
            k_head, (cfg.max_target_len, cfg.d_model), 0.02, dtype)
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------
def _block_apply(kind, p, cfg, x, pos, attn_impl):
    aux = None
    h = apply_norm(p["norm1"], x, cfg.norm, cfg.norm_eps)
    if kind in ("attn", "attn_moe"):
        x = x + attn_mod.attn_apply(p["attn"], cfg, h, pos=pos, impl=attn_impl)
    elif kind in ("mamba", "mamba_moe"):
        x = x + ssm_mod.mamba_apply(p["mamba"], cfg, h)
    elif kind == "mlstm":
        return x + xlstm_mod.mlstm_apply(p["mlstm"], cfg, h), aux
    elif kind == "slstm":
        return x + xlstm_mod.slstm_apply(p["slstm"], cfg, h), aux
    h = apply_norm(p["norm2"], x, cfg.norm, cfg.norm_eps)
    if kind.endswith("_moe"):
        y, aux = moe_mod.moe_apply(p["moe"], cfg, h)
        x = x + y
    elif cfg.d_ff:
        x = x + mlp_apply(p["ffn"], h, gated=cfg.gated_mlp)
    return x, aux


def embed_inputs(params, cfg: ArchConfig, batch: dict):
    """Token (+ stub-frontend) embedding.  Returns (x [b,s,D], pos [b,s])."""
    emb = params["embed"]
    x = emb[batch["tokens"]].astype(jnp.dtype(cfg.compute_dtype))
    if cfg.frontend == "vision_stub":
        img = batch["image_embeds"].astype(x.dtype)     # [b, n_img, D]
        x = jnp.concatenate([img, x], axis=1)
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if cfg.pos_embedding == "learned":
        x = x + params["pos_embed"][:s].astype(x.dtype)
    return x, pos


def lm_forward(params, cfg: ArchConfig, batch: dict, *, remat: bool = False,
               attn_impl: str | None = None, hidden: bool = False):
    """Full-sequence forward.  Returns (logits [b,s,V], aux dict), and with
    ``hidden`` the pre-head hidden states [b,s,D] (after the final norm)
    as a third element."""
    x, pos = embed_inputs(params, cfg, batch)
    x = constrain_batch_seq(x)   # pin DP before the layer scan (GSPMD would
                                 # otherwise happily replicate the batch)

    def period_fn(x, period_params):
        aux_sums = jnp.zeros((3,), jnp.float32)
        x = constrain_batch_seq(x)
        for j, kind in enumerate(cfg.block_pattern):
            x, aux = _block_apply(kind, period_params[j], cfg, x, pos,
                                  attn_impl)
            x = constrain_batch_seq(x)
            if aux is not None:
                aux_sums = aux_sums + jnp.stack(
                    [aux["lb_loss"], aux["z_loss"], aux["drop_frac"]])
        return x, aux_sums

    f = jax.checkpoint(period_fn) if remat else period_fn
    x, aux_sums = jax.lax.scan(lambda c, p: f(c, p), x, params["blocks"])
    aux_sums = aux_sums.sum(0)
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T.astype(x.dtype)
    else:
        logits = dense(params["lm_head"], x)
    logits = constrain(logits, ("pod", "data"), None, "model")
    n_moe = sum(1 for k in cfg.block_pattern if k.endswith("_moe"))
    denom = max(1, n_moe * cfg.n_periods)
    aux = {"lb_loss": aux_sums[0] / denom, "z_loss": aux_sums[1] / denom,
           "drop_frac": aux_sums[2] / denom}
    return (logits, aux, x) if hidden else (logits, aux)


# ---------------------------------------------------------------------------
# decode (single token, cached)
# ---------------------------------------------------------------------------
def init_cache(cfg: ArchConfig, batch: int, length: int, dtype=None) -> list:
    """Cache pytree: one entry per in-period block, leaves stacked
    [n_periods, ...]."""
    dtype = dtype or jnp.dtype(cfg.compute_dtype)

    def stack(make):
        leaves = [make() for _ in range(cfg.n_periods)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *leaves)

    cache = []
    for kind in cfg.block_pattern:
        if kind in ("attn", "attn_moe"):
            cache.append(stack(lambda: {"kv": attn_mod.init_kv_cache(
                cfg, batch, length, dtype)}))
        elif kind in ("mamba", "mamba_moe"):
            cache.append(stack(lambda: ssm_mod.mamba_init_cache(
                cfg, batch, dtype)))
        elif kind == "mlstm":
            cache.append(stack(lambda: xlstm_mod.mlstm_init_cache(
                cfg, batch, dtype)))
        elif kind == "slstm":
            cache.append(stack(lambda: xlstm_mod.slstm_init_cache(
                cfg, batch, dtype)))
    return cache


def _block_decode(kind, p, cfg, x1, cslice, pos_scalar):
    h = apply_norm(p["norm1"], x1, cfg.norm, cfg.norm_eps)
    if kind in ("attn", "attn_moe"):
        y, kv = attn_mod.attn_decode(p["attn"], cfg, h, cslice["kv"], pos_scalar)
        x1 = x1 + y
        new_c = {"kv": kv}
    elif kind in ("mamba", "mamba_moe"):
        y, new_c = ssm_mod.mamba_decode(p["mamba"], cfg, h, cslice)
        x1 = x1 + y
    elif kind == "mlstm":
        y, new_c = xlstm_mod.mlstm_decode(p["mlstm"], cfg, h, cslice)
        return x1 + y, new_c
    elif kind == "slstm":
        y, new_c = xlstm_mod.slstm_decode(p["slstm"], cfg, h, cslice)
        return x1 + y, new_c
    h = apply_norm(p["norm2"], x1, cfg.norm, cfg.norm_eps)
    if kind.endswith("_moe"):
        # dropless at decode: worst case every token routes to one expert
        y, _ = moe_mod.moe_apply(p["moe"], cfg, h, capacity=x1.shape[0])
        x1 = x1 + y
    elif cfg.d_ff:
        x1 = x1 + mlp_apply(p["ffn"], h, gated=cfg.gated_mlp)
    return x1, new_c


def lm_decode_step(params, cfg: ArchConfig, token, cache, pos_scalar, *,
                   hidden: bool = False):
    """token: [b] int32; pos_scalar: [] int32.  Returns (logits [b,V],
    cache), and with ``hidden`` the step's pre-head hidden state [b,D]
    as a third element."""
    x = params["embed"][token][:, None, :].astype(jnp.dtype(cfg.compute_dtype))
    if cfg.pos_embedding == "learned":
        x = x + params["pos_embed"][pos_scalar][None, None].astype(x.dtype)

    def period_fn(x1, xs):
        period_params, cslices = xs
        x1 = constrain_batch_seq(x1)
        new_slices = []
        for j, kind in enumerate(cfg.block_pattern):
            x1, nc = _block_decode(kind, period_params[j], cfg, x1,
                                   cslices[j], pos_scalar)
            x1 = constrain_batch_seq(x1)
            new_slices.append(nc)
        return x1, new_slices

    x, new_cache = jax.lax.scan(period_fn, x, (params["blocks"], cache))
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T.astype(x.dtype)
    else:
        logits = dense(params["lm_head"], x)
    if hidden:
        return logits[:, 0], new_cache, x[:, 0]
    return logits[:, 0], new_cache
