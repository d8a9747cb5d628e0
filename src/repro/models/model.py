"""Unified model API: init / forward / decode, per-family dispatch, and
``input_specs`` (ShapeDtypeStruct stand-ins for the dry-run — weak-type
correct, shardable, never allocated).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeSpec
from repro.models import encdec, transformer


def init_params(cfg: ArchConfig, rng) -> dict:
    if cfg.is_encdec:
        return encdec.init_encdec(cfg, rng)
    return transformer.init_lm(cfg, rng)


def _no_hidden(cfg: ArchConfig, hidden: bool) -> None:
    if hidden and cfg.is_encdec:
        raise ValueError("pre-head hidden states are returned for "
                         "decoder-only LMs")


def forward(params, cfg: ArchConfig, batch: dict, *, remat: bool = False,
            attn_impl: str | None = None, hidden: bool = False):
    """Full-sequence forward -> (logits, aux); with ``hidden`` (decoder-only
    LMs) -> (logits, aux, pre-head hidden states [b, s, D]): the states the
    output head reads, after the final norm, which key a kNN-LM."""
    _no_hidden(cfg, hidden)
    if cfg.is_encdec:
        return encdec.encdec_forward(params, cfg, batch, remat=remat,
                                     attn_impl=attn_impl)
    return transformer.lm_forward(params, cfg, batch, remat=remat,
                                  attn_impl=attn_impl, hidden=hidden)


def init_cache(cfg: ArchConfig, batch: int, length: int, dtype=None):
    if cfg.is_encdec:
        return encdec.encdec_init_cache(cfg, batch, enc_len=length, dtype=dtype)
    return transformer.init_cache(cfg, batch, length, dtype=dtype)


def decode_step(params, cfg: ArchConfig, token, cache, pos_scalar, *,
                hidden: bool = False):
    """One-token decode with cache -> (logits [b, V], new cache); with
    ``hidden`` (decoder-only LMs) -> (logits, cache, the step's pre-head
    hidden state [b, D])."""
    _no_hidden(cfg, hidden)
    if cfg.is_encdec:
        return encdec.encdec_decode_step(params, cfg, token, cache, pos_scalar)
    return transformer.lm_decode_step(params, cfg, token, cache, pos_scalar,
                                      hidden=hidden)


def loss_fn(logits, labels, mask):
    """Mean next-token cross-entropy (labels already shifted).  float32.

    The gold logit is selected with a masked reduction rather than
    take_along_axis: a dynamic gather along the vocab axis would force GSPMD
    to all-gather the (vocab-sharded) logits — the masked sum partitions to
    a cheap [b, s] psum instead."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    V = logits.shape[-1]
    hit = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1) \
        == labels[..., None]
    gold = jnp.sum(jnp.where(hit, lf, 0.0), axis=-1)
    nll = (lse - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1)


# ---------------------------------------------------------------------------
# input specs (dry-run stand-ins)
# ---------------------------------------------------------------------------
def _whisper_lens(cfg: ArchConfig, shape: ShapeSpec) -> tuple[int, int]:
    """Map the LM (seq_len, batch) cell onto enc/dec lengths: encoder takes
    seq_len frames; decoder is bounded by Whisper's 448-position window."""
    enc = shape.seq_len
    dec = min(cfg.max_target_len, max(8, shape.seq_len // 8))
    return enc, dec


def input_specs(cfg: ArchConfig, shape: ShapeSpec) -> dict[str, Any]:
    """ShapeDtypeStructs for every model input of this (arch, shape) cell."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct

    if cfg.is_encdec:
        enc, dec = _whisper_lens(cfg, shape)
        if shape.kind == "train":
            return {"frames": sds((B, enc, cfg.d_model), f32),
                    "tokens": sds((B, dec), i32),
                    "labels": sds((B, dec), i32)}
        if shape.kind == "prefill":
            return {"frames": sds((B, enc, cfg.d_model), f32),
                    "tokens": sds((B, dec), i32)}
        return {"token": sds((B,), i32)}       # decode

    if cfg.frontend == "vision_stub":
        n_img = cfg.n_image_tokens
        s_txt = max(1, S - n_img)
        if shape.kind == "train":
            return {"tokens": sds((B, s_txt), i32),
                    "image_embeds": sds((B, n_img, cfg.d_model), f32),
                    "labels": sds((B, s_txt + n_img), i32)}
        if shape.kind == "prefill":
            return {"tokens": sds((B, s_txt), i32),
                    "image_embeds": sds((B, n_img, cfg.d_model), f32)}
        return {"token": sds((B,), i32)}

    if shape.kind == "train":
        return {"tokens": sds((B, S), i32), "labels": sds((B, S), i32)}
    if shape.kind == "prefill":
        return {"tokens": sds((B, S), i32)}
    return {"token": sds((B,), i32)}           # decode: 1 new token


def cache_specs(cfg: ArchConfig, shape: ShapeSpec):
    """ShapeDtypeStructs for the decode cache of this cell."""
    B, S = shape.global_batch, shape.seq_len
    if cfg.is_encdec:
        enc, _ = _whisper_lens(cfg, shape)
        fn = lambda: init_cache(cfg, B, enc)
    else:
        fn = lambda: init_cache(cfg, B, S)
    return jax.eval_shape(fn)


def param_specs(cfg: ArchConfig, seed: int = 0):
    """Parameter ShapeDtypeStructs without allocation."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(seed)))


def exact_param_count(cfg: ArchConfig) -> int:
    import numpy as np
    specs = param_specs(cfg)
    # np.prod with int64: leaf shapes like [4, 16, 4096, 14336] overflow int32
    return sum(int(np.prod(l.shape, dtype=np.int64))
               for l in jax.tree.leaves(specs))
