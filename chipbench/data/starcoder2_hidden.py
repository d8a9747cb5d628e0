"""kNN-LM keys (Khandelwal et al., arXiv:1911.00172, section 3): the hidden
state a decoder LM feeds its output head, one per position of a token
stream.  The key model is StarCoder2 (arXiv:2402.19173) at its published
widths, with seeded random weights in place of a checkpoint.

The forward pass is plain ``jax.numpy`` in float32 at the "highest" matmul
precision, written here from the architecture and importing nothing of the
program under test, so no change to the program can move the yardstick:
token embedding, then per layer a pre-LayerNorm causal attention block (GQA,
biased QKV, rotary positions rotating the two halves of each head) and a
pre-LayerNorm GELU(tanh) MLP, each added to the residual stream, and a final
LayerNorm.  Its output is the key.  Parameters use the program's layout
(``blocks[0]`` holds each weight stacked over layers), so a test can run
both models on one set of weights.

The configuration's ``key_model`` group gives the widths and depth;
``corpus`` draws ``sequences`` token streams of ``seq_len`` tokens from a
Zipf law of exponent ``zipf_s`` over the vocabulary (token id = frequency
rank), all from ``data_seed``.  ``queries`` are test-time states: the keys
of ``query_sequences`` held-out streams drawn from the run's seed, taken
from position ``query_skip`` on, so no query repeats a corpus row (two
streams agree on a key only where they share a whole prefix).
"""
from __future__ import annotations

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

NAME = Path(__file__).stem
BATCH_SEQS = 8          # streams a forward call runs


# ---------------------------------------------------------------- the model
def init(km: dict, seed: int) -> dict:
    """Seeded weights, on the default device: truncated normals scaled by
    fan-in (embedding 1), zero biases, unit LayerNorms."""
    return _init(tuple(sorted(km.items())), seed)


@functools.partial(jax.jit, static_argnums=0)
def _init(km, seed):
    km = dict(km)
    D, L, F = km["d_model"], km["n_layers"], km["d_ff"]
    H, KV, dh = km["n_heads"], km["n_kv_heads"], km["d_head"]

    def tn(key, shape, scale):
        return scale * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                                   jnp.float32)

    def ln():
        return {"scale": jnp.ones((L, D), jnp.float32),
                "bias": jnp.zeros((L, D), jnp.float32)}

    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    attn = {"wq": tn(ks[0], (L, D, H, dh), D ** -0.5),
            "wk": tn(ks[1], (L, D, KV, dh), D ** -0.5),
            "wv": tn(ks[2], (L, D, KV, dh), D ** -0.5),
            "wo": tn(ks[3], (L, H, dh, D), (H * dh) ** -0.5),
            "bq": jnp.zeros((L, H, dh), jnp.float32),
            "bk": jnp.zeros((L, KV, dh), jnp.float32),
            "bv": jnp.zeros((L, KV, dh), jnp.float32)}
    ffn = {"wi": {"w": tn(ks[4], (L, D, F), D ** -0.5)},
           "wo": {"w": tn(ks[5], (L, F, D), F ** -0.5)}}
    return {"embed": tn(ks[6], (km["vocab_size"], D), 1.0),
            "final_norm": {"scale": jnp.ones((D,), jnp.float32),
                           "bias": jnp.zeros((D,), jnp.float32)},
            "blocks": [{"norm1": ln(), "attn": attn, "norm2": ln(),
                        "ffn": ffn}]}


def _layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _rope(x, theta):
    """x [b, heads, s, dh]: rotate the halves of each head by position."""
    s, dh = x.shape[2], x.shape[3]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs      # [s, dh/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(x, p, km):
    eps = km["norm_eps"]
    H, KV, dh = km["n_heads"], km["n_kv_heads"], km["d_head"]
    s = x.shape[1]
    h = _layernorm(x, p["norm1"]["scale"], p["norm1"]["bias"], eps)
    a = p["attn"]
    q = jnp.einsum("bsd,dhe->bhse", h, a["wq"]) + a["bq"][None, :, None]
    k = jnp.einsum("bsd,dhe->bhse", h, a["wk"]) + a["bk"][None, :, None]
    v = jnp.einsum("bsd,dhe->bhse", h, a["wv"]) + a["bv"][None, :, None]
    q, k = _rope(q, km["rope_theta"]), _rope(k, km["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    logits = jnp.einsum("bhqe,bhke->bhqk", q, k) / np.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhke->bhqe", w, v)
    x = x + jnp.einsum("bhse,hed->bsd", o, a["wo"])
    h = _layernorm(x, p["norm2"]["scale"], p["norm2"]["bias"], eps)
    f = jax.nn.gelu(h @ p["ffn"]["wi"]["w"], approximate=True)
    return x + f @ p["ffn"]["wo"]["w"]


@functools.partial(jax.jit, static_argnames=("km",))
def _forward(params, tokens, km):
    kmd = dict(km)
    x = params["embed"][tokens]
    blocks = params["blocks"][0]
    x, _ = jax.lax.scan(lambda x, p: (_layer(x, p, kmd), None), x, blocks)
    fn = params["final_norm"]
    return _layernorm(x, fn["scale"], fn["bias"], kmd["norm_eps"])


def forward(params, tokens, km: dict):
    """Pre-head hidden states [b, s, d_model] f32 of token streams
    [b, s]."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, tokens, tuple(sorted(km.items())))


# ---------------------------------------------------------------- streams
def tokens(cfg: dict, rng, n_seqs: int) -> np.ndarray:
    """[n_seqs, seq_len] token ids, Zipf over the vocabulary."""
    V = cfg["key_model"]["vocab_size"]
    p = 1.0 / np.arange(1, V + 1) ** cfg["zipf_s"]
    return rng.choice(V, size=(n_seqs, cfg["seq_len"]),
                      p=p / p.sum()).astype(np.int32)


def states(cfg: dict, params, toks: np.ndarray) -> np.ndarray:
    """Keys [n_seqs, seq_len, d_model] of the streams, on the host."""
    out = np.empty(toks.shape + (cfg["key_model"]["d_model"],), np.float32)
    for s in range(0, len(toks), BATCH_SEQS):
        out[s:s + BATCH_SEQS] = np.asarray(forward(
            params, jnp.asarray(toks[s:s + BATCH_SEQS]), cfg["key_model"]))
    return out


def corpus(cfg: dict) -> np.ndarray:
    """The datastore's ``n`` keys [n, d_model] f32, from ``data_seed``
    alone: every position of ``sequences`` streams."""
    km = cfg["key_model"]
    if cfg["n"] != cfg["sequences"] * cfg["seq_len"] or \
            cfg["dim"] != km["d_model"]:
        raise ValueError("n must be sequences x seq_len and dim d_model")
    toks = tokens(cfg, np.random.default_rng([cfg["data_seed"], 0]),
                  cfg["sequences"])
    params = init(km, cfg["data_seed"])
    return states(cfg, params, toks).reshape(cfg["n"], km["d_model"])


@functools.cache
def _config() -> dict:
    """The configuration this generator serves: those under ``configs/``
    that name it, which must agree on what the queries depend on."""
    found = []
    for p in sorted((Path(__file__).resolve().parents[1]
                     / "configs").glob("*.json")):
        c = json.loads(p.read_text())
        if c.get("generator") == NAME:
            found.append(c)
    keys = ("key_model", "data_seed", "seq_len", "zipf_s",
            "query_sequences", "query_skip")
    if not found or any({k: c[k] for k in keys}
                        != {k: found[0][k] for k in keys} for c in found):
        raise LookupError(f"no single key model names generator {NAME!r}")
    return found[0]


_POOL: dict = {}        # the last seed's query states


def query_pool(cfg: dict, seed: int) -> np.ndarray:
    """Keys of the held-out streams of ``seed`` [m, d_model], from
    position ``query_skip`` on (computed once a seed)."""
    key = (json.dumps(cfg["key_model"], sort_keys=True), cfg["data_seed"],
           seed)
    if key not in _POOL:
        _POOL.clear()
        toks = tokens(cfg, np.random.default_rng([seed, 1]),
                      cfg["query_sequences"])
        params = init(cfg["key_model"], cfg["data_seed"])
        h = states(cfg, params, toks)[:, cfg["query_skip"]:]
        _POOL[key] = np.ascontiguousarray(h.reshape(-1, h.shape[-1]))
    return _POOL[key]


def queries(corpus: np.ndarray, seed: int, n: int, block: int = 0,
            cfg: dict | None = None) -> np.ndarray:
    """``n`` query states [n, d_model] f32 drawn from the seed's held-out
    streams; ``block`` numbers successive draws of one run."""
    pool = query_pool(cfg or _config(), seed)
    if pool.shape[1] != corpus.shape[1]:
        raise ValueError("the key model's width is not the corpus's")
    rng = np.random.default_rng([seed, 2, block])
    return pool[rng.choice(len(pool), n, replace=n > len(pool))]
