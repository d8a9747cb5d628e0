"""kNN-LM serving: the SM-tree datastore as a first-class LM feature.

    PYTHONPATH=src python examples/knnlm_serve.py

1. Trains a small LM briefly on the synthetic stream.
2. Builds a kNN datastore of (hidden state -> next token) pairs from the
   training data (bulk build).
3. Serves batched requests with kNN-LM mixing p = (1-l)*p_LM + l*p_kNN and
   shows retrieval changes predictions.
4. Evicts the oldest half of the datastore ONLINE with the paper's Delete —
   no rebuild — and keeps serving.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.all_archs import smoke_config
from repro.data.pipeline import DataConfig, synth_batch
from repro.models import model as M
from repro.serve.knnlm import KnnLmConfig, KnnLmDatastore, mix_logits
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import TrainSettings, init_all, make_train_step
from repro.dist.sharding import use_mesh as _use_mesh
from repro.dist.sharding import make_mesh


cfg = dataclasses.replace(smoke_config("qwen2.5-3b"), n_layers=2,
                          block_pattern=("attn",))
mesh = make_mesh((1, 1), ("data", "model"))
dc = DataConfig(seed=0, vocab_size=cfg.vocab_size, seq_len=64, global_batch=8)

# --- 1. brief training -------------------------------------------------------
batch0 = synth_batch(dc, 0)
inputs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch0.items()}
with _use_mesh(mesh):
    step_fn, sh = make_train_step(
        cfg, mesh, inputs,
        TrainSettings(opt=AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=60)))
    jitted = jax.jit(step_fn, donate_argnums=(0, 1))
    params, opt = init_all(cfg, jax.random.PRNGKey(0))
    for step in range(60):
        params, opt, metrics = jitted(params, opt, synth_batch(dc, step))
    print(f"trained 60 steps, loss {float(metrics['loss']):.3f}")

# --- 2. datastore of (hidden, next_token) from held-out batches ---------------
def hidden_states(params, cfg, tokens):
    """Final pre-head hidden states [b, s, D]."""
    from repro.models.transformer import embed_inputs, _block_apply
    from repro.models.layers import apply_norm
    x, pos = embed_inputs(params, cfg, {"tokens": tokens})
    def period_fn(x, pp):
        for j, kind in enumerate(cfg.block_pattern):
            x, _ = _block_apply(kind, pp[j], cfg, x, pos, None)
        return x, None
    x, _ = jax.lax.scan(period_fn, x, params["blocks"])
    return apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)

keys, vals = [], []
for step in range(100, 104):
    b = synth_batch(dc, step)
    h = hidden_states(params, cfg, jnp.asarray(b["tokens"]))
    keys.append(np.asarray(h[:, :-1].reshape(-1, cfg.d_model)))
    vals.append(np.asarray(b["tokens"][:, 1:]).reshape(-1))
keys = np.concatenate(keys); vals = np.concatenate(vals)
store = KnnLmDatastore(KnnLmConfig(k=8, lam=0.3, metric="l2"), cfg.d_model)
store.build(keys, vals)
print(f"datastore: {len(vals)} entries, "
      f"{int(np.asarray(store.engine.tree.alive).sum())} tree nodes")

# --- 3. batched serving with retrieval mixing ---------------------------------
req = synth_batch(dc, 200)["tokens"][:, :16]
b, s0 = req.shape
cache = M.init_cache(cfg, b, s0 + 8)
for pos in range(s0):
    logits, cache = M.decode_step(params, cfg, jnp.asarray(req[:, pos]),
                                  cache, jnp.int32(pos))
h_last = hidden_states(params, cfg, jnp.asarray(req))[:, -1]
knn_logp = store.knn_logits(h_last, cfg.padded_vocab)
mixed = mix_logits(logits, knn_logp, lam=0.3)
base_tok = np.asarray(jnp.argmax(logits, -1))
mixed_tok = np.asarray(jnp.argmax(mixed, -1))
print("LM argmax:    ", base_tok)
print("kNN-LM argmax:", mixed_tok)
print(f"retrieval changed {int((base_tok != mixed_tok).sum())}/{b} predictions")

# --- 4. ONLINE eviction via the paper's Delete --------------------------------
n_before = store.engine.n_objects
evicted = store.evict_before(len(vals) // 2)
store.engine.validate()
print(f"evicted {evicted} of {n_before} entries online "
      f"(SM-tree Delete; invariants still hold)")
knn_logp2 = store.knn_logits(h_last, cfg.padded_vocab)
mixed2 = np.asarray(jnp.argmax(mix_logits(logits, knn_logp2, 0.3), -1))
print("post-eviction kNN-LM argmax:", mixed2)
