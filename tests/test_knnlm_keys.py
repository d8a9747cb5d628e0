"""The served kNN-LM keys on what the published method keys on: the LM's
pre-head hidden state (Khandelwal et al., arXiv:1911.00172, section 3).

* The program's StarCoder2 (the smoke preset, seeded weights) gives the
  pre-head states that the benchmark's own plain forward pass
  (``chipbench/data/starcoder2_hidden.py``, which imports nothing of the
  program) gives on the same weights: the yardstick's keys are the
  program's.
* ``launch/serve.py --knn`` builds its datastore from the model's pre-head
  states (each valued by the next token) and queries it, at every decode
  step, with that step's pre-head state.
"""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.all_archs import smoke_config
from repro.data.pipeline import DataConfig, synth_batch
from repro.models import model as M

ROOT = Path(__file__).resolve().parents[1]
# float32 on the CPU: the program's chunked attention and the plain
# softmax sum in different orders; the states are LayerNorm outputs of
# magnitude about 1
ATOL = RTOL = 1e-5


def _plain_forward():
    path = ROOT / "chipbench" / "data" / "starcoder2_hidden.py"
    spec = importlib.util.spec_from_file_location("starcoder2_hidden", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _perturbed_params(cfg, seed):
    """Seeded weights, with every bias and norm parameter drawn too (the
    initialisers leave them at 0 and 1)."""
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        x + 0.1 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


def test_program_pre_head_states_equal_the_plain_forward():
    cfg = smoke_config("starcoder2-3b")
    gen = _plain_forward()
    km = dict(d_model=cfg.d_model, n_layers=cfg.n_layers,
              n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
              d_head=cfg.d_head, d_ff=cfg.d_ff, vocab_size=cfg.vocab_size,
              norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta)
    params = _perturbed_params(cfg, 3)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (3, 40)), jnp.int32)
    logits, _, h = M.forward(params, cfg, {"tokens": toks}, hidden=True)
    want = np.asarray(gen.forward(params, toks, km))
    assert h.shape == want.shape == (3, 40, cfg.d_model)
    np.testing.assert_allclose(np.asarray(h), want, rtol=RTOL, atol=ATOL)
    # the logits are the output head applied to those states
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(h) @ np.asarray(params["lm_head"]["w"]),
        rtol=1e-4, atol=1e-4)
    # the plain forward's own weights: a pure function of the seed
    a, b = gen.init(km, 61), gen.init(km, 61)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_served_knnlm_queries_with_the_steps_pre_head_state(monkeypatch):
    from repro.launch import serve
    from repro.serve.knnlm import KnnLmConfig, KnnLmDatastore
    built, queries = [], []
    build, knn_logits = KnnLmDatastore.build, KnnLmDatastore.knn_logits

    def rec_build(self, keys, values):
        built.append((self, np.array(keys), np.array(values)))
        return build(self, keys, values)

    def rec_logits(self, h, vocab):
        queries.append(np.array(h))
        return knn_logits(self, h, vocab)

    monkeypatch.setattr(KnnLmDatastore, "build", rec_build)
    monkeypatch.setattr(KnnLmDatastore, "knn_logits", rec_logits)
    b, s0, steps = 2, 3, 2
    toks = serve.main(["--arch", "starcoder2-3b", "--smoke", "--batch",
                       str(b), "--prompt-len", str(s0), "--steps",
                       str(steps), "--knn", "--lam", "0.25"])
    cfg = smoke_config("starcoder2-3b")
    params = jax.jit(M.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))

    # the datastore: pre-head state of each position -> the next token
    (store, keys, vals), = built
    assert store.cfg.metric == KnnLmConfig().metric == "l2"
    assert store.cfg.k == KnnLmConfig().k and store.cfg.lam == 0.25
    stream = synth_batch(DataConfig(vocab_size=cfg.vocab_size,
                                    seq_len=serve.STORE_SEQ_LEN,
                                    global_batch=serve.STORE_SEQS), 1,
                         with_labels=False)["tokens"]
    _, _, hs = M.forward(params, cfg, {"tokens": jnp.asarray(stream)},
                         hidden=True)
    np.testing.assert_allclose(
        keys, np.asarray(hs)[:, :-1].reshape(-1, cfg.d_model),
        rtol=RTOL, atol=ATOL)
    np.testing.assert_array_equal(vals, stream[:, 1:].reshape(-1))

    # each decode step's query: the pre-head state at the step's position
    # of the tokens fed so far (the prompt, then each generated token)
    prompt = synth_batch(DataConfig(vocab_size=cfg.vocab_size,
                                    seq_len=s0, global_batch=b), 0,
                         with_labels=False)["tokens"]
    fed = np.concatenate([prompt, np.asarray(toks)[:, :steps]], axis=1)
    _, _, h = M.forward(params, cfg, {"tokens": jnp.asarray(fed)},
                        hidden=True)
    assert len(queries) == steps
    for step, q in enumerate(queries):
        np.testing.assert_allclose(q, np.asarray(h)[:, s0 + step],
                                   rtol=1e-4, atol=1e-4)
