"""Error-feedback wiring for int8 gradient compression (ROADMAP item).

Two layers: (1) the algebraic EF property — with the residual threaded
back in, the running sum of dequantized gradients tracks the running sum
of true gradients to within ~one quantisation step, i.e. the quantisation
error is a delayed correction, not a bias that compounds; (2) the train
step — ``TrainSettings(error_feedback=True)`` carries persistent EF state
through ``make_train_step`` and converges on par with uncompressed
training on a smoke config.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import make_mesh
from repro.dist.compression import (compressed_mean_hook, compressed_psum_mean,
                                    init_ef_state)


def test_hook_ef_error_bounded_over_steps():
    rng = np.random.default_rng(0)
    g0 = rng.normal(size=(256,)).astype(np.float32)
    grads = {"w": jnp.asarray(g0)}
    ef = init_ef_state(grads)
    acc_true = np.zeros_like(g0)
    acc_q = np.zeros_like(g0)
    worst = 0.0
    for i in range(50):
        gi = {"w": jnp.asarray(g0 * (1.0 + 0.02 * np.sin(i)))}
        out, ef = compressed_mean_hook(gi, ef=ef)
        acc_true += np.asarray(gi["w"])
        acc_q += np.asarray(out["w"])
        worst = max(worst, float(np.abs(acc_true - acc_q).max()))
    # one quantisation step of the largest per-step gradient, not O(steps)
    step_scale = 1.02 * np.abs(g0).max() / 127
    assert worst <= 2.5 * step_scale, (worst, step_scale)
    # without EF the same accumulation drifts measurably more
    acc_q0 = np.zeros_like(g0)
    for i in range(50):
        gi = {"w": jnp.asarray(g0 * (1.0 + 0.02 * np.sin(i)))}
        out = compressed_mean_hook(gi)
        acc_q0 += np.asarray(out["w"])
    assert np.abs(acc_true - acc_q0).max() >= worst


def test_hook_ef_none_mode_passthrough():
    g = {"w": jnp.ones((4,))}
    ef = init_ef_state(g)
    out, ef2 = compressed_mean_hook(g, mode="none", ef=ef)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.ones(4))
    assert ef2 is ef
    # legacy no-EF call shape unchanged
    out2 = compressed_mean_hook(g, mode="none")
    assert isinstance(out2, dict)


def test_psum_mean_accepts_ef():
    # single-axis shard_map with one device: EF residual folds in and the
    # returned err is the next state
    from repro.dist.sharding import shard_map
    from jax.sharding import PartitionSpec as P
    import functools
    mesh = make_mesh((1,), ("data",))
    g = np.linspace(-1, 1, 64).astype(np.float32)[None]
    ef0 = np.full((1, 64), 0.003, np.float32)

    @functools.partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                       out_specs=(P("data"), P("data")))
    def run(gs, efs):
        mean, err = compressed_psum_mean({"g": gs}, "data", ef={"g": efs})
        return mean["g"], err["g"]

    mean, err = run(jnp.asarray(g), jnp.asarray(ef0))
    scale = np.abs(g + ef0).max() / 127
    # mean ~ g + ef within one quantisation step; err is the new residual
    assert np.abs(np.asarray(mean) - (g + ef0)).max() <= scale * 1.01
    np.testing.assert_allclose(np.asarray(mean) + np.asarray(err), g + ef0,
                               atol=1e-6)


def test_train_step_ef_convergence_parity():
    """Smoke parity: int8+EF training loss trajectory stays close to
    uncompressed; the EF state is nonzero (it is actually wired) and the
    step round-trips params/opt/ef through jit."""
    from repro.configs.all_archs import smoke_config
    from repro.data.pipeline import DataConfig, synth_batch
    from repro.train.optimizer import AdamWConfig
    from repro.train.train_step import (TrainSettings, init_all,
                                        make_train_step)

    cfg = dataclasses.replace(smoke_config("qwen2.5-3b"), n_layers=1,
                              block_pattern=("attn",))
    mesh = make_mesh((1, 1), ("data", "model"))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)
    batch0 = synth_batch(dc, 0)
    inputs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
              for k, v in batch0.items()}
    opt = AdamWConfig(lr=5e-3, warmup_steps=2, total_steps=40)
    steps = 10

    def run(settings):
        step_fn, _ = make_train_step(cfg, mesh, inputs, settings)
        ef_mode = settings.error_feedback
        state = init_all(cfg, jax.random.PRNGKey(0), error_feedback=ef_mode)
        jitted = jax.jit(step_fn)
        losses = []
        if ef_mode:
            params, opt_state, ef = state
            for s in range(steps):
                params, opt_state, ef, m = jitted(params, opt_state, ef,
                                                  synth_batch(dc, s))
                losses.append(float(m["loss"]))
            return losses, ef
        params, opt_state = state
        for s in range(steps):
            params, opt_state, m = jitted(params, opt_state,
                                          synth_batch(dc, s))
            losses.append(float(m["loss"]))
        return losses, None

    base, _ = run(TrainSettings(opt=opt))
    efl, ef = run(TrainSettings(opt=opt, grad_compression="int8",
                                error_feedback=True))
    assert np.isfinite(base).all() and np.isfinite(efl).all()
    assert base[-1] < base[0] and efl[-1] < efl[0], (base, efl)
    # parity: compressed+EF tracks uncompressed within a loose band on
    # this smoke config (quantisation noise, not divergence)
    assert abs(efl[-1] - base[-1]) < 0.15 * abs(base[0]), (base, efl)
    # the EF state actually carries residuals
    ef_mag = max(float(jnp.abs(e).max()) for e in jax.tree.leaves(ef))
    assert ef_mag > 0.0
