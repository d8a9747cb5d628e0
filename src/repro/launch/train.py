"""Production training driver: sharded train step + checkpoint/restart.

    python -m repro.launch.train --arch qwen2.5-3b --smoke --steps 200 \\
        --ckpt-dir /tmp/ckpt [--resume] [--fail-at 120]

Fault-tolerance contract exercised here (and in tests/test_checkpoint.py):
deterministic restart — because the data pipeline is stateless in the step
index and the checkpoint carries (params, opt state, step), a run killed at
any step and resumed produces the same trajectory as an uninterrupted run.
``--fail-at`` injects a hard failure to demonstrate it.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs.all_archs import smoke_config
from repro.configs.base import get_config
from repro.data.pipeline import DataConfig, synth_batch
from repro.dist import sharding as shd
from repro.dist.checkpoint import CheckpointManager, latest_step
from repro.launch.compile_cache import enable_compile_cache
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import TrainSettings, init_all, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at", type=int, default=-1,
                    help="inject a crash at this step (restart demo)")
    ap.add_argument("--mesh", default="host", choices=["host", "single"])
    ap.add_argument("--data-seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    n_dev = len(jax.devices())
    if args.mesh == "host" and n_dev >= 2:
        nm = 2 if n_dev % 2 == 0 else 1
        mesh = shd.make_mesh((n_dev // nm, nm), ("data", "model"))
    else:
        mesh = shd.make_mesh((1, 1), ("data", "model"))

    dc = DataConfig(seed=args.data_seed, vocab_size=cfg.vocab_size,
                    seq_len=args.seq_len, global_batch=args.global_batch)
    batch0 = synth_batch(dc, 0)
    inputs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
              for k, v in batch0.items()}
    settings = TrainSettings(opt=AdamWConfig(
        lr=args.lr, warmup_steps=max(5, args.steps // 20),
        total_steps=args.steps))

    with shd.use_mesh(mesh):
        step_fn, sh = make_train_step(cfg, mesh, inputs, settings)
        jitted = jax.jit(step_fn,
                         in_shardings=(sh["params"], sh["opt"], sh["batch"]),
                         out_shardings=(sh["params"], sh["opt"], sh["metrics"]),
                         donate_argnums=(0, 1))

        params, opt = init_all(cfg, jax.random.PRNGKey(0))
        start = 0
        mgr = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
        if args.resume and args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
            out, manifest = mgr.restore_latest(
                {"params": params, "opt": opt._asdict()},
                shardings={"params": sh["params"]})
            params = out["params"]
            from repro.train.optimizer import AdamWState
            opt = AdamWState(**out["opt"])
            start = manifest["step"]
            print(f"[train] resumed from step {start}")
        params = jax.device_put(params, sh["params"])
        opt = jax.device_put(opt, sh["opt"])

        if start >= args.steps:
            print(f"[train] nothing to do: resumed at step {start} >= "
                  f"--steps {args.steps}")
            return None

        t0 = time.time()
        for step in range(start, args.steps):
            if step == args.fail_at:
                raise SystemExit(f"[train] injected failure at step {step}")
            batch = jax.device_put(synth_batch(dc, step), sh["batch"])
            params, opt, metrics = jitted(params, opt, batch)
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                print(f"[train] step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"({(time.time()-t0)/max(step-start,1):.2f}s/step)",
                      flush=True)
            if mgr and step and step % args.ckpt_every == 0:
                # step+1 = next step to run: resume must NOT replay this one
                mgr.save(step + 1, {"params": params, "opt": opt._asdict()})
        if mgr:
            mgr.save(args.steps, {"params": params, "opt": opt._asdict()})
            mgr.wait()
    print(f"[train] done: {args.steps - start} steps, final loss "
          f"{float(metrics['loss']):.4f}")
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
