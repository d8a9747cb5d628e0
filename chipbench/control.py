#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, at a cell's own
size and load, in one process (set-up once):

* the program: every number of a window, on each of ``--seeds``;
* the control: the plain reference computed in bfloat16 (the precision
  below the configuration's float32) put in the program's place, over the
  same queries at the same epochs, on each of ``--control-seeds``.

    python3 chipbench/control.py --workload clustered-20d-linf.search \\
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control-seeds 1,2,3 \\
        --seconds 30

Prints one JSON line per seed.  The benchmark's runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve()
               != ROOT / "chipbench"]
sys.path.insert(0, str(ROOT))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      str(ROOT / "chipbench" / ".cache" / "jax"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out",
                    default=str(ROOT / "chipbench" / ".cache" / "control"))
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    from chipbench import check, harness
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.Cell.from_spec(args.workload)
    control = {int(s) for s in args.control_seeds.split(",") if s}
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        cell.warm(seed, level_stats=False)
        rec = cell.window(seed, args.seconds)
        row = {"workload": args.workload, "seed": seed,
               "answers": int(len(rec.due) - rec.failed.sum()),
               "batches": rec.n_applied, "compiles": rec.compiles,
               "program": check.numbers(cell, rec)}
        if seed in control:
            row["control_bf16"] = check.control_numbers(cell, rec,
                                                        jnp.bfloat16)
        print(json.dumps(row), flush=True)
        with open(out / f"{args.workload}.jsonl", "a") as f:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
