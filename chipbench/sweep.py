#!/usr/bin/env python3
"""Find a cell's knee: the highest open-loop rate it sustains without a
growing backlog.  Sets the cell up once, then serves one window per rate,
in rising order, and stops after the first rate it cannot keep up with.

    python3 chipbench/sweep.py --workload clustered-20d-linf.search \\
        --rates 100,200,400 --seconds 10 --seed 5

Prints one JSON line per rate: offered and answered rates, p50/p99 of the
due-to-answer latency, the mean latency of the last and first fifths of
the window (a growing backlog shows as last >> first), the generator's
lateness and, with a writer, acknowledged ops/s.  Runs on the chip only:
the numbers are times.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
# import the chipbench package, not its files (its trace.py would
# shadow the standard library's)
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve()
               != ROOT / "chipbench"]
sys.path.insert(0, str(ROOT))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      str(ROOT / "chipbench" / ".cache" / "jax"))


def summary(rec, rate) -> dict:
    import numpy as np
    lat = rec.latencies_s()
    n = len(lat)
    fifth = max(1, n // 5)
    fin = np.isfinite(lat)
    return {
        "workload": rec.workload, "rate": rate, "seconds": rec.seconds,
        "offered_qps": n / rec.seconds,
        "answered_qps": rec.answered_in_window() / rec.seconds,
        "failed": int(np.sum(rec.failed)),
        "p50_ms": float(np.percentile(lat[fin], 50)) * 1e3 if fin.any() else None,
        "p99_ms": float(np.percentile(lat, 99)) * 1e3 if fin.all() else None,
        "first_fifth_ms": float(np.mean(lat[:fifth])) * 1e3,
        "last_fifth_ms": float(np.mean(lat[-fifth:])) * 1e3,
        "late_p99_ms": float(np.percentile(rec.sent - rec.due, 99)) * 1e3,
        "ops_per_s": rec.ops_acked_in_window() / rec.seconds,
        "compiles_in_window": rec.compiles,
        "mean_cohort_fill": rec.frontend.get("mean_cohort_fill"),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out",
                    default=str(ROOT / "chipbench" / ".cache" / "sweep"))
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU; the knee is a chip number", file=sys.stderr)
        return 2
    from chipbench import harness
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.Cell.from_spec(args.workload)
    cell.warm(args.seed, level_stats=False)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        rec = cell.window(args.seed + i, args.seconds, rate=rate)
        row = summary(rec, rate)
        print(json.dumps(row), flush=True)
        with open(out / f"{args.workload}.jsonl", "a") as f:
            f.write(json.dumps(row) + "\n")
        # a growing backlog: the last fifth of the window waits well
        # longer than the first
        if row["failed"] or (row["last_fifth_ms"]
                             > 1.5 * row["first_fifth_ms"] + 100):
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
