"""Benchmark orchestrator: one module per paper table/figure + engine,
kernel and roofline benches.  Prints ``name,value`` CSV lines and, with
``--tag``, writes a machine-readable ``benchmarks/BENCH_<tag>.json``
artifact (suite -> name -> value plus host/backend metadata) — the bench
trajectory the repo tracks across PRs.  REPRO_BENCH_FULL=1 restores full
paper scale; REPRO_BENCH_SMOKE=1 is the tiny CI preset."""
from __future__ import annotations

import argparse
import importlib
import json
import os
import platform
import sys
import time

if __package__ in (None, ""):   # script invocation: make repo root importable
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The mesh rows (bench_stream mesh_forest_*, bench_engine serve_sharded_*)
# run over 4 devices, on a CPU host forced host devices.  Every suite runs
# in this process, so the flag must be set before jax is imported; it sizes
# only the host platform, so on an accelerator the device list is unchanged.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                                 ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               "--xla_force_host_platform_device_count=4"
                               ).strip()

# (suite name, module) — modules import lazily and individually so one
# missing dependency (e.g. the distributed stack on a minimal single-host
# CPU image) skips its suite instead of killing the whole entrypoint
_SUITES = [
    ("paper_queries", "paper_queries"),   # Figs. 5-8
    ("paper_delete", "paper_delete"),     # Fig. 10 + occupancy
    ("bench_engine", "bench_engine"),     # JAX engine throughput
    ("bench_stream", "bench_stream"),     # mutation-stream throughput
    ("bench_serve", "bench_serve"),       # serving front-end + replicas
    ("bench_kernels", "bench_kernels"),   # kernel validation/baseline
    ("roofline", "roofline_table"),       # 40-cell dry-run table
]


def _meta() -> dict:
    meta = {
        "host": platform.node(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "env": {k: v for k, v in os.environ.items()
                if k.startswith("REPRO_")},
    }
    try:
        import jax
        meta["jax"] = jax.__version__
        meta["backend"] = jax.default_backend()
        meta["device_count"] = jax.device_count()
    except Exception as e:  # noqa: BLE001 — metadata only
        meta["jax"] = f"unavailable ({e})"
    return meta


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("suite", nargs="?", default=None,
                    help="run a single suite (default: all importable)")
    ap.add_argument("--tag", default=None,
                    help="write benchmarks/BENCH_<tag>.json")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    results: dict[str, dict[str, object]] = {}
    current = {"suite": None}
    n_rows = 0

    def report(name, value):
        nonlocal n_rows
        results.setdefault(current["suite"], {})[name] = value
        n_rows += 1
        print(f"{name},{value}", flush=True)

    only = args.suite
    suites = []
    for name, mod_name in _SUITES:
        try:
            suites.append(
                (name, importlib.import_module(f"benchmarks.{mod_name}").run))
        except ImportError as e:
            if only == name:
                # an explicitly requested suite must not skip silently
                raise SystemExit(f"suite {name!r} failed to import: {e}")
            print(f"# skip {name}: unavailable on this host ({e})",
                  flush=True)
    if only and only not in [n for n, _ in suites]:
        raise SystemExit(f"unknown suite {only!r}; "
                         f"have {[n for n, _ in _SUITES]}")
    for name, fn in suites:
        if only and only != name:
            continue
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        current["suite"] = name
        fn(report)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    print(f"# total rows: {n_rows}")

    if args.tag:
        def _jsonable(v):
            # bare NaN/inf tokens are not valid JSON; null keeps the
            # artifact strict and still trips check_bench.py
            if isinstance(v, float) and (v != v or v in (float("inf"),
                                                         float("-inf"))):
                return None
            return v

        clean = {s: {k: _jsonable(v) for k, v in rows.items()}
                 for s, rows in results.items()}
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            f"BENCH_{args.tag}.json")
        with open(path, "w") as f:
            json.dump({"meta": _meta(), "suites": clean}, f, indent=2,
                      sort_keys=True, allow_nan=False)
            f.write("\n")
        print(f"# wrote {path}")


if __name__ == "__main__":
    main()
