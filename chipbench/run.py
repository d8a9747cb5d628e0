#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, on the machine it starts on.

    python3 chipbench/run.py --workload clustered-20d-linf.search --seed 7 \\
        --seconds 30 --trace 0

Set-up (timed as ``setup_s``, from process start to the first due request)
restores the cell's index, warms its programs and draws its inputs from
``--seed``.  The window then serves the cell's traffic for ``--seconds``
through the front end.  Afterwards every answer and every acknowledged
write is checked against the plain reference.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs the
profiler and obs spans over the window and reports its per-layer metrics,
the device's busy time and a breakdown.

The last stdout line is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, [``breakdown``], ``checks``); the last
stderr lines give each number compared beside its limit.  With no TPU, or
fewer chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# import the chipbench package, not its files (its trace.py would
# shadow the standard library's)
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve()
               != ROOT / "chipbench"]
sys.path.insert(0, str(ROOT))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      str(ROOT / "chipbench" / ".cache" / "jax"))


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def e2e_metrics(rec, setup_s: float) -> dict:
    """Every end-to-end metric the run can report, by name."""
    import numpy as np
    from chipbench.harness import WAIT_PAST_CLOSE_S
    lat = rec.latencies_s()
    # a query that never came back counts as late as the wait allowed
    lat = np.where(np.isfinite(lat), lat,
                   rec.t_end + WAIT_PAST_CLOSE_S - rec.due)
    out = {"setup_s": (setup_s, "s")}
    if len(lat):
        out["query_p90_ms"] = (float(np.percentile(lat, 90)) * 1e3, "ms")
        out["query_qps"] = (rec.answered_in_window() / rec.seconds,
                            "queries/s")
    return out


def applies(metric: dict, workload: str, reported: set) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def layer_run(rec, red):
    """What a per-layer metric reader reads."""
    due = {t: d for t, d in zip(rec.trace_ids, rec.due) if t is not None}
    return types.SimpleNamespace(
        rec=rec, trace=red, spans=rec.spans, due_by_trace=due,
        answered=rec.answered_in_window(), acked_ops=rec.ops_acked_in_window(),
        window_s=rec.seconds)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    from chipbench import harness
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    wl = next((w for w in spec["workloads"] if w["name"] == args.workload),
              None)
    if wl is None:
        print(f"chipbench: no workload {args.workload!r}", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < wl["chips"]:
        print(f"chipbench: the cell needs {wl['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s). "
              f"Nothing was run.", file=sys.stderr)
        return 2
    peaks = harness.load_json(harness.BENCH / "peaks.json")
    if devices[0].device_kind not in peaks:
        print(f"chipbench: {devices[0].device_kind!r} is not in "
              f"chipbench/peaks.json", file=sys.stderr)
        return 2
    log(f"device: {devices[0].device_kind}, peaks "
        f"{json.dumps(peaks[devices[0].device_kind])} ({peaks['source']})")
    return run_cell(spec, wl, args, devices[:wl["chips"]])


def run_cell(spec, wl, args, devices, traffic_dir=None) -> int:
    """Everything after the look for a chip (the fault tests start here)."""
    import jax
    import numpy as np
    from chipbench import check, harness
    from chipbench import trace as xtrace
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {os.environ['JAX_COMPILATION_CACHE_DIR']}")

    cell = harness.Cell.from_spec(
        args.workload, spec, traffic_dir or harness.BENCH / "traffic",
        log=log)
    log(f"index: {'built' if cell.built else 'restored'} "
        f"{cell.index_dir.name}; {cell.tree0.max_nodes} node slots, "
        f"height {int(cell.tree0.height)}")
    cell.warm(args.seed, level_stats=bool(args.trace))
    trace_dir = (harness.CACHE / "trace" / args.workload
                 if args.trace else None)
    rec = cell.window(args.seed, args.seconds, trace_dir=trace_dir)
    setup_s = rec.t0 - T_START
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    lat = rec.sent - rec.due
    worst = int(np.argmax(lat)) if len(lat) else 0
    log(f"window: {len(rec.due)} queries due, {rec.answered_in_window()} "
        f"answered in it; {rec.n_applied} batches acked; generator late "
        f"p50 {1e3 * float(_pct(lat, 50)):.3f} ms, p99 "
        f"{1e3 * float(_pct(lat, 99)):.3f} ms, max "
        f"{1e3 * float(_pct(lat, 100)):.3f} ms at "
        f"{float(rec.due[worst] - rec.t0) if len(lat) else 0:.3f} s")
    stalls = (rec.stall_path.read_text()
              if rec.stall_path and rec.stall_path.is_file() else "")
    if stalls:
        print(f"[chipbench] the load generator stalled over "
              f"{harness.STALL_S} s; every thread's stack then:\n{stalls}",
              file=sys.stderr, flush=True)
    gc2 = [s for g, s in rec.gc_pauses if g == 2]
    due_lat = rec.latencies_s()
    print(json.dumps({"compiles_in_window": rec.compiles,
                      "latency_ms": {f"p{q}": 1e3 * float(_pct(due_lat, q))
                                     for q in (50, 90, 95, 99, 99.9)},
                      "gc_passes": len(rec.gc_pauses),
                      "gc_max_ms": 1e3 * max((s for _, s in rec.gc_pauses),
                                             default=0.0),
                      "gc_gen2_ms": [1e3 * s for s in gc2],
                      "frontend": rec.frontend}), flush=True)

    t = time.monotonic()
    numbers = check.numbers(cell, rec)
    log(f"checks: {time.monotonic() - t:.1f}s")
    correct, rows = check.compare(numbers, cell.cfg["limits"])
    attempted = len(rec.due) + sum(n for _, _, n in rec.batches)
    failed = (numbers["unanswered"] + rec.batch_errors
              + (0 if correct else 1))

    e2e = e2e_metrics(rec, setup_s)
    reported = {m["name"] for m in spec["end_to_end"]
                if args.workload in m.get("workloads", [args.workload])
                and m["name"] in e2e}
    metrics = {}
    out = {}
    if not args.trace:
        for m in spec["end_to_end"]:
            if m["name"] in reported:
                v, unit = e2e[m["name"]]
                metrics[m["name"]] = {"value": v, "unit": unit}
    else:
        red = None
        if rec.trace_path is not None and rec.trace_t0_ns is not None:
            red = xtrace.reduce(rec.trace_path, rec.trace_t0_ns, rec.seconds)
            log(f"trace: {rec.trace_path.stat().st_size} bytes")
        run = layer_run(rec, red)
        for m in spec["per_layer"]:
            if not applies(m, args.workload, reported):
                continue
            v = harness.load_module("metrics", m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red is not None and red.n_chips:
            out["breakdown"] = {
                "device_ops": xtrace.top_ops(red),
                "idle_gaps": xtrace.name_gaps(red, rec.spans, rec.t0)}
        from repro import obs
        print(json.dumps({"paper_counters": {
            k: v for k, v in obs.REGISTRY.snapshot().items()
            if k.startswith("descent.")}}), flush=True)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    if args.trace:
        device["busy_s"] = (red.busy_ns / 1e9) if red is not None else 0.0
        device["window_s"] = rec.seconds
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device, **out,
              "checks": {n: {"value": v, "limit": lim}
                         for n, v, lim in rows}}
    for n, v, lim in rows:
        print(f"check {n}: {v!r} (limit {lim!r}) "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def _pct(x, q):
    import numpy as np
    return np.percentile(x, q) if len(x) else float("nan")


if __name__ == "__main__":
    sys.exit(main())
