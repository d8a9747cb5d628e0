"""JAX SM-tree engine benchmarks.

The centrepiece is the query-matrix bench: batched kNN throughput over
b x n x metric x impl, where impl toggles the frontier-scoring engine
(``REPRO_FRONTIER_IMPL`` semantics — 'perquery' is the legacy
vmap(per-query) baseline, the cohort path runs as 'pallas' on TPU / 'xla'
elsewhere).  ``speedup_cohort_vs_perquery_*`` rows record the headline
number; the Pallas interpret path is correctness-only and excluded from
timing off-TPU.

The parent-distance pre-filter matrix (DESIGN.md §17) compares the cohort
descent with the filter on vs off — wall time and metric evals per query —
and emits the ``frontier_parent_prune_*`` gate rows CI checks.

Also: bulk build, engine-vs-ref page hits, insert/delete fast-path rates,
and the sharded-serve-vs-single-device decode comparison (ROADMAP item),
both legs in-process through ``repro.launch.serve.main``.

Scale envs: REPRO_BENCH_SMOKE=1 (tiny, CI) / REPRO_BENCH_FULL=1 (paper
scale); default is the PR-acceptance matrix (b up to 1024, n up to 100k).
"""
from __future__ import annotations

import contextlib
import io
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import SMTreeEngine
from repro.core.ref_impl import SMTree
from repro.data.datagen import make_dataset

FULL = os.environ.get("REPRO_BENCH_FULL") == "1"
SMOKE = os.environ.get("REPRO_BENCH_SMOKE") == "1"

if SMOKE:
    NS = [2_000]
    BATCHES = [1, 8]
elif FULL:
    NS = [10_000, 100_000, 500_000]
    BATCHES = [1, 64, 1024, 4096]
else:
    NS = [10_000, 100_000]
    BATCHES = [1, 64, 1024]
METRICS = ["d_inf", "l2"]
# the parent-distance pre-filter comparison covers every metric the
# descent supports, at the largest dataset of the run
PRUNE_METRICS = ["d_inf", "l2", "l1"]
# eval-ratio the gate row demands (pruned/unpruned metric evals): the PR
# acceptance number, >= 25% of evals eliminated.  Holds at every scale —
# at smoke scale the pre-eval parent upper bound leaves an even larger
# margin (~0.35) than at b=1024 / n=100k (~0.74).
PRUNE_EVAL_TARGET = 0.75
K = 10
MAX_FRONTIER = 64
SERVE_MESH_DEVICES = 4   # serve_sharded_*: a (2, 2) data x model mesh


def _cohort_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _time_knn(eng, Q, impl, **kw) -> float:
    """Warm (compile) then time; iteration count adapts to per-call cost."""
    res = eng.knn(Q, k=K, max_frontier=MAX_FRONTIER, impl=impl, **kw)
    jax.block_until_ready(res.dists)
    t0 = time.perf_counter()
    res = eng.knn(Q, k=K, max_frontier=MAX_FRONTIER, impl=impl, **kw)
    jax.block_until_ready(res.dists)
    warm = time.perf_counter() - t0
    iters = max(3, min(20, int(2.0 / max(warm, 1e-4))))
    t0 = time.perf_counter()
    for _ in range(iters):
        res = eng.knn(Q, k=K, max_frontier=MAX_FRONTIER, impl=impl, **kw)
    jax.block_until_ready(res.dists)
    return (time.perf_counter() - t0) / iters


def _query_matrix(report):
    """knn throughput: b x n x metric x {perquery, cohort}, plus speedups."""
    rng = np.random.default_rng(8)
    cohort = _cohort_impl()
    for n in NS:
        X = make_dataset("clustered", n, seed=7)[:, :10].copy()
        for metric in METRICS:
            t0 = time.perf_counter()
            eng = SMTreeEngine.build(X, capacity=32, metric=metric)
            report(f"bulk_build_n{n}_{metric}_s", round(time.perf_counter() - t0, 2))
            for b in BATCHES:
                Q = jnp.asarray(
                    X[rng.integers(0, n, b)]
                    + rng.normal(0, 0.01, (b, 10)).astype(np.float32),
                    jnp.float32)
                times = {}
                for impl in ("perquery", cohort):
                    dt = _time_knn(eng, Q, impl)
                    times[impl] = dt
                    report(f"knn_b{b}_n{n}_{metric}_{impl}_ms",
                           round(dt * 1e3, 2))
                report(f"speedup_cohort_vs_perquery_b{b}_n{n}_{metric}",
                       round(times["perquery"] / times[cohort], 2))


def _prune_matrix(report):
    """Parent-distance pre-filter (DESIGN.md §17): pruned vs unpruned
    cohort descent at the largest dataset of this run, per metric and
    batch — wall time plus metric evals per query straight off the
    ``QueryResult.dist_evals`` reduction (which counts evaluations
    *performed*, so the filter's savings show up directly).  Emits the
    scale-independent gate rows CI checks:

    * ``frontier_parent_prune_eval_ratio`` — pruned/unpruned evals at the
      largest batch, summed over metrics (lower is better; informational).
    * ``frontier_parent_prune_qps_ratio`` — unpruned/pruned wall time at
      the same config, >= 1 when the mask's overhead doesn't eat the win.
    * ``frontier_parent_prune_ok`` — 1.0 iff the eval ratio meets
      PRUNE_EVAL_TARGET; the row check_bench gates at min-ratio 1.0
      (min-ratio is higher-is-better, so the <=-bound is encoded as a
      boolean row).
    """
    rng = np.random.default_rng(21)
    cohort = _cohort_impl()
    n = NS[-1]
    bs = [b for b in BATCHES if b >= 64] or BATCHES[-1:]
    X = make_dataset("clustered", n, seed=7)[:, :10].copy()
    agg = {"ev_on": 0.0, "ev_off": 0.0, "t_on": 0.0, "t_off": 0.0}
    for metric in PRUNE_METRICS:
        eng = SMTreeEngine.build(X, capacity=32, metric=metric)
        for b in bs:
            Q = jnp.asarray(
                X[rng.integers(0, n, b)]
                + rng.normal(0, 0.01, (b, 10)).astype(np.float32),
                jnp.float32)
            row = {}
            for tag, pp in (("prune", True), ("noprune", False)):
                dt = _time_knn(eng, Q, cohort, parent_prune=pp)
                res = eng.knn(Q, k=K, max_frontier=MAX_FRONTIER,
                              impl=cohort, parent_prune=pp)
                ev = float(np.sum(np.asarray(res.dist_evals))) / b
                report(f"knn_b{b}_n{n}_{metric}_{cohort}_{tag}_ms",
                       round(dt * 1e3, 2))
                report(f"dist_evals_per_query_b{b}_n{n}_{metric}_{tag}",
                       round(ev, 1))
                row[tag] = (dt, ev)
            report(f"prune_eval_ratio_b{b}_n{n}_{metric}",
                   round(row["prune"][1] / row["noprune"][1], 3))
            if b == bs[-1]:
                agg["t_on"] += row["prune"][0]
                agg["t_off"] += row["noprune"][0]
                agg["ev_on"] += row["prune"][1]
                agg["ev_off"] += row["noprune"][1]
    ratio = agg["ev_on"] / agg["ev_off"]
    report("frontier_parent_prune_eval_ratio", round(ratio, 3))
    report("frontier_parent_prune_qps_ratio",
           round(agg["t_off"] / agg["t_on"], 3))
    report("frontier_parent_prune_ok",
           1.0 if ratio <= PRUNE_EVAL_TARGET else 0.0)


def _serve_case(report):
    """ROADMAP item: sharded serve (``--mesh host`` over 4 devices) vs
    single-device decode, measured in ms/step.  Both run in this process —
    a child process could not open a chip this process already holds —
    with the driver's summary line parsed off stdout."""
    from repro.launch import serve

    steps = 4 if SMOKE else 8
    base = ["--arch", "qwen2.5-3b", "--smoke", "--batch", "8",
            "--prompt-len", "4", "--steps", str(steps)]

    def run_case(name, argv):
        if "--mesh" in argv and jax.device_count() != SERVE_MESH_DEVICES:
            print(f"# serve case {name}: needs {SERVE_MESH_DEVICES} devices, "
                  f"{jax.device_count()} visible (benchmarks/run.py forces "
                  f"{SERVE_MESH_DEVICES} host devices on a CPU host)",
                  flush=True)
            report(name, float("nan"))
            return float("nan")
        out = io.StringIO()
        try:
            with contextlib.redirect_stdout(out):
                serve.main(argv)
        except Exception as exc:  # noqa: BLE001 — a bench row, not control flow
            print(f"# serve case {name} failed: {exc}", flush=True)
        m = re.search(r"\(([\d.]+) ms/step", out.getvalue())
        if m is None:
            # surface the failure so a NaN row in CI is diagnosable
            print(f"# serve case {name}: no ms/step in output\n"
                  f"# output tail: {out.getvalue()[-2000:]}", flush=True)
        value = float(m.group(1)) if m else float("nan")
        report(name, value)
        return value

    single = run_case("serve_single_ms_per_step", base)
    sharded = run_case("serve_sharded_ms_per_step", base + ["--mesh", "host"])
    if np.isfinite(single) and np.isfinite(sharded) and sharded > 0:
        report("serve_sharded_vs_single_ratio", round(single / sharded, 3))


def run(report):
    _query_matrix(report)
    _prune_matrix(report)

    # ref-impl page hits on a comparable workload (paper-faithful DFS order)
    n_ref = 500 if SMOKE else 2_500
    X = make_dataset("clustered", n_ref * 4, seed=7)[:, :10].copy()
    rng = np.random.default_rng(8)
    ref = SMTree(dim=10, capacity=32, n_dims=10)
    for i, x in enumerate(X[:n_ref]):
        ref.insert(x, i)
    tot = 0
    for q in X[:16]:
        ref.reset_counters()
        ref.knn_query(q, K)
        tot += ref.ios
    report("ref_knn10_mean_page_hits", round(tot / 16, 1))

    # insert/delete fast-path hit rates (amortised split/merge frequency)
    eng = SMTreeEngine.build(X, capacity=32)
    n_base = len(X)
    extra = make_dataset("uniform", 200 if SMOKE else 1000, seed=9)[:, :10].copy()
    from repro.core.smtree import delete_fast, insert_fast
    n_split = 0
    t0 = time.time()
    tree = eng.tree
    for i, x in enumerate(extra):
        new_tree, fits, _ = insert_fast(tree, jnp.asarray(x),
                                        jnp.int32(n_base + i))
        if bool(fits):
            tree = new_tree
        else:
            n_split += 1
            eng.tree = tree
            eng.insert(x, n_base + i)
            tree = eng.tree
    eng.tree = tree
    report("insert_fastpath_rate", round(1 - n_split / len(extra), 3))
    report("insert_us_per_op",
           round((time.time() - t0) / len(extra) * 1e6, 0))

    n_del = len(extra) // 2
    n_under = 0
    t0 = time.time()
    for i, x in enumerate(extra[:n_del]):
        new_tree, found, underflow, _ = delete_fast(
            eng.tree, jnp.asarray(x), jnp.int32(n_base + i))
        assert bool(found)
        if bool(underflow):
            n_under += 1
            eng.delete(x, n_base + i)
        else:
            eng.tree = new_tree
    report("delete_fastpath_rate", round(1 - n_under / n_del, 3))
    report("delete_us_per_op", round((time.time() - t0) / n_del * 1e6, 0))

    _serve_case(report)
