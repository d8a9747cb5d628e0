"""Descent: device time of the top-k compactions (the operations under
the ``descent.compact`` named scope of ``_knn_cohort``: the per-level
frontier cut and the leaf-chunk merges) per query answered in the
window, in us.

The trace names operations by HLO instruction only; the scope comes from
the descent's compiled text at the cohort's shapes."""

from chipbench import attribution, harness

SCOPE = "descent.compact"
PROGRAM = "_knn_cohort"


def read(run):
    if run.trace is None or not run.trace.n_chips or not run.answered:
        return None
    from repro.serve.frontend import FrontendConfig
    _, cfg, _ = harness.cell_spec(run.rec.workload)
    # the tree the window started from (the index cache), at the shapes
    # every cohort of the window ran
    tree, _, _ = harness.index(
        cfg, harness.load_module("data", cfg["generator"]),
        log=lambda m: None)
    variants = []
    for text in attribution.descent_texts(
            tree, rows=FrontendConfig().cohort_width, k=cfg["k"],
            max_frontier=cfg["max_frontier"]):
        ops = attribution.program_ops(text)
        variants.append((ops, attribution.scope_ops(ops, SCOPE)))
    if not any(want for _, want in variants):
        return None         # a program without the scope
    ns = attribution.scope_ns(run.rec.trace_path, run.trace.start_ns,
                              run.rec.seconds, PROGRAM, variants)
    return ns / 1e3 / run.answered if ns else None
