"""Descent: device time of the parent-distance bound (the operations
under the ``descent.bound`` named scope of ``_knn_cohort``: each level's
gathers of pdist and radius and its top-k of the bounds) per query
answered in the window, in us.

The trace names operations by HLO instruction only; the scope comes from
the descent's compiled text at the cohort's shapes, lowered from the
shapes of the cached index (no second copy of its pages on the chip)."""

import dataclasses

import numpy as np

from chipbench import attribution, harness

SCOPE = "descent.bound"
PROGRAM = "_knn_cohort"


def tree_shapes(cfg):
    """The cell's cached index as ``TreeArrays`` of shapes, with its
    height read; None if it is not cached."""
    import jax
    from repro.core.smtree import TreeArrays
    d = harness.CACHE / "index" / f"{cfg['name']}-{harness._index_key(cfg)}"
    if not (d / "meta.json").is_file():
        return None
    arrays = {}
    for f in dataclasses.fields(TreeArrays):
        if f.name not in harness.TREE_META:
            a = np.load(d / f"{f.name}.npy", mmap_mode="r")
            arrays[f.name] = jax.ShapeDtypeStruct(a.shape, a.dtype)
    arrays["height"] = np.load(d / "height.npy")
    return TreeArrays(**arrays, **harness.load_json(d / "meta.json"))


def read(run):
    if run.trace is None or not run.trace.n_chips or not run.answered:
        return None
    from repro.serve.frontend import FrontendConfig
    _, cfg, _ = harness.cell_spec(run.rec.workload)
    tree = tree_shapes(cfg)
    if tree is None:
        return None
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
