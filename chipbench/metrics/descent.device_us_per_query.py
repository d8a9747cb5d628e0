"""Descent: device time of the cohort-descent program (``_knn_cohort``,
the frontier kernel and top-k compactions inside it) per query answered
in the window, in us."""


def read(run):
    if run.trace is None or not run.answered:
        return None
    ns = run.trace.module_ns("_knn_cohort")
    return ns / 1e3 / run.answered if ns else None
