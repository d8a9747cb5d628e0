"""Frontier kernel: device time of the fused frontier-scoring Pallas
kernel's operations (``frontier_scores_pallas.<n>``, one per level and
leaf chunk) per query answered in the window, in us."""


def read(run):
    if run.trace is None or not run.answered:
        return None
    ns = run.trace.op_ns("frontier_scores_pallas")
    return ns / 1e3 / run.answered if ns else None
