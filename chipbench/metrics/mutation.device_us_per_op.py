"""Split/merge scans: device time of the mutation programs (the batch
scan, the split pass and the merge pass) per op acknowledged in the
window, in us."""

PROGRAMS = ("_apply_mutations_impl", "_apply_splits_impl",
            "_apply_merges_impl")


def read(run):
    if run.trace is None or not run.acked_ops:
        return None
    ns = run.trace.module_ns(*PROGRAMS)
    return ns / 1e3 / run.acked_ops if ns else None
