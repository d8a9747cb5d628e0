"""Device: share of the window in which no operation ran on the chip
(1 - busy union / window)."""


def read(run):
    t = run.trace
    if t is None or not t.n_chips or not t.busy_ns:
        return None
    return 1.0 - t.busy_ns / t.window_ns
