"""Frontier kernel: share of the kernel's grid slots that held a live
node, over the sampled dispatches (``descent.nodes_visited_total`` /
``descent.grid_slots_total``; the grid is rows x the sum of the level
widths, whatever the descent prunes).  Counters are the registry's after
the window."""


def read(run):
    c = getattr(run, "counters", None)
    if c is None:
        from repro import obs
        c = obs.REGISTRY.snapshot()
    grid = c.get("descent.grid_slots_total")
    return c.get("descent.nodes_visited_total", 0) / grid if grid else None
