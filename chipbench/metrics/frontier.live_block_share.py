"""Frontier kernel: share of the kernel's grid steps whose block of
frontier slots held a live node, over the sampled dispatches
(``descent.live_blocks_total`` / ``descent.grid_steps_total``; a step
scores a block of slots, and a block with no live node costs an empty
step).  Counters are the registry's after the window; a program without
them reads nothing."""


def read(run):
    c = getattr(run, "counters", None)
    if c is None:
        from repro import obs
        c = obs.REGISTRY.snapshot()
    steps = c.get("descent.grid_steps_total")
    live = c.get("descent.live_blocks_total")
    return live / steps if steps and live is not None else None
