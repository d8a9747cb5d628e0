"""Writer: device-to-host status and scalar reads on the apply path a
batch (``mutation.host_syncs_total`` / ``stream.batches_total``); each
waits for every program queued before it, query cohorts included.
Counters are the registry's after the window."""


def read(run):
    c = getattr(run, "counters", None)
    if c is None:
        from repro import obs
        c = obs.REGISTRY.snapshot()
    syncs = c.get("mutation.host_syncs_total")
    batches = c.get("stream.batches_total")
    return syncs / batches if syncs is not None and batches else None
