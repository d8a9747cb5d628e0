"""Frontier kernel: the page bytes it copies from HBM a query over its
device time a query, as a share of the chip's HBM bandwidth
(``chipbench/peaks.json``).  Bytes a query are
``descent.page_bytes_total`` / ``descent.queries_total`` (live slots x the
tile-padded page and its per-entry rows, live rows of the sampled
dispatches); time a query is ``frontier.device_us_per_query``'s.  Counters
are the registry's after the window; a program without the counter reads
nothing."""

from chipbench import harness


def read(run):
    if run.trace is None or not run.answered:
        return None
    c = getattr(run, "counters", None)
    if c is None:
        from repro import obs
        c = obs.REGISTRY.snapshot()
    page, rows = c.get("descent.page_bytes_total"), c.get(
        "descent.queries_total")
    ns = run.trace.op_ns("frontier_scores_pallas")
    if not page or not rows or not ns:
        return None
    kind = getattr(run, "device_kind", None)
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    peak = harness.load_json(harness.BENCH / "peaks.json").get(kind)
    if peak is None:
        return None
    s_per_query = ns / 1e9 / run.answered
    return page / rows / s_per_query / peak["hbm_bytes_per_s"]
