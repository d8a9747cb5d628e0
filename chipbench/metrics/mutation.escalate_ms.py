"""Writer: time a batch spends in the host control plane's splits and
merges (``escalate_rows``): the sum of the ``mutation.escalate`` spans
over the ``mutation.apply`` spans, in ms."""


def read(run):
    part = [s["duration_s"] for s in run.spans
            if s["name"] == "mutation.escalate" and s["duration_s"] is not None]
    batches = sum(s["name"] == "mutation.apply" for s in run.spans)
    return 1e3 * sum(part) / batches if part and batches else None
