"""Writer: time a batch spends in the device merge pass
(``resolve_underflows``): the sum of the ``mutation.merge_pass`` spans
over the ``mutation.apply`` spans, in ms."""


def read(run):
    part = [s["duration_s"] for s in run.spans
            if s["name"] == "mutation.merge_pass" and s["duration_s"] is not None]
    batches = sum(s["name"] == "mutation.apply" for s in run.spans)
    return 1e3 * sum(part) / batches if part and batches else None
