"""Writer: time a batch spends in the device split pass
(``resolve_overflows``, one device round trip an 8-row chunk): the sum
of the ``mutation.split_pass`` spans over the ``mutation.apply`` spans,
in ms."""


def read(run):
    part = [s["duration_s"] for s in run.spans
            if s["name"] == "mutation.split_pass" and s["duration_s"] is not None]
    batches = sum(s["name"] == "mutation.apply" for s in run.spans)
    return 1e3 * sum(part) / batches if part and batches else None
