"""Stream, epochs: mean duration of the ``mutation.publish`` span (the
next epoch handed to readers), in ms."""


def read(run):
    d = [s["duration_s"] for s in run.spans
         if s["name"] == "mutation.publish" and s["duration_s"] is not None]
    return 1e3 * sum(d) / len(d) if d else None
