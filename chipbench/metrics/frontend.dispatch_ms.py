"""Front end: mean ``frontend.dispatch`` span a cohort (the descent
enqueued on the device, its height read included), in ms."""


def read(run):
    d = [s["duration_s"] for s in run.spans
         if s["name"] == "frontend.dispatch" and s["duration_s"] is not None]
    return 1e3 * sum(d) / len(d) if d else None
