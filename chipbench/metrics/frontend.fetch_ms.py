"""Front end: mean ``frontend.fetch`` span a cohort (the answers copied
from the device and merged on the host), in ms."""


def read(run):
    d = [s["duration_s"] for s in run.spans
         if s["name"] == "frontend.fetch" and s["duration_s"] is not None]
    return 1e3 * sum(d) / len(d) if d else None
