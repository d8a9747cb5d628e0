"""Front end: the 99th percentile of the due-to-answer latency of every
query due in the (traced) window, in ms.  It flips between two and three
cohort times from run to run, so it stands beside ``query_p90_ms`` here
rather than as an end-to-end metric with a bound."""

import numpy as np

from chipbench.harness import WAIT_PAST_CLOSE_S


def read(run):
    lat = run.rec.latencies_s()
    if not len(lat):
        return None
    lat = np.where(np.isfinite(lat), lat, run.rec.t_end + WAIT_PAST_CLOSE_S - run.rec.due)
    return float(np.percentile(lat, 99)) * 1e3
