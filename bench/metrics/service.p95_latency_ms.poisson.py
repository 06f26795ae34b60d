"""95th percentile latency of the windows due in the window, in ms: from
each window's due time on the arrival schedule to its response, on the
host clock; windows still queued at the close are waited for, and a
window the service did not answer counts as infinitely late. It spreads
too widely from run to run to bound end to end (PERF.md)."""
import math

import numpy as np


def read(record):
    lat = [w["t_done_s"] - w["due_s"] if w["status"] == "ok" else math.inf
           for w in record["windows"]]
    if not lat:
        return None
    p95 = float(np.percentile(lat, 95))
    # between two infinite latencies numpy interpolates to nan
    return math.inf if math.isnan(p95) else p95 * 1e3
