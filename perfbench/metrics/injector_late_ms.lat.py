"""99th percentile of actual publish minus scheduled send over the
window's frames (one still unsent at the window's end counts as late by
the time left), so a starved generator is never read as a fast
validator (host clock). In a traced run the frames scheduled from the
capture's doorbell to 1.5 s after its close are left out: the
profiler's stop_trace holds the verify tile (~0.4-1 s), and a backed-up
ingest ring makes the injector late by that much."""
import math

import numpy as np


def read(r):
    late = np.where(np.isnan(r.late_s), r.t1 - r.t0, r.late_s)
    if r.capture is not None:
        lo, hi = r.capture
        keep = (r.sched_s < lo) | (r.sched_s > hi + 1.5)
        late = late[keep]
    if not len(late):
        return None
    late = np.sort(late)
    return float(late[max(0, math.ceil(0.99 * len(late)) - 1)]) * 1e3
