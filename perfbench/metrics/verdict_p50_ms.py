"""Median, over every valid txn scheduled in the window, of scheduled
send -> first delivery downstream of dedup (host clock, nearest rank).
A txn never delivered counts as infinitely late."""
import math

import numpy as np


def read(r):
    sel = r.in_window & r.corpus.valid
    lat = r.first_t[sel] - r.first_sched[sel]
    if not len(lat):
        return None
    lat = np.sort(np.where(np.isnan(lat), np.inf, lat))
    v = lat[max(0, math.ceil(0.5 * len(lat)) - 1)]
    return float(v) * 1e3 if np.isfinite(v) else None
