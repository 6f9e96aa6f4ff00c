"""Unique valid txns delivered downstream of dedup inside the window,
over the window (host clock)."""
import numpy as np


def read(r):
    t = r.first_t[r.corpus.valid]
    n = int(((t >= r.t0) & (t <= r.t1)).sum())
    return n / (r.t1 - r.t0)
