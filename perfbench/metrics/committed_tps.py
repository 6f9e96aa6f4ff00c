"""Unique valid txns first recorded in a PoH entry inside the window,
over the window (host clock): the leader's committed rate."""


def read(r):
    t = r.first_t[r.corpus.valid]
    n = int(((t >= r.t0) & (t <= r.t1)).sum())
    return n / (r.t1 - r.t0)
