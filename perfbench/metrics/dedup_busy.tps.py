"""The dedup tile's stem work time (its polls that consumed frags, from
the shm work histogram) over the window's wall time, in % (host clock)."""


def read(r):
    return r.busy_pct("dedup")
