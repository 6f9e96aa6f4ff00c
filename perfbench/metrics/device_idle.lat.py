"""Device idle share, 1 - busy/window, over the profiler capture inside
the window, in % (device trace; tracereduce.py)."""


def read(r):
    return r.idle_pct()
