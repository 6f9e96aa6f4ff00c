"""The pack tile's stem work time (its polls that consumed frags, from the
shm work histogram) over the window's wall time, in % (program
counter). None where the config has no pack tile."""


def read(r):
    return r.busy_pct(r.tile("pack"))
