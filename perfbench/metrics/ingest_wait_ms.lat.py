"""Mean queue wait of a frame on the ingest link, from its publish to
the verify tile's gather, over the window, in ms: the change of the
summed wait in verify's consumer block of that link over the change of
its count (program counter). None where the program keeps no wait
there."""


def read(r):
    m = r.delta[r.cfg["device_tile"]]["metrics"]
    n = m.get(r.cfg["ingest"] + ".wait_count")
    if not n:
        return None
    return m[r.cfg["ingest"] + ".wait_ns"] / n / 1e6
