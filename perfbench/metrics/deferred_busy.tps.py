"""Time the verify tile spent resolving parked duplicates (duplicates
that raced their original's verdict; the host reference re-verifies
those whose original failed) over the window's wall time, in %: the
change of its ns_deferred counter (program counter). None where the
tile keeps no stage counters."""


def read(r):
    m = r.delta[r.cfg["device_tile"]]["metrics"]
    if "ns_deferred" not in m:
        return None
    return 100.0 * m["ns_deferred"] / ((r.t1 - r.t0) * 1e9)
