"""Host time of the verify tile's batch path over the window's wall
time, in %: the summed change of its ns_<stage> counters for every
stage but readback (program counter) — gather, parse, dedup, assemble,
dispatch, finalize, deferred, publish. Readback is the wait for the
device, and a device capture's own start/stop is left out. None where
the tile keeps no stage counters."""

HOST_STAGES = ("gather", "parse", "dedup", "assemble", "dispatch",
               "finalize", "deferred", "publish")


def read(r):
    m = r.delta[r.cfg["device_tile"]]["metrics"]
    if "ns_gather" not in m:
        return None
    ns = sum(m["ns_" + s] for s in HOST_STAGES)
    return 100.0 * ns / ((r.t1 - r.t0) * 1e9)
