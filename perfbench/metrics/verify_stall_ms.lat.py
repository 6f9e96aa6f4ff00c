"""Time the verify tile spent in stalls over the window, in ms: polls
and housekeeping passes of 50 ms or longer, from the tile's stall
histogram (program counter). A stall in the device capture's own
start/stop is not in it (the tile counts that under its capture
stage), so a traced run reads the same as an untraced one. None where
the program keeps no stall histogram."""


def read(r):
    h = r.delta[r.cfg["device_tile"]]["hist"].get("stall")
    if h is None:
        return None
    return h[1] / 1e6
