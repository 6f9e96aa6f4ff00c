"""Lanes per device dispatch of the verify tile, mean over the window:
the change of its `staged_lanes` counter (the lanes each dispatch was
assembled, shipped and run at) over the change of `batches` (program
counter). The batch (8192) where every dispatch ships the whole staging
set; less where the tile ships narrower chunks. None where the tile
keeps no such counter, or dispatched nothing in the window."""


def read(r):
    m = r.delta[r.cfg["device_tile"]]["metrics"]
    if "staged_lanes" not in m or not m.get("batches"):
        return None
    return m["staged_lanes"] / m["batches"]
