"""Signature lanes the verify tile dispatched over the lanes its device
batches compile, in %: the change of its `lanes` counter over the
change of `batches` times the batch (program counter). None where the
tile counts no lanes, or dispatched nothing in the window."""


def read(r):
    m = r.delta[r.cfg["device_tile"]]["metrics"]
    if "lanes" not in m or not m.get("batches"):
        return None
    batch = next(t["args"]["batch"] for t in r.cfg["tiles"]
                 if t["name"] == r.cfg["device_tile"])
    return 100.0 * m["lanes"] / (m["batches"] * batch)
