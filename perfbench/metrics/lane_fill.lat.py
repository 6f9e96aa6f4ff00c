"""Signature lanes dispatched over lanes compiled, in %: the harness
counts the lanes it offered in the window (sig_cnt of each frame; the
mix has no duplicates), the verify tile counts its device batches."""


def read(r):
    if r.lanes_window is None:
        return None
    batch = next(t["args"]["batch"] for t in r.cfg["tiles"]
                 if t["name"] == r.cfg["device_tile"])
    batches = r.delta[r.cfg["device_tile"]]["metrics"].get("batches", 0)
    if not batches:
        return None
    return 100.0 * r.lanes_window / (batches * batch)
