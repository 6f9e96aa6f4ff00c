"""Mean time a txn that reached a device lane spent from the dispatch of
its device batch to the moment the verify tile sees its verdict ready,
over the txns the verify tile finished in the window, in ms: the change
of `txn_device_ns` over the change of `txn_timed` (program counter).
None where the tile keeps no such counter."""


def read(r):
    m = r.delta[r.cfg["device_tile"]]["metrics"]
    n = m.get("txn_timed")
    if not n:
        return None
    return m["txn_device_ns"] / n / 1e6
