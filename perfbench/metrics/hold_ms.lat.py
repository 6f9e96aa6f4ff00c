"""Mean time a txn that reached a device lane spent from its gather to the
dispatch of its device batch (the coalesce hold, lane assembly and the
wait for an in-flight slot), over the txns the verify tile finished in
the window, in ms: the change of `txn_hold_ns` over the change of
`txn_timed` (program counter). None where the tile keeps no such
counter."""


def read(r):
    m = r.delta[r.cfg["device_tile"]]["metrics"]
    n = m.get("txn_timed")
    if not n:
        return None
    return m["txn_hold_ns"] / n / 1e6
