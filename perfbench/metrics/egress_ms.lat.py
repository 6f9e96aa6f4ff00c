"""Mean time a txn that reached a device lane spent from the moment the
verify tile sees its verdict ready to its publish on the verify->dedup
link (finalize, parked duplicates, publish and credit waits), over the
txns the verify tile finished in the window, in ms: the change of
`txn_egress_ns` over the change of `txn_timed` (program counter). None
where the tile keeps no such counter."""


def read(r):
    m = r.delta[r.cfg["device_tile"]]["metrics"]
    n = m.get("txn_timed")
    if not n:
        return None
    return m["txn_egress_ns"] / n / 1e6
