"""The window's wall time over the ticks the poh tile made in it, in ms
(program counter `ticks`): how long a tick takes, hashes and all. A
block's cost limit holds per slot of ticks_per_slot ticks, so this sets
the most a leader can commit per second. None where the config has no
poh tile or it made no tick."""


def read(r):
    ticks = r.delta.get(r.tile("poh"), {}).get("metrics", {}).get("ticks")
    if not ticks:
        return None
    return 1e3 * (r.t1 - r.t0) / ticks
