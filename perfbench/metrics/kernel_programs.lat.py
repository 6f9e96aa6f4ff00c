"""Grid programs the strict verify kernel ran per device batch, mean
over the window: the change of the verify tile's `kernel_programs`
counter over the change of `batches` (program counter). Out of
batch / 256 (32 at batch 8192): the kernel skips its 256-lane programs
that hold only padding. None where the tile keeps no such counter, or
dispatched nothing in the window."""


def read(r):
    m = r.delta[r.cfg["device_tile"]]["metrics"]
    if "kernel_programs" not in m or not m.get("batches"):
        return None
    return m["kernel_programs"] / m["batches"]
