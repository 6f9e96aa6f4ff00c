"""Device time of one call of the strict verify program (the verify
tile's jit of `_packed`: SHA-512, the fused verify kernel and the glue),
mean over the calls in the capture (device trace). Operations and
bytes per call are not computed: no published VPU int32 peak to divide
them by (PERF.md, Open questions)."""


def read(r):
    if r.trace is None:
        return None
    return r.trace["strict_call_ms"]
