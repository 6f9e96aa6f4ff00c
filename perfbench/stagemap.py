"""The verify tile's own spans laid on the device trace.

A traced device tile writes its ring records inside a capture window
beside the profiler's output (prof/device.py: fdtrace.json, rows of
[end_ns, dur_ns, name] on CLOCK_MONOTONIC), and marks the capture with
`fdtpu_clock` annotations whose `mono_ns` stat is the monotonic time at
which each was made. Trace time = monotonic time - offset, the offset
being mono_ns - start_ns of those marks (prof/device.py
clock_offset_ns). This module maps the ring's spans onto the trace and
reads the device's idle time against them:

  - which share of the device's idle time some verify span covers (a
    stage, a stem wait or a housekeeping pass),
  - which share of the capture the device is idle while the tile is in
    a host stage (every stage but readback): `idle_host_bound`,
  - a label for each idle gap: the span that covers most of it.

    python -m perfbench.stagemap TRACE.xplane.pb OUT.json

dumps the trace's fdtpu_clock marks (run as a child pinned to the CPU,
as xplane.py is). Device busy intervals come from xplane.py's dump.
"""
from __future__ import annotations

import json
import sys

from .tracereduce import _device_planes, _op_events, union

CLOCK = "fdtpu_clock"
# ring span names that say where the verify tile was (prof/device.py
# window_records): its stages, the stem's idle wait and housekeeping,
# credit waits. A stem `work` span is a whole poll, stages and all: it
# labels a gap no stage covers, but covers nothing.
COVER = ("stage:", "wait", "housekeep", "backpressure")
READBACK = "stage:readback"          # the one stage that waits on the device


def clock_marks(path: str) -> list:
    """[(trace start_ns, mono_ns)] of the fdtpu_clock annotations."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == CLOCK:
                    stats = dict(e.stats)
                    if "mono_ns" in stats:
                        out.append((int(e.start_ns),
                                    int(stats["mono_ns"])))
    return out


def spans_on_trace(records, off: int) -> list:
    """fdtrace.json rows -> [(start, end, name)] in trace ns, spans only
    (instants have no extent)."""
    return [(ts - dur - off, ts - off, name) for ts, dur, name in records
            if dur > 0]


def device_busy(doc: dict) -> list:
    """Merged busy intervals of the first device plane (xplane.py dump)."""
    planes = _device_planes(doc)
    if not planes:
        return []
    return union([[s, s + d] for _, s, d in _op_events(planes[0])])


def idle_intervals(busy, lo: int, hi: int) -> list:
    """[lo, hi) minus the busy intervals."""
    out, cur = [], lo
    for s, e in busy:
        if e <= cur:
            continue
        if s >= hi:
            break
        if s > cur:
            out.append([cur, min(s, hi)])
        cur = max(cur, e)
    if cur < hi:
        out.append([cur, hi])
    return out


def _overlap(a, b) -> int:
    """Total length of the intersection of two merged interval lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_cover(busy, spans, lo: int, hi: int) -> dict:
    """Device idle ns in [lo, hi), and how much of it a verify span
    covers (any of COVER) or a host stage covers (every stage but
    readback)."""
    idle = idle_intervals(busy, lo, hi)
    cover = union([[s, e] for s, e, n in spans if n.startswith(COVER)])
    host = union([[s, e] for s, e, n in spans
                  if n.startswith("stage:") and n != READBACK])
    return {"window_ns": hi - lo,
            "idle_ns": sum(e - s for s, e in idle),
            "covered_ns": _overlap(idle, cover),
            "host_stage_ns": _overlap(idle, host)}


def label(spans, s: int, e: int, fallback: str) -> str:
    """The span that covers most of [s, e), named `fdtrace: <name>`; the
    fallback (the host profiler's label) where none covers any of it.
    A stage span wins over the stem's wait/work spans it nests in."""
    for stages in (True, False):
        best, best_ov = None, 0
        for hs, he, name in spans:
            ov = min(e, he) - max(s, hs)
            if name.startswith("stage:") == stages and ov > best_ov:
                best, best_ov = name, ov
        if best is not None:
            return f"fdtrace: {best}"
    return fallback


def gaps_labelled(busy, spans, lo: int, hi: int, fallback,
                  n: int = 10) -> list:
    """The n longest device-idle gaps in [lo, hi): [[label, s]], with
    fallback(s, e) naming a gap no ring span overlaps."""
    idle = sorted(idle_intervals(busy, lo, hi), key=lambda g: g[0] - g[1])
    return [[label(spans, s, e, fallback(s, e)), (e - s) / 1e9]
            for s, e in idle[:n]]


def main(argv):
    with open(argv[1], "w") as f:
        json.dump({"marks": clock_marks(argv[0])}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
