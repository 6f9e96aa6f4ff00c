"""Dump a profiler .xplane.pb as plain JSON (run as a child pinned to
the CPU, so the harness itself never imports JAX):

    python -m perfbench.xplane TRACE.xplane.pb OUT.json

Output: {"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, dur_ns], ...]}]}]} for every device plane, and the
host plane's lines (their events capped, the longest kept) so idle
gaps can be named by what the host was doing.
"""
from __future__ import annotations

import json
import sys

HOST_EVENTS_MAX = 20000


def dump(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        device = not plane.name.startswith("/host")
        lines = []
        for line in plane.lines:
            ev = [[e.name, int(e.start_ns), int(e.duration_ns)]
                  for e in line.events]
            if not device and len(ev) > HOST_EVENTS_MAX:
                ev = sorted(ev, key=lambda x: -x[2])[:HOST_EVENTS_MAX]
            lines.append({"name": line.name, "events": ev})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def main(argv):
    with open(argv[1], "w") as f:
        json.dump(dump(argv[0]), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
