"""Device trace -> busy time, idle gaps and per-call kernel time.

Input: xplane.py's JSON (planes -> lines -> [name, start_ns, dur_ns]).
A device plane is one whose name starts with "/device:" (TPU planes are
"/device:TPU:<n>"). Busy time is the union of the intervals of the
plane's op events ("XLA Ops"; every line of the plane where it has no
such line), averaged over the chips traced; idle = window - busy. The
strict verify program is the jit of the verify tile's `_packed`
(module "jit__packed..."): its per-call time is the mean duration of
those "XLA Modules" events. Idle gaps are named by the host event that
fills the gap best (the host's own profiler lines), or "host: none".
"""
from __future__ import annotations

STRICT_MODULE = "_packed"      # jax.jit(_packed) in tiles/verify.py


def union(iv) -> list:
    """Merged [start, end) intervals."""
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _device_planes(doc: dict) -> list:
    return [p for p in doc["planes"] if p["name"].startswith("/device:")
            and any(ln["events"] for ln in p["lines"])]


def _op_events(plane: dict) -> list:
    ops = [ln for ln in plane["lines"] if ln["name"] == "XLA Ops"]
    lines = ops or plane["lines"]
    return [e for ln in lines for e in ln["events"]]


def reduce_trace(doc: dict, window_ns: int) -> dict | None:
    """{busy_s, window_s, strict_call_ms, strict_calls, breakdown} or
    None where the trace holds no device plane (nothing to read)."""
    planes = _device_planes(doc)
    if not planes or window_ns <= 0:
        return None
    busy = 0
    gaps = []
    op_time: dict = {}
    calls = []
    for p in planes:
        ev = _op_events(p)
        merged = union([[s, s + d] for _, s, d in ev])
        busy += sum(e - s for s, e in merged)
        gaps += [(merged[i][1], merged[i + 1][0])
                 for i in range(len(merged) - 1)]
        for name, _, d in ev:
            name = name.split(" = ", 1)[0]      # the HLO op, not its text
            op_time[name] = op_time.get(name, 0) + d
        for ln in p["lines"]:
            if ln["name"] == "XLA Modules":
                calls += [d for name, _, d in ln["events"]
                          if STRICT_MODULE in name]
    busy_s = busy / len(planes) / 1e9
    host = [e for pl in doc["planes"] if pl["name"].startswith("/host")
            for ln in pl["lines"] for e in ln["events"]]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    idle = [[_host_label(host, s, e), (e - s) / 1e9] for s, e in top_gaps]
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_s,
        "window_s": window_ns / 1e9,
        "strict_calls": len(calls),
        "strict_call_ms": (sum(calls) / len(calls) / 1e6) if calls
        else None,
        "breakdown": {"device_ops": [[n, t / 1e9] for n, t in ops],
                      "idle_gaps": idle},
    }


def _host_label(host, s, e) -> str:
    """The host event that best fills the gap: overlap over the longer
    of the event and the gap (so a span around the whole run loses to
    the call inside the gap)."""
    best, best_score = None, 0.0
    for name, hs, hd in host:
        ov = min(e, hs + hd) - max(s, hs)
        score = ov / max(hd, e - s, 1)
        if ov > 0 and score > best_score:
            best, best_score = name, score
    return f"host: {best}" if best else "host: none"
