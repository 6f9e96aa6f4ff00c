"""fdprof device side: bounded `jax.profiler` trace windows + compile
artifacts, driven by the shm capture doorbell.

The host sampler (recorder.py) explains Python time; the questions it
cannot answer — which XLA ops, which dispatch stalls, which compiles —
belong to the device profiler. A capture is a bounded window: the
owning tile's housekeeping sees `capture_req > capture_ack` on its
ProfRegion (bumped by the metric tile on an SLO breach, or by
`tools/fdprof --capture`), starts `jax.profiler.start_trace` into a
per-tile directory, lets the normal poll loop run the window out, then
stops the trace, writes a JSON manifest next to the supervisor black
boxes, stamps an EV_PROF_CAPTURE span into the flight recorder, and
acks. `stop_trace` serializes the whole window (seconds for a busy
device tile) and, with the Python tracer off, releases the GIL while
it does, so it runs on a thread of its own: the tile keeps polling,
and a later housekeeping pass writes the manifest once the trace is
on disk. A backend without
a working profiler still produces the manifest (ok=false + the error)
— a breach-triggered drill must leave an artifact either way.

One clock with the device trace: profiler times count from the
capture's start, fdtrace records carry CLOCK_MONOTONIC. Right after
`start_trace` and right before `stop_trace` the capture makes a
`fdtpu_clock` annotation whose `mono_ns` stat is the monotonic time it
was made at, so a reader of the trace maps ring times onto trace times
(`clock_offset_ns`). A traced tile's own ring records inside the window
(its stage, wait, work, housekeeping and stall events) are written
beside the trace as fdtrace.json, so nobody has to race the
overwrite-oldest ring for them.

Compile events ride the same housekeeping pass: a jit cache-size
increase since the last pass is a compile the steady-state padding
discipline should have prevented; it leaves an EV_COMPILE trace event
and refreshes the compile manifest (count, device memory, timestamps).
"""
from __future__ import annotations

import json
import os
import tempfile
import threading

from ..utils.tempo import monotonic_ns

CLOCK_ANNOTATION = "fdtpu_clock"
FDTRACE_FILE = "fdtrace.json"


def capture_manifest_path(topology: str, tile: str) -> str:
    return f"/dev/shm/fdtpu_{topology}.prof.{tile}.capture.json"


def compile_manifest_path(topology: str, tile: str) -> str:
    return f"/dev/shm/fdtpu_{topology}.prof.{tile}.compile.json"


def trace_dir(topology: str, tile: str) -> str:
    # the heavyweight profiler output (TensorBoard/XPlane) goes to the
    # temp directory ($TMPDIR, else /tmp), not /dev/shm — only the
    # small manifest lives with the black boxes
    return os.path.join(tempfile.gettempdir(),
                        f"fdtpu_prof_{topology}_{tile}")


def clock_mark():
    """One instant on both clocks: a profiler annotation carrying the
    monotonic ns at which it was made (call inside a capture)."""
    import jax
    with jax.profiler.TraceAnnotation(CLOCK_ANNOTATION,
                                      mono_ns=monotonic_ns()):
        pass


def clock_offset_ns(marks) -> int | None:
    """monotonic ns minus trace ns, from the (trace start_ns, mono_ns)
    pairs of a capture's fdtpu_clock annotations (their mean; None when
    there are none): trace time = ring time - offset."""
    marks = list(marks)
    if not marks:
        return None
    return round(sum(m - t for t, m in marks) / len(marks))


def window_records(trace, t0: int, t1: int) -> list:
    """A traced tile's ring records that overlap [t0, t1], as
    [end_ns, dur_ns, name] rows oldest-first: spans and instants
    (frag-scoped records left out), an EV_STAGE span named
    `stage:<stage>`, an EV_STALL instant `stall:<stage>`."""
    import numpy as np

    from ..trace import events as ev
    _, recs = trace.ring.snapshot()
    ts, arg, meta = recs[:, 0], recs[:, 2], recs[:, 3]
    et = meta & 0xFFFF
    span = np.isin(et, list(ev.SPANS))
    dur = np.where(span, arg, 0)
    keep = ~np.isin(et, list(ev.FRAG_EVENTS)) \
        & np.isin(et, list(ev.NAMES)) & (ts >= t0) & (ts - dur <= t1)
    named = (ev.EV_STAGE, ev.EV_STALL)
    return [[t, d, ev.NAMES[e] + (":" + ev.STAGE_NAMES.get(c, "?")
                                  if e in named else "")]
            for t, d, e, c in zip(ts[keep].tolist(), dur[keep].tolist(),
                                  et[keep].tolist(),
                                  (meta[keep] >> 32).tolist())]


def request_capture(plan: dict, wksp, tile: str) -> bool:
    """Bump the capture doorbell on a profiled tile (requester side:
    metric tile on breach, or the fdprof CLI). False if unprofiled."""
    from .recorder import region_for
    region = region_for(plan, wksp, tile)
    if region is None:
        return False
    region.request_capture()
    return True


class DeviceCapture:
    """The owning tile's capture state machine (one per device tile,
    polled from its housekeeping — never from the hot loop):

        poll() -> started | stopping | stopped-manifest-path | None

    Window length comes from the plan's [prof] capture_ms; the window
    runs out across housekeeping passes so the poll loop keeps driving
    the device while the profiler records it, and so does the stop."""

    def __init__(self, plan: dict, tile: str, region, trace=None):
        self.plan, self.tile, self.region = plan, tile, region
        self.trace = trace
        self.topology = plan.get("topology", "?")
        self.window_ms = float(
            (plan.get("prof") or {}).get("capture_ms", 200.0))
        self._active: dict | None = None
        self._stopping: dict | None = None   # window closed, not on disk
        self.captures = 0

    @property
    def active(self) -> bool:
        """A window is open."""
        return self._active is not None

    @property
    def stopping(self) -> bool:
        """A closed window's trace is still being written."""
        return self._stopping is not None

    def _start(self, req: int):
        t0 = monotonic_ns()
        d = trace_dir(self.topology, self.tile)
        err = None
        try:
            os.makedirs(d, exist_ok=True)
            import jax
            # XLA ops, dispatches and annotations only: Python frames
            # are the host sampler's to explain (recorder.py), and the
            # Python tracer's collect holds the GIL for its event per
            # call of the busy poll loop — a second-long tile stall,
            # even with the stop off the tile's thread
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(d, profiler_options=opts)
            clock_mark()
        except Exception as e:     # noqa: BLE001 — manifest either way
            err = f"{e!r}"[:200]
        self._active = {"req": req, "t0": t0, "dir": d, "err": err,
                        "deadline": t0 + int(self.window_ms * 1e6)}

    def _stop(self):
        """Close the window on both clocks and take the tile's ring
        records in it, then start stop_trace on a thread of its own."""
        act, self._active = self._active, None
        act["t1"] = monotonic_ns()
        act["thread"] = act["records"] = None
        if act["err"] is None:
            try:
                clock_mark()
            except Exception as e:  # noqa: BLE001
                act["err"] = f"{e!r}"[:200]
        if act["err"] is None:
            if self.trace is not None:
                act["records"] = window_records(self.trace, act["t0"],
                                                act["t1"])

            def export():
                try:
                    import jax
                    jax.profiler.stop_trace()
                except Exception as e:  # noqa: BLE001
                    act["err"] = f"{e!r}"[:200]
            act["thread"] = threading.Thread(
                target=export, name="fdtpu-capture-stop", daemon=True)
            act["thread"].start()
        self._stopping = act

    def _finish(self) -> str | None:
        """The trace is on disk (or failed): manifest, span, ack."""
        act, self._stopping = self._stopping, None
        fdtrace = None
        if act["err"] is None and act["records"] is not None:
            fdtrace = os.path.join(act["dir"], FDTRACE_FILE)
            try:
                with open(fdtrace, "w") as f:
                    json.dump({"tile": self.tile, "t0_ns": act["t0"],
                               "t1_ns": act["t1"],
                               "records": act["records"]}, f)
            except OSError:
                fdtrace = None
        doc = {
            "topology": self.topology,
            "tile": self.tile,
            "req": act["req"],
            "t0_ns": act["t0"],
            "t1_ns": act["t1"],
            "window_ms": self.window_ms,
            "ok": act["err"] is None,
            "trace_dir": act["dir"] if act["err"] is None else None,
            "fdtrace": fdtrace,
            "error": act["err"],
        }
        path = capture_manifest_path(self.topology, self.tile)
        try:
            with open(path, "w") as f:
                json.dump(doc, f)
        except OSError:
            path = None
        if self.trace is not None:
            from ..trace.events import EV_PROF_CAPTURE
            self.trace.span(EV_PROF_CAPTURE, act["t0"],
                            count=act["req"])
        self.region.ack_capture(act["req"])
        self.captures += 1
        return path

    def poll(self) -> str | None:
        """One housekeeping-cadence pass; returns the manifest path
        when a window's trace just landed on disk."""
        if self._active is not None:
            if monotonic_ns() < self._active["deadline"]:
                return None
            self._stop()
        if self._stopping is not None:
            th = self._stopping["thread"]
            if th is not None and th.is_alive():
                return None
            return self._finish()
        req = self.region.capture_req
        if req > self.region.capture_ack:
            self._start(req)
        return None

    def flush(self):
        """Halt path: close an open window and wait for its trace, so
        the ack never dangles."""
        if self._active is not None:
            self._stop()
        if self._stopping is not None:
            if self._stopping["thread"] is not None:
                self._stopping["thread"].join()
            self._finish()


class CompileWatch:
    """Compile-event capture: detects jit cache growth between
    housekeeping passes, stamps EV_COMPILE into the flight recorder,
    and keeps the compile manifest fresh. `compiles_fn` returns the
    current compiled-variant count (adapter-provided: jax version
    differences stay in one place)."""

    def __init__(self, plan: dict, tile: str, compiles_fn, trace=None,
                 mem_fn=None, manifest: bool = True):
        self.topology = plan.get("topology", "?")
        self.tile = tile
        self._compiles = compiles_fn
        self._mem = mem_fn or (lambda: 0)
        self.trace = trace
        self.manifest = manifest   # manifest files only when profiled
        self.last = 0             # warmup's compile registers on the
        self.events = 0           # first pass: boot compile is event 1

    def poll(self) -> int | None:
        """Returns the new compile count when one was detected."""
        cur = self._compiles()
        if cur <= self.last:
            return None
        self.last = cur
        self.events += 1
        if self.trace is not None:
            from ..trace.events import EV_COMPILE
            self.trace.event(EV_COMPILE, arg=self._mem(), count=cur)
        if not self.manifest:
            return cur
        doc = {
            "topology": self.topology,
            "tile": self.tile,
            "compiles": cur,
            "cache_miss": max(0, cur - 1),
            "device_mem_bytes": self._mem(),
            "ts_ns": monotonic_ns(),
        }
        try:
            with open(compile_manifest_path(self.topology, self.tile),
                      "w") as f:
                json.dump(doc, f)
        except OSError:
            pass
        return cur
