"""fdtrace event schema: the binary record vocabulary.

One flat u16 event-type space shared by every writer (stem, verify
tile, adapters, supervisor) — the moral equivalent of Chrome's
trace-event categories/phases (the Perfetto timeline model): SPAN
events carry a duration (record.arg = ns, record.ts = END of the
span), INSTANT events mark a point. Frag-scoped events additionally
carry the frag's `sig` (the dedup tag for verify-pipeline traffic), so
one transaction microbatch can be followed across rings by matching
sigs — the cross-tile lineage the exporter turns into Perfetto flow
arrows.

Record wire layout lives in runtime/tango.py::TraceRing; this module
owns only the meaning of the words.
"""
from __future__ import annotations

# -- event types (u16) ------------------------------------------------------

EV_BOOT = 1          # instant: stem entered RUN
EV_HALT = 2          # instant: clean halt path taken
EV_FAIL = 3          # instant: tile raised / external CNC_FAIL observed
EV_WAIT = 4          # span: idle streak waiting on upstream frags
EV_WORK = 5          # span: productive poll_once time (count = frags;
                     #   with sample>1 one record SUMS the last
                     #   `sample` productive polls — attribution stays
                     #   exact, only the record rate is thinned)
EV_HOUSEKEEP = 6     # span: one housekeeping pass
EV_CONSUME = 7       # instant, frag-scoped: frag consumed (sig, link)
EV_PUBLISH = 8       # instant, frag-scoped: frag published (sig, link)
EV_BACKPRESSURE = 9  # span: blocked on downstream credits (link)
# 10, 11: retired (device dispatch / readback are EV_STAGE spans now)
EV_CPU_FALLBACK = 12  # instant: verify degraded to the CPU path
EV_CHAOS = 13        # instant: chaos fault fired (count = action id)
EV_WATCHDOG = 14     # instant: supervisor wedge-watchdog trip (sup-written)
EV_RESTART = 15      # instant: supervisor respawned the tile (sup-written)
EV_DOWN = 16         # instant: supervisor observed abnormal death
EV_SLO = 17          # instant: SLO breach (metric-tile-written;
                     #   arg = measured value, count = target index
                     #   into the plan's [slo] target list)
EV_COMPILE = 18      # instant: jit cache grew — a compile the padding
                     #   discipline should have prevented (fdprof
                     #   CompileWatch; arg = device mem bytes,
                     #   count = total compiled variants)
EV_PROF_CAPTURE = 19  # span: bounded device-trace window (fdprof
                     #   DeviceCapture; count = doorbell req id)
                     # 20: unused (retired; ids are a ring ABI, never
                     #   renumbered)
EV_STAGE = 21        # span: one stage of a tile's batch (count = stage
                     #   id, STAGE_IDS below) — the verify tile's host
                     #   and device stages, a device capture's own
                     #   start/stop, a collector pause
EV_STALL = 22        # instant: a poll or housekeeping pass of STALL_NS
                     #   or longer (disco/stem.py; arg = its ns,
                     #   count = the stage id it spent most time in)

NAMES = {
    EV_BOOT: "boot", EV_HALT: "halt", EV_FAIL: "fail",
    EV_WAIT: "wait", EV_WORK: "work", EV_HOUSEKEEP: "housekeep",
    EV_CONSUME: "consume", EV_PUBLISH: "publish",
    EV_BACKPRESSURE: "backpressure",
    EV_CPU_FALLBACK: "cpu_fallback", EV_CHAOS: "chaos",
    EV_WATCHDOG: "watchdog", EV_RESTART: "restart", EV_DOWN: "down",
    EV_SLO: "slo", EV_COMPILE: "compile",
    EV_PROF_CAPTURE: "prof_capture",
    EV_STAGE: "stage", EV_STALL: "stall",
}

# span events: record.ts is the END, record.arg the duration in ns
SPANS = {EV_WAIT, EV_WORK, EV_HOUSEKEEP, EV_BACKPRESSURE,
         EV_PROF_CAPTURE, EV_STAGE}

# frag-scoped events (sig is a lineage key, not 0-means-nothing)
FRAG_EVENTS = {EV_CONSUME, EV_PUBLISH}

# chaos action ids (record.count of an EV_CHAOS event); kept in lockstep
# with utils/chaos.py ACTIONS so a dumped trace names the exact fault
CHAOS_ACTION_IDS = {
    "crash": 1, "freeze_hb": 2, "wedge": 3, "stall_fseq": 4,
    "fail_dispatch": 5,
    # adversarial traffic plans (r14): injected hostile TRAFFIC, not
    # infrastructure faults — recorded before the frames flow so a
    # black-box dump names the attack even when the tile died mid-flood
    "flood_forged": 6, "flood_torsion": 7, "flood_dup": 8,
    "flood_malformed_quic": 9, "flood_crds_spam": 10,
    # snapshot/replay robustness drills (r17): the catch-up surface's
    # seeded faults — adapter-routed, recorded before the fault fires
    "crash_mid_snapshot": 11, "corrupt_checkpt_frame": 12,
    "stale_snapshot_offer": 13, "diverge_block": 14,
}
CHAOS_ACTION_NAMES = {v: k for k, v in CHAOS_ACTION_IDS.items()}

# stage ids (record.count of EV_STAGE spans and EV_STALL instants). The
# verify tile's batch runs through the first nine; each also keeps a
# cumulative host-ns counter in its metric slots (ns_<stage>):
#   gather    ring gather + the coalesce hold copy
#   parse     txn parse + dedup tags
#   dedup     tcache query, in-flight reservation, parking
#   assemble  device lane assembly
#   dispatch  device dispatch (also the RLC prefilter's equation, and
#             a degraded tile's host verify)
#   readback  every wait for a verdict: a free staging set, the drain
#   finalize  verdict merge, tcache insert (and a lost record's host
#             re-verify)
#   deferred  resolving parked duplicates (host re-verifies included)
#   publish   publish and the credit waits it takes
# The rest name what a stall was in outside those stages: a device
# capture's own start/stop, a collector pause, a tile's poll or a
# housekeeping pass that no stage accounts for.
STAGE_IDS = {
    "gather": 1, "parse": 2, "dedup": 3, "assemble": 4, "dispatch": 5,
    "readback": 6, "finalize": 7, "deferred": 8, "publish": 9,
    "capture": 10, "gc": 11, "poll": 12, "housekeep": 13,
}
STAGE_NAMES = {v: k for k, v in STAGE_IDS.items()}
VERIFY_STAGES = ("gather", "parse", "dedup", "assemble", "dispatch",
                 "readback", "finalize", "deferred", "publish")


def decode(rec, link_names: list[str] | None = None) -> dict:
    """One raw (4,) u64 record -> a plain dict (the export/JSON shape).
    link_names is the plan's sorted link-name list; an out-of-range id
    (TRACE_LINK_NONE, or a torn record) decodes to link=None."""
    from ..runtime.tango import TRACE_LINK_NONE
    ts, sig, arg, meta = (int(rec[0]), int(rec[1]), int(rec[2]),
                          int(rec[3]))
    etype = meta & 0xFFFF
    link_id = (meta >> 16) & 0xFFFF
    count = meta >> 32
    link = None
    if link_names is not None and link_id != TRACE_LINK_NONE \
            and link_id < len(link_names):
        link = link_names[link_id]
    return {"ts": ts, "ev": NAMES.get(etype, f"?{etype}"),
            "etype": etype, "sig": sig, "arg": arg, "link": link,
            "count": count}
