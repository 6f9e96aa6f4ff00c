#!/usr/bin/env python3
"""One cell, one run, as run.py, with the verify tile's own accounting
read out beside the result:

    python3 perfbench/stages_run.py --workload NAME --seed N --seconds S \
        --trace 0|1

The harness runs unchanged but for three additions. With --trace 1 the
device tile also keeps an fdtrace ring (RING_DEPTH records, sized to
hold the 2 s capture and its stop at the dupflood cell's ~0.3M records
per second), whose records inside the capture window it writes beside
the profiler trace (prof/device.py fdtrace.json). Those spans are laid
on the device trace through the capture's fdtpu_clock marks
(stagemap.py): the share of the device's idle time no verify span
covers, `idle_host_bound` (device idle while the tile is in a host
stage, in % of the capture) and the longest idle gaps named by the span
covering them. And the `window:` line gains the decomposition of a
txn's time: the harness's mean scheduled send -> first delivery of the
window's valid txns, the injector's mean lateness, the ingest queue
wait, the verify tile's hold, device wait and egress, the
verify->dedup queue wait, their sum's share of that mean (with and
without the injector's lateness), and the verify tile's stage ms,
counts and stall/collector histograms over the window. The files the
reading used are kept under perfbench/.out/stages/<workload>.<seed>/.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RING_DEPTH = 1 << 21


def _wait_ms(r, tile: str, link: str):
    m = r.delta.get(tile, {}).get("metrics", {})
    n = m.get(link + ".wait_count")
    return m[link + ".wait_ns"] / n / 1e6 if n else None


def decomposition(r) -> dict:
    """A txn's time through the front door, in ms, from the program's
    counters over the window, beside the harness's own mean."""
    import numpy as np
    dev = r.cfg["device_tile"]
    out_link = next(t["outs"][0] for t in r.cfg["tiles"]
                    if t["name"] == dev)
    down = next(t["name"] for t in r.cfg["tiles"] if out_link in
                t.get("ins", ()))
    sel = r.in_window & r.corpus.valid
    lat = r.first_t[sel] - r.first_sched[sel]
    out = {"mean_ms": float(np.nanmean(lat)) * 1e3 if sel.any() else None,
           "injector_late_mean_ms": float(np.nanmean(r.late_s)) * 1e3,
           "ingest_wait_ms": _wait_ms(r, dev, r.cfg["ingest"])}
    m = r.delta[dev]["metrics"]
    n = m.get("txn_timed")
    for k in ("hold", "device", "egress"):
        out[k + "_ms"] = m["txn_" + k + "_ns"] / n / 1e6 if n else None
    out[out_link + "_wait_ms"] = _wait_ms(r, down, out_link)
    parts = [out[k] for k in ("ingest_wait_ms", "hold_ms", "device_ms",
                              "egress_ms", out_link + "_wait_ms")]
    if out["mean_ms"] and None not in parts:
        out["parts_share_of_mean"] = sum(parts) / out["mean_ms"]
        # a txn the injector could not publish (the ingest ring full)
        # waited there first: its lateness is part of the harness's
        # latency, not of any ring's wait
        out["parts_and_late_share_of_mean"] = (
            sum(parts) + out["injector_late_mean_ms"]) / out["mean_ms"]
    # the tile's own counters over the window: stage ms, counts, and
    # its stall and collector-pause histograms as (count, ms)
    out["verify_window"] = {
        k: (v / 1e6 if k.startswith("ns_") else v) for k, v in m.items()
        if k.startswith("ns_") or k in (
            "batches", "lanes", "rx", "tx", "dedup_drop", "verify_fail",
            "deferred_parked", "deferred_host_verifies", "txn_timed")}
    out["verify_hists"] = {k: (c, ns / 1e6) for k, (c, ns)
                           in r.delta[dev]["hist"].items()}
    return out


def stage_map(trace_dir: str, man: dict, keep: str) -> dict:
    """The ring's spans on the device trace (stagemap.py)."""
    from firedancer_tpu.prof.device import clock_offset_ns

    from perfbench import harness, stagemap, tracereduce
    pbs = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True))
    if not pbs or not man.get("fdtrace"):
        return {"stage_map": "no trace or no fdtrace.json"}
    os.makedirs(keep, exist_ok=True)
    clock = os.path.join(keep, "clock.json")
    r = subprocess.run([sys.executable, "-m", "perfbench.stagemap",
                        pbs[-1], clock], cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=240)
    if r.returncode:
        return {"stage_map": "reading the clock marks failed: "
                + r.stderr[-400:]}
    marks = harness.load_json(clock)["marks"]
    off = clock_offset_ns(marks)
    if off is None:
        return {"stage_map": "no fdtpu_clock marks"}
    shutil.copy(man["fdtrace"], keep)
    shutil.copy(os.path.join(harness.OUT, "trace_dump.json"), keep)
    doc = harness.load_json(os.path.join(keep, "trace_dump.json"))
    spans = stagemap.spans_on_trace(
        harness.load_json(man["fdtrace"])["records"], off)
    busy = stagemap.device_busy(doc)
    lo, hi = man["t0_ns"] - off, man["t1_ns"] - off
    cov = stagemap.idle_cover(busy, spans, lo, hi)
    host = [e for pl in doc["planes"] if pl["name"].startswith("/host")
            for ln in pl["lines"] for e in ln["events"]]
    idle = cov["idle_ns"] or 1
    return {
        "clock_marks": marks,
        "clock_mark_spread_ns": max(m - t for t, m in marks)
        - min(m - t for t, m in marks),
        "device_idle_pct": 100.0 * cov["idle_ns"] / cov["window_ns"],
        "idle_uncovered_share": 1.0 - cov["covered_ns"] / idle,
        "idle_host_bound_pct": 100.0 * cov["host_stage_ns"]
        / cov["window_ns"],
        "idle_gaps_fdtrace": stagemap.gaps_labelled(
            busy, spans, lo, hi,
            lambda s, e: tracereduce._host_label(host, s, e)),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    args = ap.parse_args(argv)
    from perfbench import harness
    keep = os.path.join(harness.OUT, "stages",
                        f"{args.workload}.{args.seed}")
    mapped = {}
    build, read, diag = (harness.build_topology, harness.read_trace,
                         harness.diagnostics)

    def build_traced(cfg, name, trace):
        topo = build(cfg, name, trace)
        if trace:
            topo.trace = {"enable": True, "tiles": [cfg["device_tile"]],
                          "depth": RING_DEPTH}
        return topo

    def read_and_map(trace_dir, man):
        red = read(trace_dir, man)
        mapped.update(stage_map(trace_dir, man, keep))
        return red

    def diagnostics(r, rate):
        return dict(diag(r, rate), **decomposition(r), **mapped)

    harness.build_topology = build_traced
    harness.read_trace = read_and_map
    harness.diagnostics = diagnostics
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), T_START, rate=args.rate)
    except harness.BenchError as e:
        print(f"perfbench: FAILED: {e}", file=sys.stderr)
        return 1
    harness.report(out)
    os.makedirs(keep, exist_ok=True)
    with open(os.path.join(keep, "result.json"), "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__mp_main__":
    from perfbench.run import tile_process_hook
    tile_process_hook()

if __name__ == "__main__":
    sys.exit(main())
