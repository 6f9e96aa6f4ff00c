"""The readers of the verify tile's stage counters and queue waits, on
a hand-built run, and the ring's spans laid on a hand-built device
trace (stagemap.py): idle cover, the host-bound idle share and the
gap labels."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness, stagemap  # noqa: E402

MS = 1_000_000
NEW = ["ingest_wait_ms.lat", "hold_ms.lat", "device_wait_ms.lat",
       "egress_ms.lat", "verify_stall_ms.lat", "dispatch_fill.lat",
       "dispatch_fill.tps", "verify_host_busy.tps", "deferred_busy.tps"]


def _run(verify_metrics: dict, hist: dict) -> harness.Run:
    r = harness.Run()
    r.cfg = harness.load_json(os.path.join(
        ROOT, "perfbench", "configs", "frontdoor-1chip.json"))
    r.t0, r.t1 = 100.0, 120.0             # a 20 s window
    r.delta = {"verify": {"metrics": verify_metrics, "hist": hist},
               "dedup": {"metrics": {"rx": 1}, "hist": {}}}
    return r


def _counted() -> harness.Run:
    ns = {f"ns_{s}": 0 for s in ("gather", "parse", "dedup", "assemble",
                                 "dispatch", "readback", "finalize",
                                 "deferred", "publish", "capture")}
    ns.update(ns_gather=1_000 * MS, ns_parse=500 * MS,
              ns_readback=9_000 * MS, ns_deferred=2_000 * MS,
              ns_publish=500 * MS, ns_capture=800 * MS)
    return _run({"batches": 100, "lanes": 131_072,
                 "quic_verify.wait_count": 4, "quic_verify.wait_ns": 40 * MS,
                 "txn_timed": 1000, "txn_hold_ns": 3_000 * MS,
                 "txn_device_ns": 25_000 * MS, "txn_egress_ns": 2_000 * MS,
                 **ns},
                {"work": (10, 5 * MS), "stall": (2, 300 * MS)})


@pytest.mark.parametrize("name,want", [
    ("ingest_wait_ms.lat", 10.0),
    ("hold_ms.lat", 3.0),
    ("device_wait_ms.lat", 25.0),
    ("egress_ms.lat", 2.0),
    ("verify_stall_ms.lat", 300.0),
    ("dispatch_fill.lat", 16.0),          # 131072 / (100 x 8192)
    ("dispatch_fill.tps", 16.0),
    ("verify_host_busy.tps", 20.0),       # 4 s of host stages in 20 s
    ("deferred_busy.tps", 10.0),
])
def test_reader_on_a_hand_built_run(name, want):
    assert harness.reader(name)(_counted()) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_is_silent_on_a_program_without_the_counters(name):
    """The parent program keeps none of these counters: each reader
    returns None there, and raises nothing."""
    r = _run({"batches": 100, "rx": 5, "tx": 5},
             {"wait": (1, 1), "work": (1, 1), "tpu": (1, 1)})
    assert harness.reader(name)(r) is None


def test_new_metrics_are_declared_for_the_cells_that_read_them():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = entries[name]
        assert m["source"] == "program_counter"
        cell = "frontdoor.dupflood.over" if name.endswith(".tps") \
            else "frontdoor.mainnet.60k"
        assert m["workloads"] == [cell]


# -- the ring's spans on the device trace --------------------------------

OFF = 5_000 * MS                          # monotonic - trace, ns


def _doc():
    """Device busy [0, 10) [30, 40) [70, 100) ms of a 100 ms capture."""
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["verify", 0, 10 * MS], ["verify", 30 * MS, 10 * MS],
            ["verify", 70 * MS, 30 * MS]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["_finalize", 40 * MS, 30 * MS]]}]}]}


def _records():
    """fdtrace.json rows [end_ns, dur_ns, name] on the monotonic clock:
    a deferred stage over [10, 25) ms, a readback over [25, 30), a
    whole poll's work span [10, 30), a stem wait [45, 55), an instant,
    and nothing over [40, 45) or [55, 70)."""
    def row(s, e, name):
        return [OFF + e * MS, (e - s) * MS, name]
    return [row(10, 30, "work"), row(10, 25, "stage:deferred"),
            row(25, 30, "stage:readback"), row(45, 55, "wait"),
            [OFF + 50 * MS, 0, "stall:deferred"]]


def test_clock_marks_give_the_offset():
    from firedancer_tpu.prof.device import clock_offset_ns
    assert clock_offset_ns([(1_000, OFF + 1_000),
                            (90 * MS, OFF + 90 * MS + 2)]) == OFF + 1
    assert clock_offset_ns([]) is None


def test_idle_cover_and_host_bound_share():
    spans = stagemap.spans_on_trace(_records(), OFF)
    assert (10 * MS, 25 * MS, "stage:deferred") in spans
    assert len(spans) == 4                 # the instant has no extent
    busy = stagemap.device_busy(_doc())
    assert stagemap.idle_intervals(busy, 0, 100 * MS) == [
        [10 * MS, 30 * MS], [40 * MS, 70 * MS]]
    cov = stagemap.idle_cover(busy, spans, 0, 100 * MS)
    # idle 50 ms: [10, 30) covered by deferred + readback (the work span
    # covers nothing on its own), [45, 55) by the stem wait
    assert cov == {"window_ns": 100 * MS, "idle_ns": 50 * MS,
                   "covered_ns": 30 * MS, "host_stage_ns": 15 * MS}


def test_gap_labels_prefer_stages_then_fall_back():
    spans = stagemap.spans_on_trace(_records(), OFF)
    busy = stagemap.device_busy(_doc())
    gaps = stagemap.gaps_labelled(busy, spans, 0, 100 * MS,
                                  lambda s, e: "host: _finalize")
    # [40, 70): the stem wait covers a third; [10, 30): the deferred
    # stage beats the poll's work span around it
    assert gaps == [["fdtrace: wait", pytest.approx(0.03)],
                    ["fdtrace: stage:deferred", pytest.approx(0.02)]]
    assert stagemap.label(spans, 56 * MS, 69 * MS, "host: x") == "host: x"


def test_cpu_rehearsal_reads_the_stage_counters():
    """A small CPU run through stages_run.py: the program's counters
    reach every new reader through the harness, the verify tile counts
    its batches, lanes and timed txns over the window, and each part of
    a txn's time the program counts lies within the harness's own mean
    latency. (On the chip the parts' sum is 85-105% of that mean; a
    loaded CPU stretches the mean, so no share is held here.)"""
    import subprocess
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "stages_rehearsal.py")
    r = subprocess.run([sys.executable, script, "frontdoor.mainnet.60k"],
                       cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    got = out["metrics"]
    for name in ("ingest_wait_ms.lat", "hold_ms.lat", "device_wait_ms.lat",
                 "egress_ms.lat", "dispatch_fill.lat"):
        assert got[name]["value"] > 0, name
    assert got["verify_stall_ms.lat"]["value"] >= 0
    window = json.loads(next(
        ln for ln in r.stderr.splitlines()
        if "perfbench: window: " in ln).split("window: ", 1)[1])
    counted = window["verify_window"]
    assert counted["batches"] > 0 and counted["txn_timed"] > 0, counted
    assert counted["lanes"] >= counted["batches"]      # >= 1 lane a batch
    assert counted["tx"] > 0
    parts = [window[k] for k in ("ingest_wait_ms", "hold_ms", "device_ms",
                                 "egress_ms", "verify_dedup_wait_ms")]
    assert all(p is not None and p >= 0 for p in parts), window
    assert sum(parts) > 0
    assert max(parts) <= window["mean_ms"], window
    assert window["parts_share_of_mean"] > 0
