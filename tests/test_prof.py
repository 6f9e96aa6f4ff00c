"""fdprof: whole-topology continuous profiler (firedancer_tpu/prof/).

Covers the ISSUE 6 test checklist: sampler on/off overhead bound,
folded-stack shm ABI round-trip, post-mortem export after tile death,
merged Perfetto bundle schema (single clock domain, no colliding
thread/span ids), the SLO-triggered device-capture drill under chaos,
and the fdbench diff gate's pass + regression exit paths.
"""
import json
import os
import threading
import time

import pytest

from firedancer_tpu.prof import (
    PROF_DEFAULTS, STATE_NAMES, TILE_PROF_KEYS, ProfRegion, ProfState,
    Sampler, effective_prof, folded_text, merged_chrome, normalize_prof,
    profile_summary, read_folded, read_samples,
)
from firedancer_tpu.runtime import Workspace

pytestmark = pytest.mark.prof


# -- schema ------------------------------------------------------------------

def test_normalize_prof_defaults_and_validation():
    d = normalize_prof(None)
    assert d["enable"] is False and d["hz"] == 97.0
    assert d["tiles"] is None and d["breach_capture"] == []
    on = normalize_prof({"enable": True, "hz": 29, "slots": 64})
    assert on["enable"] is True and on["hz"] == 29.0
    with pytest.raises(ValueError, match="did you mean 'slots'"):
        normalize_prof({"slotz": 64})
    with pytest.raises(ValueError, match="power of two"):
        normalize_prof({"ring": 100})
    with pytest.raises(ValueError, match="hz"):
        normalize_prof({"hz": 0})
    with pytest.raises(ValueError, match="stack_depth"):
        normalize_prof({"stack_depth": 0})
    with pytest.raises(ValueError, match="capture_ms"):
        normalize_prof({"capture_ms": -1})
    with pytest.raises(ValueError, match="list of tile names"):
        normalize_prof({"breach_capture": "verify"})
    # per-tile override: only the TILE_PROF_KEYS subset
    with pytest.raises(ValueError, match="unknown prof key"):
        normalize_prof({"tiles": ["x"]}, per_tile=True)


def test_registry_mirrors_prof_keys():
    """The fdlint key registry's [prof] mirror must track the one
    validator's schema (the same honesty contract [trace]/[slo]
    have)."""
    from firedancer_tpu.lint import registry as reg
    assert set(reg.PROF_SECTION_KEYS) == set(PROF_DEFAULTS)
    assert set(reg.TILE_PROF_KEYS) == set(TILE_PROF_KEYS)
    assert "prof" in reg.COMMON_KEYS


def test_effective_prof_resolution():
    topo = normalize_prof({"enable": True, "hz": 50, "tiles": ["a"]})
    assert effective_prof(topo, "a", {}) == {
        "hz": 50.0, "slots": 256, "ring": 2048, "stack_depth": 16}
    assert effective_prof(topo, "b", {}) is None        # allowlist
    assert effective_prof(topo, "b", {"enable": True})["hz"] == 50.0
    assert effective_prof(topo, "a", {"enable": False}) is None
    off = normalize_prof(None)
    assert effective_prof(off, "a", {}) is None
    assert effective_prof(off, "a", {"enable": True, "hz": 9})["hz"] \
        == 9


# -- shm ABI round-trip ------------------------------------------------------

@pytest.fixture
def wksp():
    w = Workspace(f"/fdtpu_proftest{os.getpid()}", 1 << 21)
    yield w
    w.close()
    Workspace.unlink_name(w.name)


def test_region_abi_roundtrip(wksp):
    """Writer-side records must read back identically through a SECOND
    region instance over the same offsets — the cross-process ABI."""
    r = ProfRegion.create(wksp, slots=64, ring=128)
    r.record("root:main;mod:fn", 1, 1000)
    r.record("root:main;mod:fn", 0, 2000)
    r.record("root:main;other:fn2", 2, 3000)
    r2 = ProfRegion(wksp, r.off, 64, 128)       # the reader's join
    assert r2.samples == 3 and r2.dropped == 0
    folded = r2.folded()
    assert folded["root:main;mod:fn"] == {"wait": 1, "work": 1}
    assert folded["root:main;other:fn2"] == {"housekeep": 1}
    ring = r2.snapshot_ring()
    assert [(ts, st) for ts, _, st in ring] == [(1000, 1), (2000, 0),
                                               (3000, 2)]
    assert r2.stack_at(ring[2][1]) == "root:main;other:fn2"
    # capture doorbell: requester and owner write DIFFERENT words
    r2.request_capture()
    assert r.capture_req == 1 and r.capture_ack == 0
    r.ack_capture(r.capture_req)
    assert r2.capture_ack == 1


def test_region_ring_wraps_and_table_drops(wksp):
    r = ProfRegion.create(wksp, slots=8, ring=8)
    for i in range(40):
        r.record(f"stack-{i}", 1, i)
    assert r.samples == 40
    # only the newest `ring` samples are materialized; cursor counts all
    assert r.ring_cursor == 40 and len(r.snapshot_ring()) == 8
    # 8 slots minus probe-collision losses: overflow counted, not lost
    assert r.dropped > 0
    assert len(r.folded()) <= 8


def test_folded_text_stable_format():
    text = folded_text({"tileB": {"a;b": {"work": 3}},
                        "tileA": {"x;y": {"wait": 1, "work": 2}}})
    assert text.splitlines() == [
        "tileA;wait;x;y 1",
        "tileA;work;x;y 2",
        "tileB;work;a;b 3",
    ]


# -- sampler -----------------------------------------------------------------

def _busy(dur_s: float):
    t0 = time.perf_counter()
    acc = 0
    while time.perf_counter() - t0 < dur_s:
        acc += sum(range(200))
    return acc


def test_sampler_collects_and_attributes(wksp):
    r = ProfRegion.create(wksp, slots=256, ring=512)
    st = ProfState()
    st.state = 1
    st.link = "in_link"
    s = Sampler(r, 400, threading.get_ident(), st, stack_depth=8)
    s.start()
    _busy(0.25)
    st.state = 0
    st.link = None
    _busy(0.1)
    s.stop()
    assert r.samples > 5
    folded = r.folded()
    # work samples carry the active in-link as the flamegraph root
    work = [k for k, v in folded.items() if "work" in v]
    assert any(k.startswith("[in_link];") for k in work)
    assert any("test_prof:_busy" in k for k in folded)
    by_state = set()
    for v in folded.values():
        by_state |= set(v)
    assert "work" in by_state and "wait" in by_state


def test_sampler_overhead_bound(wksp):
    """ISSUE 6 acceptance companion: the sampler must be cheap. The
    e2e bench criterion is <=2% at the bench's 29 Hz; here a noisy CI
    box gets a loose 1.5x bound at a much hotter 250 Hz (best-of-3
    each way to shed scheduler noise), plus proof the sampler actually
    sampled during the measured window."""
    base = min(_timed() for _ in range(3))
    r = ProfRegion.create(wksp, slots=256, ring=256)
    s = Sampler(r, 250, threading.get_ident(), ProfState(),
                stack_depth=12)
    s.start()
    on = min(_timed() for _ in range(3))
    s.stop()
    assert r.samples > 5
    assert on < base * 1.5, (base, on)


def _timed() -> float:
    t0 = time.perf_counter()
    _busy(0.2)
    return time.perf_counter() - t0


# -- topology build plumbing -------------------------------------------------

def _build(prof=None, **topo_kw):
    from firedancer_tpu.disco import Topology
    topo = (Topology(f"pfb{os.getpid()}", wksp_size=1 << 22, prof=prof,
                     **topo_kw)
            .link("a_b", depth=16, mtu=256)
            .tile("a", "synth", outs=["a_b"], count=4)
            .tile("b", "sink", ins=["a_b"]))
    return topo.build()


def test_build_carves_regions_only_when_enabled():
    from firedancer_tpu.disco.stem import Stem
    from firedancer_tpu.disco.topo import TileCtx
    plan = _build()                      # default: unprofiled
    try:
        assert not any("prof_off" in s for s in plan["tiles"].values())
        ctx = TileCtx(plan, "b")
        try:
            assert ctx.prof is None

            class _T:
                def poll_once(self):
                    return 0
            stem = Stem(ctx, _T(), idle_sleep_s=0)
            assert stem._prof_region is None    # whole disabled path
            stem.run(max_iters=4)
            assert stem._sampler is None
        finally:
            ctx.close()
    finally:
        Workspace.unlink_name(plan["wksp"]["name"])
    plan = _build(prof={"enable": True, "slots": 64, "ring": 128,
                        "tiles": ["b"]})
    try:
        assert "prof_off" in plan["tiles"]["b"]
        assert "prof_off" not in plan["tiles"]["a"]     # allowlist
        assert plan["tiles"]["b"]["prof_slots"] == 64
        assert plan["prof"]["enable"] is True
    finally:
        Workspace.unlink_name(plan["wksp"]["name"])
    with pytest.raises(ValueError, match="unknown tile"):
        _build(prof={"enable": True, "tiles": ["ghost"]})
    with pytest.raises(ValueError, match="unknown tile"):
        _build(prof={"enable": True, "breach_capture": ["ghost"]})


def test_config_toml_prof_section_roundtrip(tmp_path):
    from firedancer_tpu.app.config import build_topology, load_config
    p = tmp_path / "t.toml"
    p.write_text("""
[prof]
enable = true
hz = 31
tiles = ["snk"]

[[link]]
name = "a_b"
depth = 16
mtu = 256

[[tile]]
name = "src"
kind = "synth"
outs = ["a_b"]
count = 4

[[tile]]
name = "snk"
kind = "sink"
ins = ["a_b"]

[tile.prof]
hz = 59
""")
    cfg = load_config(str(p))
    topo = build_topology(cfg, name=f"pft{os.getpid()}")
    assert topo.prof["hz"] == 31
    plan = topo.build()
    try:
        assert plan["tiles"]["snk"]["prof_hz"] == 59   # override wins
        assert "prof_off" not in plan["tiles"]["src"]
    finally:
        Workspace.unlink_name(plan["wksp"]["name"])
    bad = tmp_path / "bad.toml"
    bad.write_text("[prof]\nhzz = 10\n")
    with pytest.raises(ValueError, match="did you mean 'hz'"):
        build_topology(load_config(str(bad)))


# -- fdbench (bench-trend observatory) ---------------------------------------

_OLD_BENCH = {
    "value": 400_000.0, "e2e_tps": 13_000.0, "e2e_knee_tps": 11_000.0,
    "e2e_link_budget": {"ingest": {"pub": 100, "lost": 0,
                                   "backpressure": 2,
                                   "consume_p99_us": 40.0}},
    "e2e_profile": {"verify": {"top": [
        {"stack": "a;b", "count": 50}, {"stack": "c;d", "count": 10}]}},
}


def test_fdbench_diff_and_gate_paths(tmp_path):
    from firedancer_tpu.prof.bench_diff import (diff_bench,
                                                gate_regressions, main)
    good = dict(_OLD_BENCH, value=410_000.0, e2e_tps=13_500.0,
                e2e_knee_tps=11_100.0)
    d = diff_bench(_OLD_BENCH, good)
    assert gate_regressions(d) == []
    bad = dict(_OLD_BENCH, value=300_000.0)          # -25% kernel
    regs = gate_regressions(diff_bench(_OLD_BENCH, bad),
                            threshold=0.05)
    assert [r["metric"] for r in regs] == ["value"]
    assert regs[0]["frac"] < -0.2
    # a missing metric is reported but never gated (CPU-fallback round)
    nope = {"value": 420_000.0}
    assert gate_regressions(diff_bench(_OLD_BENCH, nope)) == []
    # ...but the witnessed fallback stands in when present
    wit = {"value": 420_000.0,
           "witnessed_tpu": {"e2e_tps": 9_000.0}}
    regs = gate_regressions(diff_bench(_OLD_BENCH, wit))
    assert [r["metric"] for r in regs] == ["e2e_tps"]
    # CLI exit codes: clean diff -> 0, --gate on a regression -> 1
    po, pn = tmp_path / "old.json", tmp_path / "new.json"
    po.write_text(json.dumps(_OLD_BENCH))
    pn.write_text(json.dumps(bad))
    assert main([str(po), str(pn)]) == 0             # report only
    assert main([str(po), str(pn), "--gate"]) == 1
    assert main([str(po), str(pn), "--gate", "--threshold", "0.9"]) \
        == 0
    pn.write_text(json.dumps(good))
    assert main([str(po), str(pn), "--gate"]) == 0


def test_fdbench_loads_driver_wrapper_and_bare_record(tmp_path):
    """The committed BENCH_r*.json round artifacts are driver wrappers
    whose `tail` string holds the bench record as its last JSON line;
    witnessed files are the bare record — load_bench takes both."""
    from firedancer_tpu.prof.bench_diff import load_bench
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(_OLD_BENCH | {"metric": "x"}))
    assert load_bench(str(bare))["value"] == 400_000.0
    wrapped = tmp_path / "wrapped.json"
    wrapped.write_text(json.dumps({
        "n": 4, "rc": 0,
        "tail": "noise\n" + json.dumps(
            {"metric": "x", "value": 123.0}) + "\n"}))
    assert load_bench(str(wrapped))["value"] == 123.0
    # unparseable tail falls back to the outer document
    broken = tmp_path / "broken.json"
    broken.write_text(json.dumps({"tail": "{trunc", "value": 7}))
    assert load_bench(str(broken))["value"] == 7


def test_fdbench_profile_topk_deltas():
    from firedancer_tpu.prof.bench_diff import diff_bench
    new = dict(_OLD_BENCH, e2e_profile={"verify": {"top": [
        {"stack": "a;b", "count": 80}, {"stack": "z;z", "count": 5}]}})
    d = diff_bench(_OLD_BENCH, new)
    rows = d["profile"]["verify"]
    assert rows["a;b"] == {"old": 50, "new": 80}
    assert rows["c;d"] == {"old": 10, "new": 0}
    assert rows["z;z"] == {"old": 0, "new": 5}


# -- the live acceptance drill ----------------------------------------------

N_TXNS = 24


@pytest.fixture(scope="module")
def prof_pipeline():
    """verify + sink + metric over an external ingest ring, fully
    profiled and traced, with (a) an SLO objective that MUST breach,
    (b) breach_capture pointed at the verify tile, and (c) seeded
    chaos crashing the sink mid-stream (restart policy) — the
    'SLO-triggered device-capture drill under chaos'."""
    from firedancer_tpu.disco import Topology, TopologyRunner
    from firedancer_tpu.runtime import Ring
    from firedancer_tpu.tiles.synth import make_signed_txns
    txns = make_signed_txns(N_TXNS, seed=11)
    topo = (
        Topology(f"pfl{os.getpid()}", wksp_size=1 << 23,
                 trace={"enable": True, "depth": 1024, "sample": 1},
                 prof={"enable": True, "hz": 200, "slots": 256,
                       "ring": 1024, "capture_ms": 150.0,
                       "breach_capture": ["verify"]},
                 slo={"fast_window_s": 0.4, "slow_window_s": 30.0,
                      "burn_fast": 1.0,
                      "target": [{"name": "impossible-latency",
                                  "expr": "verify.work p99 < 1ns"}]})
        .link("in_verify", depth=64, mtu=1280, external=True)
        .link("verify_sink", depth=64, mtu=1280)
        .tcache("vtc", depth=512)
        .tile("verify", "verify", ins=["in_verify"],
              outs=["verify_sink"], batch=32, tcache="vtc")
        .tile("sink", "sink", ins=["verify_sink"],
              supervise={"policy": "restart", "backoff_s": 0.05,
                         "max_restarts": 3, "window_s": 60.0},
              chaos={"seed": 3,
                     "events": [{"action": "crash", "at_rx": 8}]})
        .tile("metric", "metric", port=0)
    )
    plan = topo.build()
    runner = TopologyRunner(plan).start()
    try:
        runner.wait_running(timeout_s=540)
        li = plan["links"]["in_verify"]
        ring = Ring(runner.wksp, li["ring_off"], li["depth"],
                    li["arena_off"], li["mtu"])
        for i, t in enumerate(txns):
            ring.publish(t, sig=i)
        # full recovery under chaos: all txns verified AND the crashed
        # sink respawned (frags published while down are the
        # documented loss — rx <= N)
        t0 = time.time()
        while time.time() - t0 < 120:
            runner.check_failures()
            if runner.metrics("verify")["rx"] >= N_TXNS \
                    and runner.metrics("sink")["sup_restarts"] >= 1 \
                    and runner.metrics("sink")["sup_down"] == 0:
                break
            time.sleep(0.05)
        # the drill: wait for breach -> doorbell -> capture ack
        from firedancer_tpu.prof import region_for
        region = region_for(plan, runner.wksp, "verify")
        t0 = time.time()
        while time.time() - t0 < 150:      # generous: 2-core CI boxes
            runner.check_failures()
            if region.capture_ack >= 1:
                break
            time.sleep(0.05)
        time.sleep(0.3)                    # one housekeeping flush
        yield runner
    finally:
        runner.halt(join_timeout_s=10)
        runner.close()


def test_live_folded_stacks_for_at_least_two_tiles(prof_pipeline):
    runner = prof_pipeline
    folded = read_folded(runner.plan, runner.wksp)
    populated = [tn for tn, f in folded.items()
                 if sum(sum(v.values()) for v in f.values()) > 0]
    assert len(populated) >= 2, folded.keys()


def test_merged_bundle_single_clock_and_device_events(prof_pipeline):
    """ACCEPTANCE: the merged Perfetto bundle holds host flamegraph
    slices for >=2 tiles AND the verify tile's device/compile events
    on one timeline — one clock domain, no colliding thread ids."""
    runner = prof_pipeline
    doc = json.loads(json.dumps(
        merged_chrome(runner.plan, runner.wksp)))
    te = doc["traceEvents"]
    names = {}
    for e in te:
        if e.get("name") == "thread_name":
            # no two threads may share a tid (fdtrace tiles vs /host)
            assert e["tid"] not in names, (e, names)
            names[e["tid"]] = e["args"]["name"]
    host_tids = {t for t, n in names.items() if n.endswith("/host")}
    assert len(host_tids) >= 2, names
    trace_tids = {n: t for t, n in names.items()
                  if not n.endswith("/host")}
    # host slices actually present for >=2 tiles
    hosts_with_slices = {e["tid"] for e in te
                         if e.get("cat") == "fdprof"}
    assert len(hosts_with_slices & host_tids) >= 2
    # verify's device + compile events ride the same timeline
    vtid = trace_tids["verify"]
    vnames = {e["name"] for e in te if e.get("tid") == vtid}
    assert "dispatch" in vnames and "compile" in vnames
    # single clock domain: host slices interleave the fdtrace span
    # range (both are utils/tempo.monotonic_ns)
    trace_ts = [e["ts"] for e in te
                if e.get("tid") in set(trace_tids.values())
                and e.get("ph") in ("X", "i")]
    host_ts = [e["ts"] for e in te if e.get("cat") == "fdprof"]
    assert host_ts and trace_ts
    lo, hi = min(trace_ts), max(trace_ts)
    assert any(lo <= t <= hi for t in host_ts), (lo, hi)


def test_slo_breach_triggered_capture_under_chaos(prof_pipeline):
    """The drill's artifacts: doorbell acked, capture manifest on
    disk, EV_PROF_CAPTURE + EV_COMPILE in the verify ring, breach
    history in the engine's /summary.json surface, and the chaos
    restart actually happened (the 'under chaos' half)."""
    runner = prof_pipeline
    from firedancer_tpu.prof import region_for
    from firedancer_tpu.prof.device import capture_manifest_path
    region = region_for(runner.plan, runner.wksp, "verify")
    assert region.capture_ack >= 1, "capture never acked"
    path = capture_manifest_path(runner.plan["topology"], "verify")
    with open(path) as f:
        doc = json.load(f)
    assert doc["tile"] == "verify" and doc["window_ms"] == 150.0
    assert doc["t1_ns"] > doc["t0_ns"]
    assert runner.metrics("verify")["prof_captures"] >= 1
    from firedancer_tpu.trace import read_rings
    evs = read_rings(runner.plan, runner.wksp, tiles=["verify"])
    kinds = {e["ev"] for e in evs["verify"]}
    assert "prof_capture" in kinds and "compile" in kinds
    assert runner.metrics("sink")["sup_restarts"] >= 1
    os.unlink(path)                        # test hygiene (/dev/shm)


def test_summary_json_and_monitor_surface_breach_history(prof_pipeline):
    runner = prof_pipeline
    import urllib.request
    port = runner.metrics("metric")["port"]
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/summary.json", timeout=10) as r:
        doc = json.loads(r.read())
    hist = doc["slo_history"]
    assert hist and hist[0]["target"] == "impossible-latency"
    assert hist[0]["kind"] == "breach"
    # the monitor recovers the same breaches from shm alone: EV_SLO in
    # the metric tile's ring when recent, the engine's durable breach
    # dump when the wrapping ring has moved on — this read happens
    # MINUTES after the breach, so it exercises the dump fallback
    from firedancer_tpu.disco.monitor import slo_breach_events
    evs = slo_breach_events(runner.plan, runner.wksp)
    assert evs and evs[-1]["target"] == "impossible-latency"


def test_profile_summary_shape_for_bench(prof_pipeline):
    runner = prof_pipeline
    prof = profile_summary(runner.plan, runner.wksp, top_k=3)
    assert "verify" in prof and "sink" in prof
    v = prof["verify"]
    assert v["samples"] > 0 and v["top"]
    assert set(v["top"][0]) == {"stack", "count", "states"}
    assert all(len(t["stack"]) for t in v["top"])


def test_fdprof_cli_live(prof_pipeline, tmp_path, capsys):
    from firedancer_tpu.prof.cli import main as prof_main
    runner = prof_pipeline
    out = tmp_path / "bundle.json"
    folded = tmp_path / "run.folded"
    rc = prof_main([runner.plan["topology"], "--out", str(out),
                    "--folded", str(folded)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["otherData"]["prof"] == "fdprof"
    assert any(e.get("cat") == "fdprof" for e in doc["traceEvents"])
    lines = folded.read_text().splitlines()
    assert lines and all(" " in ln for ln in lines)
    text = capsys.readouterr().out
    assert "fdprof summary" in text and "samples" in text


def test_post_mortem_export_after_tile_death():
    """The shm regions outlive the tile processes: halt everything,
    THEN read folded stacks and the merged bundle (the same
    post-mortem contract as fdtrace black boxes)."""
    from firedancer_tpu.disco import Topology, TopologyRunner
    topo = (Topology(f"pfpm{os.getpid()}", wksp_size=1 << 22,
                     prof={"enable": True, "hz": 300, "slots": 128,
                           "ring": 256})
            .link("a_b", depth=32, mtu=256)
            .tile("a", "synth", outs=["a_b"], count=200, unique=8,
                  burst=8)
            .tile("b", "sink", ins=["a_b"]))
    plan = topo.build()
    runner = TopologyRunner(plan).start()
    try:
        runner.wait_running(timeout_s=540)
        runner.wait_idle("b", "rx", 8, timeout_s=120)
        time.sleep(0.2)
        runner.halt(join_timeout_s=10)     # tiles are DEAD now
        assert all(not p.is_alive() for p in runner.procs.values())
        folded = read_folded(plan, runner.wksp)
        assert any(sum(sum(v.values()) for v in f.values()) > 0
                   for f in folded.values()), folded
        samples = read_samples(plan, runner.wksp)
        assert any(samples.values())
    finally:
        runner.halt(join_timeout_s=5)
        runner.close()


# -- one clock with the device trace ----------------------------------------

class _Doorbell:
    """A ProfRegion's capture doorbell, in process."""

    def __init__(self):
        self.capture_req = self.capture_ack = 0

    def ack_capture(self, req):
        self.capture_ack = req


def test_trace_dir_honours_tmpdir(tmp_path, monkeypatch):
    import tempfile

    from firedancer_tpu.prof import device
    monkeypatch.setattr(tempfile, "tempdir", None)
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    assert device.trace_dir("topo", "verify") == str(
        tmp_path / "fdtpu_prof_topo_verify")


def _python_frame_probe():
    return sum(range(10))


def _poll_until_landed(cap, timeout_s=60.0):
    """Housekeeping passes until the capture's trace is on disk."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        path = cap.poll()
        if path is not None:
            return path
        time.sleep(0.01)
    raise AssertionError("the capture's trace never landed")


def test_clock_anchor_maps_ring_span_onto_trace(wksp, tmp_path,
                                                monkeypatch):
    """A CPU jax.profiler capture: the fdtpu_clock marks DeviceCapture
    makes at its start and stop map a ring span (CLOCK_MONOTONIC) onto
    the trace's own times within 1 ms — checked against a profiler
    annotation made over the same interval — the trace holds no Python
    frames, and the tile's ring records inside the window land beside
    the trace as fdtrace.json."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from firedancer_tpu.prof import device
    from firedancer_tpu.runtime import TraceRing
    from firedancer_tpu.trace import TraceWriter
    from firedancer_tpu.trace import events as tev
    from firedancer_tpu.utils.tempo import monotonic_ns
    monkeypatch.setattr(device, "trace_dir",
                        lambda topo, tile: str(tmp_path / "cap"))
    tr = TraceWriter(TraceRing.create(wksp, 256))
    bell = _Doorbell()
    cap = device.DeviceCapture({"topology": "clk",
                                "prof": {"capture_ms": 50.0}},
                               "verify", bell, trace=tr)
    bell.capture_req = 1
    cap.poll()
    assert cap.active
    t0 = monotonic_ns()
    with jax.profiler.TraceAnnotation("probe"):
        time.sleep(0.005)
        _python_frame_probe()
    tr.span(tev.EV_STAGE, t0, count=tev.STAGE_IDS["deferred"])
    time.sleep(0.06)
    path = _poll_until_landed(cap)
    assert not cap.active and not cap.stopping and bell.capture_ack == 1
    man = json.load(open(path))
    os.unlink(path)
    assert man["ok"] and man["fdtrace"] == str(tmp_path / "cap" /
                                               device.FDTRACE_FILE)
    pd = ProfileData.from_file(sorted(glob.glob(
        str(tmp_path / "cap" / "**" / "*.xplane.pb"), recursive=True))[-1])
    evs = [e for p in pd.planes for ln in p.lines for e in ln.events]
    marks = [(int(e.start_ns), int(dict(e.stats)["mono_ns"]))
             for e in evs if e.name == device.CLOCK_ANNOTATION]
    assert len(marks) == 2                 # after start, before stop
    off = device.clock_offset_ns(marks)
    probe = next(e for e in evs if e.name == "probe")
    # annotations yes, Python frames no (the Python tracer is off)
    assert not any("_python_frame_probe" in e.name for e in evs)
    recs = json.load(open(man["fdtrace"]))["records"]
    end, dur, name = next(r for r in recs if r[2] == "stage:deferred")
    assert abs((end - dur - off) - probe.start_ns) < 1e6
    assert abs((end - off) - (probe.start_ns + probe.duration_ns)) < 1e6
    # only the window's records, frag-scoped ones left out
    assert all(man["t0_ns"] <= r[0] for r in recs)


@pytest.mark.parametrize("end", ["poll", "flush", "stop_fails"])
def test_capture_stop_runs_off_the_tile_thread(tmp_path, monkeypatch, end):
    """stop_trace (seconds on a busy device tile) runs on a thread of
    its own: the housekeeping pass that closes the window returns at
    once and acks nothing; a later pass (or the halt path's flush,
    which waits for it) writes the manifest and acks — ok=false with
    the error when the stop fails."""
    import jax

    from firedancer_tpu.prof import device
    monkeypatch.setattr(device, "trace_dir",
                        lambda topo, tile: str(tmp_path / "cap"))
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)

    def slow_stop():
        time.sleep(0.5)
        if end == "stop_fails":
            raise RuntimeError("no profile started")
    monkeypatch.setattr(jax.profiler, "stop_trace", slow_stop)
    bell = _Doorbell()
    cap = device.DeviceCapture({"topology": f"stp{os.getpid()}",
                                "prof": {"capture_ms": 20.0}},
                               "verify", bell)
    bell.capture_req = 1
    assert cap.poll() is None and cap.active
    time.sleep(0.03)
    t0 = time.monotonic()
    assert cap.poll() is None
    assert time.monotonic() - t0 < 0.25      # not held for the stop
    assert not cap.active and cap.stopping and bell.capture_ack == 0
    bell.capture_req = 2                     # no new window mid-stop
    assert cap.poll() is None and not cap.active
    if end == "flush":
        cap.flush()
        path = device.capture_manifest_path(f"stp{os.getpid()}", "verify")
    else:
        path = _poll_until_landed(cap)
    assert not cap.stopping and bell.capture_ack == 1 and cap.captures == 1
    man = json.load(open(path))
    os.unlink(path)
    assert man["ok"] is (end != "stop_fails")
    assert (man["error"] is None) is (end != "stop_fails")
    assert man["t1_ns"] - man["t0_ns"] >= 20e6
