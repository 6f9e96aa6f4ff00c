"""fdtrace flight recorder: ring semantics, config schema, the
zero-cost disabled path, and the tier-1 acceptance drill — a live
two-tile topology (verify + downstream sink over an external ingest
ring) whose Perfetto/Chrome JSON export shows one frag's lineage as
correlated spans across both tiles.
"""
import json
import os
import time

import pytest

from firedancer_tpu.runtime import TraceRing, Workspace
from firedancer_tpu.trace import (
    TRACE_DEFAULTS, TILE_TRACE_KEYS, TraceWriter, effective_trace,
    events as tev, lineage, normalize_trace, read_rings, summary,
    to_chrome,
)

pytestmark = pytest.mark.trace


# -- ring + writer semantics ------------------------------------------------

@pytest.fixture(scope="module")
def wksp():
    w = Workspace(f"/fdtpu_tr_{os.getpid()}", 1 << 22)
    yield w
    w.close()
    w.unlink()


def test_ring_wraps_keeps_newest_in_order(wksp):
    r = TraceRing.create(wksp, 8)
    for i in range(11):
        r.append(1000 + i, tev.EV_CONSUME, sig=i, link=2, count=1)
    assert r.cursor == 11                 # counts ALL writes ever
    cur, recs = r.snapshot()
    assert cur == 11 and len(recs) == 8   # ring keeps the newest depth
    evs = [tev.decode(x, ["a", "b", "c"]) for x in recs]
    assert [e["sig"] for e in evs] == list(range(3, 11))  # oldest-first
    assert evs[0]["link"] == "c" and evs[0]["ev"] == "consume"
    # a second reader attached by offset sees the same history
    r2 = TraceRing(wksp, r.off, 8)
    assert r2.snapshot()[0] == 11


def test_ring_rejects_non_pow2_depth(wksp):
    with pytest.raises(ValueError, match="power of two"):
        TraceRing.create(wksp, 100)


def test_writer_samples_frag_events_records_all_lifecycle(wksp):
    r = TraceRing.create(wksp, 64)
    tw = TraceWriter(r, sample=4, links={"x": 0})
    for i in range(16):
        tw.frag(tev.EV_CONSUME, sig=i, link=tw.link_id("x"))
    assert r.cursor == 4                  # every 4th frag event
    tw.event(tev.EV_BOOT)                 # lifecycle: always recorded
    tw.event(tev.EV_CPU_FALLBACK)
    assert r.cursor == 6
    _, recs = r.snapshot()
    sigs = [tev.decode(x)["sig"] for x in recs[:4]]
    assert sigs == [3, 7, 11, 15]


def test_append_batch_one_cursor_bump_and_wrap_accounting(wksp):
    """Vectorized append: the whole batch lands under one cursor bump;
    oversized batches keep only the newest `depth` records but the
    cursor still counts every one (history-loss accounting)."""
    r = TraceRing.create(wksp, 8)
    r.append_batch(500, tev.EV_PUBLISH, list(range(5)), link=1)
    assert r.cursor == 5
    cur, recs = r.snapshot()
    assert [tev.decode(x)["sig"] for x in recs] == [0, 1, 2, 3, 4]
    # batch larger than depth: newest 8 survive, cursor counts all 20
    r.append_batch(501, tev.EV_PUBLISH, list(range(100, 120)))
    assert r.cursor == 25
    _, recs = r.snapshot()
    assert [tev.decode(x)["sig"] for x in recs] == list(range(112, 120))
    r.append_batch(502, tev.EV_PUBLISH, [])          # empty: no-op
    assert r.cursor == 25


def test_frag_batch_matches_sequential_sampling_stream(wksp):
    """frag_batch is n sequential frag() calls: same records selected
    from the running frag count regardless of batch boundaries."""
    ra = TraceRing.create(wksp, 64)
    rb = TraceRing.create(wksp, 64)
    ta = TraceWriter(ra, sample=3, links={"x": 0})
    tb = TraceWriter(rb, sample=3, links={"x": 0})
    sigs = list(range(21))
    for s in sigs:
        ta.frag(tev.EV_CONSUME, sig=s, link=0)
    for lo, hi in ((0, 7), (7, 12), (12, 12), (12, 21)):
        tb.frag_batch(tev.EV_CONSUME, sigs[lo:hi], link=0)
    assert ra.cursor == rb.cursor == 7            # every 3rd of 21
    got_a = [tev.decode(x)["sig"] for x in ra.snapshot()[1]]
    got_b = [tev.decode(x)["sig"] for x in rb.snapshot()[1]]
    assert got_a == got_b == [2, 5, 8, 11, 14, 17, 20]
    # sample=1 fast path records everything
    r1 = TraceRing.create(wksp, 64)
    t1 = TraceWriter(r1, sample=1, links={"x": 0})
    t1.frag_batch(tev.EV_CONSUME, sigs[:5], link=0)
    assert r1.cursor == 5


def test_span_records_end_ts_and_duration(wksp):
    from firedancer_tpu.utils.tempo import monotonic_ns
    r = TraceRing.create(wksp, 8)
    tw = TraceWriter(r)
    t0 = monotonic_ns()
    time.sleep(0.002)
    tw.span(tev.EV_WAIT, t0)
    e = tev.decode(r.snapshot()[1][0])
    assert e["ev"] == "wait" and e["arg"] >= 1_500_000
    assert e["ts"] >= t0 + e["arg"]


def test_shared_clock_is_the_heartbeat_clock():
    """Satellite contract: traces and watchdog staleness share ONE
    monotonic-ns source (utils/tempo.monotonic_ns == the native
    fdtpu_ticks that stamps cnc heartbeats)."""
    from firedancer_tpu.runtime.tango import lib
    from firedancer_tpu.utils.tempo import monotonic_ns
    a = lib.fdtpu_ticks()
    b = monotonic_ns()
    c = lib.fdtpu_ticks()
    assert a <= b <= c
    from firedancer_tpu.disco import topo as topo_mod
    assert abs(topo_mod.now_ticks() - monotonic_ns()) < 1e9
    # the stem stamps wait-end records with time.perf_counter_ns
    # directly (disco/stem.py) — pin that it shares the CLOCK_MONOTONIC
    # epoch with the heartbeat/trace clock on this platform
    assert abs(time.perf_counter_ns() - monotonic_ns()) < 1e9


# -- config schema ----------------------------------------------------------

def test_normalize_trace_defaults_and_validation():
    assert normalize_trace(None) == TRACE_DEFAULTS
    assert normalize_trace(None)["enable"] is False   # off by default
    full = normalize_trace({"enable": True, "depth": 64, "sample": 8,
                            "tiles": ["a"]})
    assert full == {"enable": True, "depth": 64, "sample": 8,
                    "tiles": ["a"]}
    with pytest.raises(ValueError, match="did you mean 'depth'"):
        normalize_trace({"dept": 64})
    with pytest.raises(ValueError, match="power of two"):
        normalize_trace({"depth": 100})
    with pytest.raises(ValueError, match="sample"):
        normalize_trace({"sample": 0})
    with pytest.raises(ValueError, match="list of tile names"):
        normalize_trace({"tiles": "verify"})
    with pytest.raises(ValueError, match="unknown trace key"):
        normalize_trace({"tiles": ["a"]}, per_tile=True)  # no allowlist
    with pytest.raises(ValueError, match="table"):
        normalize_trace([1, 2])


def test_effective_trace_resolution():
    topo = normalize_trace({"enable": True, "depth": 256,
                            "tiles": ["a"]})
    assert effective_trace(topo, "a", {}) == {"depth": 256, "sample": 1}
    assert effective_trace(topo, "b", {}) is None       # not allowlisted
    # per-tile override wins in both directions
    assert effective_trace(topo, "a", {"enable": False}) is None
    assert effective_trace(topo, "b", {"enable": True,
                                       "depth": 64, "sample": 4}) \
        == {"depth": 64, "sample": 4}


def test_registry_mirrors_trace_keys():
    """fdlint's key registry and the trace schema must not drift."""
    from firedancer_tpu.lint import registry as reg
    assert set(reg.TRACE_SECTION_KEYS) == set(TRACE_DEFAULTS)
    assert set(reg.TILE_TRACE_KEYS) == set(TILE_TRACE_KEYS)
    assert "trace" in reg.COMMON_KEYS


def _fe(ts, etype, sig, link):
    return {"ts": ts, "ev": tev.NAMES[etype], "etype": etype,
            "sig": sig, "arg": 0, "link": link, "count": 0}


def test_lineage_sig_zero_and_per_hop_latency():
    """sig=0 is a real lineage key (synth sigs start at 0), and the
    summary's per-link latency is each hop's own queue wait (publish ->
    consume, from the link telemetry block's histogram), not the
    cumulative time from the chain's origin."""
    from firedancer_tpu.disco.metrics import HistAccum
    evs = {
        "a": [_fe(100_000, tev.EV_PUBLISH, 0, "a_b")],
        "b": [_fe(150_000, tev.EV_CONSUME, 0, "a_b"),
              _fe(160_000, tev.EV_PUBLISH, 0, "b_c")],
        "c": [_fe(200_000, tev.EV_CONSUME, 0, "b_c")],
    }
    chains = lineage(evs)
    assert 0 in chains and len(chains[0]) == 4

    def hop(*waits_ns):
        h = HistAccum()
        for w in waits_ns:
            h.add(w)
        return {"producer": None, "pub": 0, "pub_bytes": 0,
                "backpressure": 0,
                "consumers": {"c": {"consumed": len(waits_ns),
                                    "bytes": 0, "overruns": 0,
                                    "hist": {"count": h.count,
                                             "sum_ns": h.sum_ns,
                                             "buckets": h.buckets}}}}
    text = summary(evs, links={"b_c": hop(40_000, 40_000, 44_000),
                               "a_b": hop()})
    row = next(ln for ln in text.splitlines() if ln.startswith("b_c"))
    # the b_c hop's own waits (mean 41.3us; both quantiles in the
    # [32.768, 65.536) us bucket), NOT 100us from the origin publish
    assert row.split()[1:] == ["c", "3", "41.3", "65.5", "65.5"]
    assert "queue wait (publish -> consume)" in text
    assert not any(ln.startswith("a_b") for ln in text.splitlines())
    doc = to_chrome(evs)
    flows = [e for e in doc["traceEvents"] if e.get("id") == "0x0"]
    assert any(e["ph"] == "s" for e in flows)
    assert any(e["ph"] == "f" for e in flows)


def test_chaos_action_ids_mirror_chaos_harness():
    """Every chaos action the harness can fire has a trace id, so a
    dumped black box always names the exact injected fault."""
    from firedancer_tpu.utils.chaos import ACTIONS
    assert set(tev.CHAOS_ACTION_IDS) == set(ACTIONS)
    assert all(tev.CHAOS_ACTION_NAMES[i] == a
               for a, i in tev.CHAOS_ACTION_IDS.items())


def test_config_toml_trace_section_roundtrip(tmp_path):
    """[trace] flows TOML -> load_config -> build_topology -> Topology;
    an unknown key fails at config load with a did-you-mean."""
    from firedancer_tpu.app.config import build_topology, load_config
    p = tmp_path / "t.toml"
    p.write_text("""
[trace]
enable = true
depth = 256

[[link]]
name = "a_b"
depth = 64
mtu = 256

[[tile]]
name = "a"
kind = "synth"
outs = ["a_b"]

[[tile]]
name = "b"
kind = "sink"
ins = ["a_b"]

[tile.trace]
sample = 4
""")
    cfg = load_config(str(p))
    topo = build_topology(cfg, name=f"trc{os.getpid()}")
    assert topo.trace == {"enable": True, "depth": 256}
    assert topo.tiles["b"].args["trace"] == {"sample": 4}
    bad = tmp_path / "bad.toml"
    bad.write_text(p.read_text().replace("enable = true",
                                         "enabled = true"))
    with pytest.raises(ValueError, match="did you mean 'enable'"):
        build_topology(load_config(str(bad)))


# -- build-time carving + the zero-cost disabled path -----------------------

def _build(trace=None, tiles=None):
    from firedancer_tpu.disco import Topology
    topo = Topology(f"trb{os.getpid()}_{_build.n}", wksp_size=1 << 21,
                    trace=trace)
    _build.n += 1
    topo.link("a_b", depth=32, mtu=256)
    topo.tile("a", "synth", outs=["a_b"], count=8, unique=4,
              **(tiles or {}).get("a", {}))
    topo.tile("b", "sink", ins=["a_b"], **(tiles or {}).get("b", {}))
    return topo.build()


_build.n = 0


def test_build_carves_rings_only_when_enabled():
    plan = _build()                        # no [trace] section at all
    try:
        for tn in ("a", "b"):
            assert "trace_off" not in plan["tiles"][tn]
        assert plan["trace"]["enable"] is False
    finally:
        Workspace.unlink_name(plan["wksp"]["name"])
    plan = _build(trace={"enable": True, "depth": 128},
                  tiles={"a": {"trace": {"enable": False}}})
    try:
        assert "trace_off" not in plan["tiles"]["a"]   # opted out
        b = plan["tiles"]["b"]
        assert b["trace_depth"] == 128 and b["trace_sample"] == 1
        assert b["trace_off"] % 64 == 0
    finally:
        Workspace.unlink_name(plan["wksp"]["name"])
    with pytest.raises(ValueError, match="unknown tile"):
        _build(trace={"enable": True, "tiles": ["ghost"]})


def test_disabled_path_is_a_single_none_check():
    """Acceptance: tracing off (the default) leaves NO trace region in
    the plan, TileCtx.trace is None, and the stem's cached hook
    attribute is None — the hot loop's only tracing cost is that one
    attribute test (no allocation, no syscall, no ring)."""
    from firedancer_tpu.disco.stem import Stem
    from firedancer_tpu.disco.topo import TileCtx
    from firedancer_tpu.runtime import CNC_HALT
    plan = _build()
    try:
        ctx = TileCtx(plan, "b")
        try:
            assert ctx.trace is None

            class _Tile:
                def __init__(self):
                    self.polls = 0

                def poll_once(self):
                    self.polls += 1
                    return 0

            stem = Stem(ctx, _Tile(), idle_sleep_s=0)
            assert stem._trace is None        # the whole disabled path
            stem.run(max_iters=16)
            assert stem.tile.polls == 16
            assert ctx.cnc.state == CNC_HALT
            # and nothing anywhere in the plan points at a ring
            assert not any("trace_off" in s
                           for s in plan["tiles"].values())
        finally:
            ctx.close()
    finally:
        Workspace.unlink_name(plan["wksp"]["name"])


# -- the live acceptance drill ---------------------------------------------

N_TXNS = 12


@pytest.fixture(scope="module")
def traced_pipeline():
    """verify + sink (two tiles) over an external ingest ring; the
    test process IS the producer, so the frag lineage under test is
    exactly verify -> downstream consumer."""
    from firedancer_tpu.disco import Topology, TopologyRunner
    from firedancer_tpu.runtime import Ring
    from firedancer_tpu.tiles.synth import make_signed_txns
    txns = make_signed_txns(N_TXNS, seed=7)
    topo = (
        Topology(f"trl{os.getpid()}", wksp_size=1 << 23,
                 trace={"enable": True, "depth": 1024, "sample": 1})
        .link("in_verify", depth=64, mtu=1280, external=True)
        .link("verify_sink", depth=64, mtu=1280)
        .tcache("vtc", depth=512)
        .tile("verify", "verify", ins=["in_verify"],
              outs=["verify_sink"], batch=32, tcache="vtc")
        .tile("sink", "sink", ins=["verify_sink"])
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
        runner.wait_idle("sink", "rx", N_TXNS, timeout_s=180)
        time.sleep(0.3)                   # one housekeeping flush
        yield runner
    finally:
        runner.halt(join_timeout_s=10)
        runner.close()


def test_frag_lineage_appears_as_correlated_spans(traced_pipeline):
    """ACCEPTANCE: export Perfetto/Chrome JSON from the live topology
    and prove a single frag's lineage — published by verify, consumed
    downstream — appears as correlated events, by parsing the JSON."""
    runner = traced_pipeline
    evs = read_rings(runner.plan, runner.wksp)
    assert set(evs) == {"verify", "sink"}
    # raw-event view: each forwarded txn's dedup tag is a sig that
    # verify PUBLISHED and sink CONSUMED
    chains = lineage(evs)
    correlated = [
        sig for sig, chain in chains.items()
        if any(t == "verify" and n == "publish" for _, t, n, _ in chain)
        and any(t == "sink" and n == "consume" for _, t, n, _ in chain)]
    assert len(correlated) == N_TXNS
    for sig in correlated:                 # publish precedes consume
        names = [(t, n) for _, t, n, _ in chains[sig]]
        assert names.index(("verify", "publish")) \
            < names.index(("sink", "consume"))

    # JSON view (what Perfetto ingests): thread-named tiles, X spans,
    # and s/f flow arrows binding the two tiles through the sig id
    doc = json.loads(json.dumps(to_chrome(evs, runner.plan["topology"])))
    te = doc["traceEvents"]
    tids = {e["args"]["name"]: e["tid"] for e in te
            if e.get("name") == "thread_name"}
    assert set(tids) == {"verify", "sink"}
    sig = correlated[0]
    fid = f"{sig:#x}"
    starts = [e for e in te if e.get("ph") == "s" and e["id"] == fid]
    finishes = [e for e in te if e.get("ph") == "f" and e["id"] == fid]
    assert starts and finishes
    assert starts[0]["tid"] == tids["verify"]
    assert finishes[-1]["tid"] == tids["sink"]
    assert starts[0]["ts"] <= finishes[-1]["ts"]
    # the verify tile's stage spans (device dispatch and readback
    # among them) are present as complete events, named by stage
    span_names = {e["name"] for e in te if e.get("ph") == "X"
                  and e["tid"] == tids["verify"]}
    assert {"gather", "parse", "dedup", "assemble", "dispatch",
            "readback", "finalize", "deferred", "publish"} <= span_names


def test_cli_exports_live_and_post_mortem(traced_pipeline, tmp_path,
                                          capsys):
    """tools/fdtrace drains by topology name — live now, and the shm
    rings outlive the tile processes for post-mortem drains."""
    from firedancer_tpu.trace.cli import main as trace_main
    runner = traced_pipeline
    out = tmp_path / "trace.json"
    rc = trace_main([runner.plan["topology"], "--out", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["otherData"]["source"] == "fdtrace"
    assert any(e.get("ph") == "X" for e in doc["traceEvents"])
    text = capsys.readouterr().out
    assert "verify_sink" in text           # per-link latency table
    assert "tile" in text and "wait_ms" in text


def test_summary_attributes_wait_and_link_latency(traced_pipeline):
    from firedancer_tpu.disco.metrics import read_link_metrics
    runner = traced_pipeline
    evs = read_rings(runner.plan, runner.wksp)
    text = summary(evs, links=read_link_metrics(runner.wksp,
                                                runner.plan))
    assert "verify_sink" in text and "p99_us" in text
    # every frag the sink consumed was timed on the verify_sink hop
    row = next(ln for ln in text.splitlines()
               if ln.startswith("verify_sink"))
    assert row.split()[1:3] == ["sink", str(N_TXNS)]
    # the verify tile's stage time is broken out per stage
    assert "verify stages (ms):" in text and "dispatch=" in text
    # the idle sink accumulated wait spans; verify did device work
    assert "sink" in text and "verify" in text


def test_monitor_snapshot_surfaces_trace_cursor(traced_pipeline):
    from firedancer_tpu.disco.monitor import snapshot
    runner = traced_pipeline
    snap = snapshot(runner.plan, runner.wksp)
    assert snap["verify"]["trace"]["events"] > 0
    assert snap["verify"]["trace"]["depth"] == 1024
