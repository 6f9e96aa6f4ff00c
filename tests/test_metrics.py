"""Metrics subsystem tests: named-slot ABI, wait/work/tpu histograms,
per-link telemetry, SLO engine, prometheus exposition + metric tile
endpoints (ref: src/disco/metrics/fd_metrics.h:6-40, fd_prometheus.c,
fd_metric_tile.c; histograms src/util/hist/fd_histf.h).

The exposition is validated by a STRICT text-format parser below —
every emitted line must parse, every sample's family must be TYPE-
declared first, labels must unescape, and histograms must be
cumulative-monotone with +Inf == _count (including the raced-flush
clamp in metrics.py::_render_hist).
"""
import json
import os
import re
import time
import urllib.request

import pytest

from firedancer_tpu.disco import Topology, TopologyRunner
from firedancer_tpu.disco.metrics import (
    HIST_U64, NBUCKETS, HistAccum, bucket_of, quantile_ns, read_hists,
    read_link_metrics, render_prometheus,
)
from firedancer_tpu.disco.monitor import attach, snapshot

# the histogram/quantile/parser/SLO unit tests below run in tier-1;
# only the live-topology pipeline tests are slow-marked (the fixture
# spawns processes and compiles the verify jit)
slow = pytest.mark.slow
slo = pytest.mark.slo


# ---------------------------------------------------------------------------
# strict prometheus text-format parser (the test-side contract)
# ---------------------------------------------------------------------------

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"          # family
    r"(?:\{(.*)\})?"                        # optional label block
    r" (-?(?:[0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?|\+?Inf|NaN))$")
_VALID_TYPES = {"counter", "gauge", "histogram", "summary", "untyped"}


def _parse_labels(s: str) -> dict:
    """Parse `k="v",...` with exposition-format escapes; assert on any
    malformed label (unterminated string, bad escape, dup key)."""
    out: dict = {}
    i = 0
    while i < len(s):
        m = re.match(r'([a-zA-Z_][a-zA-Z0-9_]*)="', s[i:])
        assert m, f"bad label at ...{s[i:]!r}"
        key = m.group(1)
        i += m.end()
        val = []
        while True:
            assert i < len(s), f"unterminated label value for {key}"
            ch = s[i]
            if ch == "\\":
                assert i + 1 < len(s) and s[i + 1] in '\\"n', \
                    f"bad escape in label {key}"
                val.append({"\\": "\\", '"': '"', "n": "\n"}[s[i + 1]])
                i += 2
                continue
            if ch == '"':
                i += 1
                break
            assert ch != "\n", f"raw newline in label {key}"
            val.append(ch)
            i += 1
        assert key not in out, f"duplicate label {key}"
        out[key] = "".join(val)
        if i < len(s):
            assert s[i] == ",", f"expected ',' at ...{s[i:]!r}"
            i += 1
    return out


def parse_prometheus(text: str):
    """Validate a whole exposition; returns (types, samples) where
    samples = [(family, labels, value)]. Histogram families are
    checked for le-ordering, cumulative monotonicity, +Inf presence,
    _count == +Inf and _sum presence."""
    assert text.endswith("\n"), "exposition must end with a newline"
    types: dict[str, str] = {}
    samples: list[tuple[str, dict, float]] = []
    for lineno, line in enumerate(text.splitlines(), 1):
        assert line == line.rstrip(), f"line {lineno}: trailing space"
        assert line, f"line {lineno}: blank line"
        if line.startswith("# TYPE "):
            parts = line.split(" ")
            assert len(parts) == 4, f"line {lineno}: bad TYPE"
            _, _, name, typ = parts
            assert _NAME_RE.match(name), f"line {lineno}: bad name"
            assert typ in _VALID_TYPES, f"line {lineno}: bad type"
            assert name not in types, f"line {lineno}: dup TYPE {name}"
            types[name] = typ
            continue
        if line.startswith("#"):
            continue                     # HELP/comment
        m = _SAMPLE_RE.match(line)
        assert m, f"line {lineno}: unparseable sample {line!r}"
        name, labels_s, value_s = m.groups()
        labels = _parse_labels(labels_s) if labels_s else {}
        family = name
        for suffix in ("_bucket", "_sum", "_count"):
            base = name[: -len(suffix)] if name.endswith(suffix) else None
            if base and types.get(base) == "histogram":
                family = base
        assert family in types, \
            f"line {lineno}: sample {name!r} before its TYPE"
        if types[family] == "histogram" and name.endswith("_bucket"):
            assert "le" in labels, f"line {lineno}: bucket without le"
        value = float("inf") if value_s in ("+Inf", "Inf") \
            else float(value_s)
        samples.append((name, labels, value))
    # histogram structural checks
    hist_series: dict[tuple, list] = {}
    sums, counts = {}, {}
    for name, labels, value in samples:
        for suffix, store in (("_sum", sums), ("_count", counts)):
            base = name[: -len(suffix)] if name.endswith(suffix) else None
            if base and types.get(base) == "histogram":
                store[(base, tuple(sorted(labels.items())))] = value
        base = name[:-7] if name.endswith("_bucket") else None
        if base and types.get(base) == "histogram":
            key = (base, tuple(sorted((k, v) for k, v in labels.items()
                                      if k != "le")))
            le = float("inf") if labels["le"] == "+Inf" \
                else float(labels["le"])
            hist_series.setdefault(key, []).append((le, value))
    for (base, lab), buckets in hist_series.items():
        les = [le for le, _ in buckets]
        assert les == sorted(les), f"{base}{lab}: le out of order"
        cum = [v for _, v in buckets]
        assert cum == sorted(cum), f"{base}{lab}: non-monotone buckets"
        assert les[-1] == float("inf"), f"{base}{lab}: no +Inf bucket"
        assert (base, lab) in counts, f"{base}{lab}: missing _count"
        assert (base, lab) in sums, f"{base}{lab}: missing _sum"
        assert counts[(base, lab)] == cum[-1], \
            f"{base}{lab}: _count != +Inf bucket"
    return types, samples


def test_bucket_of_log2():
    assert bucket_of(0) == 0
    assert bucket_of(1) == 0
    assert bucket_of(2) == 1
    assert bucket_of(3) == 1
    assert bucket_of(1024) == 10
    assert bucket_of(1 << 60) == NBUCKETS - 1


def test_quantile_upper_bound():
    h = HistAccum()
    for ns in [10, 10, 10, 10_000]:
        h.add(ns)
    d = {"count": h.count, "sum_ns": h.sum_ns, "buckets": h.buckets}
    assert quantile_ns(d, 0.5) == 16          # 2^(3+1): bucket of 10
    assert quantile_ns(d, 0.99) == 16384      # 2^(13+1): bucket of 10_000
    assert quantile_ns({"count": 0, "sum_ns": 0,
                        "buckets": [0] * NBUCKETS}, 0.5) == 0


def test_quantile_edges_empty_and_q0_q1():
    """Histogram edge pins: an EMPTY histogram is 0 at every q; q=0.0
    is the minimum sample's bucket bound — NOT bucket 0's bound when
    bucket 0 is empty — and q=1.0 is the maximum sample's bound."""
    empty = {"count": 0, "sum_ns": 0, "buckets": [0] * NBUCKETS}
    assert quantile_ns(empty, 0.0) == 0
    assert quantile_ns(empty, 1.0) == 0
    h = HistAccum()
    for ns in [10, 10_000]:
        h.add(ns)
    d = {"count": h.count, "sum_ns": h.sum_ns, "buckets": h.buckets}
    assert quantile_ns(d, 0.0) == 16          # min sample's bucket (10)
    assert quantile_ns(d, 1.0) == 16384       # max sample's bucket (10k)
    # a single sample far from bucket 0: q=0 must still find it
    h1 = HistAccum()
    h1.add(1 << 20)
    d1 = {"count": h1.count, "sum_ns": h1.sum_ns, "buckets": h1.buckets}
    assert quantile_ns(d1, 0.0) == quantile_ns(d1, 1.0) == 1 << 21


def test_flush_into_is_idempotent():
    """flush_into overwrites (cumulative counts, single writer): a
    second flush with no new samples must add NOTHING — a += bug here
    would double every counter each housekeeping pass."""
    import numpy as np
    h = HistAccum()
    for ns in [5, 50, 500]:
        h.add(ns)
    view = np.zeros(HIST_U64, np.uint64)
    h.flush_into(view)
    first = view.copy()
    h.flush_into(view)                    # no adds in between
    assert (view == first).all()
    assert int(view[0]) == 3 and int(view[1]) == 555
    assert int(view[2:].sum()) == 3
    h.add(7)                              # and a real add still lands
    h.flush_into(view)
    assert int(view[0]) == 4 and int(view[2:].sum()) == 4


# ---------------------------------------------------------------------------
# parser self-tests (a validator that cannot reject is no validator)
# ---------------------------------------------------------------------------

@slo
def test_parser_rejects_malformed_expositions():
    with pytest.raises(AssertionError, match="before its TYPE"):
        parse_prometheus('orphan{a="b"} 1\n')
    with pytest.raises(AssertionError, match="bad label"):
        parse_prometheus("# TYPE x counter\nx{a=b} 1\n")
    with pytest.raises(AssertionError, match="newline"):
        parse_prometheus("# TYPE x counter\nx 1")
    with pytest.raises(AssertionError, match="non-monotone"):
        parse_prometheus(
            "# TYPE h histogram\n"
            'h_bucket{le="1"} 5\nh_bucket{le="+Inf"} 3\n'
            "h_sum 1\nh_count 3\n")
    with pytest.raises(AssertionError, match="no \\+Inf"):
        parse_prometheus(
            "# TYPE h histogram\n"
            'h_bucket{le="1"} 5\nh_sum 1\nh_count 5\n')
    with pytest.raises(AssertionError, match="_count"):
        parse_prometheus(
            "# TYPE h histogram\n"
            'h_bucket{le="+Inf"} 5\nh_sum 1\nh_count 4\n')
    with pytest.raises(AssertionError, match="bad escape"):
        parse_prometheus('# TYPE x counter\nx{a="\\q"} 1\n')
    # the good shape parses
    types, samples = parse_prometheus(
        "# TYPE h histogram\n"
        'h_bucket{t="a\\"b",le="1"} 2\nh_bucket{t="a\\"b",le="+Inf"} 5\n'
        'h_sum{t="a\\"b"} 1.5\nh_count{t="a\\"b"} 5\n')
    assert types == {"h": "histogram"}
    assert samples[0][1]["t"] == 'a"b'   # label unescaping


# ---------------------------------------------------------------------------
# in-process drills: link-telemetry ABI + exposition, no process spawn
# ---------------------------------------------------------------------------

def _mk_inline(plan, tile_name):
    """Construct a tile adapter + stem inside THIS process (the tier-1
    way to exercise the stem's telemetry feed without multi-process
    overhead); callers alternate bounded stem.run(max_iters=...)."""
    from firedancer_tpu.disco.stem import Stem
    from firedancer_tpu.disco.tiles import REGISTRY
    from firedancer_tpu.disco.topo import TileCtx
    ctx = TileCtx(plan, tile_name)
    adapter = REGISTRY[plan["tiles"][tile_name]["kind"]](
        ctx, plan["tiles"][tile_name]["args"])
    return ctx, adapter, Stem(ctx, adapter)


@slo
def test_link_telemetry_abi_end_to_end_inline():
    """synth -> sink through real rings + stems, single process: the
    per-link blocks must agree with the tile-side truth — published ==
    consumed (lossless run), byte counts equal on both sides of the
    hop, the consume-latency histogram populated, and the rendered
    fdtpu_link_* series parser-clean."""
    from firedancer_tpu.runtime import Workspace
    topo = (
        Topology(f"lm{os.getpid()}", wksp_size=1 << 22)
        .link("a_b", depth=64, mtu=256)
        .tile("a", "synth", outs=["a_b"], count=96, unique=8, burst=8)
        .tile("b", "sink", ins=["a_b"])
    )
    plan = topo.build()
    w = Workspace(plan["wksp"]["name"], plan["wksp"]["size"],
                  create=False)
    try:
        ctx_a, _, stem_a = _mk_inline(plan, "a")
        ctx_b, _, stem_b = _mk_inline(plan, "b")
        for _ in range(6):               # alternate producer/consumer
            stem_a.run(max_iters=40)     # (credit-gated: synth blocks
            stem_b.run(max_iters=40)     #  at depth until sink drains)
        links = read_link_metrics(w, plan)
        rec = links["a_b"]
        assert rec["producer"] == "a"
        assert rec["pub"] == 96
        cons = rec["consumers"]["b"]
        assert cons["consumed"] == 96
        assert cons["bytes"] == rec["pub_bytes"] > 0
        assert cons["overruns"] == 0
        assert cons["hist"]["count"] > 0
        assert sum(cons["hist"]["buckets"]) == cons["hist"]["count"]
        # the rendered per-link series are parser-clean and carry the
        # link/producer/consumer labels
        text = render_prometheus(plan, w)
        types, samples = parse_prometheus(text)
        assert types["fdtpu_link_consume_seconds"] == "histogram"
        by_name = {}
        for name, labels, value in samples:
            by_name.setdefault(name, []).append((labels, value))
        (labels, value), = by_name["fdtpu_link_pub"]
        assert labels["link"] == "a_b" and labels["producer"] == "a"
        assert value == 96
        (labels, value), = by_name["fdtpu_link_consumed"]
        assert labels["consumer"] == "b" and value == 96
        (labels, value), = by_name["fdtpu_link_lag"]
        assert value == 0
        # the monitor surfaces the same telemetry (links table + the
        # --json document shape)
        from firedancer_tpu.disco.monitor import (format_links,
                                                  full_snapshot)
        doc = full_snapshot(plan, w)
        assert doc["links"]["a_b"]["consumers"]["b"]["consumed"] == 96
        table = format_links(doc["links"])
        assert "a_b" in table and "p99us" in table
        hist_count = cons["hist"]["count"]
        ctx_a.close()
        ctx_b.close()
        # restart continuity: a respawned tile (fresh TileCtx + stem,
        # exactly what the supervisor spawns) must RESUME the link's
        # cumulative series from shm, not rewind it — a zeroed flush
        # would turn everything consumed before the restart into
        # per-hop loss
        ctx_a2, _, stem_a2 = _mk_inline(plan, "a")
        ctx_b2, _, stem_b2 = _mk_inline(plan, "b")
        assert ctx_b2.in_rings["a_b"].m_consumed == 96
        assert ctx_a2.out_rings["a_b"].m_pub == 96
        stem_a2._flush_metrics()
        stem_b2._flush_metrics()
        rec = read_link_metrics(w, plan)["a_b"]
        assert rec["pub"] == 96
        assert rec["consumers"]["b"]["consumed"] == 96
        assert rec["consumers"]["b"]["hist"]["count"] == hist_count
        ctx_a2.close()
        ctx_b2.close()
    finally:
        w.close()
        Workspace.unlink_name(plan["wksp"]["name"])


@slo
def test_old_plan_hist_region_not_overread():
    """Version skew: a plan carved by a pre-tpu build holds a 2-kind
    hist region (and records no hist_u64 key). Readers and the stem
    must size their views from the PLAN, not the current
    HIST_REGION_U64 — reading 3 kinds there would decode the adjacent
    allocation as the tpu histogram (and a stem would flush over it)."""
    from firedancer_tpu.runtime import Workspace
    topo = (
        Topology(f"hv{os.getpid()}", wksp_size=1 << 22)
        .link("a_b", depth=64, mtu=256)
        .tile("a", "synth", outs=["a_b"], count=8, unique=8)
        .tile("b", "sink", ins=["a_b"])
    )
    plan = topo.build()
    w = Workspace(plan["wksp"]["name"], plan["wksp"]["size"],
                  create=False)
    try:
        # current plans record the region length
        assert plan["tiles"]["b"]["hist_u64"] == 5 * HIST_U64
        # simulate attaching to an old topology: 2-kind region, no key
        old = json.loads(json.dumps(plan))
        del old["tiles"]["b"]["hist_u64"]
        hists = read_hists(w, old, "b")
        assert sorted(hists) == ["wait", "work"]     # no phantom tpu
        ctx, _, stem = _mk_inline(old, "b")
        assert len(ctx.hist_view()) == 2 * HIST_U64
        # poison the u64 right after the old-sized region; a flush
        # through the old plan must leave it untouched
        import numpy as np
        sentinel_off = old["tiles"]["b"]["hist_off"] + 2 * HIST_U64 * 8
        view = w.view(sentinel_off, 8).view(np.uint64)
        view[0] = 0xDEADBEEF
        stem._hists["work"].add(100)
        stem._flush_metrics()
        assert int(view[0]) == 0xDEADBEEF
        ctx.close()
    finally:
        w.close()
        Workspace.unlink_name(plan["wksp"]["name"])


@slo
def test_render_clamps_raced_flush_and_escapes_labels():
    """A reader racing a flush can see count written ahead of buckets
    (metrics.py:flush order); the renderer must clamp +Inf/_count to
    stay monotone — and tile names with quotes/backslashes must
    escape. The whole document is run through the strict parser."""
    from firedancer_tpu.runtime import Workspace
    import numpy as np
    topo = (
        Topology(f"esc{os.getpid()}", wksp_size=1 << 22)
        .link("a_b", depth=64, mtu=256)
        .tile("a", "synth", outs=["a_b"], count=8)
        .tile('we"ird\\tile', "sink", ins=["a_b"])
    )
    plan = topo.build()
    w = Workspace(plan["wksp"]["name"], plan["wksp"]["size"],
                  create=False)
    try:
        # simulate the raced flush: count > sum(buckets) in shm
        off = plan["tiles"]['we"ird\\tile']["hist_off"]
        hv = w.view(off, HIST_U64 * 8).view(np.uint64)
        hv[2] = 3                        # work hist handled separately
        hv[0] = 7                        # count ahead of buckets
        hv[1] = 1000
        text = render_prometheus(plan, w)
        types, samples = parse_prometheus(text)   # must not raise
        waits = [(labels, v) for name, labels, v in samples
                 if name == "fdtpu_poll_wait_seconds_count"
                 and labels["tile"] == 'we"ird\\tile']
        assert waits and waits[0][1] == 7         # clamped to count
    finally:
        w.close()
        Workspace.unlink_name(plan["wksp"]["name"])


# ---------------------------------------------------------------------------
# SLO engine units (schema, grammar, burn windows)
# ---------------------------------------------------------------------------

@slo
def test_slo_schema_and_grammar():
    from firedancer_tpu.disco.slo import (SLO_DEFAULTS, TARGET_KEYS,
                                          normalize_slo, parse_expr)
    from firedancer_tpu.lint import registry as reg
    # registry mirror stays honest (the fdlint side of the schema)
    assert set(reg.SLO_SECTION_KEYS) == set(SLO_DEFAULTS)
    assert set(reg.SLO_TARGET_KEYS) == set(TARGET_KEYS)
    norm = normalize_slo(None)
    assert norm["target"] == [] and norm["fast_window_s"] > 0
    p = parse_expr("verify.work p99 < 500us")
    assert p == {"kind": "hist", "tile": "verify", "hist": "work",
                 "agg": "p99", "op": "<", "threshold": 500_000.0}
    p = parse_expr("sink.rx rate > 100/s")
    assert p["kind"] == "metric" and p["agg"] == "rate" \
        and p["threshold"] == 100.0
    p = parse_expr("link.a_b.backpressure rate < 1/s")
    assert p["kind"] == "link" and p["counter"] == "backpressure"
    with pytest.raises(ValueError, match="did you mean 'fast_window_s'"):
        normalize_slo({"fast_windw_s": 1})
    with pytest.raises(ValueError, match="unknown aggregation"):
        parse_expr("verify.work p98 < 1ms")
    with pytest.raises(ValueError, match="unknown operator"):
        parse_expr("verify.work p99 != 1ms")
    with pytest.raises(ValueError, match="duration unit"):
        parse_expr("verify.work p99 < 500")
    with pytest.raises(ValueError, match="rate"):
        parse_expr("sink.rx value > 100/s")
    with pytest.raises(ValueError, match="duplicate slo target"):
        normalize_slo({"target": [
            {"name": "x", "expr": "a.b > 1"},
            {"name": "x", "expr": "a.b > 2"}]})
    with pytest.raises(ValueError, match="burn_fast"):
        normalize_slo({"burn_fast": 1.5})
    # per-target overrides pass the same range gates as the section
    # (an unreachable burn would make the objective silently dead)
    with pytest.raises(ValueError, match="burn_fast"):
        normalize_slo({"target": [
            {"name": "x", "expr": "a.b > 1", "burn_fast": 1.5}]})
    with pytest.raises(ValueError, match="fast_window_s"):
        normalize_slo({"target": [
            {"name": "x", "expr": "a.b > 1", "fast_window_s": -1}]})
    # sample history is pruned to the slow window: a fast window past
    # it could never be covered, killing the acute breach path
    with pytest.raises(ValueError, match="<= slow_window_s"):
        normalize_slo({"fast_window_s": 120.0, "slow_window_s": 60.0})
    with pytest.raises(ValueError, match="<= slow_window_s"):
        normalize_slo({"target": [
            {"name": "x", "expr": "a.b > 1", "fast_window_s": 90.0}]})


@slo
def test_slo_burn_windows_with_fake_clock():
    """Burn-rate semantics against a scripted value source: no breach
    before the fast window is COVERED, breach once the window is all
    bad, clear only after the fast window is clean and the slow
    window's bad fraction drops under burn_slow."""
    from firedancer_tpu.disco.slo import SloEngine, normalize_slo
    cfg = normalize_slo({
        "fast_window_s": 1.0, "slow_window_s": 4.0,
        "burn_fast": 1.0, "burn_slow": 0.5,
        "target": [{"name": "lat", "expr": "v.work p99 < 1ms"}]})
    plan = {"topology": "fake", "tiles": {"v": {}}, "links": {},
            "slo": cfg}
    clock_now = [0.0]
    eng = SloEngine(plan, None, clock=lambda: clock_now[0], dump=False)
    values = [2e6]                       # scripted p99 values (ns)
    eng._read = lambda st, now: float(values[0])
    evs = []
    for _ in range(9):                   # 0.0 .. 1.2s, all bad
        evs += eng.sample()
        clock_now[0] += 0.15
    assert eng.breached == 1
    assert [e["kind"] for e in evs] == ["breach"]
    assert eng.total_breaches == 1
    # recovery: good values — fast window empties of bad samples but
    # the slow window still carries them until they age out
    values[0] = 5e5
    for _ in range(8):                   # +1.2s of good
        evs += eng.sample()
        clock_now[0] += 0.15
    assert eng.breached == 1             # slow window still >= 0.5 bad
    for _ in range(12):                  # bad samples age out of 4s
        evs += eng.sample()
        clock_now[0] += 0.15
    assert eng.breached == 0
    assert [e["kind"] for e in evs] == ["breach", "clear"]


@slo
def test_slo_fast_path_alive_when_windows_equal():
    """fast_window_s == slow_window_s passes validation, so the acute
    path must still fire there: coverage comes from the PRE-prune
    oldest sample — the post-prune oldest is >= now - slow_w by
    construction, which once left the fast path silently dead and the
    objective unmonitored at burn_fast < 1 <= burn_slow."""
    from firedancer_tpu.disco.slo import SloEngine, normalize_slo
    cfg = normalize_slo({
        "fast_window_s": 2.0, "slow_window_s": 2.0,
        "burn_fast": 0.5, "burn_slow": 1.0,
        "target": [{"name": "lat", "expr": "v.work p99 < 1ms"}]})
    plan = {"topology": "fake", "tiles": {"v": {}}, "links": {},
            "slo": cfg}
    clock_now = [0.0]
    eng = SloEngine(plan, None, clock=lambda: clock_now[0], dump=False)
    values = [2e6, 5e5]                  # alternate bad / good: 50%
    eng._read = lambda st, now: float(values[eng.evals % 2])
    for _ in range(40):                  # 5.2s of 50%-bad samples
        eng.sample()
        clock_now[0] += 0.13
    assert eng.breached == 1 and eng.total_breaches >= 1


# ---------------------------------------------------------------------------
# live acceptance: chaos stall -> backpressure ticks -> SLO breach ->
# EV_SLO in the trace ring, /metrics parser-clean (tier-1: no jax)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
@slo
def test_stall_fseq_drives_backpressure_and_slo_breach():
    """The fdmetrics-v2 acceptance drill on a live chaos topology: a
    stall_fseq fault on the sink freezes its fseq publication; the
    producer's publish path starts taking backpressure ticks on the
    link; the SLO engine's fast window flips slo_breach on the metric
    tile; the breach leaves an EV_SLO event in the metric tile's
    flight-recorder ring and a dump next to the supervisor black
    boxes; and GET /metrics stays parser-clean with the fdtpu_link_*
    series showing the damage."""
    from firedancer_tpu.disco.slo import slo_dump_path
    from firedancer_tpu.trace import read_rings
    topo = (
        Topology(f"slo{os.getpid()}", wksp_size=1 << 22,
                 trace={"enable": True, "depth": 1024, "sample": 1},
                 slo={"fast_window_s": 0.5, "slow_window_s": 10.0,
                      "target": [{
                          "name": "sink-bp",
                          "expr": "link.a_b.backpressure rate < 5/s"}]})
        .link("a_b", depth=32, mtu=256)
        .tile("a", "synth", outs=["a_b"], count=1_000_000, unique=16,
              burst=8)
        .tile("b", "sink", ins=["a_b"],
              chaos={"events": [{"action": "stall_fseq", "at_rx": 8}]})
        .tile("metric", "metric", port=0)
    )
    runner = TopologyRunner(topo.build()).start()
    try:
        runner.wait_running(timeout_s=540)
        deadline = time.time() + 60
        while time.time() < deadline:
            runner.check_failures()
            if runner.metrics("metric").get("slo_breach", 0) >= 1:
                break
            time.sleep(0.05)
        m = runner.metrics("metric")
        assert m["slo_breach"] == 1, m
        assert m["slo_breaches"] >= 1 and m["slo_evals"] > 0
        # the fault drove backpressure ticks on the affected link
        links = read_link_metrics(runner.wksp, runner.plan)
        assert links["a_b"]["backpressure"] > 0
        # EV_SLO is recoverable from the metric tile's trace ring
        evs = read_rings(runner.plan, runner.wksp)["metric"]
        slo_evs = [e for e in evs if e["ev"] == "slo"]
        assert slo_evs and slo_evs[0]["count"] == 0   # target index
        # breach dump landed next to the supervisor black boxes
        path = slo_dump_path(runner.plan["topology"], "sink-bp")
        with open(path) as f:
            dump = json.load(f)
        assert dump["target"] == "sink-bp" \
            and dump["expr"].startswith("link.a_b")
        os.unlink(path)                  # test hygiene (/dev/shm)
        # /metrics: parser-clean, link series present and nonzero
        port = runner.metrics("metric")["port"]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            body = r.read().decode()
        types, samples = parse_prometheus(body)
        bp = [v for name, labels, v in samples
              if name == "fdtpu_link_backpressure"
              and labels["link"] == "a_b"]
        assert bp and bp[0] > 0
        breach = [v for name, labels, v in samples
                  if name == "fdtpu_tile_gauge"
                  and labels.get("name") == "slo_breach"]
        assert breach == [1]
        # liveness roll-up stays healthy: a burning SLO is a service
        # problem, not a liveness one (every tile still heartbeats)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
            health = json.loads(r.read())
        assert r.status == 200 and health["ok"]
        assert health["slo_breached"] == ["sink-bp"]
        # monitor --json: one machine-readable document off the same
        # shm, attached by topology name alone
        import contextlib
        import io
        from firedancer_tpu.disco import monitor as mon
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = mon.main([runner.plan["topology"], "--json"])
        assert rc == 0
        doc = json.loads(buf.getvalue())
        assert doc["links"]["a_b"]["backpressure"] > 0
        assert doc["tiles"]["b"]["state"] == "run"
    finally:
        runner.halt(join_timeout_s=10)
        runner.close()


@pytest.fixture(scope="module")
def pipeline():
    topo = (
        Topology(f"tm{os.getpid()}", wksp_size=1 << 23)
        .link("s_k", depth=64, mtu=1280)
        .tile("synth", "synth", outs=["s_k"], count=32, unique=8, seed=9)
        .tile("sink", "sink", ins=["s_k"])
        .tile("metric", "metric", port=0)
    )
    plan = topo.build()
    runner = TopologyRunner(plan).start()
    try:
        runner.wait_running(timeout_s=120)
        runner.wait_idle("sink", "rx", 32, timeout_s=120)
        yield runner
    finally:
        runner.halt()
        runner.close()


@slow
def test_plan_carries_slot_names(pipeline):
    tiles = pipeline.plan["tiles"]
    assert tiles["synth"]["metrics_names"] == \
        ["tx", "backpressure", "attack_tx", "attack_drop"]
    assert tiles["sink"]["metrics_names"] == ["rx", "bytes", "overruns"]
    # readers resolve by plan names — values land under the right keys
    # (synth publishes its whole count in one poll; give its NEXT
    # housekeeping flush a moment to land in shm)
    deadline = time.time() + 30
    while time.time() < deadline \
            and pipeline.metrics("synth")["tx"] < 32:
        time.sleep(0.05)
    assert pipeline.metrics("synth")["tx"] == 32
    assert pipeline.metrics("sink")["rx"] == 32


@slow
def test_histograms_populate(pipeline):
    # one housekeeping flush after the traffic
    deadline = time.time() + 30
    while time.time() < deadline:
        h = read_hists(pipeline.wksp, pipeline.plan, "sink")
        if h and h["work"]["count"] > 0 and h["wait"]["count"] > 0:
            break
        time.sleep(0.05)
    assert h["work"]["count"] > 0, "sink did work but no work samples"
    assert h["wait"]["count"] > 0, "sink idled but no wait samples"
    assert h["work"]["sum_ns"] > 0
    assert sum(h["work"]["buckets"]) == h["work"]["count"]
    # monitor surfaces latency quantiles
    plan, wksp = attach(pipeline.plan["topology"])
    try:
        snap = snapshot(plan, wksp)
        lat = snap["sink"]["latency"]
        assert lat["work"]["count"] > 0
        assert lat["work"]["p99_us"] >= lat["work"]["p50_us"] > 0
    finally:
        wksp.close()


@slow
def test_prometheus_endpoint(pipeline):
    port = pipeline.metrics("metric")["port"]
    assert port > 0
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
        assert r.status == 200
        body = r.read().decode()
    assert '# TYPE fdtpu_tile_metric counter' in body
    assert 'tile="sink"' in body and 'name="rx"} 32' in body
    # histogram exposition: cumulative buckets, monotone, +Inf == count
    lines = [ln for ln in body.splitlines()
             if ln.startswith('fdtpu_poll_work_seconds_bucket{'
                              'topology') and 'tile="sink"' in ln]
    assert lines, body[:500]
    cum = [int(ln.rsplit(" ", 1)[1]) for ln in lines]
    assert cum == sorted(cum)
    count_ln = [ln for ln in body.splitlines()
                if ln.startswith("fdtpu_poll_work_seconds_count")
                and 'tile="sink"' in ln]
    assert int(count_ln[0].rsplit(" ", 1)[1]) == cum[-1]
    # scrape counter advanced
    deadline = time.time() + 15
    while time.time() < deadline:
        if pipeline.metrics("metric")["scrapes"] >= 1:
            break
        time.sleep(0.05)
    assert pipeline.metrics("metric")["scrapes"] >= 1


# ---------------------------------------------------------------------------
# e2e: the metric tile over a live synth -> verify -> sink topology
# (device telemetry + per-link series + healthz; slow: verify compile)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def verify_pipeline():
    n = 48
    topo = (
        Topology(f"vm{os.getpid()}", wksp_size=1 << 23,
                 trace={"enable": True, "depth": 1024, "sample": 1},
                 slo={"fast_window_s": 1.0, "target": [
                     {"name": "verify-latency",
                      "expr": "verify.work p99 < 30s"}]})
        .link("s_v", depth=128, mtu=1280)
        .link("v_k", depth=128, mtu=1280)
        .tcache("tc", depth=1024)
        .tile("synth", "synth", outs=["s_v"], count=n, unique=n,
              seed=3)
        .tile("verify", "verify", ins=["s_v"], outs=["v_k"],
              batch=16, tcache="tc")
        .tile("sink", "sink", ins=["v_k"])
        .tile("metric", "metric", port=0)
    )
    plan = topo.build()
    runner = TopologyRunner(plan).start()
    try:
        runner.wait_running(timeout_s=600)
        runner.wait_idle("sink", "rx", n, timeout_s=600)
        yield runner
    finally:
        runner.halt()
        runner.close()


@slow
@slo
def test_metric_tile_e2e_tpu_and_link_series(verify_pipeline):
    """GET /metrics on a live verify topology: parser-clean text with
    fdtpu_link_* per-link series (every hop, with per-hop loss) and
    fdtpu_tile_tpu_* device telemetry (dispatch/readback histogram +
    jit/memory/inflight gauges from the verify tile)."""
    runner = verify_pipeline
    # one housekeeping flush after the traffic so the tpu hist landed
    deadline = time.time() + 30
    while time.time() < deadline:
        h = read_hists(runner.wksp, runner.plan, "verify")
        if h and h["tpu"]["count"] > 0:
            break
        time.sleep(0.05)
    assert h["tpu"]["count"] > 0, "verify dispatched but no tpu samples"
    port = runner.metrics("metric")["port"]
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
        assert r.status == 200
        body = r.read().decode()
    types, samples = parse_prometheus(body)
    # device telemetry series (the fdtpu_tile_tpu_* family)
    assert types["fdtpu_tile_tpu_seconds"] == "histogram"
    assert types["fdtpu_tile_tpu_jit_compiles"] == "gauge"
    by = {}
    for name, labels, value in samples:
        by.setdefault(name, []).append((labels, value))
    tpu_counts = [v for labels, v in by["fdtpu_tile_tpu_seconds_count"]
                  if labels["tile"] == "verify"]
    assert tpu_counts and tpu_counts[0] > 0
    (labels, compiles), = by["fdtpu_tile_tpu_jit_compiles"]
    assert labels["tile"] == "verify" and compiles >= 1
    # per-link series cover both hops with zero loss
    pubs = {labels["link"]: v for labels, v in by["fdtpu_link_pub"]}
    assert pubs["s_v"] == 48 and pubs["v_k"] == 48
    lags = {labels["link"]: v for labels, v in by["fdtpu_link_lag"]}
    assert lags == {"s_v": 0, "v_k": 0}
    cons = {(labels["link"], labels["consumer"]): v
            for labels, v in by["fdtpu_link_consumed"]}
    assert cons[("s_v", "verify")] == 48 and cons[("v_k", "sink")] == 48


@slow
@slo
def test_metric_tile_healthz_and_summary(verify_pipeline):
    runner = verify_pipeline
    port = runner.metrics("metric")["port"]
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
        assert r.status == 200
        health = json.loads(r.read())
    assert health["ok"] and health["slo_breached"] == []
    assert set(health["tiles"]) == set(runner.plan["tiles"])
    assert all(t["healthy"] for t in health["tiles"].values())
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/summary.json", timeout=10) as r:
        doc = json.loads(r.read())
    assert doc["topology"] == runner.plan["topology"]
    assert doc["tiles"]["verify"]["state"] == "run"
    assert doc["links"]["s_v"]["consumers"]["verify"]["consumed"] == 48
    assert doc["slo"]["verify-latency"]["breached"] is False
    # the SLO engine is live (evals advancing) and the objective holds
    assert runner.metrics("metric")["slo_evals"] > 0
    assert runner.metrics("metric")["slo_breach"] == 0


def test_seed_from_snapshots_the_live_view():
    """Restart resurrect: seed_from must read ONE coherent copy of the
    shm block, not field-by-field loads of the live view — the dead
    tile's final flush writes count LAST, so a count belonging to newer
    buckets double-adds samples for the rest of the restarted tile's
    life. The lint torn-read rule pins the discipline; this pins the
    behavior."""
    import numpy as np
    h = HistAccum()
    for ns in [5, 50, 500]:
        h.add(ns)
    view = np.zeros(HIST_U64, np.uint64)
    h.flush_into(view)

    class TornView:
        """Simulates the racing writer: the first element access flips
        the block to the NEXT flush's contents mid-read."""
        def __init__(self, now, later):
            self._now, self._later, self._reads = now, later, 0
        def __getitem__(self, idx):
            self._reads += 1
            src = self._now if self._reads == 1 else self._later
            return src[idx]
        def __array__(self, dtype=None, copy=None):
            # np.array(view, copy=True) — the u64_snapshot path —
            # lands entirely on the pre-race contents
            return np.array(self._now, dtype=dtype)

    later = view.copy()
    later[0] += 100                       # racing flush bumps count
    h2 = HistAccum()
    h2.seed_from(TornView(view, later))
    assert h2.count == 3                  # coherent: pre-race block
    assert h2.sum_ns == 555
    assert sum(h2.buckets) == h2.count    # count never exceeds buckets

    # the ownership analyzer keeps the fixed module fixed
    from firedancer_tpu.lint.ownership import lint_ownership_source
    import firedancer_tpu.disco.metrics as m
    with open(m.__file__) as f:
        src = f.read()
    assert lint_ownership_source(src, "disco/metrics.py") == []
