"""fdlint tier-1 gate + per-rule fixtures.

Two halves: (1) the shipped tree — `cfg/*.toml` + `firedancer_tpu/`
— must lint clean (zero non-baselined findings), so topology/contract/
purity regressions fail CI before they can wedge a topology at
runtime; (2) every shipped rule has a deliberately broken fixture
proving it fires exactly once (a rule that cannot fire is a rule that
silently rotted)."""
import json
import textwrap

import pytest

from firedancer_tpu.lint import core
from firedancer_tpu.lint.cli import main as lint_main
from firedancer_tpu.lint.contracts import (adapter_summaries,
                                           lint_tiles_source)
from firedancer_tpu.lint.graph import (lint_config, lint_config_file,
                                       lint_topology)
from firedancer_tpu.lint.jaxlint import lint_jax_source

pytestmark = pytest.mark.lint


def rule_count(findings, rule):
    return sum(1 for f in findings if f.rule == rule)


def fires_once(findings, rule):
    assert rule_count(findings, rule) == 1, \
        f"{rule}: expected exactly 1, got " \
        f"{[f.render() for f in findings]}"


# ---------------------------------------------------------------------------
# the shipped tree lints clean
# ---------------------------------------------------------------------------

def test_shipped_tree_is_clean(capsys):
    rc = lint_main(["--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["findings"] == [], doc["findings"]


def test_every_rule_has_severity_and_family():
    for rule, (family, sev, desc) in core.RULES.items():
        assert family in ("graph", "contract", "jax", "abi",
                          "ownership", "core")
        assert sev in core.SEVERITIES
        assert desc
    assert len(core.RULES) >= 12          # ISSUE 2 acceptance floor


def test_every_rule_has_a_fixture():
    """A rule without a broken-fixture test is a rule that can rot
    silently: scan this module's own source for fires_once(..., rule)
    call sites per catalog entry (a mere quoted mention — e.g. a
    does-NOT-fire assertion — must not count)."""
    import re
    with open(__file__) as f:
        src = f.read()
    exercised = set()
    for rule in core.RULES:
        # fires_once closes with `, "<rule>")` — either after the
        # findings expression's closing paren or a bare name
        if re.search(r'fires_once\(\w+,\s*"' + rule + r'"\)', src) or \
                re.search(r'\),\s*"' + rule + r'"\)', src):
            exercised.add(rule)
    missing = set(core.RULES) - exercised
    assert not missing, f"rules without fixtures: {sorted(missing)}"


def test_sup_constants_match_supervise():
    """The contract analyzer mirrors the supervisor slot ABI without
    importing the native runtime — keep the mirror honest."""
    from firedancer_tpu.disco.supervise import SUP_SLOT_MIN, SUP_SLOTS
    from firedancer_tpu.lint import contracts
    assert set(contracts.SUP_NAMES) == set(SUP_SLOTS)
    assert contracts.SUP_SLOT_MIN == SUP_SLOT_MIN


def test_registry_covers_every_adapter_kind():
    """lint/registry.py TILE_ARGS and the @register'd adapters are the
    same kind set — a new adapter must declare its arg keys."""
    from firedancer_tpu.lint.registry import TILE_ARGS
    kinds = set(adapter_summaries())
    assert kinds == set(TILE_ARGS), \
        kinds.symmetric_difference(TILE_ARGS)


# ---------------------------------------------------------------------------
# graph-family fixtures
# ---------------------------------------------------------------------------

def _cfg(links=None, tiles=None, **extra):
    cfg = {
        "link": links if links is not None else [
            {"name": "a_b", "depth": 64, "mtu": 1280}],
        "tile": tiles if tiles is not None else [
            {"name": "src", "kind": "synth", "outs": ["a_b"]},
            {"name": "dst", "kind": "sink", "ins": ["a_b"]}],
    }
    cfg.update(extra)
    return cfg


def test_graph_base_fixture_is_clean():
    assert lint_config(_cfg(), "<fixture>") == []


def test_dead_link():
    cfg = _cfg(tiles=[{"name": "src", "kind": "synth", "outs": ["a_b"]}])
    fires_once(lint_config(cfg, "<fixture>"), "dead-link")


def test_orphan_link():
    cfg = _cfg(tiles=[{"name": "dst", "kind": "sink", "ins": ["a_b"]}])
    fires_once(lint_config(cfg, "<fixture>"), "orphan-link")


def test_dup_producer():
    cfg = _cfg(tiles=[
        {"name": "s1", "kind": "synth", "outs": ["a_b"]},
        {"name": "s2", "kind": "synth", "outs": ["a_b"]},
        {"name": "dst", "kind": "sink", "ins": ["a_b"]}])
    fires_once(lint_config(cfg, "<fixture>"), "dup-producer")


def test_depth_pow2():
    cfg = _cfg(links=[{"name": "a_b", "depth": 96, "mtu": 1280}])
    fires_once(lint_config(cfg, "<fixture>"), "depth-pow2")


def test_mtu_underflow():
    cfg = _cfg(
        links=[{"name": "a_b", "depth": 64, "mtu": 1280},
               {"name": "b_c", "depth": 64, "mtu": 512}],
        tiles=[{"name": "src", "kind": "synth", "outs": ["a_b"]},
               {"name": "v", "kind": "verify", "ins": ["a_b"],
                "outs": ["b_c"]},
               {"name": "dst", "kind": "sink", "ins": ["b_c"]}])
    fires_once(lint_config(cfg, "<fixture>"), "mtu-underflow")


def test_backpressure_cycle():
    cfg = _cfg(
        links=[{"name": "a", "depth": 64, "mtu": 1280},
               {"name": "b", "depth": 64, "mtu": 1280}],
        tiles=[{"name": "t1", "kind": "dedup", "ins": ["b"],
                "outs": ["a"]},
               {"name": "t2", "kind": "dedup", "ins": ["a"],
                "outs": ["b"]}])
    fires_once(lint_config(cfg, "<fixture>"), "backpressure-cycle")


def test_reliable_sink():
    # metric never consumes rings: a RELIABLE in wedges the producer
    cfg = _cfg(tiles=[
        {"name": "src", "kind": "synth", "outs": ["a_b"]},
        {"name": "m", "kind": "metric", "ins": ["a_b"]}])
    fires_once(lint_config(cfg, "<fixture>"), "reliable-sink")


def test_unread_in():
    cfg = _cfg(tiles=[
        {"name": "src", "kind": "synth", "outs": ["a_b"]},
        {"name": "m", "kind": "metric", "ins": [["a_b", False]]}])
    findings = lint_config(cfg, "<fixture>")
    fires_once(findings, "unread-in")
    assert rule_count(findings, "reliable-sink") == 0


def test_unknown_kind():
    cfg = _cfg(tiles=[
        {"name": "src", "kind": "synht", "outs": ["a_b"]},
        {"name": "dst", "kind": "sink", "ins": ["a_b"]}])
    findings = lint_config(cfg, "<fixture>")
    fires_once(findings, "unknown-kind")
    assert "did you mean 'synth'" in findings[0].message


def test_bad_supervise():
    cfg = _cfg(tiles=[
        {"name": "src", "kind": "synth", "outs": ["a_b"]},
        {"name": "dst", "kind": "sink", "ins": ["a_b"],
         "supervise": {"policy": "sometimes"}}])
    fires_once(lint_config(cfg, "<fixture>"), "bad-supervise")


def test_bad_chaos_unknown_action():
    cfg = _cfg(tiles=[
        {"name": "src", "kind": "synth", "outs": ["a_b"]},
        {"name": "dst", "kind": "sink", "ins": ["a_b"],
         "chaos": {"events": [{"action": "explode"}]}}])
    fires_once(lint_config(cfg, "<fixture>"), "bad-chaos")


def test_bad_chaos_stall_fseq_unknown_link():
    cfg = _cfg(tiles=[
        {"name": "src", "kind": "synth", "outs": ["a_b"]},
        {"name": "dst", "kind": "sink", "ins": ["a_b"],
         "chaos": {"events": [{"action": "stall_fseq",
                               "link": "ghost", "at_rx": 4}]}}])
    fires_once(lint_config(cfg, "<fixture>"), "bad-chaos")


def test_dangling_ref():
    cfg = _cfg(tiles=[
        {"name": "src", "kind": "synth", "outs": ["a_b"]},
        {"name": "dst", "kind": "sink", "ins": ["a_b"]},
        {"name": "g", "kind": "gui", "tps_tile": "nosuch"}])
    fires_once(lint_config(cfg, "<fixture>"), "dangling-ref")


def test_bad_gui_schema_and_did_you_mean():
    # out-of-range ws bound (gui/schema.py normalize_gui, the same
    # validator topo.build runs)
    cfg = _cfg(tiles=[
        {"name": "src", "kind": "synth", "outs": ["a_b"]},
        {"name": "dst", "kind": "sink", "ins": ["a_b"]},
        {"name": "g", "kind": "gui", "ws_queue": 1}])
    fires_once(lint_config(cfg, "<fixture>"), "bad-gui")
    # unknown key with a did-you-mean (programmatic Topology builds
    # skip app/config.py's registry gate — the linter still catches it)
    cfg = _cfg(tiles=[
        {"name": "src", "kind": "synth", "outs": ["a_b"]},
        {"name": "dst", "kind": "sink", "ins": ["a_b"]},
        {"name": "g", "kind": "gui", "ws_quee": 8}])
    findings = lint_config(cfg, "<fixture>")
    fires_once(findings, "bad-gui")
    assert any("did you mean 'ws_queue'" in f.message
               for f in findings if f.rule == "bad-gui")


def test_bad_trace_unknown_key():
    cfg = _cfg(trace={"enable": True, "dept": 64})
    findings = lint_config(cfg, "<fixture>")
    fires_once(findings, "bad-trace")
    assert "did you mean 'depth'" in findings[0].message


def test_bad_trace_depth_and_tile_override():
    fires_once(lint_config(_cfg(trace={"enable": True, "depth": 100}),
                           "<fixture>"), "bad-trace")
    cfg = _cfg(tiles=[
        {"name": "src", "kind": "synth", "outs": ["a_b"]},
        {"name": "dst", "kind": "sink", "ins": ["a_b"],
         "trace": {"sample": 0}}])
    fires_once(lint_config(cfg, "<fixture>"), "bad-trace")


def test_bad_trace_unknown_tile_allowlist():
    cfg = _cfg(trace={"enable": True, "tiles": ["ghost"]})
    findings = lint_config(cfg, "<fixture>")
    fires_once(findings, "bad-trace")
    assert "not a declared tile" in findings[0].message


def test_trace_section_is_clean_when_valid():
    cfg = _cfg(trace={"enable": True, "depth": 256, "sample": 4,
                      "tiles": ["dst"]})
    assert lint_config(cfg, "<fixture>") == []


def test_bad_prof_unknown_key_and_shape():
    cfg = _cfg(prof={"enable": True, "slotz": 64})
    findings = lint_config(cfg, "<fixture>")
    fires_once(findings, "bad-prof")
    assert "did you mean 'slots'" in findings[0].message
    fires_once(lint_config(_cfg(prof={"enable": True, "ring": 100}),
                           "<fixture>"), "bad-prof")
    # per-tile override table goes through the same schema gate
    cfg = _cfg(tiles=[
        {"name": "src", "kind": "synth", "outs": ["a_b"]},
        {"name": "dst", "kind": "sink", "ins": ["a_b"],
         "prof": {"hz": 0}}])
    fires_once(lint_config(cfg, "<fixture>"), "bad-prof")


def test_bad_prof_unknown_tile_refs():
    cfg = _cfg(prof={"enable": True, "tiles": ["ghost"]})
    findings = lint_config(cfg, "<fixture>")
    fires_once(findings, "bad-prof")
    assert "not a declared tile" in findings[0].message
    fires_once(lint_config(
        _cfg(prof={"enable": True, "breach_capture": ["ghost"]}),
        "<fixture>"), "bad-prof")


def test_prof_section_is_clean_when_valid():
    cfg = _cfg(prof={"enable": True, "hz": 29, "slots": 128,
                     "ring": 512, "tiles": ["dst"],
                     "breach_capture": ["dst"]})
    assert lint_config(cfg, "<fixture>") == []


def test_bad_slo_unknown_key_and_grammar():
    cfg = _cfg(slo={"fast_windw_s": 1.0})
    findings = lint_config(cfg, "<fixture>")
    fires_once(findings, "bad-slo")
    assert "did you mean 'fast_window_s'" in findings[0].message
    # broken expression grammar is a schema failure too
    fires_once(lint_config(_cfg(slo={"target": [
        {"name": "t", "expr": "dst.rx frobnicate > 1"}]}),
        "<fixture>"), "bad-slo")


def test_bad_slo_unknown_tile_metric_link():
    # target naming a metric the tile kind never exports: did-you-mean
    cfg = _cfg(slo={"target": [{"name": "t", "expr": "dst.bytez > 1"}]})
    findings = lint_config(cfg, "<fixture>")
    fires_once(findings, "bad-slo")
    assert "did you mean 'bytes'" in findings[0].message
    fires_once(lint_config(_cfg(slo={"target": [
        {"name": "t", "expr": "link.ghost.backpressure rate < 1/s"}]}),
        "<fixture>"), "bad-slo")
    fires_once(lint_config(_cfg(slo={"target": [
        {"name": "t", "expr": "ghost.work p99 < 1ms"}]}),
        "<fixture>"), "bad-slo")


def test_slo_section_is_clean_when_valid():
    cfg = _cfg(slo={"fast_window_s": 1.0, "target": [
        {"name": "lat", "expr": "dst.work p99 < 5ms"},
        {"name": "bp", "expr": "link.a_b.backpressure rate < 10/s"},
        {"name": "rx", "expr": "dst.rx rate > 1/s"}]})
    assert lint_config(cfg, "<fixture>") == []


def test_bad_shed_schema_did_you_mean_and_dead_config():
    # typo'd [shed] key: the disco/shed.py schema gate with suggestion
    cfg = _cfg(shed={"rate_ppz": 1.0})
    findings = lint_config(cfg, "<fixture>")
    fires_once(findings, "bad-shed")
    assert "did you mean 'rate_pps'" in findings[0].message
    # out-of-range value
    fires_once(lint_config(_cfg(shed={"max_peers": 1}), "<fixture>"),
               "bad-shed")
    # malformed per-tile override
    fires_once(lint_config(_cfg(tiles=[
        {"name": "src", "kind": "synth", "outs": ["a_b"]},
        {"name": "dst", "kind": "sink", "ins": ["a_b"],
         "shed": {"burst": 0}}]), "<fixture>"), "bad-shed")
    # dead config: a shed override on a kind with no ingest door —
    # a topo that THINKS it is protected must actually be
    findings = lint_config(_cfg(tiles=[
        {"name": "src", "kind": "synth", "outs": ["a_b"]},
        {"name": "dst", "kind": "sink", "ins": ["a_b"],
         "shed": {"rate_pps": 5.0}}]), "<fixture>")
    fires_once(findings, "bad-shed")
    assert "no ingest door" in findings[0].message


def test_shed_section_is_clean_when_valid():
    cfg = _cfg(
        links=[{"name": "a_b", "depth": 64, "mtu": 1280}],
        tiles=[{"name": "src", "kind": "sock", "outs": ["a_b"],
                "shed": {"rate_pps": 50.0}},
               {"name": "dst", "kind": "sink", "ins": ["a_b"]}],
        shed={"rate_pps": 1000.0, "burst": 64, "max_peers": 256,
              "min_stake": 1, "overload_hold_s": 2.0,
              "stakes": {"127.0.0.1:9001": 500}})
    assert lint_config(cfg, "<fixture>") == []


def test_bad_witness_schema_and_stage_did_you_mean():
    # typo'd [witness] key: the witness/plan.py schema gate
    cfg = _cfg(witness={"stagez": ["kernel_vps"]})
    findings = lint_config(cfg, "<fixture>")
    fires_once(findings, "bad-witness")
    assert "did you mean 'stage'" in findings[0].message
    # unknown stage name with suggestion
    findings = lint_config(_cfg(witness={"stages": ["kernel_vp"]}),
                           "<fixture>")
    fires_once(findings, "bad-witness")
    assert "did you mean 'kernel_vps'" in findings[0].message
    # malformed per-stage override
    fires_once(lint_config(_cfg(witness={
        "stage": {"kernel_vps": {"cmd": "not an argv list"}}}),
        "<fixture>"), "bad-witness")
    # out-of-range park window
    fires_once(lint_config(_cfg(witness={"park_s": 10.0,
                                         "park_max_s": 1.0}),
                           "<fixture>"), "bad-witness")


def test_witness_section_is_clean_when_valid():
    cfg = _cfg(witness={"stages": ["device_probe", "kernel_vps"],
                        "park_s": 5.0, "park_max_s": 60.0,
                        "stage": {"kernel_vps": {"timeout_s": 900.0}}})
    assert lint_config(cfg, "<fixture>") == []


def test_bad_funk_schema_and_did_you_mean():
    # typo'd [funk] key: the funk/shmfunk.py schema gate
    findings = lint_config(_cfg(funk={"bakend": "shm"}), "<fixture>")
    fires_once(findings, "bad-funk")
    assert "did you mean 'backend'" in findings[0].message
    # unknown backend with suggestion
    findings = lint_config(_cfg(funk={"backend": "sm"}), "<fixture>")
    fires_once(findings, "bad-funk")
    assert "did you mean 'shm'" in findings[0].message
    # out-of-range heap
    fires_once(lint_config(_cfg(funk={"backend": "shm", "heap_mb": 0}),
                           "<fixture>"), "bad-funk")


def test_funk_section_is_clean_when_valid():
    cfg = _cfg(funk={"backend": "shm", "heap_mb": 4, "rec_max": 1024})
    assert lint_config(cfg, "<fixture>") == []


def test_bad_replay_schema_and_did_you_mean():
    # typo'd [replay] key: the tiles/replay.py schema gate
    findings = lint_config(_cfg(replay={"exec_tile_cn": 2}),
                           "<fixture>")
    fires_once(findings, "bad-replay")
    assert "did you mean 'exec_tile_cnt'" in findings[0].message
    # out-of-range values
    fires_once(lint_config(_cfg(replay={"exec_tile_cnt": -1}),
                           "<fixture>"), "bad-replay")
    fires_once(lint_config(_cfg(replay={"redispatch_s": 0}),
                           "<fixture>"), "bad-replay")


def test_bad_snapshot_schema_and_did_you_mean():
    # typo'd [snapshot] key: the tiles/snapshot.py schema gate
    findings = lint_config(_cfg(snapshot={"every_slot": 8}),
                           "<fixture>")
    fires_once(findings, "bad-snapshot")
    assert "did you mean 'every_slots'" in findings[0].message
    # out-of-range values
    fires_once(lint_config(_cfg(snapshot={"min_slot": -1}),
                           "<fixture>"), "bad-snapshot")
    fires_once(lint_config(_cfg(snapshot={"chunk": 8}),
                           "<fixture>"), "bad-snapshot")


def test_replay_snapshot_sections_clean_when_valid():
    cfg = _cfg(replay={"exec_tile_cnt": 2, "redispatch_s": 1.5},
               snapshot={"path": "/tmp/snap.ckpt", "every_slots": 8,
                         "min_slot": 4})
    assert lint_config(cfg, "<fixture>") == []


def test_replay_snapshot_registry_mirrors():
    """The lint registry's section-key tuples mirror the validators'
    defaults tables — a key added to one side without the other is a
    review gap."""
    from firedancer_tpu.lint.registry import (REPLAY_SECTION_KEYS,
                                              SNAPSHOT_SECTION_KEYS)
    from firedancer_tpu.tiles.replay import REPLAY_DEFAULTS
    from firedancer_tpu.tiles.snapshot import SNAPSHOT_DEFAULTS
    assert set(REPLAY_SECTION_KEYS) == set(REPLAY_DEFAULTS)
    assert set(SNAPSHOT_SECTION_KEYS) == set(SNAPSHOT_DEFAULTS)


def test_bad_flight_schema_and_did_you_mean():
    # typo'd [flight] key: the flight/__init__.py schema gate
    findings = lint_config(_cfg(flight={"segmnt_mb": 8.0}),
                           "<fixture>")
    fires_once(findings, "bad-flight")
    assert "did you mean 'segment_mb'" in findings[0].message
    # out-of-range values
    fires_once(lint_config(_cfg(flight={"hz": 0}),
                           "<fixture>"), "bad-flight")
    fires_once(lint_config(_cfg(flight={"segment_mb": 8.0,
                                        "retain_mb": 1.0}),
                           "<fixture>"), "bad-flight")
    # unknown source with suggestion
    findings = lint_config(_cfg(flight={"sources": ["metrics",
                                                    "linkz"]}),
                           "<fixture>")
    fires_once(findings, "bad-flight")
    assert "did you mean 'links'" in findings[0].message


def test_flight_section_is_clean_when_valid():
    cfg = _cfg(flight={"dir": "/tmp/fl", "segment_mb": 4.0,
                       "retain_mb": 32.0, "hz": 8.0,
                       "sources": ["metrics", "links", "slo"],
                       "incident_window_s": 2.0, "node_id": 3})
    assert lint_config(cfg, "<fixture>") == []


# a config that predates the retired runtime-knob plane: fdlint
# reports it through the rules it already has, no rule of its own
CLEAN_TOML = """
[[link]]
name = "a_b"
depth = 64
mtu = 1280

[[tile]]
name = "src"
kind = "synth"
outs = ["a_b"]

[[tile]]
name = "dst"
kind = "sink"
ins = ["a_b"]
"""

STALE_TUNE = {
    "disabled": "[tune]\nenable = false\n",
    "enabled": "[tune]\nenable = true\ninterval_s = 0.25\n",
    "knob_override": "[tune.knob.coalesce_us]\nmax = 1000\n",
}


@pytest.mark.parametrize("body", list(STALE_TUNE.values()),
                         ids=list(STALE_TUNE))
def test_stale_tune_section_is_a_load_failure(tmp_path, body):
    p = tmp_path / "stale.toml"
    p.write_text(CLEAN_TOML)
    assert lint_config_file(str(p)) == []
    p.write_text(CLEAN_TOML + body)
    findings = lint_config_file(str(p))
    fires_once(findings, "dangling-ref")
    assert "config failed to load" in findings[0].message
    assert "unknown config sections ['tune']" in findings[0].message


def test_stale_controller_tile_fires_unknown_kind():
    cfg = _cfg()
    cfg["tile"].append({"name": "ctl", "kind": "controller"})
    findings = lint_config(cfg, "<fixture>")
    fires_once(findings, "unknown-kind")
    assert "'controller'" in findings[0].message


def test_stale_controller_tile_in_a_file_fires_unknown_kind(tmp_path):
    """From a TOML file the controller tile still loads (the kind gate
    is the topology builder's), so fdlint names it, with its line."""
    p = tmp_path / "stale.toml"
    p.write_text(CLEAN_TOML + '\n[[tile]]\nname = "ctl"\n'
                 'kind = "controller"\n')
    findings = lint_config_file(str(p))
    fires_once(findings, "unknown-kind")
    assert findings[0].line > 0


def test_flight_registry_mirror():
    """FLIGHT_SECTION_KEYS mirrors the validator's defaults table —
    same contract the replay/snapshot mirrors pin."""
    from firedancer_tpu.flight import FLIGHT_DEFAULTS
    from firedancer_tpu.lint.registry import FLIGHT_SECTION_KEYS
    assert set(FLIGHT_SECTION_KEYS) == set(FLIGHT_DEFAULTS)


def test_per_shard_ins_entry_expands_not_folds():
    """A sharded-tile per-shard ins entry (all-str list: shard k
    consumes entry[k]) must count every listed link as consumed — the
    old pair-folding read it as ('first', True) and orphaned the other
    shards' links into dead-link false positives."""
    cfg = _cfg(
        links=[{"name": "a_b0", "depth": 64, "mtu": 1280},
               {"name": "a_b1", "depth": 64, "mtu": 1280}],
        tiles=[{"name": "src", "kind": "synth",
                "outs": ["a_b0", "a_b1"]},
               {"name": "dst", "kind": "sink",
                "ins": [["a_b0", "a_b1"]]}])
    assert lint_config(cfg, "<fixture>") == []


def test_lint_topology_programmatic():
    """Programmatic Topology builds get the same pass as TOML."""
    from firedancer_tpu.disco import Topology
    topo = (Topology("lintfix")
            .link("a_b", depth=64, mtu=1280)
            .tile("src", "synth", outs=["a_b"]))
    fires_once(lint_topology(topo), "dead-link")


# ---------------------------------------------------------------------------
# contract-family fixtures
# ---------------------------------------------------------------------------

def _tiles_findings(src: str):
    return lint_tiles_source(textwrap.dedent(src), "<fixture.py>")


def test_reserved_metric():
    fires_once(_tiles_findings("""
        class T:
            METRICS = ["rx", "sup_down"]
        """), "reserved-metric")


def test_metrics_overflow():
    names = ", ".join(f'"m{i}"' for i in range(62))
    fires_once(_tiles_findings(f"""
        class T:
            METRICS = [{names}]
        """), "metrics-overflow")


def test_undeclared_gauge():
    fires_once(_tiles_findings("""
        class T:
            METRICS = ["rx"]
            GAUGES = ["port"]
        """), "undeclared-gauge")


def test_dup_metric():
    fires_once(_tiles_findings("""
        class T:
            METRICS = ["rx", "rx"]
        """), "dup-metric")


def test_uncredited_publish():
    fires_once(_tiles_findings("""
        class T:
            def poll_once(self):
                self.out_ring.publish(b"x", sig=1)
                return 1
        """), "uncredited-publish")


def test_credited_publish_is_clean():
    assert _tiles_findings("""
        class T:
            def poll_once(self):
                while self.fseqs and \\
                        self.out_ring.credits(self.fseqs) <= 0:
                    pass
                self.out_ring.publish(b"x", sig=1)
                return 1
        """) == []


def test_uncredited_publish_nested_credit_does_not_exempt():
    """A credit check inside a never-called nested helper must not
    exempt the OUTER function's publish (scope-sensitive scan)."""
    fires_once(_tiles_findings("""
        class T:
            def poll_once(self):
                def helper():
                    return self.out_ring.credits(self.fseqs)
                self.out_ring.publish(b"x", sig=1)
                return 1
        """), "uncredited-publish")


def test_uncredited_publish_in_nested_fn_reported_once():
    fires_once(_tiles_findings("""
        class T:
            def poll_once(self):
                def helper():
                    self.out_ring.publish(b"x", sig=1)
                return helper()
        """), "uncredited-publish")


def test_stale_outside_supervision():
    fires_once(_tiles_findings("""
        class T:
            def poll_once(self):
                self.fseq.mark_stale()
        """), "stale-outside-supervision")


def test_per_frag_loop_trace_frag():
    fires_once(_tiles_findings("""
        class T:
            def poll_once(self):
                for i in range(n):
                    self.trace.frag(3, sig=int(sigs[i]))
        """), "per-frag-loop")


def test_per_frag_loop_publish_via_helper_closure():
    """The rule follows poll_once's same-module call closure: a
    per-frag publish loop in a helper the hot path calls is just as
    hot as one written inline."""
    f = _tiles_findings("""
        class T:
            def poll_once(self):
                self._wait_credits()
                return self._egress(rows)
            def _egress(self, rows):
                self._wait_credits()
                for r in rows:
                    self.out_ring.publish(r, sig=1)
        """)
    fires_once(f, "per-frag-loop")


def test_per_frag_loop_indirect_through_tainted_helper():
    """A loop calling a helper whose closure reaches a single-item API
    is the same defect one frame deeper — the loop line is flagged."""
    fires_once(_tiles_findings("""
        class T:
            def poll_once(self):
                for i in range(n):
                    self._emit(buf[i])
            def _emit(self, frame):
                self.out_ring.publish(frame, sig=1)
        """), "per-frag-loop")


def test_per_frag_loop_callback_closure_is_hot():
    """A nested closure handed into a gather helper as a callback joins
    the hot closure via the argument edge — its own per-frag loop is
    flagged even though nothing calls it by name."""
    fires_once(_tiles_findings("""
        def _gather_all(ctx, handle):
            return 0
        class T:
            def poll_once(self):
                def cb(frame):
                    for s in frame.sigs:
                        self.trace.frag(3, sig=s)
                return _gather_all(self.ctx, cb)
        """), "per-frag-loop")


def test_per_frag_loop_untainted_helper_in_loop_is_clean():
    """Per-frame calls to helpers that do NOT reach single-item APIs
    (parse / state-machine work) stay legal — that is the
    frame-granular grain the rule's docstring carves out."""
    assert rule_count(_tiles_findings("""
        class T:
            def poll_once(self):
                for i in range(n):
                    self._handle(buf[i])
            def _handle(self, frame):
                return parse(frame)
        """), "per-frag-loop") == 0


def test_per_frag_loop_tcache_insert():
    fires_once(_tiles_findings("""
        class T:
            def poll_once(self):
                for s in sigs:
                    if self.tcache.insert(int(s)):
                        pass
        """), "per-frag-loop")


def test_per_frag_loop_pack_bank_fill_shape():
    """The pre-r13 pack shape — one credit-checked publish per idle
    bank inside the bank loop — is exactly what the wave rewrite
    removed; the rule must keep it out (publish_batch outside the
    loop is the fix)."""
    fires_once(_tiles_findings("""
        class T:
            def poll_once(self):
                for bank, ln in enumerate(self.bank_links):
                    out = self.ctx.out_rings[ln]
                    fseqs = self.ctx.out_fseqs[ln]
                    if fseqs and out.credits(fseqs) <= 0:
                        continue
                    metas = self.sched.schedule_microblock(bank)
                    if not metas:
                        continue
                    out.publish(self._serialize(bank, 0, metas), sig=0)
        """), "per-frag-loop")


def test_per_frag_loop_bank_microblock_shape():
    """The pre-r13 bank shape — per-gathered-frame execution emitting
    its poh + completion publishes inside the frame loop — stays
    flagged; the wave rewrite batches both publishes per poll."""
    f = _tiles_findings("""
        class T:
            def poll_once(self):
                self._wait_credits()
                for i in range(n):
                    frame = bytes(buf[i])
                    self._execute(frame)
                    self.poh_out.publish(frame, sig=1)
                    self.out.publish(b"done", sig=1)
        """)
    assert rule_count(f, "per-frag-loop") == 2


def test_per_frag_loop_wave_batch_shape_is_clean():
    """The r13 wave shape — schedule/serialize in the loop, ONE
    publish_batch after it (while-resume on backpressure) — is the
    rule-clean rewrite of both pack and bank."""
    assert rule_count(_tiles_findings("""
        class T:
            def poll_once(self):
                frames = []
                for bank in range(self.n_banks):
                    metas = self.sched.schedule_microblock(bank)
                    if metas:
                        frames.append(self._serialize(bank, 0, metas))
                start = 0
                while True:
                    stop, pub = self.out.publish_batch(
                        wb, sz, ids, mask, fseqs=self.fseqs,
                        start=start)
                    start = stop
                    if start >= len(frames):
                        break
        """), "per-frag-loop") == 0


def test_per_frag_loop_outside_hot_path_is_clean():
    """A per-frag loop in a function poll_once never reaches (boot
    code, test helpers) is not a hot-path defect."""
    assert rule_count(_tiles_findings("""
        class T:
            def poll_once(self):
                return 0
            def boot_fill(self, rows):
                for r in rows:
                    self.trace.frag(3, sig=1)
        """), "per-frag-loop") == 0


def test_per_frag_loop_batched_calls_are_clean():
    assert rule_count(_tiles_findings("""
        class T:
            def poll_once(self):
                self.trace.frag_batch(3, sigs)
                for ln in self.in_links:
                    n = self.rings[ln].gather(0, 64, 1280)
                stop, pub = self.out_ring.publish_batch(
                    buf, sizes, sigs, mask, fseqs=self.fseqs)
        """), "per-frag-loop") == 0


def test_per_frag_loop_suppression_on_loop_line():
    assert rule_count(_tiles_findings("""
        class T:
            def poll_once(self):
                # fdlint: disable=per-frag-loop — bounded recovery
                for s in sigs:
                    self.tcache.query(int(s))
        """), "per-frag-loop") == 0


def test_per_frag_loop_nested_loops_report_once():
    """A call inside nested fors is ONE defect, anchored at the
    outermost loop (the suppression point)."""
    f = _tiles_findings("""
        class T:
            def poll_once(self):
                for t in tags:
                    for p in pool[t]:
                        self.out_ring.publish(p, sig=t)
        """)
    fires_once(f, "per-frag-loop")


def test_silent_consumer():
    fires_once(_tiles_findings("""
        @register("demo")
        class D:
            def __init__(self, ctx, args):
                self.ring = ctx.in_rings["a"]

            def poll_once(self):
                return 0
        """), "silent-consumer")


def test_silent_consumer_with_in_seqs_is_clean():
    assert _tiles_findings("""
        @register("demo")
        class D:
            def __init__(self, ctx, args):
                self.ring = ctx.in_rings["a"]
                self.seq = 0

            def in_seqs(self):
                return {"a": self.seq}
        """) == []


# ---------------------------------------------------------------------------
# jax-family fixtures
# ---------------------------------------------------------------------------

def _jax_findings(src: str):
    return lint_jax_source(textwrap.dedent(src), "<fixture.py>")


def test_host_sync_item():
    fires_once(_jax_findings("""
        import jax

        @jax.jit
        def f(x):
            return x.sum().item()
        """), "host-sync-item")


def test_host_cast_traced():
    fires_once(_jax_findings("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return float(jnp.sum(x))
        """), "host-cast-traced")


def test_numpy_in_jit():
    fires_once(_jax_findings("""
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.asarray(x)
        """), "numpy-in-jit")


def test_numpy_outside_jit_is_clean():
    assert _jax_findings("""
        import numpy as np

        def host_prep(x):
            return np.asarray(x, np.int64)
        """) == []


def test_traced_bool():
    fires_once(_jax_findings("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            if jnp.any(x > 0):
                return x
            return -x
        """), "traced-bool")


def test_x64_in_kernel():
    fires_once(_jax_findings("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return x.astype(jnp.int64)
        """), "x64-in-kernel")


def test_x64_in_pallas_kernel_body():
    # kernels are regions through the pallas_call reference, not a
    # decorator
    fires_once(_jax_findings("""
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...].astype(jnp.float64)

        def entry(x):
            return pl.pallas_call(kernel, out_shape=x)(x)
        """), "x64-in-kernel")


def test_prng_key_reuse():
    fires_once(_jax_findings("""
        import jax

        def f(key):
            a = jax.random.uniform(key, (2,))
            b = jax.random.normal(key, (2,))
            return a + b
        """), "prng-key-reuse")


def test_prng_split_is_clean():
    assert _jax_findings("""
        import jax

        def f(key):
            k1, k2 = jax.random.split(key)
            a = jax.random.uniform(k1, (2,))
            b = jax.random.normal(k2, (2,))
            return a + b
        """) == []


def test_prng_rebinding_idiom_is_clean():
    """The standard `key, sub = split(key)` loop rebinds sub between
    draws — not reuse."""
    assert _jax_findings("""
        import jax

        def f(key):
            key, sub = jax.random.split(key)
            a = jax.random.uniform(sub, (2,))
            key, sub = jax.random.split(key)
            b = jax.random.normal(sub, (2,))
            return a + b
        """) == []


def test_prng_reuse_in_nested_fn_reported_once():
    fires_once(_jax_findings("""
        import jax

        def outer(key):
            def inner():
                a = jax.random.uniform(key, (2,))
                b = jax.random.normal(key, (2,))
                return a + b
            return inner()
        """), "prng-key-reuse")


def test_missing_donate():
    fires_once(_jax_findings("""
        import jax

        def f(x):
            return x + 1

        g = jax.jit(f)
        """), "missing-donate")


def test_donated_jit_is_clean():
    assert _jax_findings("""
        import jax

        def f(x):
            return x + 1

        g = jax.jit(f, donate_argnums=(0,))
        """) == []


# ---------------------------------------------------------------------------
# suppressions, baseline, CLI
# ---------------------------------------------------------------------------

def test_inline_suppression_same_line():
    assert _tiles_findings("""
        class T:
            def poll_once(self):
                self.out_ring.publish(b"x")  # fdlint: disable=uncredited-publish — req/resp ring, depth-bounded
        """) == []


def test_inline_suppression_prev_line():
    assert _tiles_findings("""
        class T:
            def poll_once(self):
                # fdlint: disable=uncredited-publish — depth-bounded
                self.out_ring.publish(b"x")
        """) == []


def test_suppression_does_not_leak_to_other_rules():
    fires_once(_tiles_findings("""
        class T:
            def poll_once(self):
                self.out_ring.publish(b"x")  # fdlint: disable=dup-metric
        """), "uncredited-publish")


def test_baseline_filters_by_rule_path_line():
    f = core.finding("dead-link", "cfg/x.toml", 7, "m")
    assert core.filter_baselined(
        [f], [{"rule": "dead-link", "path": "x.toml"}]) == []
    assert core.filter_baselined(
        [f], [{"rule": "dead-link", "path": "x.toml", "line": 9}]) == [f]
    assert core.filter_baselined(
        [f], [{"rule": "orphan-link", "path": "x.toml"}]) == [f]


def test_baseline_path_needs_component_boundary():
    """An entry for demo.toml must not swallow cluster-demo.toml."""
    f = core.finding("dead-link", "cfg/cluster-demo.toml", 7, "m")
    assert core.filter_baselined(
        [f], [{"rule": "dead-link", "path": "demo.toml"}]) == [f]
    assert core.filter_baselined(
        [f], [{"rule": "dead-link", "path": "cluster-demo.toml"}]) == []


def test_bad_suppression():
    fires_once(core.check_suppressions(
        "x = 1  # fdlint: disable=missing-donte\n", "<fixture>"),
        "bad-suppression")
    assert core.check_suppressions(
        "x = 1  # fdlint: disable=missing-donate\n", "<fixture>") == []
    assert core.check_suppressions(
        "x = 1  # fdlint: disable=all\n", "<fixture>") == []


BROKEN_TOML = """
[[link]]
name = "a_b"
depth = 96
mtu = 1280

[[tile]]
name = "src"
kind = "synth"
outs = ["a_b"]
"""


def test_cli_nonzero_on_broken_fixture(tmp_path, capsys):
    p = tmp_path / "broken.toml"
    p.write_text(BROKEN_TOML)
    assert lint_main([str(p)]) == 1
    out = capsys.readouterr().out
    assert "depth-pow2" in out and "dead-link" in out


def test_cli_baseline_grandfathers(tmp_path, capsys):
    p = tmp_path / "broken.toml"
    p.write_text(BROKEN_TOML)
    bl = tmp_path / "bl.toml"
    bl.write_text('[[finding]]\nrule = "depth-pow2"\n'
                  'path = "broken.toml"\n'
                  '[[finding]]\nrule = "dead-link"\n'
                  'path = "broken.toml"\n')
    assert lint_main([str(p), "--baseline", str(bl)]) == 0
    capsys.readouterr()


def test_cli_json_is_stable(tmp_path, capsys):
    p = tmp_path / "broken.toml"
    p.write_text(BROKEN_TOML)
    lint_main([str(p), "--format", "json"])
    one = capsys.readouterr().out
    lint_main([str(p), "--format", "json"])
    two = capsys.readouterr().out
    assert one == two
    doc = json.loads(one)
    assert doc["fdlint"] == 1
    assert doc["counts"]["error"] == 2
    assert [sorted(f) for f in doc["findings"]] == [
        ["line", "message", "path", "rule", "severity"]] * 2


def test_overlay_layer_directive(tmp_path):
    """`# fdlint: layers=` loads the base stack; findings attribute to
    the layer that declares the entity, so ONE suppression in the base
    covers every stack (the cfg/cluster-demo.toml pattern)."""
    base = tmp_path / "base.toml"
    base.write_text(BROKEN_TOML)
    overlay = tmp_path / "over.toml"
    overlay.write_text("# fdlint: layers=base.toml\n"
                       '[[tile]]\nname = "dst"\nkind = "sink"\n'
                       'ins = ["a_b"]\n')
    findings = lint_config_file(str(overlay))
    assert rule_count(findings, "dead-link") == 0    # overlay consumes
    fires_once(findings, "depth-pow2")
    assert findings[0].path == str(base)             # attributed to base


# ---------------------------------------------------------------------------
# abi-family fixtures (lint/abi.py): wire contracts, short keys,
# registry drift
# ---------------------------------------------------------------------------

# a tower module whose three cataloged sites all match the catalog —
# the base the skew fixtures mutate
TOWER_OK = textwrap.dedent("""
    import struct
    def pack_block(slot, parent_slot, block_id, parent_id):
        return bytes([0]) + struct.pack("<QQ", slot, parent_slot) \\
            + block_id + parent_id
    def pack_vote(voter, stake, block_id):
        return bytes([1]) + voter + struct.pack("<Q", stake) + block_id
    class TowerCore:
        def handle(self, frame):
            slot, parent = struct.unpack_from("<QQ", frame, 1)
            (stake,) = struct.unpack_from("<Q", frame, 33)
""")


def test_wire_contracts_base_fixture_is_clean():
    from firedancer_tpu.lint.abi import lint_wire_contracts
    assert lint_wire_contracts({"tiles/tower.py": TOWER_OK}) == []


def test_wire_contracts_shipped_tree_is_clean():
    from firedancer_tpu.lint.abi import lint_wire_contracts
    assert lint_wire_contracts() == []


def test_wire_mismatch():
    # a cataloged site vanishing (rename/drop) is drift: the other
    # side of the wire still parses the cataloged layout
    from firedancer_tpu.lint.abi import lint_wire_contracts
    src = TOWER_OK.replace("def pack_vote", "def pack_vote_v2")
    findings = lint_wire_contracts({"tiles/tower.py": src})
    fires_once(findings, "wire-mismatch")
    assert "pack_vote" in findings[0].message


def test_wire_mismatch_skewed_format_names_the_site():
    """The static half of the skewed-wire drill: narrowing pack_vote's
    stake from <Q to <I flags exactly that site, both as a lost
    cataloged format and as uncataloged ABI growth."""
    from firedancer_tpu.lint.abi import lint_wire_contracts
    src = TOWER_OK.replace('struct.pack("<Q", stake)',
                           'struct.pack("<I", stake)')
    findings = lint_wire_contracts({"tiles/tower.py": src})
    assert findings and all(f.rule == "wire-mismatch" for f in findings)
    assert all("pack_vote" in f.message for f in findings)


def test_wire_mismatch_whitespace_in_format_is_not_drift():
    # struct ignores whitespace in format strings; the comparison must
    # too ("<Q Q" == "<QQ")
    from firedancer_tpu.lint.abi import lint_wire_contracts
    src = TOWER_OK.replace('struct.pack("<QQ", slot', 
                           'struct.pack("<Q Q", slot')
    assert lint_wire_contracts({"tiles/tower.py": src}) == []


def test_wire_mtu():
    # a tower vote frame is 73B fixed; a 64B out link asserts at the
    # first publish instead of failing review
    cfg = _cfg(
        links=[{"name": "a_b", "depth": 64, "mtu": 1280},
               {"name": "votes", "depth": 64, "mtu": 64}],
        tiles=[{"name": "src", "kind": "synth", "outs": ["a_b"]},
               {"name": "t", "kind": "tower", "ins": ["a_b"],
                "outs": ["votes"]},
               {"name": "dst", "kind": "sink", "ins": ["votes"]}])
    fires_once(lint_config(cfg, "<fixture>"), "wire-mtu")


def test_wire_mtu_exec_dispatch():
    # exec dispatch = 18B header + one 80B txn row minimum
    cfg = _cfg(
        links=[{"name": "a_b", "depth": 64, "mtu": 1280},
               {"name": "d0", "depth": 64, "mtu": 96},
               {"name": "c0", "depth": 64, "mtu": 8}],
        tiles=[{"name": "src", "kind": "synth", "outs": ["a_b"]},
               {"name": "b", "kind": "bank", "ins": ["a_b"],
                "outs": ["d0"], "exec_links": ["d0"],
                "exec_done": ["c0"]},
               {"name": "e", "kind": "exec", "ins": ["d0"],
                "outs": ["c0"]},
               {"name": "dst", "kind": "sink", "ins": ["c0"]}])
    findings = lint_config(cfg, "<fixture>")
    assert rule_count(findings, "wire-mtu") == 2   # dispatch AND done


def _abi_findings(body):
    from firedancer_tpu.lint.abi import lint_abi_source
    return lint_abi_source(textwrap.dedent(body), "<fixture>")


def test_short_key():
    fires_once(_abi_findings("""
        def install(funk, acct_hex):
            funk.rec_write(None, bytes.fromhex(acct_hex), 1)
    """), "short-key")


def test_short_key_provably_wrong_width():
    f = _abi_findings("""
        def install(store, h):
            store.rec_write(None, h[:15], 1)
    """)
    fires_once(f, "short-key")
    assert "provably 15 bytes" in f[0].message


def test_short_key_proofs_are_accepted():
    assert _abi_findings("""
        MARKER = b"m" * 32
        def install(funk, h, k, raw):
            funk.rec_write(None, key32(h), 1)       # helper
            funk.rec_write(None, h2(raw).digest(), 2)  # hash width
            funk.rec_write(None, raw[9:41], 3)      # const 32B slice
            funk.rec_write(None, MARKER, 4)         # module constant
            if len(k) != 32:
                raise ValueError("short")
            funk.rec_write(None, k, 5)              # guarded name
    """) == []


def test_short_key_kv_receiver_filter():
    # .put on a db/store/funk/vinyl receiver is a store write; .put on
    # anything else (dicts, caches) is not this rule's business
    f = _abi_findings("""
        def go(self, k):
            self.db.put(k, 1)
            self.cache.put(k, 2)
    """)
    fires_once(f, "short-key")


def test_registry_drift_unknown_arg():
    from firedancer_tpu.lint.abi import check_adapter_registry
    src = textwrap.dedent("""
        @register("sink")
        class SinkAdapter:
            def __init__(self, ctx, args):
                self.batch = args.get("batch", 1)
                self.bogus = args.get("not_a_registered_key")
    """)
    findings = check_adapter_registry(src, "<fixture>")
    fires_once(findings, "registry-drift")
    assert "not_a_registered_key" in findings[0].message


def test_registry_drift_did_you_mean():
    from firedancer_tpu.lint.abi import check_adapter_registry
    src = textwrap.dedent("""
        @register("sink")
        class SinkAdapter:
            def __init__(self, ctx, args):
                self.batch = args.get("bach", 1)
    """)
    findings = check_adapter_registry(src, "<fixture>")
    assert findings and "did you mean 'batch'" in findings[0].message


def test_registry_drift_unread_key():
    from firedancer_tpu.lint.abi import check_adapter_registry
    src = textwrap.dedent("""
        @register("sink")
        class SinkAdapter:
            def __init__(self, ctx, args):
                pass
    """)
    findings = check_adapter_registry(src, "<fixture>")
    fires_once(findings, "registry-drift")
    assert "'batch'" in findings[0].message


def test_registry_drift_section_mirror():
    from firedancer_tpu.lint import registry as reg
    from firedancer_tpu.lint.abi import check_section_mirror
    keys = ", ".join(f"{k!r}: None"
                     for k in reg.TRACE_SECTION_KEYS + ("bogus",))
    src = f"TRACE_DEFAULTS = {{{keys}}}\n"
    findings = check_section_mirror(
        "trace", src, "<fixture>", "TRACE_DEFAULTS",
        "TRACE_SECTION_KEYS")
    fires_once(findings, "registry-drift")
    assert "bogus" in findings[0].message


def test_registry_drift_shipped_mirrors_are_clean():
    from firedancer_tpu.lint.abi import lint_registry_drift
    assert lint_registry_drift() == []


def test_bad_suppression_new_rule_did_you_mean():
    f = core.check_suppressions(
        "x = 1  # fdlint: disable=wire-missmatch — why\n", "<f>")
    fires_once(f, "bad-suppression")
    assert "did you mean 'wire-mismatch'" in f[0].message


# ---------------------------------------------------------------------------
# ownership-family fixtures (lint/ownership.py)
# ---------------------------------------------------------------------------

def _own_findings(body, path="gossip/pusher.py"):
    from firedancer_tpu.lint.ownership import lint_ownership_source
    return lint_ownership_source(textwrap.dedent(body), path)


def test_dual_writer():
    fires_once(_own_findings("""
        def leak(self, etype):
            self._tr.frag(etype, sig=1)
    """), "dual-writer")


def test_dual_writer_sup_slots():
    fires_once(_own_findings("""
        def poke(slots, tn):
            slots[SUP_SLOTS["sup_restarts"]] = 0
    """, path="tiles/evil.py"), "dual-writer")


def test_dual_writer_restore_marker():
    fires_once(_own_findings("""
        def fake_restore(funk):
            funk.rec_write(None, RESTORE_MARKER_KEY, b"1")
    """, path="gossip/pusher.py"), "dual-writer")


def test_dual_writer_cataloged_writer_is_clean():
    # the snapshot inserter IS the restore marker's cataloged writer
    assert _own_findings("""
        def mark(funk):
            funk.rec_write(None, RESTORE_MARKER_KEY, b"1")
    """, path="tiles/snapshot.py") == []


def test_dual_writer_handoff_annotation():
    assert _own_findings("""
        def reap_mark(self, etype):
            # fdlint: disable=dual-writer — handoff: owner reaped
            self._tr.event(etype)
    """) == []


def test_torn_read():
    f = _own_findings("""
        def seed(self, view_u64):
            self.count = int(view_u64[0])
            self.sum = int(view_u64[1])
    """)
    fires_once(f, "torn-read")


def test_torn_read_snapshot_is_clean():
    assert _own_findings("""
        def seed(self, view_u64):
            snap = u64_snapshot(view_u64)
            self.count = int(snap[0])
            self.sum = int(snap[1])
    """) == []


def test_torn_read_slicing_subviews_is_clean():
    # carving sub-views at setup is lazy offset algebra, not a read
    assert _own_findings("""
        def carve(self, raw):
            v = raw.view()
            self.hdr = v[:64]
            self.ring = v[64:]
    """) == []


def test_torn_read_tango_is_exempt():
    # tango IS the atomic discipline: its speculative double-read of
    # seq around the payload copy is the protocol, not a bug
    assert _own_findings("""
        def consume(self, view_u64):
            a = view_u64[0]
            b = view_u64[0]
    """, path="runtime/tango.py") == []


# ---------------------------------------------------------------------------
# the fixed real defects stay fixed (abi/ownership rules on the
# shipped modules they flagged)
# ---------------------------------------------------------------------------

def test_fixed_defects_stay_clean():
    import os
    from firedancer_tpu.lint.abi import lint_abi_source, pkg_root
    from firedancer_tpu.lint.ownership import lint_ownership_source
    for rel in ("disco/metrics.py", "vinyl/vinyl.py",
                "utils/checkpt.py", "gossip/crds.py"):
        p = os.path.join(pkg_root(), *rel.split("/"))
        with open(p) as fp:
            src = fp.read()
        assert lint_abi_source(src, rel) == [], rel
        assert lint_ownership_source(src, rel) == [], rel


# ---------------------------------------------------------------------------
# --changed incremental mode
# ---------------------------------------------------------------------------

def test_changed_paths_lists_modified_and_untracked(tmp_path):
    import os
    import subprocess
    from firedancer_tpu.lint.cli import changed_paths
    repo = tmp_path / "r"
    repo.mkdir()
    env = {"GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
           "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t",
           "PATH": os.environ["PATH"], "HOME": str(tmp_path)}
    def git(*a):
        subprocess.run(["git", *a], cwd=repo, env=env, check=True,
                       capture_output=True)
    git("init", "-q")
    (repo / "a.py").write_text("x = 1\n")
    git("add", "a.py")
    git("commit", "-qm", "seed")
    (repo / "a.py").write_text("x = 2\n")          # modified
    (repo / "b.toml").write_text("[link]\n")       # untracked
    got = {os.path.basename(p)
           for p in changed_paths(str(repo), "HEAD")}
    assert got == {"a.py", "b.toml"}


def test_cli_changed_mode_runs(capsys):
    # on whatever state the repo is in, --changed must produce valid
    # json and a sane exit code (full-run fallback included)
    rc = lint_main(["--changed", "--format", "json"])
    out = capsys.readouterr().out
    assert rc in (0, 1)
    if out.strip().startswith("{"):
        assert json.loads(out)["fdlint"] == 1


# ---------------------------------------------------------------------------
# provenance stamp
# ---------------------------------------------------------------------------

def test_provenance_block_carries_lint_stamp(tmp_path, monkeypatch):
    import firedancer_tpu.witness.provenance as prov
    monkeypatch.setattr(prov, "_LINT_STATE",
                        {"clean": True, "errors": 0, "warnings": 0})
    block = prov.provenance_block(str(tmp_path))
    assert block["lint"] == {"clean": True, "errors": 0, "warnings": 0}


def test_verify_artifact_flags_dirty_lint_stamp(tmp_path, capsys):
    from firedancer_tpu.witness import provenance as prov
    from firedancer_tpu.witness.cli import verify_artifact
    header = {"lint": {"clean": False, "errors": 3, "warnings": 0}}
    wit = {"header": header, "genesis": prov.chain_hash("", header),
           "stages": [], "run_id": "t"}
    p = tmp_path / "a.json"
    p.write_text(json.dumps({"witness": wit}))
    rc = verify_artifact(str(p))
    err = capsys.readouterr().err
    assert rc == 1
    assert "lint" in err
    # same artifact with a clean stamp verifies
    header2 = {"lint": {"clean": True, "errors": 0, "warnings": 0}}
    wit2 = {"header": header2, "genesis": prov.chain_hash("", header2),
            "stages": [], "run_id": "t"}
    p.write_text(json.dumps({"witness": wit2}))
    assert verify_artifact(str(p)) == 0


# ---------------------------------------------------------------------------
# the live skewed-wire drill: two real processes over a tango ring
# exchange vote frames under a deliberately narrowed stake field; the
# analyzer flagged exactly that site statically (see
# test_wire_mismatch_skewed_format_names_the_site) and the runtime
# consumer demonstrates the failure the flag prevented
# ---------------------------------------------------------------------------

SKEWED_PACK_VOTE = textwrap.dedent("""
    import struct
    def pack_vote(voter, stake, block_id):
        return bytes([1]) + voter + struct.pack("<I", stake) + block_id
""")


def _skewed_vote_producer(name, ring_off, arena_off, depth, mtu):
    from firedancer_tpu.runtime import Workspace, Ring
    w = Workspace(name, 1 << 22, create=False)
    ring = Ring(w, ring_off, depth, arena_off, mtu)
    ns = {}
    exec(compile(SKEWED_PACK_VOTE, "<skewed>", "exec"), ns)
    frame = ns["pack_vote"](b"v" * 32, 7, b"b" * 32)
    ring.publish(frame, sig=1)
    w.close()


def test_skewed_wire_drill_cross_process():
    import multiprocessing as mp
    import os
    import time
    from firedancer_tpu.lint.abi import lint_wire_contracts
    from firedancer_tpu.runtime import Workspace, Ring
    from firedancer_tpu.tiles.tower import TowerCore, pack_vote

    # static half: the analyzer flags the skewed producer site BEFORE
    # any process runs
    skewed_mod = TOWER_OK.replace('struct.pack("<Q", stake)',
                                  'struct.pack("<I", stake)')
    flagged = lint_wire_contracts({"tiles/tower.py": skewed_mod})
    assert flagged and all("pack_vote" in f.message for f in flagged)

    # runtime half: the skewed frame crosses a REAL ring between two
    # REAL processes and the consumer silently drops the vote — the
    # wedge class the static flag catches at review time
    name = f"/fdtpu_lintdrill_{os.getpid()}"
    w = Workspace(name, 1 << 22)
    try:
        depth, mtu = 8, 256
        ring = Ring.create(w, depth=depth, mtu=mtu)
        ctx = mp.get_context("fork")
        p = ctx.Process(target=_skewed_vote_producer,
                        args=(name, ring.off, ring.arena_off, depth,
                              mtu), daemon=True)
        p.start()
        deadline = time.monotonic() + 30
        frame = None
        while time.monotonic() < deadline:
            rc, frag = ring.consume(0)
            if rc == 0:
                frame = bytes(ring.payload(frag))
                break
        p.join(timeout=30)
        assert frame is not None, "producer never published"
        core_ = TowerCore(total_stake=100)
        core_.handle(frame)                   # skewed: 69B < 73B vote
        assert core_.metrics["bad_frames"] == 1
        assert core_.metrics["votes_in"] == 0
        # the correctly-packed frame from the same inputs is accepted
        core_.handle(pack_vote(b"v" * 32, 7, b"b" * 32))
        assert core_.metrics["bad_frames"] == 1
    finally:
        w.close()
        w.unlink()
