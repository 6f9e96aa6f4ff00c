"""Front-door policing engine (disco/shed.py): schema triple gate,
the lint/registry key mirror, and the PeerGate policy — token buckets,
bounded peer table with stake-aware eviction, stake-weighted overload
shedding with clock-expiry recovery. All host-side, no jax.
"""
import os

import pytest

from firedancer_tpu.disco.shed import (PeerGate, SHED_DEFAULTS,
                                       TILE_SHED_KEYS, effective_shed,
                                       normalize_shed)

pytestmark = pytest.mark.flood

S = 1_000_000_000              # 1 s in ns (explicit now= clocks)


# -- schema -----------------------------------------------------------------

def test_normalize_defaults_and_typo_did_you_mean():
    out = normalize_shed({})
    assert out == SHED_DEFAULTS
    with pytest.raises(ValueError, match="did you mean 'rate_pps'"):
        normalize_shed({"rate_ppz": 1.0})
    with pytest.raises(ValueError, match="rate_pps must be > 0"):
        normalize_shed({"rate_pps": 0})
    with pytest.raises(ValueError, match="burst must be >= 1"):
        normalize_shed({"burst": 0.5})
    with pytest.raises(ValueError, match="max_peers must be >= 2"):
        normalize_shed({"max_peers": 1})
    with pytest.raises(ValueError, match="min_stake must be >= 0"):
        normalize_shed({"min_stake": -1})
    with pytest.raises(ValueError, match="overload_hold_s must be > 0"):
        normalize_shed({"overload_hold_s": 0})
    with pytest.raises(ValueError, match="stakes"):
        normalize_shed({"stakes": [1, 2]})
    with pytest.raises(ValueError, match="non-empty string"):
        normalize_shed({"stakes": {"": 5}})
    with pytest.raises(ValueError, match="must be >= 0"):
        normalize_shed({"stakes": {"1.2.3.4:5": -3}})
    with pytest.raises(ValueError, match="table"):
        normalize_shed("nope")


def test_per_tile_is_partial_and_registry_mirror_holds():
    # per-tile tables stay partial (the topology section fills the
    # rest at effective_shed time)
    assert normalize_shed({"rate_pps": 9.0}, per_tile=True) == \
        {"rate_pps": 9.0}
    assert normalize_shed(None, per_tile=True) == {}
    # fdlint's registry mirrors the one validator's key set — a key
    # added to SHED_DEFAULTS without the registry (or vice versa)
    # fails here, keeping did-you-mean suggestions honest
    from firedancer_tpu.lint import registry
    assert set(registry.SHED_SECTION_KEYS) == set(SHED_DEFAULTS)
    assert set(registry.TILE_SHED_KEYS) == set(TILE_SHED_KEYS)
    assert "shed" in registry.COMMON_KEYS


def test_effective_shed_merge_precedence():
    assert effective_shed(None, None) is None
    topo = {"rate_pps": 100.0, "stakes": {"a:1": 5}}
    assert effective_shed(topo, None)["rate_pps"] == 100.0
    eff = effective_shed(topo, {"rate_pps": 7.0, "stakes": {"b:2": 9}})
    assert eff["rate_pps"] == 7.0            # tile override wins
    assert eff["stakes"] == {"a:1": 5, "b:2": 9}   # stakes union
    assert eff["max_peers"] == SHED_DEFAULTS["max_peers"]
    # disable at either level -> no gate at all
    assert effective_shed({"enable": False}, None) is None
    assert effective_shed(topo, {"enable": False}) is None
    # a tile-only override polices even without a topology section
    assert effective_shed(None, {"rate_pps": 3.0})["rate_pps"] == 3.0


# -- triple gate ------------------------------------------------------------

def test_bad_shed_rejected_at_config_load_and_topo_build():
    from firedancer_tpu.app.config import build_topology
    cfg = {"topology": {"name": "t"},
           "link": [{"name": "a_b", "depth": 32}],
           "tile": [{"name": "s", "kind": "sock", "outs": ["a_b"]},
                    {"name": "d", "kind": "sink", "ins": ["a_b"]}],
           "shed": {"rate_ppz": 1.0}}
    with pytest.raises(ValueError, match="did you mean 'rate_pps'"):
        build_topology(cfg)
    # programmatic Topology skips config load: topo.build is the gate
    from firedancer_tpu.disco import Topology
    topo = (Topology("bad_shed", shed={"max_peers": 1})
            .link("a_b", depth=32)
            .tile("s", "sock", outs=["a_b"])
            .tile("d", "sink", ins=["a_b"]))
    with pytest.raises(ValueError, match="max_peers"):
        topo.build()
    # per-tile override validates too
    topo2 = (Topology("bad_shed2")
             .link("a_b", depth=32)
             .tile("s", "sock", outs=["a_b"],
                   shed={"overload_hold_s": -1})
             .tile("d", "sink", ins=["a_b"]))
    with pytest.raises(ValueError, match="overload_hold_s"):
        topo2.build()


def test_plan_carries_shed_and_breach_reader_is_zero_safe():
    from firedancer_tpu.disco import Topology
    from firedancer_tpu.disco.slo import PressureProbe
    topo = (Topology("shedplan", shed={"rate_pps": 11.0})
            .link("a_b", depth=32)
            .tile("s", "sock", outs=["a_b"])
            .tile("d", "sink", ins=["a_b"]))
    plan = topo.build()
    assert plan["shed"]["rate_pps"] == 11.0
    assert plan["shed"]["burst"] == SHED_DEFAULTS["burst"]
    # no metric tile in the plan: the overload coupling reads 0, never
    # raises (ingest tiles poll this at housekeeping cadence)
    assert PressureProbe(plan, None).overloaded() is False


# -- PeerGate: token buckets ------------------------------------------------

def test_token_bucket_rate_limits_per_peer():
    g = PeerGate({"rate_pps": 2.0, "burst": 2, "max_peers": 16})
    a, b = ("10.0.0.1", 5), ("10.0.0.2", 5)
    now = 0
    assert g.admit(a, now) and g.admit(a, now)
    assert not g.admit(a, now)           # burst exhausted
    assert g.admit(b, now)               # another peer: own bucket
    assert g.shed_total == 1 and g.shed_rate == 1
    # 1 s later the bucket earned rate_pps tokens back
    now += S
    assert g.admit(a, now) and g.admit(a, now)
    assert not g.admit(a, now)
    # ...and never more than burst accumulates
    now += 100 * S
    assert g.admit(a, now) and g.admit(a, now)
    assert not g.admit(a, now)


def test_key_namespaces_sockets_and_origins():
    assert PeerGate.key_of(("1.2.3.4", 80)) == "1.2.3.4:80"
    assert PeerGate.key_of(b"\xaa\xbb") == "aabb"   # gossip origins


# -- PeerGate: bounded table + eviction -------------------------------------

def test_sybil_flood_churns_unstaked_slots_never_staked():
    g = PeerGate({"rate_pps": 100.0, "burst": 4, "max_peers": 4,
                  "min_stake": 1,
                  "stakes": {"10.0.0.1:1": 100, "10.0.0.2:1": 100}})
    now = 0
    assert g.admit(("10.0.0.1", 1), now)
    assert g.admit(("10.0.0.2", 1), now)
    # a flood of fresh unstaked identities: table NEVER exceeds
    # max_peers, and the staked entries are never evicted
    for i in range(1000):
        g.admit((f"172.16.{i % 250}.{i // 250}", 9), now)
    assert len(g.peers) <= 4
    assert "10.0.0.1:1" in g.peers and "10.0.0.2:1" in g.peers
    assert g.evicted > 0


def test_all_staked_table_sheds_unstaked_newcomer():
    g = PeerGate({"rate_pps": 100.0, "burst": 4, "max_peers": 2,
                  "min_stake": 1,
                  "stakes": {"10.0.0.1:1": 50, "10.0.0.2:1": 50,
                             "10.0.0.3:1": 50}})
    now = 0
    assert g.admit(("10.0.0.1", 1), now)
    assert g.admit(("10.0.0.2", 1), now)
    # unstaked newcomer: shed at the door, no staked entry evicted
    assert not g.admit(("99.9.9.9", 1), now)
    assert g.shed_unstaked == 1
    assert set(g.peers) == {"10.0.0.1:1", "10.0.0.2:1"}
    # a STAKED newcomer may evict the oldest entry instead
    assert g.admit(("10.0.0.3", 1), now)
    assert "10.0.0.3:1" in g.peers and len(g.peers) == 2


# -- PeerGate: overload mode ------------------------------------------------

def test_overload_sheds_unstaked_first_and_recovers_on_expiry():
    g = PeerGate({"rate_pps": 100.0, "burst": 8, "max_peers": 16,
                  "min_stake": 10, "overload_hold_s": 1.0,
                  "stakes": {"10.0.0.1:1": 50, "10.0.0.9:1": 3}})
    now = 0
    staked, low, unstaked = ("10.0.0.1", 1), ("10.0.0.9", 1), ("6.6.6.6", 1)
    assert g.admit(unstaked, now)        # peacetime: everyone admitted
    g.trip_overload(now)
    assert g.overloaded(now)
    # overload: below-min_stake sheds at the door (no table growth),
    # staked keeps its token budget
    assert not g.admit(unstaked, now)
    assert not g.admit(low, now)         # stake 3 < min_stake 10
    assert g.admit(staked, now)
    peers_during = len(g.peers)
    for i in range(100):
        assert not g.admit((f"7.7.{i}.1", 1), now)
    assert len(g.peers) == peers_during  # overload cannot grow the table
    assert g.shed_unstaked >= 102
    # refresh keeps it latched; expiry IS the recovery
    g.trip_overload(now + S // 2)
    assert g.overloaded(now + S)
    assert not g.overloaded(now + S // 2 + S)
    assert g.admit(unstaked, now + S // 2 + S)


def test_count_drop_attributes_drop_newest():
    g = PeerGate({"stakes": {"10.0.0.1:1": 5}})
    g.count_drop(("10.0.0.1", 1))
    g.count_drop(("8.8.8.8", 1))
    assert g.shed_total == 2 and g.shed_drop == 2
    assert g.shed_unstaked == 1          # only the unstaked peer
    c = g.counters()
    assert c["shed"] == 2 and c["overload"] == 0 and c["peers"] == 0


# -- cross-tile overload coupling (disco/tiles._shed_slo_poll) ---------------

def _door_plan(metric: bool, tag: str):
    from firedancer_tpu.disco import Topology
    topo = (Topology(f"door{tag}{os.getpid()}", wksp_size=1 << 21)
            .link("a_b", depth=32)
            .tile("s", "sock", outs=["a_b"])
            .tile("d", "sink", ins=["a_b"]))
    if metric:
        topo.tile("metric", "metric", port=0)
    return topo.build()


def _set_breach(plan, w, value: int):
    import numpy as np
    from firedancer_tpu.disco.topo import METRICS_SLOTS
    spec = plan["tiles"]["metric"]
    view = w.view(spec["metrics_off"], METRICS_SLOTS * 8).view(np.uint64)
    view[spec["metrics_names"].index("slo_breach")] = value


# (metric tile declared, slo_breach gauge, door expected to trip)
DOOR_CASES = {"breach": (True, 1, True), "clear": (True, 0, False),
              "no_metric_tile": (False, None, False)}


@pytest.mark.parametrize("metric,breach,tripped",
                         list(DOOR_CASES.values()), ids=list(DOOR_CASES))
def test_pressure_probe_reads_the_slo_breach_gauge(metric, breach,
                                                   tripped):
    """PressureProbe on a real plan: overloaded() is the metric tile's
    slo_breach gauge as it stands in shm, and False with no metric
    tile to read."""
    from firedancer_tpu.disco.slo import PressureProbe
    from firedancer_tpu.runtime import Workspace
    plan = _door_plan(metric, "p")
    w = Workspace(plan["wksp"]["name"], plan["wksp"]["size"],
                  create=False)
    try:
        probe = PressureProbe(plan, w)
        assert probe.overloaded() is False       # fresh plan: all zero
        if breach is not None:
            _set_breach(plan, w, breach)
        assert probe.overloaded() is tripped
        if breach:
            _set_breach(plan, w, 0)              # the breach clears
            assert probe.overloaded() is False
    finally:
        w.close()
        Workspace.unlink_name(plan["wksp"]["name"])


@pytest.mark.parametrize("metric,breach,tripped",
                         list(DOOR_CASES.values()), ids=list(DOOR_CASES))
def test_shed_slo_poll_trips_the_door_only_on_a_breach(metric, breach,
                                                       tripped):
    """The ingest doors' housekeeping poll: a set slo_breach gauge
    trips the gate into overload for its hold window; a clear gauge, or
    a topology with no metric tile, leaves the door alone. The probe
    is made once per tile and kept on its context."""
    from types import SimpleNamespace

    from firedancer_tpu.disco.tiles import _shed_slo_poll
    from firedancer_tpu.runtime import Workspace
    plan = _door_plan(metric, "s")
    w = Workspace(plan["wksp"]["name"], plan["wksp"]["size"],
                  create=False)
    try:
        if breach is not None:
            _set_breach(plan, w, breach)
        ctx = SimpleNamespace(plan=plan, wksp=w)
        gate = PeerGate({"overload_hold_s": 30.0})
        _shed_slo_poll(ctx, None)                # no gate: nothing read
        assert not hasattr(ctx, "_pressure_probe")
        _shed_slo_poll(ctx, gate)
        assert gate.overloaded() is tripped
        probe = ctx._pressure_probe
        _shed_slo_poll(ctx, gate)
        assert ctx._pressure_probe is probe
        assert gate.overloaded() is tripped
    finally:
        w.close()
        Workspace.unlink_name(plan["wksp"]["name"])
