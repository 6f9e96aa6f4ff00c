"""Layered TOML configuration -> topology.

The reference boots from a layered TOML stack: a 1,799-line default
config overridden by the user's file, parsed into a typed struct and
handed to the topology builder (ref: src/app/fdctl/config/default.toml,
src/app/shared/fd_config.h, fd_config_load). This module is that seam
re-expressed: TOML layers deep-merge (later layers win), and the merged
document declares the whole topology — links, tcaches, tiles with their
args — which `build_topology` materializes into the declarative
`Topology` builder (disco/topo.py).

Schema:

    [topology]
    name = "demo"            # shm namespace (default: file stem + pid)
    wksp_size = 16777216

    [[link]]
    name = "synth_verify"
    depth = 128              # frags (power of two)
    mtu = 1280

    [[tcache]]
    name = "dedup_tc"
    depth = 4096

    [[tile]]
    name = "verify"
    kind = "verify"          # registry kind (disco/tiles.py)
    ins = ["synth_verify"]
    outs = ["verify_dedup"]
    batch = 32               # every other key = tile arg, verbatim
    # tile_cnt = 2           # rr-sharded scale-out: expands into tiles
    #                        # verify0/verify1 (rr_cnt/rr_idx auto-set),
    # outs = ["vd0", "vd1"]  # one declared out link PER shard (SPMC);
    # cpu0 = 2               # optional: pin shard i to core cpu0+i;
    # tcache = ["tc0","tc1"] # a list-valued tcache distributes one
    #                        # per shard (other args are shared)

    [tile.supervise]         # per-tile restart policy (supervise.py)
    policy = "restart"       # "fail_fast" (default) | "restart"
    backoff_s = 0.05         # first respawn delay (doubles, capped
    backoff_max_s = 1.0      #  at backoff_max_s)
    max_restarts = 3         # within window_s -> circuit breaker
    window_s = 30.0
    wedge_timeout_s = 2.0    # heartbeat/fseq-progress staleness
                             #  deadline (omit to disable watchdog)

    [topology.supervise]     # optional topology-wide defaults,
    policy = "restart"       #  deep-merged under each tile's table

    [trace]                  # fdtrace flight recorder (trace/recorder.py)
    enable = true            # default false: untraced topologies pay
    depth = 2048             #  NOTHING per frag (hooks stay None)
    sample = 1               # record every Nth frag-scoped event
    tiles = ["verify"]       # optional allowlist (default: all tiles)

    [tile.trace]             # per-tile override (opt out/in, depth,
    sample = 16              #  sample) — highest precedence

    [prof]                   # fdprof continuous profiler (prof/recorder.py)
    enable = true            # default false: unprofiled tiles pay one
    hz = 97                  #  attribute check, no sampler thread
    slots = 256              # folded-stack table entries (power of two)
    ring = 2048              # timestamped sample ring (power of two)
    tiles = ["verify"]       # optional allowlist (default: all tiles)
    capture_ms = 200.0       # device-trace window length
    breach_capture = ["verify"]  # SLO breach -> device capture here

    [tile.prof]              # per-tile override (opt out/in, hz,
    hz = 29                  #  slots, ring, stack_depth)

    [slo]                    # service-level objectives (disco/slo.py),
    fast_window_s = 5.0      #  evaluated by the metric tile; breaches
    slow_window_s = 60.0     #  flip its slo_breach gauge, leave an
    burn_fast = 1.0          #  EV_SLO trace event, and dump next to
    burn_slow = 0.5          #  the supervisor black boxes

    [[slo.target]]           # one objective per table (merged by name
    name = "verify-latency"  #  across layers); expr grammar:
    expr = "verify.work p99 < 500us"   # <source> [agg] <op> <threshold>
                             #  sources: tile.metric,
                             #  tile.wait|work|tpu|stall|gc,
                             #  link.<link>.<counter>

    [shed]                   # front-door policing (disco/shed.py):
    rate_pps = 1000.0        #  per-peer token buckets, bounded peer
    max_peers = 4096         #  table, stake-weighted overload shedding
    min_stake = 1            #  — read by the ingest tiles (sock/quic/
                             #  gossip); [shed.stakes] maps peer keys
                             #  ("ip:port" / origin hex) to stake

    [tile.shed]              # per-tile override (same keys; highest
    rate_pps = 50.0          #  precedence, like [tile.trace])

    [witness]                # fdwitness sweep plan (witness/plan.py):
    stages = ["kernel_vps"]  #  ordered stage subset, watch-mode
    park_s = 30.0            #  backoff, per-stage deadlines; read by
    park_max_s = 360.0       #  tools/fdwitness, not the topology

    [witness.stage.kernel_vps]   # per-stage override: enable,
    timeout_s = 900.0            #  timeout_s, cmd (argv), env

    [[tile.chaos.events]]    # seeded fault plan (utils/chaos.py):
    action = "crash"         #  crash | freeze_hb | wedge | stall_fseq
    at_rx = 24               #  | fail_dispatch (verify tile); fire at
                             #  stem iteration (at_iter) or cumulative
                             #  frags consumed (at_rx); [lo, hi] picks
                             #  seeded-uniform from tile.chaos.seed;
                             #  traffic plans (flood_forged | flood_dup
                             #  | flood_torsion | flood_malformed_quic
                             #  | flood_crds_spam) add frames= + seed=
                             #  (+ pool= distinct payloads, default 8)

Unknown top-level sections are rejected (typo safety — the reference
validates its config the same way, fd_config_validate); a bad
supervise table fails topology build before launch.
"""
from __future__ import annotations

import os

try:
    import tomllib
except ModuleNotFoundError:          # py<3.11
    try:
        import tomli as tomllib
    except ModuleNotFoundError:
        try:                         # last resort: setuptools' vendored
            from setuptools._vendor import tomli as tomllib
        except ModuleNotFoundError as e:
            raise ModuleNotFoundError(
                "no TOML parser available on this Python (<3.11): "
                "install 'tomli'") from e

_TOP_SECTIONS = {"topology", "link", "tcache", "tile", "trace", "slo",
                 "prof", "shed", "witness", "funk", "replay",
                 "snapshot", "flight"}


def _deep_merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out


def _merge_named_lists(base: list, over: list, where: str) -> list:
    """[[link]]/[[tile]] arrays merge by `name`: same-name entries
    deep-merge (override layer wins per key), new names append."""
    out = {}
    for src, entries in (("base", base), (where, over)):
        for e in entries:
            name = e.get("name")
            if not isinstance(name, str):
                raise ValueError(
                    f"{src}: array-of-tables entry missing 'name': {e}")
            out[name] = _deep_merge(out.get(name, {}), e)
    return list(out.values())


def load_config(*paths, overrides: dict | None = None) -> dict:
    """Parse + deep-merge TOML layers left-to-right (later wins), then
    apply the `overrides` dict (the -D command-line escape hatch, same
    by-name merge semantics for link/tcache/tile arrays as TOML
    layers)."""
    cfg: dict = {}
    layers = [(p, None) for p in paths]
    if overrides:
        layers.append(("<overrides>", overrides))
    for p, preparsed in layers:
        if preparsed is None:
            with open(p, "rb") as f:
                layer = tomllib.load(f)
        else:
            layer = preparsed
        bad = set(layer) - _TOP_SECTIONS
        if bad:
            raise ValueError(f"{p}: unknown config sections {sorted(bad)}")
        for key in ("link", "tcache", "tile"):
            if key in layer:
                cfg[key] = _merge_named_lists(cfg.get(key, []),
                                              layer[key], str(p))
        for key in ("topology", "trace", "slo", "prof", "shed",
                    "witness", "funk", "replay", "snapshot",
                    "flight"):
            if key in layer:
                merged = _deep_merge(cfg.get(key, {}), layer[key])
                if key == "slo" and "target" in layer[key]:
                    # [[slo.target]] arrays merge by name like
                    # [[link]]/[[tile]]: an overlay can tighten one
                    # objective without restating the rest
                    merged["target"] = _merge_named_lists(
                        cfg.get(key, {}).get("target", []),
                        layer[key]["target"], str(p))
                cfg[key] = merged
    return cfg


def _validate_tile_keys(t: dict):
    """Reject unknown [[tile]] keys with a did-you-mean hint. The key
    registry is shared with fdlint (lint/registry.py) — the linter's
    dangling-ref checks and this schema gate stay in sync by
    construction. A typo'd arg key used to pass through silently as a
    tile arg the adapter never reads."""
    from ..lint import registry as reg
    kind = t["kind"]
    known = reg.known_keys(kind)
    if not known:
        raise ValueError(
            f"[[tile]] {t.get('name')!r}: unknown kind {kind!r}"
            + reg.suggest(str(kind), reg.TILE_ARGS))
    bad = set(t) - known
    if bad:
        key = sorted(bad)[0]
        raise ValueError(
            f"[[tile]] {t.get('name')!r} (kind {kind!r}): unknown "
            f"key(s) {sorted(bad)}" + reg.suggest(key, known))


def build_topology(cfg: dict, name: str | None = None):
    """Merged config -> Topology (unbuilt; caller runs .build())."""
    from ..disco import Topology

    top = cfg.get("topology", {})
    # [trace] flight-recorder section — validated here (fail at config
    # load with a did-you-mean, like every other schema gate) and again
    # by topo.build
    from ..trace import normalize_trace
    trace_cfg = cfg.get("trace")
    if trace_cfg is not None:
        normalize_trace(trace_cfg)
    # [slo] objectives — schema-validated here (fail at config load
    # with a did-you-mean); target references resolve at topo.build
    # once the declared tiles/links/metrics are known
    from ..disco.slo import normalize_slo
    slo_cfg = cfg.get("slo")
    if slo_cfg is not None:
        normalize_slo(slo_cfg)
    # [prof] continuous profiler — same gate (tiles/breach_capture
    # references resolve at topo.build)
    from ..prof import normalize_prof
    prof_cfg = cfg.get("prof")
    if prof_cfg is not None:
        normalize_prof(prof_cfg)
    # [shed] front-door policing — same gate (disco/shed.py is the one
    # validator; per-tile `shed` overrides validate at topo.build)
    from ..disco.shed import normalize_shed
    shed_cfg = cfg.get("shed")
    if shed_cfg is not None:
        normalize_shed(shed_cfg)
    # [witness] sweep plan — same gate (witness/plan.py is the one
    # validator; the section configures tools/fdwitness, not the
    # topology, but a typo'd stage name must still fail at load with a
    # did-you-mean, not on the chip machine mid-sweep)
    from ..witness.plan import normalize_witness
    wit_cfg = cfg.get("witness")
    if wit_cfg is not None:
        normalize_witness(wit_cfg)
    # [funk] account store — same gate (funk/shmfunk.py is the one
    # validator; backend "shm" makes topo.build carve the store region)
    from ..funk.shmfunk import normalize_funk
    funk_cfg = cfg.get("funk")
    if funk_cfg is not None:
        normalize_funk(funk_cfg)
    # [replay]/[snapshot] follower surface — same gate (tiles/replay.py
    # and tiles/snapshot.py are the one validator each)
    from ..tiles.replay import normalize_replay
    replay_cfg = cfg.get("replay")
    if replay_cfg is not None:
        normalize_replay(replay_cfg)
    from ..tiles.snapshot import normalize_snapshot
    snap_cfg = cfg.get("snapshot")
    if snap_cfg is not None:
        normalize_snapshot(snap_cfg)
    # [flight] durable telemetry archive — same gate (flight/__init__
    # is the one validator; the recorder tile reads the normalized
    # section off the plan)
    from ..flight import normalize_flight
    flight_cfg = cfg.get("flight")
    if flight_cfg is not None:
        normalize_flight(flight_cfg)
    topo = Topology(name or top.get("name", f"cfg{os.getpid()}"),
                    wksp_size=int(top.get("wksp_size", 1 << 26)),
                    trace=trace_cfg, slo=slo_cfg, prof=prof_cfg,
                    shed=shed_cfg, funk=funk_cfg, replay=replay_cfg,
                    snapshot=snap_cfg, flight=flight_cfg)
    for ln in cfg.get("link", []):
        topo.link(ln["name"], depth=int(ln.get("depth", 128)),
                  mtu=int(ln.get("mtu", 1280)))
    for tc in cfg.get("tcache", []):
        topo.tcache(tc["name"], depth=int(tc.get("depth", 4096)))
    default_sup = top.get("supervise")
    for t in cfg.get("tile", []):
        if "kind" not in t:
            raise ValueError(f"[[tile]] {t.get('name')!r}: missing 'kind'")
        _validate_tile_keys(t)
        args = {k: v for k, v in t.items()
                if k not in ("name", "kind", "ins", "outs")}
        if default_sup:
            # topology-wide supervision defaults; the tile's own table
            # wins per key (validated by topo.build via supervise.py)
            args["supervise"] = _deep_merge(default_sup,
                                            args.get("supervise", {}))
        tile_cnt = int(args.pop("tile_cnt", 1) or 1)
        cpu0 = args.pop("cpu0", None)
        if tile_cnt > 1:
            # rr-sharded scale-out (verify_tile_cnt as config): one
            # [[tile]] stanza expands into tile_cnt round-robin shards
            # sharing the ins, one declared out link per shard
            topo.sharded_tile(t["name"], t["kind"], tile_cnt,
                              ins=t.get("ins", ()),
                              outs=t.get("outs", ()), cpu0=cpu0,
                              **args)
        else:
            if cpu0 is not None:
                # cpu0 on an unsharded tile still pins it (shard 0)
                args["cpu_idx"] = int(cpu0)
            topo.tile(t["name"], t["kind"], ins=t.get("ins", ()),
                      outs=t.get("outs", ()), **args)
    return topo
