"""The tile-arg key registry: one table shared by fdlint's graph
analyzer (dangling-reference checks) and `app/config.py` (unknown-key
rejection with a did-you-mean hint).

Every adapter in disco/tiles.py reads its args with `args.get(...)` /
`args[...]`; this table is the static mirror of those reads. A key's
value classifies what it references so the linter can resolve it
against the topology:

    None        plain value, nothing to resolve
    IN          a link name that must be among the tile's ins
    OUT         a link name that must be among the tile's outs
    IN_LIST     list of link names, each among the tile's ins
    OUT_LIST    list of link names, each among the tile's outs
    TCACHE      a tcache name declared in the topology
    TILE        another tile's name

Keys every tile understands (consumed by the stem/launcher/builder,
not the adapter) live in COMMON_KEYS.
"""
from __future__ import annotations

IN, OUT, IN_LIST, OUT_LIST, TCACHE, TILE = (
    "in", "out", "in[]", "out[]", "tcache", "tile")

# consumed by topo.build / launch.tile_main / stem, valid on any tile
COMMON_KEYS: dict[str, str | None] = {
    "supervise": None,      # disco/supervise.py policy table
    "chaos": None,          # utils/chaos.py fault plan
    "trace": None,          # trace/recorder.py per-tile override table
    "prof": None,           # prof/recorder.py per-tile override table
    "shed": None,           # disco/shed.py per-tile policing override
    "cpu_idx": None,        # launch: sched_setaffinity pin
    "sandbox": None,        # launch: utils/sandbox hardening
    "sandbox_files": None,
    "lazy_ns": None,        # stem: pinned housekeeping cadence
    "lazy_auto": None,      # stem: depth-derived cadence
}

# [trace] topology-section keys (mirror of trace/recorder.py
# TRACE_DEFAULTS / TILE_TRACE_KEYS — tests/test_trace.py keeps the
# mirror honest). `tiles` entries are tile-name references, resolved by
# the graph analyzer's bad-trace check.
TRACE_SECTION_KEYS = ("enable", "depth", "sample", "tiles")
TILE_TRACE_KEYS = ("enable", "depth", "sample")

# [prof] topology-section keys (mirror of prof/recorder.py
# PROF_DEFAULTS / TILE_PROF_KEYS — tests/test_prof.py keeps the mirror
# honest). `tiles`/`breach_capture` entries are tile-name references,
# resolved by the graph analyzer's bad-prof check.
PROF_SECTION_KEYS = ("enable", "hz", "slots", "ring", "stack_depth",
                     "tiles", "capture_ms", "breach_capture")
TILE_PROF_KEYS = ("enable", "hz", "slots", "ring", "stack_depth")

# [slo] topology-section keys (mirror of disco/slo.py SLO_DEFAULTS /
# TARGET_KEYS — tests/test_metrics.py keeps the mirror honest).
# Target expressions reference tiles/metrics/links, resolved by the
# graph analyzer's bad-slo check.
SLO_SECTION_KEYS = ("fast_window_s", "slow_window_s", "burn_fast",
                    "burn_slow", "target")
SLO_TARGET_KEYS = ("name", "expr", "fast_window_s", "slow_window_s",
                   "burn_fast", "burn_slow")

# [shed] topology-section keys (mirror of disco/shed.py SHED_DEFAULTS /
# TILE_SHED_KEYS — tests/test_shed.py keeps the mirror honest). The
# per-tile `shed` override (COMMON_KEYS) takes the same table; both are
# validated by normalize_shed at config load, topo.build, and the graph
# analyzer's bad-shed rule.
SHED_SECTION_KEYS = ("enable", "rate_pps", "burst", "max_peers",
                     "min_stake", "overload_hold_s", "stakes")
TILE_SHED_KEYS = SHED_SECTION_KEYS

# [funk] topology-section keys (mirror of funk/shmfunk.py
# FUNK_DEFAULTS — tests/test_exec_tile.py keeps the mirror honest).
# Validated by normalize_funk at config load, topo.build (which carves
# the shm store for backend="shm"), and the graph analyzer's bad-funk
# rule.
FUNK_SECTION_KEYS = ("backend", "rec_max", "txn_max", "heap_mb")

# [replay] topology-section keys (mirror of tiles/replay.py
# REPLAY_DEFAULTS — tests/test_follower.py keeps the mirror honest).
# Validated by normalize_replay at config load, topo.build, and the
# graph analyzer's bad-replay rule.
REPLAY_SECTION_KEYS = ("exec_tile_cnt", "redispatch_s", "verify_poh",
                       "hashes_per_tick")

# [snapshot] topology-section keys (mirror of tiles/snapshot.py
# SNAPSHOT_DEFAULTS — tests/test_follower.py keeps the mirror honest).
# Validated by normalize_snapshot at config load, topo.build, and the
# graph analyzer's bad-snapshot rule.
SNAPSHOT_SECTION_KEYS = ("path", "every_slots", "min_slot", "compress",
                         "chunk")

# [flight] topology-section keys (mirror of flight/__init__.py
# FLIGHT_DEFAULTS — tests/test_flight.py keeps the mirror honest).
# Validated by normalize_flight at config load, topo.build, and the
# graph analyzer's bad-flight rule.
FLIGHT_SECTION_KEYS = ("dir", "segment_mb", "retain_mb", "hz",
                       "sources", "incident_window_s", "node_id")

# [witness] topology-section keys (mirror of witness/plan.py
# WITNESS_DEFAULTS / WITNESS_STAGE_KEYS — tests/test_witness.py keeps
# the mirror honest). Stage names in `stages` / [witness.stage.<name>]
# resolve against the witness/plan.py STAGES catalog; validated by
# normalize_witness at config load, plan build (fdwitness run/dry-run),
# and the graph analyzer's bad-witness rule.
WITNESS_SECTION_KEYS = ("stages", "out_dir", "round", "stage_timeout_s",
                        "probe_timeout_s", "park_s", "park_max_s",
                        "keep_going", "report", "stage")
WITNESS_STAGE_KEYS = ("enable", "timeout_s", "cmd", "env")

TILE_ARGS: dict[str, dict[str, str | None]] = {
    "synth": {"count": None, "burst": None, "unique": None, "seed": None,
              "rate_tps": None},
    "verify": {"batch": None, "max_len": None, "tcache": TCACHE,
               "device_retries": None, "device_timeout_s": None,
               "device_fail_limit": None, "rr_cnt": None, "rr_idx": None,
               "devices": None, "coalesce_us": None,
               # rr-sharded scale-out (config-side expansion in
               # app/config.py: tile_cnt shards, one out link each,
               # optional cpu0+i core pinning; a list-valued tcache
               # distributes per shard)
               "tile_cnt": None, "cpu0": None,
               # front-door bulk pre-filter (r14): mode =
               # "bulk_prefilter" gates every strict dispatch behind
               # the RLC batch kernel — fail -> bisect, shed garbage
               # halves under ingest saturation (tiles/verify.py)
               "mode": None, "prefilter_shed": None},
    "dedup": {"tcache": TCACHE, "batch": None},
    "pack": {"txn_in": IN, "bank_links": OUT_LIST, "done_links": IN_LIST,
             "slot_in": IN, "bundle_in": IN, "slot_ms": None,
             "batch": None, "max_txn_per_microblock": None,
             "wave": None,
             # resolved_in: txn_in carries RESOLVED frames from a
             # resolv tile (account sets + cost precomputed upstream —
             # pack/scheduler.py meta_from_resolved), the reference's
             # resolv->pack seam (src/discof/resolv/)
             "resolved_in": None},
    "bank": {"exec": None, "poh_link": OUT, "forward_payloads": None,
             "slots_per_epoch": None, "genesis_ckpt": None,
             "genesis": None, "genesis_synth": None, "rpc_port": None,
             "ws_port": None, "wave": None, "redispatch_s": None,
             # exec tile fan-out (r16): one dispatch out link + one
             # completion in link per exec shard; the bank keeps wave
             # scheduling/commit ordering/poh handoff, execution runs
             # in the exec tile family over the shm funk store
             "exec_links": OUT_LIST, "exec_done": IN_LIST},
    "sock": {"port": None, "bind_addr": None, "batch": None, "mtu": None},
    "quic": {"port": None, "bind_addr": None, "batch": None, "mtu": None},
    "poh": {"hashes_per_tick": None, "ticks_per_slot": None,
            "seed": None, "slot_link": OUT},
    "shred": {"mode": None, "req": OUT, "resp": IN,
              "shreds_link": OUT, "batches_link": OUT,
              "turbine_in": IN, "identity_hex": None, "cluster": None,
              "shred_version": None, "fanout": None, "flush_bytes": None,
              "drop_slot_every": None, "leader_pubkey_hex": None},
    "sign": {"seed": None, "clients": None},   # clients resolved specially
    "tower": {"total_stake": None},
    "repair": {"req": OUT, "resp": IN, "identity_hex": None,
               "port": None, "bind_addr": None, "peers": None,
               "root_slot": None},
    "replay": {"genesis": None, "genesis_synth": None,
               "hashes_per_tick": None, "verify_poh": None,
               "slots_per_epoch": None,
               # follower fan-out (r17): same shape as the bank's exec
               # family seam, plus the catch-up surface — leader
               # bank-hash pins, snapshot-gated cold start, periodic
               # snapshot writing (defaults from [replay]/[snapshot])
               "exec_links": OUT_LIST, "exec_done": IN_LIST,
               "redispatch_s": None, "expected": None,
               "wait_restore": None, "snapshot_path": None,
               "snapshot_every": None, "snapshot_compress": None},
    "send": {"req": OUT, "resp": IN, "identity_hex": None,
             "vote_account_hex": None, "dest": None},
    "archiver": {"path": None},
    "playback": {"path": None},
    "gossip": {"seed": None, "port": None, "bind_addr": None,
               "entrypoints": None, "publish": None,
               "device_verify": None,
               # gossvf bulk pre-filter (r14): front the per-packet
               # device sigcheck with the RLC batch kernel
               # (gossip/gossvf.py mode="bulk")
               "gossvf_bulk": None},
    "snapld": {"path": None, "chunk": None, "stale_path": None},
    "snapdc": {},
    "snapin": {"format": None, "min_slot": None},
    "metric": {"port": None, "bind_addr": None, "healthz_stale_s": None},
    # flight recorder tile (r19): all configuration rides the plan's
    # [flight] section — the adapter reads no args at all
    "flight": {},
    "bundle": {"engine": None, "path": None, "authority": None},
    "plugin": {"sock_path": None, "data_hex_max": None},
    "netlnk": {},
    "vinyl": {"path": None, "gc": None},
    "gui": {"port": None, "bind_addr": None, "tps_tile": TILE,
            "tps_metric": None,                 # validated against TILE's kind
            # fdgui v2 knobs (gui/schema.py GUI_DEFAULTS is the
            # authoritative mirror — tests/test_gui.py keeps it honest)
            "ws_max_clients": None, "ws_queue": None,
            "ws_sndbuf": None, "bench_glob": None,
            "report_on_halt": None},
    "cswtch": {},
    # exec tile family (r16, ref: src/discof/exec/fd_exec_tile.c):
    # consumes the bank's conflict-group dispatch frames, executes via
    # the WaveExecutor against the shm funk store, publishes
    # completion frags; declared via tile_cnt (sharded_tile) with a
    # per-shard ins distribution
    "exec": {"batch": None, "rr_cnt": None, "rr_idx": None,
             "tile_cnt": None, "cpu0": None},
    # resolv tile (r16, ref: src/discof/resolv/): ahead of pack —
    # parses txns, resolves v0 ALUT loads + checks the fee payer
    # against the shm store, emits RESOLVED frames
    "resolv": {"batch": None, "fee_payer_check": None},
    "ipecho": {"shred_version": None, "port": None, "bind_addr": None},
    "pcap": {"path": None, "realtime": None, "loop": None},
    "sink": {"batch": None},
}


def known_keys(kind: str) -> set[str]:
    """All valid [[tile]] keys for a kind (structural + common + args);
    empty set means the kind itself is unknown."""
    if kind not in TILE_ARGS:
        return set()
    return ({"name", "kind", "ins", "outs"} | set(COMMON_KEYS)
            | set(TILE_ARGS[kind]))


def suggest(key: str, candidates) -> str:
    """did-you-mean suffix for an unknown key/kind ('' if no close
    match)."""
    import difflib
    close = difflib.get_close_matches(key, sorted(candidates), n=1)
    return f" (did you mean {close[0]!r}?)" if close else ""


# Frame-growth contracts for the mtu-underflow rule: tiles that re-wrap
# an in-link payload into a larger out-link frame, mirrored from the
# adapters' own boot-time checks (disco/tiles.py Bank/Poh) and the
# verbatim-forwarding hot paths (verify/dedup publish the original
# payload). Checked statically so a too-small link fails review, not
# boot (or worse, mid-flight publish).
FORWARD_VERBATIM = {"verify", "dedup"}    # every out mtu >= max in mtu
BANK_POH_GROWTH = -20 + 42     # microblock hdr 20 -> poh frame hdr 42
POH_ENTRY_GROWTH = -42 + 116   # poh frame hdr 42 -> entry frame hdr 116

# Minimum out-link mtus per wire family, for the wire-mtu rule (the
# r16/r17 extension of the growth contracts above): a link too small
# for even one frame of its producer kind's wire is a review-time
# finding, not a publish assert. Mirrored from the frame layouts in
# disco/tiles.py (exec wire), tiles/shred.py (slice + shred wire) and
# tiles/tower.py (vote frame) — lint/abi.py's WIRE_CONTRACTS catalog
# pins the same formats, and tests/test_lint.py recomputes these from
# the live struct sizes.
EXEC_DISPATCH_MIN_MTU = 18 + 80   # <QQH> header + one 80B txn row
EXEC_DONE_MIN_MTU = 16            # <QII> completion frame
SLICE_MIN_MTU = 13 + 1            # <QIB> slice header + >=1 payload byte
SHRED_WIRE_MIN_MTU = 0x49 + 4     # fixed shred header through the idx u32
TOWER_WIRE_MIN_MTU = 1 + 32 + 8 + 32   # vote frame (largest fixed frame)

# TILE_ARGS keys consumed OUTSIDE the adapter class (config-side
# expansion in app/config.py, topo.build sizing, launch) — the
# registry-drift analyzer exempts these from its "registered but never
# consumed by the adapter" direction. Every entry names its consumer.
EXTERNAL_ARG_KEYS: dict[str, tuple[str, ...]] = {
    # app/config.py sharded_tile expansion: tile_cnt shards, cpu0+i
    # core pinning, per-shard out-link/tcache distribution
    "verify": ("tile_cnt", "cpu0"),
    # rr_cnt/rr_idx are stamped onto every shard by Topology's generic
    # shard expansion (disco/topo.py); exec ignores them (it shards by
    # dedicated per-shard exec_links, not round-robin seq filtering)
    "exec": ("tile_cnt", "cpu0", "rr_cnt", "rr_idx"),
    # the gui adapter hands its args dict wholesale to
    # gui/schema.py normalize_gui, which validates and consumes every
    # key at config load
    "gui": ("bench_glob", "bind_addr", "port", "report_on_halt",
            "tps_metric", "tps_tile", "ws_max_clients", "ws_queue",
            "ws_sndbuf"),
}
