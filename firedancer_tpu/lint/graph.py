"""Topology graph analysis: prove the dataflow graph before it runs.

The whole tile graph — links, credit flow, supervision policy — is
statically knowable from config (the reference's stance: fd_topob
validates at build; here review-time is even earlier than build-time).
This analyzer loads `cfg/*.toml` through `app/config.py` (layer
directives honored, see below) or accepts a programmatic `Topology`
via `lint_topology`, and checks:

  * every non-external link has exactly one producer and >=1 consumer
  * depths are powers of two; out-link mtus absorb the producing
    tile's worst-case frame (verify/dedup forward verbatim; bank->poh
    and poh->entry re-wrap with known header growth)
  * no backpressure cycles: an edge A->B exists when B reliably
    consumes a link A produces (B's fseq gates A's credits); a cycle
    means every member can end up waiting on the next — a static
    deadlock candidate
  * reliable consumers actually publish progress (their adapter kind
    defines in_seqs) — otherwise the producer wedges on a frozen fseq
    and only the FSEQ_STALE supervision path could ever unwedge it
  * supervise/chaos tables satisfy the disco/supervise.py and
    utils/chaos.py schemas, and stall_fseq targets a link the tile
    consumes
  * args that name links/tiles/tcaches resolve (registry.TILE_ARGS)

Overlay configs (files meant to be layered over another TOML, like
cfg/cluster-demo.toml) declare their base with a directive comment:

    # fdlint: layers=default.toml

paths are relative to the overlay file; the linter loads the stack in
order before analyzing.
"""
from __future__ import annotations

import re

from .core import Finding, filter_suppressed, finding
from . import registry as reg


# ---------------------------------------------------------------------------
# model extraction
# ---------------------------------------------------------------------------

def _norm_ins(ins) -> list[tuple[str, bool]]:
    out = []
    for i in ins or ():
        if isinstance(i, str):
            out.append((i, True))
        elif isinstance(i, dict):
            out.append((i["link"], bool(i.get("reliable", True))))
        elif isinstance(i, (list, tuple)) and i and \
                all(isinstance(e, str) for e in i):
            # per-shard distribution entry (sharded_tile / tile_cnt in
            # config): shard k consumes i[k]. The un-expanded model
            # consumes them all — folding to i[0] would orphan the
            # other shards' links into dead-link false positives.
            out.extend((e, True) for e in i)
        else:
            out.append((i[0], bool(i[1])))
    return out


def model_from_config(cfg: dict) -> dict:
    links = {ln["name"]: {"depth": int(ln.get("depth", 128)),
                          "mtu": int(ln.get("mtu", 1280)),
                          "external": bool(ln.get("external", False))}
             for ln in cfg.get("link", [])}
    tcaches = {tc["name"] for tc in cfg.get("tcache", [])}
    default_sup = cfg.get("topology", {}).get("supervise")
    tiles = {}
    for t in cfg.get("tile", []):
        args = {k: v for k, v in t.items()
                if k not in ("name", "kind", "ins", "outs")}
        if default_sup:
            merged = dict(default_sup)
            merged.update(args.get("supervise", {}) or {})
            args["supervise"] = merged
        tiles[t["name"]] = {"kind": t.get("kind"),
                            "ins": _norm_ins(t.get("ins")),
                            "outs": list(t.get("outs", ())),
                            "args": args}
    return {"links": links, "tcaches": tcaches, "tiles": tiles,
            "trace": cfg.get("trace"), "slo": cfg.get("slo"),
            "prof": cfg.get("prof"), "shed": cfg.get("shed"),
            "witness": cfg.get("witness"), "funk": cfg.get("funk"),
            "replay": cfg.get("replay"),
            "snapshot": cfg.get("snapshot"),
            "flight": cfg.get("flight")}


def model_from_topology(topo) -> dict:
    """disco.topo.Topology (unbuilt) -> the same model shape."""
    links = {ln: {"depth": s.depth, "mtu": s.mtu, "external": s.external}
             for ln, s in topo.links.items()}
    tiles = {tn: {"kind": t.kind,
                  "ins": [(i["link"], bool(i["reliable"]))
                          for i in t.ins],
                  "outs": list(t.outs), "args": dict(t.args)}
             for tn, t in topo.tiles.items()}
    return {"links": links, "tcaches": set(topo.tcaches),
            "tiles": tiles, "trace": getattr(topo, "trace", None),
            "slo": getattr(topo, "slo", None),
            "prof": getattr(topo, "prof", None),
            "shed": getattr(topo, "shed", None),
            "witness": getattr(topo, "witness", None),
            "funk": getattr(topo, "funk", None),
            "replay": getattr(topo, "replay", None),
            "snapshot": getattr(topo, "snapshot", None),
            "flight": getattr(topo, "flight", None)}


# ---------------------------------------------------------------------------
# line attribution (best-effort: the `name = "..."` line in the TOML)
# ---------------------------------------------------------------------------

class _Lines:
    """Attribute an entity (link/tile name) to the layer file + line
    where its `name = "..."` appears — for an overlay config the
    finding points INTO the base layer, so one inline suppression
    covers every stack that includes it. Later layers win (an overlay
    redeclaring the entity owns the finding)."""

    def __init__(self, sources: list[tuple[str, str]], default: str):
        self.sources = sources
        self.default = default
        self._cache: dict[str, tuple[str, int]] = {}

    def of(self, entity: str) -> tuple[str, int]:
        if entity not in self._cache:
            pat = re.compile(
                r'^\s*name\s*=\s*"' + re.escape(entity) + r'"', re.M)
            hit = (self.default, 0)
            for path, source in self.sources:
                m = pat.search(source)
                if m:
                    hit = (path, source.count("\n", 0, m.start()) + 1)
            self._cache[entity] = hit
        return self._cache[entity]


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

LAYERS_RE = re.compile(r"^#\s*fdlint:\s*layers=(\S+)", re.M)


def lint_config_file(path: str) -> list[Finding]:
    """One TOML file (with its declared base layers) -> findings."""
    import os
    from ..app.config import load_config
    with open(path) as f:
        source = f.read()
    m = LAYERS_RE.search(source)
    stack = []
    if m:
        base_dir = os.path.dirname(os.path.abspath(path))
        stack = [os.path.join(base_dir, p)
                 for p in m.group(1).split(",") if p]
    try:
        cfg = load_config(*stack, path)
    except Exception as e:
        return [finding("dangling-ref", path, 0,
                        f"config failed to load: {e}")]
    sources = []
    for p in stack + [path]:
        with open(p) as f:
            sources.append((p, f.read()))
    return _lint_model(model_from_config(cfg), sources, path)


def lint_config(cfg: dict, path: str,
                source: str = "") -> list[Finding]:
    return _lint_model(model_from_config(cfg), [(path, source)], path)


def lint_topology(topo, path: str = "<topology>") -> list[Finding]:
    """Programmatic Topology builds get the same static pass the TOML
    path gets (tests call this on fixtures before .build())."""
    return _lint_model(model_from_topology(topo), [(path, "")], path)


def _lint_model(model: dict, sources: list[tuple[str, str]],
                default_path: str) -> list[Finding]:
    findings = _check_model(model, default_path,
                            _Lines(sources, default_path))
    by_path = dict(sources)
    return [f for f in findings
            if f in filter_suppressed([f], by_path.get(f.path, ""))]


# ---------------------------------------------------------------------------
# the checks
# ---------------------------------------------------------------------------

def _emit(out: list, lines: _Lines, rule: str, entity: str, msg: str):
    p, ln = lines.of(entity)
    out.append(finding(rule, p, ln, msg))


def _check_model(model: dict, path: str, lines: _Lines) -> list[Finding]:
    out: list[Finding] = []
    links, tiles = model["links"], model["tiles"]
    from .contracts import adapter_summaries
    kinds = adapter_summaries()

    producers: dict[str, str] = {}
    consumers: dict[str, list[tuple[str, bool]]] = {}
    for tn, t in tiles.items():
        for ln in t["outs"]:
            if ln in producers:
                _emit(out, lines, "dup-producer", ln,
                      f"link {ln!r} produced by both "
                      f"{producers[ln]!r} and {tn!r}")
            producers.setdefault(ln, tn)
        for ln, rel in t["ins"]:
            consumers.setdefault(ln, []).append((tn, rel))

    # dead / orphan / shape
    for ln, spec in links.items():
        if not spec["external"]:
            if ln in producers and ln not in consumers:
                _emit(out, lines, "dead-link", ln,
                      f"link {ln!r} is produced by "
                      f"{producers[ln]!r} but never consumed")
            if ln in consumers and ln not in producers:
                _emit(out, lines, "orphan-link", ln,
                      f"link {ln!r} is consumed by "
                      f"{[c for c, _ in consumers[ln]]} but never "
                      f"produced")
        d = spec["depth"]
        if d <= 0 or d & (d - 1):
            _emit(out, lines, "depth-pow2", ln,
                  f"link {ln!r} depth {d} is not a positive power "
                  f"of two")
    # unknown links referenced in ins/outs
    for tn, t in tiles.items():
        for ln in t["outs"]:
            if ln not in links:
                _emit(out, lines, "dangling-ref", tn,
                      f"tile {tn!r}: out link {ln!r} is not declared")
        for ln, _ in t["ins"]:
            if ln not in links:
                _emit(out, lines, "dangling-ref", tn,
                      f"tile {tn!r}: in link {ln!r} is not declared")

    out.extend(_check_mtus(model, lines))
    out.extend(_check_cycles(model, producers, lines))
    out.extend(_check_tiles(model, kinds, lines))
    out.extend(_check_trace(model, path, lines))
    out.extend(_check_slo(model, kinds, path, lines))
    out.extend(_check_prof(model, path, lines))
    out.extend(_check_gui(model, lines))
    out.extend(_check_shed(model, path, lines))
    out.extend(_check_witness(model, path))
    out.extend(_check_funk(model, path))
    out.extend(_check_replay(model, path))
    out.extend(_check_snapshot(model, path))
    out.extend(_check_flight(model, path))
    return out


def _check_witness(model, path) -> list[Finding]:
    """[witness] section: the witness/plan.py schema gate (one
    validator, same as config load and fdwitness plan build) — unknown
    keys, unknown stage names, malformed per-stage overrides all land
    as review-time findings."""
    from ..witness.plan import normalize_witness
    out: list[Finding] = []
    spec = model.get("witness")
    if spec is not None:
        try:
            normalize_witness(spec)
        except Exception as e:
            out.append(finding("bad-witness", path, 0,
                               f"[witness]: {e}"))
    return out


def _check_funk(model, path) -> list[Finding]:
    """[funk] section: the funk/shmfunk.py schema gate (one validator,
    same as config load and topo.build's store carve) — unknown keys,
    unknown backend, out-of-range rec_max/txn_max/heap_mb all land as
    review-time findings with a did-you-mean."""
    from ..funk.shmfunk import normalize_funk
    out: list[Finding] = []
    spec = model.get("funk")
    if spec is not None:
        try:
            normalize_funk(spec)
        except Exception as e:
            out.append(finding("bad-funk", path, 0, f"[funk]: {e}"))
    return out


def _check_replay(model, path) -> list[Finding]:
    """[replay] section: the tiles/replay.py schema gate (one
    validator, same as config load and topo.build) — unknown keys,
    negative exec_tile_cnt, non-positive redispatch_s all land as
    review-time findings with a did-you-mean."""
    from ..tiles.replay import normalize_replay
    out: list[Finding] = []
    spec = model.get("replay")
    if spec is not None:
        try:
            normalize_replay(spec)
        except Exception as e:
            out.append(finding("bad-replay", path, 0, f"[replay]: {e}"))
    return out


def _check_snapshot(model, path) -> list[Finding]:
    """[snapshot] section: the tiles/snapshot.py schema gate (one
    validator, same as config load and topo.build) — unknown keys,
    negative every_slots/min_slot, undersized chunk all land as
    review-time findings with a did-you-mean."""
    from ..tiles.snapshot import normalize_snapshot
    out: list[Finding] = []
    spec = model.get("snapshot")
    if spec is not None:
        try:
            normalize_snapshot(spec)
        except Exception as e:
            out.append(finding("bad-snapshot", path, 0,
                               f"[snapshot]: {e}"))
    return out


def _check_flight(model, path) -> list[Finding]:
    """[flight] section: the flight/__init__.py schema gate (one
    validator, same as config load and topo.build) — unknown keys,
    empty dir, retention below one segment, out-of-range hz/node_id,
    unknown source families all land as review-time findings with a
    did-you-mean."""
    from ..flight import normalize_flight
    out: list[Finding] = []
    spec = model.get("flight")
    if spec is not None:
        try:
            normalize_flight(spec)
        except Exception as e:
            out.append(finding("bad-flight", path, 0,
                               f"[flight]: {e}"))
    return out


# tile kinds with an ingest door the shed gate can police (the only
# readers of an effective shed table — shed on anything else is dead
# config, flagged so a topo that THINKS it is protected actually is)
SHED_KINDS = {"sock", "quic", "gossip", "repair"}


def _check_shed(model, path, lines) -> list[Finding]:
    """[shed] section + per-tile `shed` overrides: the disco/shed.py
    schema gate (one validator, same as config load and topo.build),
    plus a dead-config check — a tile-level shed override on a kind
    that has no ingest door to police protects nothing."""
    from ..disco.shed import normalize_shed
    out: list[Finding] = []
    spec = model.get("shed")
    if spec is not None:
        try:
            normalize_shed(spec)
        except Exception as e:
            out.append(finding("bad-shed", path, 0, f"[shed]: {e}"))
    for tn, t in model["tiles"].items():
        if "shed" not in t["args"]:
            continue
        try:
            normalize_shed(t["args"]["shed"], per_tile=True)
        except Exception as e:
            _emit(out, lines, "bad-shed", tn, f"tile {tn!r}: {e}")
            continue
        if t["kind"] not in SHED_KINDS:
            _emit(out, lines, "bad-shed", tn,
                  f"tile {tn!r}: kind {t['kind']!r} has no ingest "
                  f"door to police — shed is only read by "
                  f"{sorted(SHED_KINDS)}")
    return out


def _check_gui(model, lines) -> list[Finding]:
    """[tile.gui] args: the fdgui schema gate (gui/schema.py is the
    one validator, same as topo.build) — ws queue/client bounds, knob
    types, unknown keys with a did-you-mean. The tps_tile/tps_metric
    REFERENCES stay under dangling-ref (_check_arg_refs), like every
    other registry-typed arg."""
    out: list[Finding] = []
    for tn, t in model["tiles"].items():
        if t["kind"] != "gui":
            continue
        from ..gui import normalize_gui
        try:
            normalize_gui(t["args"])
        except Exception as e:
            _emit(out, lines, "bad-gui", tn, f"tile {tn!r}: {e}")
    return out


def _check_trace(model, path, lines) -> list[Finding]:
    """[trace] section + [tile.trace] overrides: the fdtrace schema
    gate (trace/recorder.py is the one validator) plus tile-name
    resolution for the `tiles` allowlist."""
    from ..trace import normalize_trace
    out: list[Finding] = []
    spec = model.get("trace")
    if spec is not None:
        try:
            norm = normalize_trace(spec)
        except Exception as e:
            out.append(finding("bad-trace", path, 0, f"[trace]: {e}"))
        else:
            for tn in norm["tiles"] or ():
                if tn not in model["tiles"]:
                    _emit(out, lines, "bad-trace", tn,
                          f"[trace] tiles entry {tn!r} is not a "
                          f"declared tile"
                          + reg.suggest(str(tn), model["tiles"]))
    for tn, t in model["tiles"].items():
        if "trace" in t["args"]:
            try:
                normalize_trace(t["args"]["trace"], per_tile=True)
            except Exception as e:
                _emit(out, lines, "bad-trace", tn, f"tile {tn!r}: {e}")
    return out


def _check_prof(model, path, lines) -> list[Finding]:
    """[prof] section + [tile.prof] overrides: the fdprof schema gate
    (prof/recorder.py is the one validator) plus tile-name resolution
    for the `tiles` allowlist and the breach_capture list."""
    from ..prof import normalize_prof
    out: list[Finding] = []
    spec = model.get("prof")
    if spec is not None:
        try:
            norm = normalize_prof(spec)
        except Exception as e:
            out.append(finding("bad-prof", path, 0, f"[prof]: {e}"))
        else:
            for key in ("tiles", "breach_capture"):
                for tn in norm[key] or ():
                    if tn not in model["tiles"]:
                        _emit(out, lines, "bad-prof", tn,
                              f"[prof] {key} entry {tn!r} is not a "
                              f"declared tile"
                              + reg.suggest(str(tn), model["tiles"]))
    for tn, t in model["tiles"].items():
        if "prof" in t["args"]:
            try:
                normalize_prof(t["args"]["prof"], per_tile=True)
            except Exception as e:
                _emit(out, lines, "bad-prof", tn, f"tile {tn!r}: {e}")
    return out


def _check_slo(model, kinds, path, lines) -> list[Finding]:
    """[slo] section: the disco/slo.py schema gate (one validator,
    same as topo.build) plus target-source resolution against the
    DECLARED topology — tile metric slot names come from the adapter
    registry's static summaries, so a target naming a metric the tile
    kind never exports is a review-time finding with a did-you-mean."""
    from ..disco.slo import check_target, normalize_slo
    out: list[Finding] = []
    spec = model.get("slo")
    if spec is None:
        return out
    try:
        norm = normalize_slo(spec)
    except Exception as e:
        out.append(finding("bad-slo", path, 0, f"[slo]: {e}"))
        return out
    tiles_metrics = {
        tn: kinds.get(t["kind"], {}).get("metrics", [])
        for tn, t in model["tiles"].items()
    }
    for t in norm["target"]:
        err = check_target(t["parsed"], tiles_metrics, model["links"])
        if err:
            _emit(out, lines, "bad-slo", t["name"],
                  f"slo target {t['name']!r}: {err}")
    return out


def _check_mtus(model, lines) -> list[Finding]:
    """Frame-growth contracts (registry.py): a producing tile's
    worst-case frame must fit the out link."""
    out: list[Finding] = []
    links, tiles = model["links"], model["tiles"]

    def mtu(ln):
        return links[ln]["mtu"] if ln in links else None

    for tn, t in tiles.items():
        in_mtus = [mtu(ln) for ln, _ in t["ins"] if mtu(ln)]
        if not in_mtus:
            continue
        worst_in = max(in_mtus)
        kind, args = t["kind"], t["args"]
        if kind in reg.FORWARD_VERBATIM:
            for ln in t["outs"]:
                m = mtu(ln)
                if m is not None and m < worst_in:
                    _emit(out, lines, "mtu-underflow", ln,
                          f"link {ln!r} mtu {m} < {worst_in} ({kind} "
                          f"tile {tn!r} forwards in-payloads verbatim)")
        elif kind == "bank" and args.get("forward_payloads") and \
                args.get("poh_link") in links:
            need = worst_in + reg.BANK_POH_GROWTH
            m = mtu(args["poh_link"])
            if m is not None and m < need:
                _emit(out, lines, "mtu-underflow", args["poh_link"],
                      f"link {args['poh_link']!r} mtu {m} < {need} "
                      f"(bank {tn!r} re-wraps microblocks with "
                      f"forward_payloads: header 20 -> 42)")
        elif kind == "poh":
            entry = [ln for ln in t["outs"]
                     if ln != args.get("slot_link")]
            need = worst_in + reg.POH_ENTRY_GROWTH
            for ln in entry:
                m = mtu(ln)
                if m is not None and m < need:
                    _emit(out, lines, "mtu-underflow", ln,
                          f"link {ln!r} mtu {m} < {need} (poh {tn!r} "
                          f"re-wraps bank frames: header 42 -> 116)")
    out.extend(_check_wire_mtus(model, lines))
    return out


def _check_wire_mtus(model, lines) -> list[Finding]:
    """wire-mtu: fixed wire-family minimums per producer kind (the
    r16 exec wire, r17 snapshot stream, shred/tower wires) — the lint
    graph model attributes each cataloged wire to its topology links,
    so a link too small for one frame of its family fails review."""
    out: list[Finding] = []
    links, tiles = model["links"], model["tiles"]

    def mtu(ln):
        return links[ln]["mtu"] if ln in links else None

    def need(ln, floor, why):
        m = mtu(ln)
        if m is not None and m < floor:
            _emit(out, lines, "wire-mtu", ln,
                  f"link {ln!r} mtu {m} < {floor} ({why})")

    for tn, t in tiles.items():
        kind, args = t["kind"], t["args"]
        if kind in ("bank", "replay"):
            for ln in args.get("exec_links") or ():
                need(ln, reg.EXEC_DISPATCH_MIN_MTU,
                     f"{kind} {tn!r} exec dispatch: <QQH> header + "
                     f"one 80B txn row")
        elif kind == "exec":
            for ln in t["outs"]:
                need(ln, reg.EXEC_DONE_MIN_MTU,
                     f"exec {tn!r} completion frame <QII>")
        elif kind == "shred":
            if args.get("batches_link"):
                need(args["batches_link"], reg.SLICE_MIN_MTU,
                     f"shred {tn!r} slice frame <QIB> + payload")
            if args.get("shreds_link"):
                need(args["shreds_link"], reg.SHRED_WIRE_MIN_MTU,
                     f"shred {tn!r} wire: fixed header through idx")
        elif kind == "tower":
            for ln in t["outs"]:
                need(ln, reg.TOWER_WIRE_MIN_MTU,
                     f"tower {tn!r} vote frame (1+32+8+32)")
        elif kind == "snapld":
            chunk = args.get("chunk")
            if not isinstance(chunk, int):
                snap = model.get("snapshot") or {}
                chunk = snap.get("chunk") if isinstance(snap, dict) \
                    else None
            if isinstance(chunk, int):
                for ln in t["outs"]:
                    need(ln, chunk,
                         f"snapld {tn!r} publishes {chunk}B snapshot "
                         f"stream chunks ([snapshot].chunk)")
    return out


def _check_cycles(model, producers, lines) -> list[Finding]:
    """Reliable-consumption cycles. Edge A->B when B reliably consumes
    a link A produces: A's credits gate on B's fseq, so A waits on B;
    a cycle is mutual waiting — the static deadlock candidate."""
    out: list[Finding] = []
    edges: dict[str, set[str]] = {tn: set() for tn in model["tiles"]}
    for tn, t in model["tiles"].items():
        for ln, rel in t["ins"]:
            if rel and ln in producers:
                edges[producers[ln]].add(tn)

    color: dict[str, int] = {}
    stack: list[str] = []
    reported: set[frozenset] = set()

    def dfs(u: str):
        color[u] = 1
        stack.append(u)
        for v in sorted(edges[u]):
            if color.get(v) == 1:
                cyc = stack[stack.index(v):] + [v]
                key = frozenset(cyc)
                if key not in reported:
                    reported.add(key)
                    _emit(out, lines, "backpressure-cycle",
                          min(cyc),
                          "reliable-consumption cycle "
                          + " -> ".join(cyc)
                          + " — every member credit-waits on the next")
            elif color.get(v) is None:
                dfs(v)
        stack.pop()
        color[u] = 2

    for tn in sorted(edges):
        if color.get(tn) is None:
            dfs(tn)
    return out


def _check_tiles(model, kinds, lines) -> list[Finding]:
    out: list[Finding] = []
    tiles = model["tiles"]
    tcaches = model["tcaches"]

    for tn, t in tiles.items():
        kind, args = t["kind"], t["args"]
        summary = kinds.get(kind)
        if summary is None:
            _emit(out, lines, "unknown-kind", tn,
                  f"tile {tn!r}: kind {kind!r} has no registered "
                  f"adapter" + reg.suggest(str(kind), kinds))
        else:
            if t["ins"] and not summary["reads_in_rings"]:
                _emit(out, lines, "unread-in", tn,
                      f"tile {tn!r} declares ins but kind {kind!r} "
                      f"never reads in_rings — dead wiring")
            if not summary["in_seqs"]:
                for ln, rel in t["ins"]:
                    if rel:
                        _emit(out, lines, "reliable-sink", tn,
                              f"tile {tn!r} consumes {ln!r} reliably "
                              f"but kind {kind!r} never publishes "
                              f"consumer progress (no in_seqs): the "
                              f"producer wedges after depth frags; "
                              f"declare the in unreliable")

        # supervise schema (disco/supervise.py is the one validator)
        if "supervise" in args:
            from ..disco.supervise import normalize_policy
            try:
                normalize_policy(args["supervise"])
            except Exception as e:
                _emit(out, lines, "bad-supervise", tn,
                      f"tile {tn!r}: {e}")

        # chaos schema (utils/chaos.py) + stall_fseq link resolution
        if "chaos" in args:
            from ..utils.chaos import ChaosPlan
            try:
                ChaosPlan(args["chaos"])
            except Exception as e:
                _emit(out, lines, "bad-chaos", tn, f"tile {tn!r}: {e}")
            else:
                my_ins = {ln for ln, _ in t["ins"]}
                for ev in args["chaos"].get("events", []):
                    if ev.get("action") == "stall_fseq" and \
                            ev.get("link") is not None and \
                            ev["link"] not in my_ins:
                        _emit(out, lines, "bad-chaos", tn,
                              f"tile {tn!r}: stall_fseq targets "
                              f"{ev['link']!r}, not one of its ins "
                              f"{sorted(my_ins)}")

        out.extend(_check_arg_refs(tn, t, tcaches, tiles, kinds,
                                   lines))
    return out


def _check_arg_refs(tn, t, tcaches, tiles, kinds, lines) -> list[Finding]:
    out: list[Finding] = []
    kind, args = t["kind"], t["args"]
    ins = {ln for ln, _ in t["ins"]}
    outs = set(t["outs"])
    spec = reg.TILE_ARGS.get(kind, {})

    def bad(key, val, what):
        _emit(out, lines, "dangling-ref", tn,
              f"tile {tn!r}: {key} = {val!r} is not {what}")

    for key, ref in spec.items():
        if ref is None or key not in args:
            continue
        # list-valued refs: IN_LIST/OUT_LIST by schema, and TCACHE for
        # the sharded-tile expansion (a per-shard tcache list — each
        # entry must still resolve)
        vals = args[key] if isinstance(args[key], (list, tuple)) and \
            ref in (reg.IN_LIST, reg.OUT_LIST, reg.TCACHE) \
            else [args[key]]
        for v in vals:
            if ref in (reg.IN, reg.IN_LIST) and v not in ins:
                bad(key, v, f"one of the tile's ins {sorted(ins)}")
            elif ref in (reg.OUT, reg.OUT_LIST) and v not in outs:
                bad(key, v, f"one of the tile's outs {sorted(outs)}")
            elif ref == reg.TCACHE and v not in tcaches:
                bad(key, v, "a declared tcache"
                    + reg.suggest(str(v), tcaches))
            elif ref == reg.TILE and v not in tiles:
                bad(key, v, "a declared tile"
                    + reg.suggest(str(v), tiles))

    # sign.clients: role-bound ring pairs — req must be an in, resp an
    # out (the keyguard contract binds policy to the wire)
    if kind == "sign":
        clients = args.get("clients", [])
        for c in clients if isinstance(clients, list) else []:
            if not isinstance(c, dict):
                continue
            if c.get("req") not in ins:
                bad("clients.req", c.get("req"),
                    f"one of the tile's ins {sorted(ins)}")
            if c.get("resp") not in outs:
                bad("clients.resp", c.get("resp"),
                    f"one of the tile's outs {sorted(outs)}")

    # gui.tps_metric must exist on the target tile's kind
    if kind == "gui" and "tps_metric" in args:
        target = args.get("tps_tile", "sink")
        tkind = tiles.get(target, {}).get("kind")
        metrics = kinds.get(tkind, {}).get("metrics")
        if metrics is not None and args["tps_metric"] not in metrics:
            bad("tps_metric", args["tps_metric"],
                f"a metric of tile {target!r} (kind {tkind!r}: "
                f"{metrics})")
    return out
