"""Declarative topology: workspaces, links, tiles, objects.

The reference declares its whole dataflow graph up front — workspaces,
links (mcache+dcache), tiles with in/out link lists, and shared objects —
then materializes it and launches one process per tile
(ref: src/disco/topo/fd_topo.h:36-662 — fd_topo_t model;
src/disco/topo/fd_topob.h — builder; src/app/fdctl/topology.c:88-254 —
a concrete topology description).

Here the model is plain data: `Topology` is the builder; `build()`
materializes every object into one shared-memory workspace and returns a
picklable `plan` dict of offsets — the inter-process ABI. Tile processes
receive (plan, tile_name), join the workspace with create=False, and
reconstruct their rings/fseqs/cnc/metrics views from offsets alone
(gaddr discipline, ref: src/util/wksp/fd_wksp.h:27-47).

Reliability: a tile input declared reliable gets an fseq; the upstream
link's producer credit-gates on every reliable consumer's fseq
(ref: src/tango/fctl/fd_fctl.h:4-10). Unreliable consumers are never
waited on and must tolerate overruns.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from ..runtime import Workspace, Ring, Fseq, Cnc, Tcache, lib

METRICS_SLOTS = 64          # u64 counters per tile


def _metric_names(kind: str) -> list[str]:
    """Slot names for a tile kind, frozen into the plan at build time
    (the moral equivalent of the reference's metrics codegen fixing
    offsets at compile time, src/disco/metrics/gen_metrics.py)."""
    from .tiles import REGISTRY
    return list(getattr(REGISTRY.get(kind, object), "METRICS", []))


def _metric_gauges(kind: str) -> list[str]:
    """Slot names the adapter declares as GAUGES (point-in-time values
    like bound ports) rather than counters — explicit declaration, not
    name heuristics, decides the prometheus series type."""
    from .tiles import REGISTRY
    return list(getattr(REGISTRY.get(kind, object), "GAUGES", []))


# fdtpu_tile_<name> families the renderer already emits; a promoted
# device series may not shadow them (checked at build, below)
_RESERVED_TILE_FAMILIES = ("metric", "gauge", "liveness_seconds",
                           "tpu_seconds", "stall_seconds", "gc_seconds")


def _metric_device(kind: str) -> list[str]:
    """Slot names the adapter declares as DEVICE_SERIES: promoted by
    the prometheus renderer to first-class fdtpu_tile_<name> families
    (device telemetry dashboards key on) instead of the generic
    name-labeled series — explicit declaration, never name sniffing."""
    from .tiles import REGISTRY
    return list(getattr(REGISTRY.get(kind, object), "DEVICE_SERIES",
                        []))


@dataclass
class LinkSpec:
    name: str
    depth: int
    mtu: int
    external: bool = False        # producer/consumer outside the topo
    #   (a client process drives the ring directly — the vinyl rq/cq
    #   pattern, ref: fd_vinyl.h clients joining over rings)


@dataclass
class TileSpec:
    name: str
    kind: str
    ins: list[dict] = field(default_factory=list)   # {link, reliable}
    outs: list[str] = field(default_factory=list)
    args: dict = field(default_factory=dict)


class Topology:
    """Builder. Declare links/tiles/objects, then build() into a wksp."""

    def __init__(self, name: str, wksp_size: int = 1 << 26,
                 trace: dict | None = None, slo: dict | None = None,
                 prof: dict | None = None, shed: dict | None = None,
                 funk: dict | None = None, replay: dict | None = None,
                 snapshot: dict | None = None,
                 flight: dict | None = None):
        self.name = name
        self.wksp_size = wksp_size
        self.links: dict[str, LinkSpec] = {}
        self.tiles: dict[str, TileSpec] = {}
        self.tcaches: dict[str, int] = {}           # name -> depth
        # [trace] flight-recorder config (trace/recorder.py schema);
        # validated at build so a typo fails before launch
        self.trace = trace
        # [slo] objectives (disco/slo.py schema); targets resolve
        # against the declared tiles/links/metrics at build, so a typo
        # or a dangling reference fails before launch too
        self.slo = slo
        # [prof] continuous-profiler config (prof/recorder.py schema)
        self.prof = prof
        # [shed] front-door policing defaults (disco/shed.py schema);
        # ingest tiles resolve their effective gate from this + their
        # own `shed` override at adapter construction
        self.shed = shed
        # [funk] account-store config (funk/shmfunk.py schema); backend
        # "shm" makes build() carve the record/txn store into the wksp
        # so bank + the resolv/exec tile family share one fork tree
        self.funk = funk
        # [replay]/[snapshot] follower surface (tiles/replay.py and
        # tiles/snapshot.py schemas): replay fan-out defaults and the
        # snapshot path/cadence/min_slot the snapld/snapin/replay
        # adapters read off the plan
        self.replay = replay
        self.snapshot = snapshot
        # [flight] durable telemetry archive (flight/__init__ schema):
        # the recorder tile reads the normalized section off the plan
        self.flight = flight

    def link(self, name: str, depth: int = 128, mtu: int = 1280,
             external: bool = False):
        if name in self.links:
            raise ValueError(f"duplicate link {name}")
        self.links[name] = LinkSpec(name, depth, mtu, external)
        return self

    def tile(self, name: str, kind: str, ins=(), outs=(), **args):
        """ins: link names (reliable) or (link, False) for unreliable."""
        if name in self.tiles:
            raise ValueError(f"duplicate tile {name}")
        norm = []
        for i in ins:
            if isinstance(i, str):
                norm.append({"link": i, "reliable": True})
            else:
                norm.append({"link": i[0], "reliable": bool(i[1])})
        self.tiles[name] = TileSpec(name, kind, norm, list(outs), args)
        return self

    def tcache(self, name: str, depth: int = 4096):
        self.tcaches[name] = depth
        return self

    def sharded_tile(self, name: str, kind: str, cnt: int, ins=(),
                     outs=(), cpu0: int | None = None, **args):
        """Round-robin scale-out as a first-class topology concept
        (verify_tile_cnt >= 2, ROADMAP item 2 / the reference's
        multi-verify-tile layout, fd_verify_tile.c:49-53): declare
        `cnt` shards of one consumer tile kind. Shard i becomes tile
        f"{name}{i}" with rr_cnt=cnt / rr_idx=i, consuming the SAME in
        links (frag ownership is disjoint by seq % cnt) and producing
        outs[i] — one out link per shard, because links are SPMC and
        shards can never share a producer side; the downstream stage
        (dedup) fans in over all shard links and stays the cross-shard
        convergence point. cpu0 pins shard i to core cpu0+i
        (sched_setaffinity via the launcher's cpu_idx, clamped to the
        online set — a no-op gain on single-core hosts). A
        list-valued `tcache` of length cnt distributes one ha-dedup
        tcache per shard (they are per-tile by design), and cnt-length
        lists of `chaos`/`supervise` distribute per shard too (None =
        not on this shard — how a drill targets ONE shard); every
        other arg is shared verbatim — list args like `cluster` mean
        the same list for every shard, never a distribution.

        Per-shard in links (the exec tile family, r16): an `ins` entry
        that is itself a list of cnt link names distributes one link
        per shard — shard i consumes entry[i] instead of the shared
        link. This is how an upstream ROUTING producer (the bank's
        conflict-group dispatch) addresses a specific shard: rr
        seq-ownership can't express content-based routing, a dedicated
        SPSC link per shard can. The (link, reliable) tuple form stays
        shared — a distribution entry is all-strings of length cnt."""
        cnt = int(cnt)
        if cnt < 1:
            raise ValueError(f"sharded tile {name}: cnt {cnt} < 1")
        outs = list(outs)
        if len(outs) != cnt:
            raise ValueError(
                f"sharded tile {name}: need one out link per shard "
                f"({cnt}), got {outs}")

        def _shard_ins(i):
            out = []
            for e in ins:
                if isinstance(e, (list, tuple)) and len(e) > 0 \
                        and all(isinstance(x, str) for x in e):
                    if len(e) != cnt:
                        raise ValueError(
                            f"sharded tile {name}: per-shard ins "
                            f"entry needs one link per shard ({cnt}),"
                            f" got {list(e)}")
                    out.append(e[i])
                else:
                    out.append(e)
            return out

        for i in range(cnt):
            a = {}
            for k, v in args.items():
                if isinstance(v, (list, tuple)) and len(v) == cnt \
                        and k in ("tcache", "chaos", "supervise"):
                    # per-shard distribution (chaos/supervise take
                    # dicts, so a cnt-length list is unambiguous; a
                    # None entry means 'not on this shard')
                    if v[i] is not None:
                        a[k] = v[i]
                else:
                    a[k] = v
            a["rr_cnt"] = cnt
            a["rr_idx"] = i
            if cpu0 is not None:
                a["cpu_idx"] = int(cpu0) + i
            self.tile(f"{name}{i}", kind, ins=_shard_ins(i),
                      outs=[outs[i]], **a)
        return self

    def _validate(self):
        producers: dict[str, str] = {}
        consumed: set[str] = set()
        for t in self.tiles.values():
            for ln in t.outs:
                if ln not in self.links:
                    raise ValueError(f"tile {t.name}: unknown out link {ln}")
                if ln in producers:
                    raise ValueError(
                        f"link {ln} has two producers "
                        f"({producers[ln]}, {t.name}) — links are SPMC")
                producers[ln] = t.name
            for i in t.ins:
                if i["link"] not in self.links:
                    raise ValueError(
                        f"tile {t.name}: unknown in link {i['link']}")
                consumed.add(i["link"])
        for ln, spec in self.links.items():
            if ln not in producers and not spec.external:
                raise ValueError(f"link {ln} has no producer")
            if ln not in consumed and not spec.external:
                raise ValueError(f"link {ln} has no consumer")

    def _device_owners(self) -> tuple[list[str], str]:
        """(tiles that get the accelerator, its platform). A chip
        belongs to one process and JAX's TPU backend takes every chip
        of the host, so a plan on a device platform has at most one
        owner (`devices = N` is how that one tile spans more chips);
        a CPU-pinned plan has no limit."""
        from ..jaxcfg import plan_platform
        from .tiles import REGISTRY
        plat = plan_platform()
        owners = [tn for tn, t in self.tiles.items()
                  if getattr(REGISTRY.get(t.kind), "DEVICE", False)]
        if plat != "cpu" and len(owners) > 1:
            raise ValueError(
                f"tiles {owners} each want the {plat} device, but a "
                f"chip belongs to one process: give the plan one "
                f"device tile (use `devices` to span chips), or pin it "
                f"to the host with JAX_PLATFORMS=cpu")
        return owners, plat

    def build(self, wksp_name: str | None = None) -> dict:
        """Materialize into a fresh workspace; return the picklable plan.

        The caller is the single creator (replace mode); every tile
        process joins with create=False.
        """
        self._validate()
        import os
        owners, plat = self._device_owners()
        wksp_name = wksp_name or f"/fdtpu_{self.name}"
        w = Workspace(wksp_name, self.wksp_size)
        plan: dict = {
            "topology": self.name,
            "wksp": {"name": wksp_name, "size": self.wksp_size},
            # per-boot seed shared by verify (tag computation) and dedup
            # (ref: verify/dedup share hashmap_seed via topology)
            "seed": os.urandom(16).hex(),
            "links": {}, "fseqs": {}, "tcaches": {}, "tiles": {},
        }
        try:
            from .metrics import LINK_CONS_U64, LINK_PROD_U64
            for ln, spec in self.links.items():
                r = Ring.create(w, depth=spec.depth, mtu=spec.mtu)
                # per-link producer telemetry block (single writer:
                # links are SPMC, the one producing tile's stem owns it)
                po = w.alloc(LINK_PROD_U64 * 8)
                w.view(po, LINK_PROD_U64 * 8)[:] = 0
                plan["links"][ln] = {
                    "ring_off": r.off, "arena_off": r.arena_off,
                    "depth": spec.depth, "mtu": r.mtu,
                    "prod_metrics_off": po,
                }
            for name, depth in self.tcaches.items():
                tc = Tcache(w, depth=depth)
                plan["tcaches"][name] = {"off": tc.off, "depth": depth}
            from ..trace import effective_trace, normalize_trace
            from ..runtime import TraceRing
            from .metrics import HIST_REGION_U64
            from .supervise import SUP_SLOT_MIN, normalize_policy
            trace_cfg = normalize_trace(self.trace)
            unknown = set(trace_cfg["tiles"] or ()) - set(self.tiles)
            if unknown:
                raise ValueError(
                    f"trace.tiles names unknown tile(s) "
                    f"{sorted(unknown)}")
            plan["trace"] = trace_cfg
            # [prof] continuous profiler: same carve-at-build shape as
            # the flight recorder — unprofiled tiles get NO region and
            # NO plan keys, so TileCtx.prof stays None
            from ..prof import ProfRegion, effective_prof, \
                normalize_prof
            prof_cfg = normalize_prof(self.prof)
            for key in ("tiles", "breach_capture"):
                unknown = set(prof_cfg[key] or ()) - set(self.tiles)
                if unknown:
                    raise ValueError(
                        f"prof.{key} names unknown tile(s) "
                        f"{sorted(unknown)}")
            plan["prof"] = prof_cfg
            # [shed] policing defaults: schema-validated here (the
            # same fail-before-launch gate as trace/prof/slo) and
            # carried on the plan for the ingest adapters; per-tile
            # overrides validate below with the tile loop
            from .shed import normalize_shed as _norm_shed
            plan["shed"] = _norm_shed(self.shed) \
                if self.shed is not None else None
            # [funk] shm account store: carve the record/txn store the
            # way metric/trace/prof regions are carved — offsets on the
            # plan are the ABI; bank creates the facade, resolv/exec
            # tiles join read/write through runtime.Store at plan off
            from ..funk.shmfunk import normalize_funk as _norm_funk
            funk_cfg = _norm_funk(self.funk)
            plan["funk"] = dict(funk_cfg)
            if funk_cfg["backend"] == "shm":
                from ..runtime import Store
                heap_sz = funk_cfg["heap_mb"] << 20
                st = Store(w, rec_max=funk_cfg["rec_max"],
                           txn_max=funk_cfg["txn_max"], heap_sz=heap_sz)
                plan["funk"]["off"] = st.off
                plan["funk"]["heap_sz"] = heap_sz
            # [replay]/[snapshot]: validated here (fail before launch)
            # and carried on the plan — the replay/snapld/snapin
            # adapters take their defaults from these sections, tile
            # args win per key
            from ..tiles.replay import normalize_replay as _norm_replay
            plan["replay"] = _norm_replay(self.replay) \
                if self.replay is not None else None
            from ..tiles.snapshot import normalize_snapshot \
                as _norm_snap
            plan["snapshot"] = _norm_snap(self.snapshot) \
                if self.snapshot is not None else None
            # [flight]: validated here (fail before launch) and carried
            # on the plan — the flight recorder tile and the gui
            # history route read it; None = no archive on this topology
            from ..flight import normalize_flight as _norm_flight
            plan["flight"] = _norm_flight(self.flight) \
                if self.flight is not None else None
            for tn, t in self.tiles.items():
                if "shed" in t.args:
                    _norm_shed(t.args["shed"], per_tile=True)
                if t.kind == "gui":
                    # [tile.gui] schema gate (gui/schema.py is the one
                    # validator — same three-layer contract as
                    # [trace]/[prof]: config load, build, fdlint)
                    from ..gui import normalize_gui
                    normalize_gui(t.args)
                for i in t.ins:
                    if i["reliable"]:
                        fs = Fseq(w)
                        plan["fseqs"][f"{i['link']}:{tn}"] = fs.off
                cnc = Cnc(w)
                metrics_off = w.alloc(METRICS_SLOTS * 8)
                w.view(metrics_off, METRICS_SLOTS * 8)[:] = 0
                hist_off = w.alloc(HIST_REGION_U64 * 8)
                w.view(hist_off, HIST_REGION_U64 * 8)[:] = 0
                names = _metric_names(t.kind)
                if len(names) > SUP_SLOT_MIN:
                    raise ValueError(
                        f"tile kind {t.kind}: {len(names)} metric slots "
                        f"collide with supervisor slots (max "
                        f"{SUP_SLOT_MIN})")
                # per-(consumer, in-link) telemetry block: consume
                # counters + a queue-wait histogram, fed by this
                # tile's stem (single writer) — the reader side matches
                # by (tile, link) from the plan, never by order
                link_metrics = {}
                for i in t.ins:
                    lo = w.alloc(LINK_CONS_U64 * 8)
                    w.view(lo, LINK_CONS_U64 * 8)[:] = 0
                    link_metrics[i["link"]] = lo
                plan["tiles"][tn] = {
                    "kind": t.kind,
                    # JAX_PLATFORMS of the tile process: the device
                    # for its one owner, the host for everyone else
                    "jax_platform": plat if tn in owners else "cpu",
                    "ins": list(t.ins),
                    "outs": list(t.outs),
                    "args": dict(t.args),
                    "link_metrics": link_metrics,
                    # per-tile restart/watchdog policy, validated at
                    # build so a config typo fails before launch
                    "supervise": normalize_policy(
                        t.args.get("supervise")),
                    "cnc_off": cnc.off,
                    "metrics_off": metrics_off,
                    "hist_off": hist_off,
                    # region length in u64 — readers and the stem size
                    # their views from the PLAN so a newer build
                    # attaching to an older topology (fewer hist
                    # kinds) never reads past the carved region
                    "hist_u64": HIST_REGION_U64,
                    # explicit slot-name ABI: readers match by these names,
                    # never by adapter class declaration order (r2 W7)
                    "metrics_names": names,
                    "metrics_gauges": _metric_gauges(t.kind),
                    "metrics_device": _metric_device(t.kind),
                }
                for nm in plan["tiles"][tn]["metrics_device"]:
                    if nm not in names:
                        raise ValueError(
                            f"tile kind {t.kind}: DEVICE_SERIES "
                            f"{nm!r} is not a declared metric slot")
                    if nm in _RESERVED_TILE_FAMILIES:
                        raise ValueError(
                            f"tile kind {t.kind}: DEVICE_SERIES "
                            f"{nm!r} would shadow the built-in "
                            f"fdtpu_tile_{nm} family")
                # flight-recorder ring, carved next to the metric
                # slots (trace/recorder.py resolves topology default
                # + per-tile override; untraced tiles get NO region
                # and NO plan keys — TileCtx.trace stays None)
                eff = effective_trace(
                    trace_cfg, tn,
                    normalize_trace(t.args.get("trace"), per_tile=True))
                if eff is not None:
                    tr = TraceRing.create(w, eff["depth"])
                    plan["tiles"][tn]["trace_off"] = tr.off
                    plan["tiles"][tn]["trace_depth"] = eff["depth"]
                    plan["tiles"][tn]["trace_sample"] = eff["sample"]
                # profile region (fdprof): folded-stack table +
                # timestamped sample ring + capture doorbell, carved
                # only for profiled tiles (prof/recorder.py)
                peff = effective_prof(
                    prof_cfg, tn,
                    normalize_prof(t.args.get("prof"), per_tile=True))
                if peff is not None:
                    pr = ProfRegion.create(w, peff["slots"],
                                           peff["ring"])
                    plan["tiles"][tn]["prof_off"] = pr.off
                    plan["tiles"][tn]["prof_slots"] = peff["slots"]
                    plan["tiles"][tn]["prof_ring"] = peff["ring"]
                    plan["tiles"][tn]["prof_hz"] = peff["hz"]
                    plan["tiles"][tn]["prof_stack_depth"] = \
                        peff["stack_depth"]
                if t.kind == "sign":
                    # live identity hot-swap region (fd_keyswitch)
                    from ..keyguard.keyswitch import FOOTPRINT as KS_FP
                    ks_off = w.alloc(KS_FP)
                    w.view(ks_off, KS_FP)[:] = 0
                    plan["tiles"][tn]["keyswitch_off"] = ks_off
            # [slo] objectives: schema-validate AND resolve every
            # target's source against the tiles/metrics/links this plan
            # actually declares — a dangling objective fails the build,
            # not the first housekeeping pass of the metric tile
            from .slo import normalize_slo, resolve_slo
            slo_cfg = normalize_slo(self.slo)
            resolve_slo(slo_cfg, plan)
            plan["slo"] = slo_cfg
        except Exception:
            w.close()
            w.unlink()
            raise
        w.close()
        return plan


# ---------------------------------------------------------------------------
# plan-side join helpers (used inside tile processes and by the monitor)
# ---------------------------------------------------------------------------

class TileCtx:
    """A tile process's materialized view of the plan: joined workspace,
    in/out rings, fseqs (own consumer fseqs + downstream reliable fseqs
    for each out link), cnc and metrics."""

    def __init__(self, plan: dict, tile_name: str):
        self.plan, self.tile_name = plan, tile_name
        self.spec = plan["tiles"][tile_name]
        self.wksp = Workspace(plan["wksp"]["name"], plan["wksp"]["size"],
                              create=False)
        self.cnc = Cnc(self.wksp, off=self.spec["cnc_off"])
        self.metrics_off = self.spec["metrics_off"]

        def ring(ln):
            li = plan["links"][ln]
            return Ring(self.wksp, li["ring_off"], li["depth"],
                        li["arena_off"], li["mtu"])

        self.in_rings = {}
        self.in_fseqs = {}
        for i in self.spec["ins"]:
            ln = i["link"]
            self.in_rings[ln] = ring(ln)
            key = f"{ln}:{tile_name}"
            if i["reliable"] and key in plan["fseqs"]:
                self.in_fseqs[ln] = Fseq(self.wksp, off=plan["fseqs"][key])

        # ring rejoin: a RESTARTED consumer attaches at each producer's
        # current mcache seq instead of replaying from 0 (the supervisor
        # sets rejoin_at_tail on the respawn plan; frags published while
        # the tile was down are skipped — the documented loss contract).
        # Publishing the fseq here also clears the STALE sentinel so the
        # producer's credit flow re-includes this consumer immediately.
        rejoin = bool(self.spec.get("rejoin_at_tail"))
        self.in_seq0 = {}
        for ln, r in self.in_rings.items():
            self.in_seq0[ln] = int(r.seq) if rejoin else 0
            fs = self.in_fseqs.get(ln)
            if fs is not None and rejoin:
                fs.update(self.in_seq0[ln])

        self.out_rings = {}
        self.out_fseqs = {}
        for ln in self.spec["outs"]:
            self.out_rings[ln] = ring(ln)
            fseqs = []
            for key, off in plan["fseqs"].items():
                if key.split(":", 1)[0] == ln:
                    fseqs.append(Fseq(self.wksp, off=off))
            self.out_fseqs[ln] = fseqs

        self.tcaches = {
            name: Tcache(self.wksp, depth=tc["depth"], off=tc["off"])
            for name, tc in plan["tcaches"].items()
        }

        # flight recorder (fdtrace): None unless topo.build carved a
        # ring for this tile — the None IS the disabled fast path
        # (every hook is a single attribute check, trace/__init__.py)
        from ..trace import writer_for
        self.trace = writer_for(plan, self.wksp, tile_name)

        # continuous profiler (fdprof): same None-is-disabled contract
        # — the stem starts a sampler thread only when a region exists
        from ..prof import region_for as _prof_region_for
        self.prof = _prof_region_for(plan, self.wksp, tile_name)

        # per-link telemetry views (fdmetrics v2): consumer blocks for
        # this tile's in links, producer blocks for its out links —
        # single-writer by construction, flushed by the stem. Plans
        # built before the link ABI existed leave both dicts empty.
        import numpy as np
        from .metrics import HIST_U64, LINK_CONS_U64, LINK_PROD_U64
        self.link_cons_views = {
            ln: self.wksp.view(off, LINK_CONS_U64 * 8).view(np.uint64)
            for ln, off in (self.spec.get("link_metrics") or {}).items()
        }
        self.link_prod_views = {}
        for ln in self.spec["outs"]:
            off = plan["links"][ln].get("prod_metrics_off")
            if off is not None:
                self.link_prod_views[ln] = self.wksp.view(
                    off, LINK_PROD_U64 * 8).view(np.uint64)
        # restart continuity: a supervised respawn joins fresh Ring
        # instances whose telemetry counters start at 0, but the shm
        # blocks hold the link's cumulative history and the stem
        # flushes the instance counters WHOLESALE — seed them from shm
        # so the series resumes instead of resetting (a zeroed consumed
        # counter would count everything consumed before the restart as
        # per-hop loss). Fresh boots seed zeros: a no-op.
        from ..runtime.tango import u64_snapshot
        for ln, view in self.link_cons_views.items():
            r = self.in_rings.get(ln)
            if r is not None:
                r.m_consumed = int(view[0])
                r.m_bytes = int(view[1])
                r.m_overruns = int(view[2])
                r.m_wait[:] = u64_snapshot(view[3:3 + HIST_U64])
        for ln, view in self.link_prod_views.items():
            r = self.out_rings[ln]
            r.m_pub = int(view[0])
            r.m_pub_bytes = int(view[1])
            r.m_backpressure = int(view[2])

    def in_seqs0(self) -> dict[str, int]:
        """Initial consume cursor per in link: 0 on a fresh boot, the
        producer's current seq on a supervised restart (ring rejoin)."""
        return dict(self.in_seq0)

    def metrics_view(self):
        import numpy as np
        return self.wksp.view(self.metrics_off, METRICS_SLOTS * 8) \
            .view(np.uint64)

    def hist_view(self):
        """u64 view of this tile's wait/work[/tpu] histogram region (or
        None for plans built before histograms existed). Sized by the
        plan-recorded region length, NOT the current HIST_REGION_U64:
        attaching to a plan carved by an older build (fewer hist kinds)
        must not read/write past its region into the adjacent
        allocation."""
        import numpy as np
        off = self.spec.get("hist_off")
        if off is None:
            return None
        from .metrics import HIST_U64
        n = int(self.spec.get("hist_u64", 2 * HIST_U64))
        return self.wksp.view(off, n * 8).view(np.uint64)

    def close(self):
        self.wksp.close()


def read_metrics(wksp: Workspace, plan: dict, tile_name: str):
    import numpy as np
    off = plan["tiles"][tile_name]["metrics_off"]
    return wksp.view(off, METRICS_SLOTS * 8).view(np.uint64).copy()


def read_heartbeat(wksp: Workspace, plan: dict, tile_name: str) -> int:
    cnc = Cnc(wksp, off=plan["tiles"][tile_name]["cnc_off"])
    return cnc.last_heartbeat


def now_ticks() -> int:
    # ONE clock for heartbeats, watchdog staleness, and trace
    # timestamps (utils/tempo.monotonic_ns == native fdtpu_ticks)
    from ..utils.tempo import monotonic_ns
    return monotonic_ns()
