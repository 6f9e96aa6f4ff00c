"""fdgui v2: `[tile.gui]` arg schema + the snapshot/delta protocol.

The reference documents its gui wire protocol explicitly
(book/api/websocket.md): on connect the client receives one FULL
topology snapshot, then a stream of incremental updates — never a
re-poll. This module is that protocol's server side, pure functions
over (plan, wksp):

  snapshot_doc(plan)     the on-connect document: topology shape
                         (tiles, links, cfg digest), declared SLO
                         targets, which tiles are traced/profiled
  DeltaSource.delta()    one per-housekeeping update: TPS, per-tile
                         state/metrics/latency/occupancy (+ CNC and
                         supervisor counters), per-link
                         pub/consumed/loss/backpressure + consume
                         quantiles, SLO status + recent breach events

Everything is READ-side over the existing shm surfaces (metric slots,
cnc, wait/work/tpu histograms, link telemetry blocks, the metric
tile's trace ring) — the gui adds zero writer-side cost, the fdtrace
disabled-path stance applied to a whole subsystem.

The arg schema (`normalize_gui`) follows the [trace]/[prof] three-
layer contract: validated at config load (registry key gate), at
topo.build, and by fdlint's bad-gui rule — with a did-you-mean on
typos.
"""
from __future__ import annotations

import hashlib
import json

GUI_DEFAULTS = {
    "port": 0,
    "bind_addr": "127.0.0.1",
    "tps_tile": "sink",
    "tps_metric": "rx",
    "ws_max_clients": 8,     # concurrent upgrades; excess get 503
    "ws_queue": 64,          # per-client frame high-water (drop-oldest)
    "ws_sndbuf": 0,          # kernel send-buffer cap (0 = OS default)
    "bench_glob": "BENCH_r*.json",   # /bench.json trend source
    "report_on_halt": None,  # write a static report artifact on halt
}


def _suggest(key: str, candidates) -> str:
    from ..lint.registry import suggest
    return suggest(str(key), candidates)


def normalize_gui(args) -> dict:
    """Validate + default-fill a gui tile's args (the full tile-arg
    dict: structural/common keys are ignored, they belong to the
    stem/launcher). Raises ValueError with a did-you-mean on typos —
    the same fail-before-launch stance as supervise/trace/prof."""
    from ..lint.registry import COMMON_KEYS
    out = dict(GUI_DEFAULTS)
    if args is None:
        return out
    if not isinstance(args, dict):
        raise ValueError(f"gui args must be a table, got {args!r}")
    skip = set(COMMON_KEYS) | {"name", "kind", "ins", "outs"}
    unknown = {k for k in args if k not in GUI_DEFAULTS
               and k not in skip}
    if unknown:
        key = sorted(unknown)[0]
        raise ValueError(f"unknown gui key(s) {sorted(unknown)}"
                         + _suggest(key, GUI_DEFAULTS))
    out.update({k: v for k, v in args.items() if k in GUI_DEFAULTS})
    out["port"] = int(out["port"])
    if out["port"] < 0:
        raise ValueError(f"gui.port must be >= 0, got {out['port']}")
    for k in ("tps_tile", "tps_metric", "bind_addr", "bench_glob"):
        if not isinstance(out[k], str) or not out[k]:
            raise ValueError(f"gui.{k} must be a non-empty string, "
                             f"got {out[k]!r}")
    for k, lo in (("ws_max_clients", 1), ("ws_queue", 2),
                  ("ws_sndbuf", 0)):
        out[k] = int(out[k])
        if out[k] < lo:
            raise ValueError(f"gui.{k} must be >= {lo}, got {out[k]}")
    if out["report_on_halt"] is not None and (
            not isinstance(out["report_on_halt"], str)
            or not out["report_on_halt"]):
        raise ValueError("gui.report_on_halt must be a non-empty "
                         "path string")
    return out


# ---------------------------------------------------------------------------
# the protocol documents
# ---------------------------------------------------------------------------

def cfg_digest(plan: dict) -> str:
    """Short stable digest of the topology SHAPE (links + tiles with
    kinds/wiring/args) — lets a reconnecting client detect that the
    topology it knew was rebuilt under the same name."""
    shape = {
        "links": {ln: {"depth": li["depth"], "mtu": li["mtu"]}
                  for ln, li in plan["links"].items()},
        "tiles": {tn: {"kind": s["kind"], "ins": s["ins"],
                       "outs": s["outs"], "args": s.get("args", {})}
                  for tn, s in plan["tiles"].items()},
    }
    blob = json.dumps(shape, sort_keys=True, default=str).encode()
    return hashlib.sha256(blob).hexdigest()[:12]


def snapshot_doc(plan: dict) -> dict:
    """The on-connect document: everything static about the topology.
    Pure function of the plan — no shm read, safe even mid-teardown."""
    from ..disco.metrics import link_producers
    producers = link_producers(plan)
    consumers: dict[str, list[str]] = {ln: [] for ln in plan["links"]}
    tiles = {}
    for tn, spec in plan["tiles"].items():
        tiles[tn] = {
            "kind": spec["kind"],
            "ins": [i["link"] for i in spec.get("ins", [])],
            "outs": list(spec.get("outs", [])),
            "metrics_names": list(spec.get("metrics_names", [])),
            "traced": spec.get("trace_off") is not None,
            "profiled": spec.get("prof_off") is not None,
        }
        for i in spec.get("ins", []):
            consumers.setdefault(i["link"], []).append(tn)
    links = {
        ln: {"depth": li["depth"], "mtu": li["mtu"],
             "producer": producers.get(ln),
             "consumers": consumers.get(ln, [])}
        for ln, li in plan["links"].items()
    }
    slo = plan.get("slo") or {}
    return {
        "type": "snapshot", "v": 2,
        "topology": plan.get("topology", "?"),
        "cfg_digest": cfg_digest(plan),
        "tiles": tiles,
        "links": links,
        "slo": {"targets": [{"name": t["name"], "expr": t["expr"]}
                            for t in slo.get("target", [])]},
    }


class DeltaSource:
    """Stateful per-housekeeping delta builder (one per gui tile or
    report pass). State exists only to turn cumulative shm counters
    into rates/occupancies between calls; the first call falls back
    to lifetime ratios so a post-mortem report still shows where the
    time went."""

    def __init__(self, plan: dict, wksp, tps_tile: str = "sink",
                 tps_metric: str = "rx", tps_window_s: float = 1.0):
        from collections import deque
        self.plan, self.wksp = plan, wksp
        self.tps_tile, self.tps_metric = tps_tile, tps_metric
        self.tps_window_s = float(tps_window_s)
        self.tps = 0.0
        self._tps_win: deque = deque()       # (ns, count) samples
        self._hist_last: dict[str, tuple[int, int, int, int]] = {}
        self._metric_tile = next(
            (tn for tn, s in plan["tiles"].items()
             if s["kind"] == "metric"), None)
        # catch-up surface (r17): replay + snapld tiles, if the
        # topology has them (follower mode)
        self._replay_tile = next(
            (tn for tn, s in plan["tiles"].items()
             if s["kind"] == "replay"), None)
        self._snapld_tile = next(
            (tn for tn, s in plan["tiles"].items()
             if s["kind"] == "snapld"), None)
        self._replay_win: deque = deque()    # (ns, txns) samples

    # -- TPS (satellite fix: tempo.monotonic_ns, THE topology clock —
    # the rate must agree with trace/prof timelines, not drift on a
    # second perf_counter epoch). Computed over a rolling window, not
    # adjacent samples: the gui samples faster than the writer's stem
    # flushes its shm slots, so a consecutive-sample rate reads
    # spurious zeros whenever two passes land inside one flush
    # interval (the SLO engine's rate rationale, disco/slo.py) -------------

    def sample_tps(self) -> float:
        from ..disco.topo import read_metrics
        from ..utils.tempo import monotonic_ns
        spec = self.plan["tiles"].get(self.tps_tile)
        if spec is None:
            return self.tps
        names = spec.get("metrics_names", [])
        if self.tps_metric not in names:
            return self.tps
        vals = read_metrics(self.wksp, self.plan, self.tps_tile)
        cnt = int(vals[names.index(self.tps_metric)])
        now = monotonic_ns()
        self._tps_win.append((now, cnt))
        lo = now - int(self.tps_window_s * 1e9)
        while len(self._tps_win) > 1 and self._tps_win[1][0] <= lo:
            self._tps_win.popleft()   # keep one sample at the edge
        t0, c0 = self._tps_win[0]
        if now > t0:
            self.tps = max(0.0, (cnt - c0) / ((now - t0) / 1e9))
        return self.tps

    # -- per-tile occupancy --------------------------------------------------

    def _occupancy(self, tn: str, now_ns: int) -> dict:
        """{"work": fraction of poll time productive, "tpu": fraction
        of wall time on-device} over the interval since the previous
        delta (lifetime ratios on the first call)."""
        from ..disco.metrics import read_hists
        hists = read_hists(self.wksp, self.plan, tn)
        wait = hists.get("wait", {}).get("sum_ns", 0)
        work = hists.get("work", {}).get("sum_ns", 0)
        tpu = hists.get("tpu", {}).get("sum_ns", 0)
        last = self._hist_last.get(tn)
        self._hist_last[tn] = (now_ns, wait, work, tpu)
        if last is None or now_ns <= last[0]:
            tot = wait + work
            return {"work": round(work / tot, 4) if tot else 0.0,
                    "tpu": 0.0}
        dwall = now_ns - last[0]
        dwait = max(0, wait - last[1])
        dwork = max(0, work - last[2])
        dtpu = max(0, tpu - last[3])
        tot = dwait + dwork
        return {
            "work": round(dwork / tot, 4) if tot else 0.0,
            "tpu": round(min(1.0, dtpu / dwall), 4),
        }

    # -- SLO (read-side: the metric tile's slots + trace ring + dumps) ------

    def _slo(self) -> dict:
        from ..disco.monitor import slo_breach_events
        out: dict = {"breach": 0, "breaches": 0, "events": []}
        mt = self._metric_tile
        if mt is not None:
            from ..disco.topo import read_metrics
            spec = self.plan["tiles"][mt]
            names = spec.get("metrics_names", [])
            vals = read_metrics(self.wksp, self.plan, mt)
            for k in ("slo_breach", "slo_breaches"):
                if k in names:
                    out[k.replace("slo_", "")] = int(
                        vals[names.index(k)])
        out["events"] = slo_breach_events(self.plan, self.wksp)
        return out

    # -- catch-up progress (r17 follower surface) ---------------------------

    def _tile_metrics(self, tn: str) -> dict:
        from ..disco.topo import read_metrics
        spec = self.plan["tiles"].get(tn) or {}
        names = spec.get("metrics_names", [])
        vals = read_metrics(self.wksp, self.plan, tn)
        return {n: int(vals[i]) for i, n in enumerate(names)}

    def _catchup(self, now_ns: int) -> dict | None:
        """Follower catch-up panel: slots behind the live tip, the
        rolling replayed-txn rate, restore stream progress. None on a
        topology with no replay tile (the common leader case — the
        delta stays lean)."""
        if self._replay_tile is None:
            return None
        rm = self._tile_metrics(self._replay_tile)
        self._replay_win.append((now_ns, rm.get("txns", 0)))
        lo = now_ns - int(self.tps_window_s * 1e9)
        while len(self._replay_win) > 1 \
                and self._replay_win[1][0] <= lo:
            self._replay_win.popleft()
        t0, c0 = self._replay_win[0]
        rate = 0.0
        if now_ns > t0:
            rate = max(0.0, (self._replay_win[-1][1] - c0)
                       / ((now_ns - t0) / 1e9))
        out = {
            "behind": rm.get("behind", 0),
            "replay_tps": round(rate, 1),
            "slots_replayed": rm.get("slots_replayed", 0),
            "restore_slot": rm.get("restore_slot", 0),
            "divergent_slot": rm.get("divergent_slot", 0),
            "restore_pct": None,
        }
        if self._snapld_tile is not None:
            sm = self._tile_metrics(self._snapld_tile)
            total = sm.get("total_bytes", 0)
            if total:
                out["restore_pct"] = round(
                    100.0 * min(sm.get("bytes", 0), total) / total, 1)
        return out

    def delta(self) -> dict:
        """One protocol delta. Raises on a torn/halting topology —
        callers own the 503/skip policy (the gui tile's summary route
        guard, the report collector's retry)."""
        from ..disco.monitor import links_table, snapshot
        from ..disco.metrics import read_link_metrics
        from ..utils.tempo import monotonic_ns
        now = monotonic_ns()
        self.sample_tps()
        tiles = snapshot(self.plan, self.wksp)
        for tn, row in tiles.items():
            row["occupancy"] = self._occupancy(tn, now)
        return {
            "type": "delta", "ts": now, "tps": round(self.tps, 1),
            "tiles": tiles,
            "links": links_table(
                read_link_metrics(self.wksp, self.plan)),
            "slo": self._slo(),
            "catchup": self._catchup(now),
        }
