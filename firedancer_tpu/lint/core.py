"""fdlint core: findings, rule catalog, suppressions, baseline, output.

The moral equivalent of the reference's build-time discipline: the tile
graph, credit flow, and shared-memory protocol are statically knowable,
so violations should be REVIEW-time findings, not runtime wedges. Every
analyzer family (graph/contracts/jaxlint) emits the same `Finding`
shape through the same suppression/baseline filters, so one CLI and one
pytest gate cover all of them.

Suppression syntax (checked against the rule catalog):

    x = thing()        # fdlint: disable=<rule-id>[,<rule-id2>] — why
    # fdlint: disable=<rule-id> — why          (applies to next line)

Baseline (`lint-baseline.toml` at the repo root) grandfathers legacy
findings by (rule, path[, line]) so the CLI can gate NEW findings while
a burn-down is in flight; intentional keeps belong inline (with a
justification), not in the baseline.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass

SEVERITIES = ("error", "warning")

# rule id -> (family, severity, one-line description). THE catalog:
# analyzers must emit ids from here, suppressions are validated against
# it, and the README table is generated from these descriptions.
RULES: dict[str, tuple[str, str, str]] = {
    # -- topology graph family (lint/graph.py) ---------------------------
    "dead-link": (
        "graph", "error",
        "link is produced but never consumed (dead ring: frags are "
        "dropped silently and the config lies about the dataflow)"),
    "orphan-link": (
        "graph", "error",
        "link is consumed but never produced (consumer polls a ring "
        "that never advances)"),
    "dup-producer": (
        "graph", "error",
        "link has two producers — rings are SPMC, a second producer "
        "corrupts seq ordering"),
    "depth-pow2": (
        "graph", "error",
        "link depth is not a positive power of two (ring init fails "
        "at build)"),
    "mtu-underflow": (
        "graph", "error",
        "out link mtu is smaller than the producing tile's worst-case "
        "frame (publish asserts mid-flight instead of at review)"),
    "backpressure-cycle": (
        "graph", "error",
        "reliable-consumption cycle between tiles: every member waits "
        "on the next one's credits — a static deadlock candidate"),
    "reliable-sink": (
        "graph", "error",
        "reliable input on a tile kind that never publishes consumer "
        "progress (no in_seqs): its fseq never advances and the "
        "producer wedges after `depth` frags"),
    "unread-in": (
        "graph", "warning",
        "tile declares ins but its adapter kind never reads in_rings "
        "(dead wiring: the frags are never consumed)"),
    "unknown-kind": (
        "graph", "error",
        "tile kind has no registered adapter"),
    "bad-supervise": (
        "graph", "error",
        "[tile.supervise] table rejected by the supervise.py schema "
        "(unknown key, bad policy, out-of-range value)"),
    "bad-chaos": (
        "graph", "error",
        "chaos fault plan rejected by the chaos.py schema (unknown "
        "action) or stall_fseq names a link the tile does not consume"),
    "dangling-ref": (
        "graph", "error",
        "tile arg references an unknown link/tile/tcache, or a link "
        "outside the tile's declared ins/outs"),
    "bad-trace": (
        "graph", "error",
        "[trace] section or [tile.trace] table rejected by the fdtrace "
        "schema (unknown key, non-power-of-two depth, sample < 1) or "
        "trace.tiles names an undeclared tile"),
    "bad-slo": (
        "graph", "error",
        "[slo] section rejected by the disco/slo.py schema (unknown "
        "key, bad expression grammar, out-of-range window/burn) or a "
        "target references an undeclared tile/metric/link"),
    "bad-prof": (
        "graph", "error",
        "[prof] section or [tile.prof] table rejected by the fdprof "
        "schema (unknown key, non-power-of-two slots/ring, hz out of "
        "range) or prof.tiles / prof.breach_capture names an "
        "undeclared tile"),
    "bad-gui": (
        "graph", "error",
        "[tile.gui] args rejected by the gui schema (unknown key, "
        "out-of-range ws_max_clients/ws_queue/ws_sndbuf, empty "
        "tps/bench/report strings) — the fdgui v2 knob set, "
        "gui/schema.py normalize_gui"),
    "bad-shed": (
        "graph", "error",
        "[shed] section or per-tile `shed` override rejected by the "
        "disco/shed.py schema (unknown key with did-you-mean, "
        "non-positive rate_pps/burst/overload_hold_s, max_peers < 2, "
        "malformed stakes table), or shed configured on a tile kind "
        "with no ingest door to police"),
    "bad-witness": (
        "graph", "error",
        "[witness] section rejected by the witness/plan.py schema "
        "(unknown key with did-you-mean, unknown stage name, "
        "non-positive timeout/park values, malformed per-stage "
        "cmd/env override) — the fdwitness sweep plan must validate "
        "at review, not on the chip machine mid-sweep"),
    "bad-funk": (
        "graph", "error",
        "[funk] section rejected by the funk/shmfunk.py schema "
        "(unknown key with did-you-mean, unknown backend, rec_max/"
        "txn_max < 16, heap_mb < 1) — the account-store carve must "
        "validate at review, not when topo.build sizes the workspace"),
    "bad-replay": (
        "graph", "error",
        "[replay] section rejected by the tiles/replay.py schema "
        "(unknown key with did-you-mean, exec_tile_cnt < 0, "
        "redispatch_s <= 0, hashes_per_tick < 1) — the follower "
        "fan-out defaults must validate at review, not when the "
        "catch-up node boots"),
    "bad-snapshot": (
        "graph", "error",
        "[snapshot] section rejected by the tiles/snapshot.py schema "
        "(unknown key with did-you-mean, negative every_slots/"
        "min_slot, chunk < 64) — the snapshot path/cadence the "
        "snapld/snapin/replay tiles share must validate at review, "
        "not mid-restore"),
    "bad-flight": (
        "graph", "error",
        "[flight] section rejected by the flight/__init__.py schema "
        "(unknown key with did-you-mean, empty dir, segment_mb <= 0, "
        "retain_mb < segment_mb, hz out of (0,1000], negative "
        "incident_window_s, node_id not u16, unknown source family) — "
        "the telemetry-archive config must validate at review, not "
        "when the recorder tile boots"),
    # -- tile-contract family (lint/contracts.py) ------------------------
    "reserved-metric": (
        "contract", "error",
        "tile METRICS name collides with the supervisor's reserved "
        "top slots (sup_restarts/sup_watchdog_trips/sup_down)"),
    "metrics-overflow": (
        "contract", "error",
        "tile declares more metric slots than SUP_SLOT_MIN — the "
        "topology builder will reject the kind at build"),
    "undeclared-gauge": (
        "contract", "error",
        "GAUGES or DEVICE_SERIES entry is not a declared METRICS name "
        "(the prometheus renderer matches both declarations by name)"),
    "dup-metric": (
        "contract", "error",
        "duplicate name in a tile's METRICS declaration (slots are "
        "positional; the second name shadows the first)"),
    "uncredited-publish": (
        "contract", "error",
        "Ring.publish with no credit check in the same function — "
        "tango order requires publish inside a credit window "
        "(fd_fctl discipline) or it laps reliable consumers"),
    "stale-outside-supervision": (
        "contract", "error",
        "Fseq.mark_stale called from tile code — the STALE sentinel "
        "is supervision-owned (supervisor marks, rejoin clears)"),
    "per-frag-loop": (
        "contract", "error",
        "per-frag Python for loop calling a single-item hot-path API "
        "(.frag/.publish/tcache .insert/.query) inside a tile's "
        "poll_once call closure — batched equivalents exist "
        "(frag_batch/publish_batch/insert_batch/query_batch); "
        "per-txn Python is the host-pipeline bottleneck the batched "
        "tile contract forbids"),
    "silent-consumer": (
        "contract", "error",
        "registered adapter reads ctx.in_rings but defines no "
        "in_seqs(): the stem never publishes its consumer progress, "
        "so any reliable upstream producer wedges"),
    # -- JAX/Pallas purity family (lint/jaxlint.py) ----------------------
    "host-sync-item": (
        "jax", "error",
        ".item() inside jitted code forces a device->host sync per "
        "call (or a tracer error under jit)"),
    "host-cast-traced": (
        "jax", "error",
        "float()/int() on a traced value inside jitted code — host "
        "sync or ConcretizationTypeError"),
    "numpy-in-jit": (
        "jax", "error",
        "np.* call inside jitted code: applied to a traced array it "
        "forces a host sync; constants belong hoisted out of the "
        "traced region"),
    "traced-bool": (
        "jax", "error",
        "Python if/while on a jnp expression inside jitted code — "
        "traced booleans cannot drive Python control flow"),
    "x64-in-kernel": (
        "jax", "error",
        "int64/float64 dtype inside jitted/Pallas code — x64 is "
        "disabled on TPU, the dtype silently truncates or fails"),
    "prng-key-reuse": (
        "jax", "error",
        "same PRNG key passed to multiple jax.random draws without a "
        "split — correlated randomness"),
    "missing-donate": (
        "jax", "warning",
        "jax.jit entry point without donate_argnums/donate_argnames: "
        "large device inputs are copied instead of reused"),
    # -- wire/shm ABI family (lint/abi.py) -------------------------------
    "wire-mismatch": (
        "abi", "error",
        "a cataloged cross-process wire site drifted: the struct "
        "format strings extracted at the site no longer match the "
        "WIRE_CONTRACTS catalog (or the site vanished) — producer and "
        "consumer tiles would parse different bytes"),
    "wire-mtu": (
        "abi", "error",
        "link mtu below the wire family's minimum frame for its "
        "producer kind (exec dispatch header+row, exec done, shred "
        "slice/shred wire, tower vote, snapshot chunk) — publish "
        "asserts mid-flight instead of at review"),
    "short-key": (
        "abi", "error",
        "bytes key reaches a store/funk WRITE api without a provable "
        "32-byte width — the native store ABI reads EXACTLY 32 bytes, "
        "so a shorter buffer hashes per-process trailing garbage and "
        "the record becomes unfindable from other tiles (the r17 "
        "_key32 bug class)"),
    "registry-drift": (
        "abi", "error",
        "lint/registry.py mirror disagrees with the code it mirrors: "
        "an adapter consumes an args key the registry does not "
        "declare (or declares one nothing consumes), or a "
        "*_SECTION_KEYS tuple drifted from its module's *_DEFAULTS"),
    # -- shm single-writer family (lint/ownership.py) --------------------
    "dual-writer": (
        "ownership", "error",
        "write API of a single-writer shm region (trace ring, sup_* "
        "metric slots, restore marker, funk root) called from a "
        "module outside the region's cataloged writer set — two "
        "uncoordinated writers tear the region (the supervisor's "
        "post-mortem blackbox append is the annotated handoff "
        "exemplar)"),
    "torn-read": (
        "ownership", "error",
        "multiple subscript reads of a live shm u64 view in one "
        "function — a concurrent writer can update between the "
        "accesses, so the fields read belong to different states; "
        "snapshot with .copy() (tango.u64_snapshot) first"),
    # -- suppression hygiene (lint/core.py itself) -----------------------
    "bad-suppression": (
        "core", "error",
        "a '# fdlint: disable=' comment names a rule id not in the "
        "catalog — the suppression has no effect (typo?)"),
}


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int          # 1-based; 0 = file-level
    message: str

    @property
    def severity(self) -> str:
        return RULES[self.rule][1]

    @property
    def family(self) -> str:
        return RULES[self.rule][0]

    def render(self) -> str:
        return (f"{self.path}:{self.line}: {self.severity}: "
                f"[{self.rule}] {self.message}")


def finding(rule: str, path: str, line: int, message: str) -> Finding:
    if rule not in RULES:
        raise KeyError(f"unknown fdlint rule {rule!r}")
    return Finding(rule, path, int(line), message)


# ---------------------------------------------------------------------------
# inline suppressions
# ---------------------------------------------------------------------------

_SUPPRESS_RE = re.compile(r"#\s*fdlint:\s*disable=([A-Za-z0-9_,\- ]+)")


def suppressions(source: str) -> dict[int, set[str]]:
    """line (1-based) -> suppressed rule ids. A suppression on a line
    holding only the comment also covers the NEXT line."""
    out: dict[int, set[str]] = {}
    for i, text in enumerate(source.splitlines(), start=1):
        m = _SUPPRESS_RE.search(text)
        if not m:
            continue
        rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
        out.setdefault(i, set()).update(rules)
        if text.split("#", 1)[0].strip() == "":     # comment-only line
            out.setdefault(i + 1, set()).update(rules)
    return out


def filter_suppressed(findings: list[Finding],
                      source: str) -> list[Finding]:
    sup = suppressions(source)
    return [f for f in findings
            if f.rule not in sup.get(f.line, ()) and
            "all" not in sup.get(f.line, ())]


def check_suppressions(source: str, path: str) -> list[Finding]:
    """Validate disable= tokens against the catalog: a typo'd rule id
    suppresses nothing, which for a warning-severity rule can go
    unnoticed forever — so the typo itself is an error finding."""
    out = []
    for i, text in enumerate(source.splitlines(), start=1):
        m = _SUPPRESS_RE.search(text)
        if not m:
            continue
        for r in m.group(1).split(","):
            r = r.strip()
            if r and r != "all" and r not in RULES:
                from .registry import suggest
                out.append(finding(
                    "bad-suppression", path, i,
                    f"disable={r!r} is not a known rule id"
                    f"{suggest(r, RULES)}"))
    return out


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

def load_baseline(path: str) -> list[dict]:
    """[[finding]] entries with rule, path, optional line. Missing file
    -> empty baseline."""
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError:
        return []
    from ..app.config import tomllib       # shared TOML-parser fallback
    doc = tomllib.loads(data.decode())
    entries = doc.get("finding", [])
    for e in entries:
        if "rule" not in e or "path" not in e:
            raise ValueError(
                f"{path}: baseline entry needs rule + path: {e}")
    return entries


def filter_baselined(findings: list[Finding],
                     baseline: list[dict]) -> list[Finding]:
    def matches(f: Finding) -> bool:
        for e in baseline:
            if e["rule"] != f.rule:
                continue
            # path-component boundary: an entry for "demo.toml" must
            # not swallow findings in "cluster-demo.toml"
            if f.path != e["path"] and \
                    not f.path.endswith("/" + e["path"]):
                continue
            if "line" in e and int(e["line"]) != f.line:
                continue
            return True
        return False
    return [f for f in findings if not matches(f)]


# ---------------------------------------------------------------------------
# emitters
# ---------------------------------------------------------------------------

def sort_findings(findings: list[Finding]) -> list[Finding]:
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule,
                                           f.message))


def render_text(findings: list[Finding]) -> str:
    fs = sort_findings(findings)
    lines = [f.render() for f in fs]
    errs = sum(1 for f in fs if f.severity == "error")
    warns = len(fs) - errs
    lines.append(f"fdlint: {errs} error(s), {warns} warning(s)")
    return "\n".join(lines)


def render_json(findings: list[Finding]) -> str:
    """Stable machine-readable form: schema-versioned, findings sorted
    by (path, line, rule), keys fixed — safe to diff in CI."""
    fs = sort_findings(findings)
    doc = {
        "fdlint": 1,
        "counts": {
            "error": sum(1 for f in fs if f.severity == "error"),
            "warning": sum(1 for f in fs if f.severity == "warning"),
        },
        "findings": [
            {"rule": f.rule, "severity": f.severity, "path": f.path,
             "line": f.line, "message": f.message}
            for f in fs
        ],
    }
    return json.dumps(doc, indent=2, sort_keys=True) + "\n"
