"""fdbench: the bench-trend observatory — diff two BENCH_r*.json
files with a regression-threshold exit code.

Every bench round prints one JSON document (bench.py): kernel
verifies/s, e2e tps + knee, per-hop link budget, and (since fdprof)
the per-stage profile summary. This tool turns two of those documents
into the answer a perf PR must ship with: WHAT moved, by HOW much, and
WHERE the time went — instead of a bare before/after number.

    tools/fdbench OLD.json NEW.json             # human diff
    tools/fdbench OLD.json NEW.json --gate      # exit 1 on regression
        [--threshold 0.05]                      # allowed fractional drop
    tools/fdbench --verify BENCH_r05_witnessed.json
                                                # fdwitness chain check

Provenance is explicit per metric: the diff badges every number
[wit] (fdwitness chain-stamped on a real device), [cpu] (measured on
the CPU backend) or [fb] (the prior witnessed record standing in), and
--verify recomputes a witnessed artifact's provenance hash chain +
record seal, exiting 1 on tamper.

Gated metrics (higher is better): the kernel vps (`value`), `e2e_tps`,
`e2e_knee_tps`, the leader knee, and the r14 front-door set
(`rlc_bulk_vps`, `rlc_prefilter_vps`, `flood_goodput_tps`). A metric
absent from either side is reported but
never gated (a pre-r21 CPU-fallback record must not fail the gate for
skipping e2e — its embedded witnessed_tpu record stands in when
present). The profile top-k
and link-budget deltas are attribution, not gates.
"""
from __future__ import annotations

import argparse
import json
import sys

# (json key, label); all higher-is-better, gate-eligible
GATE_METRICS = (
    ("value", "kernel vps"),
    ("e2e_tps", "e2e tps"),
    ("e2e_knee_tps", "e2e knee tps"),
    ("e2e_leader_knee_tps", "leader knee tps"),
    # front-door survival (r14): RLC bulk kernel + prefilter rate and
    # staked goodput under the seeded forged-sig flood
    ("rlc_bulk_vps", "rlc bulk vps"),
    ("rlc_prefilter_vps", "rlc prefilter vps"),
    ("flood_goodput_tps", "flood goodput tps"),
    # execution scale-out (r16, widened r19): the exec-family leader
    # loop's capacity at 1/2/4 exec tiles — the full scaling curve,
    # so a regression that only shows at one shard count still gates
    ("exec_scale_tps_1", "exec scale tps (1 tile)"),
    ("exec_scale_tps_2", "exec scale tps (2 tiles)"),
    ("exec_scale_tps_4", "exec scale tps (4 tiles)"),
    # follower catch-up (r17): snapshot-restore + tail replay over the
    # exec family — the "become a follower" throughput contract
    ("replay_tps", "catch-up replay tps"),
)

# report-only metrics: lower-is-better (or too noisy to gate), so a
# "drop" is an improvement — diffed and rendered, never gated
REPORT_METRICS = (
    ("catchup_s", "catch-up wall s (lower is better)"),
)
_REPORT_ONLY = frozenset(k for k, _ in REPORT_METRICS)

# the knee subset: what bench.py's implicit previous-round gate
# (FDTPU_BENCH_PREV unset -> latest BENCH_r*.json) compares — knee
# regressions are the r13 contract; kernel/raw-tps noise across
# heterogeneous rounds stays report-only there
KNEE_METRICS = ("e2e_knee_tps", "e2e_leader_knee_tps")


def load_bench(path: str) -> dict:
    """A BENCH json in either shape: the bare record bench.py prints
    (BENCH_r*_witnessed.json) or the driver wrapper whose `tail`
    string holds that record as its last JSON-object line
    (BENCH_r*.json round artifacts)."""
    with open(path) as f:
        doc = json.load(f)
    tail = doc.get("tail")
    if isinstance(tail, str):
        for line in reversed(tail.strip().splitlines()):
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict) and "metric" in rec:
                return rec
    return doc


def load_multichip(path: str) -> dict | None:
    """The machine-readable `multichip_layout` stanza of a driver
    MULTICHIP_r*.json (its `tail` string carries the dryrun's one JSON
    line) or of a BENCH json that persists it as a field — so layout
    records diff round over round without scraping printed text."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc.get("multichip_layout"), dict):
        return doc["multichip_layout"]
    tail = doc.get("tail")
    if isinstance(tail, str):
        for line in reversed(tail.strip().splitlines()):
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict) and "multichip_layout" in rec:
                return rec["multichip_layout"]
    return None


def _metric(doc: dict, key: str):
    """A gated metric, honoring the witnessed-record fallback that
    records from before r21 carry when their e2e stage was skipped."""
    return _metric_src(doc, key)[0]


def _metric_src(doc: dict, key: str):
    """(value, source) — the source says EXPLICITLY where the number
    came from: 'witnessed' (an fdwitness chain stamped it on a real
    device), 'cpu' (measured, but on the CPU backend — a smoke number,
    not a chip claim), 'measured' (no provenance info, taken at face
    value), or 'fallback' (this round skipped the stage and carries
    the prior witnessed record)."""
    v = doc.get(key)
    src = None
    if v is not None:
        wit = doc.get("witnessed")
        if isinstance(wit, dict) and key in wit:
            src = "witnessed" if wit[key].get("witnessed") else "cpu"
        elif str(doc.get("platform", "")).startswith("cpu"):
            src = "cpu"
        else:
            src = "measured"
    elif key.startswith("e2e"):
        v = doc.get("witnessed_tpu", {}).get(key)
        if v is not None:
            src = "fallback"
    try:
        return (float(v), src) if v is not None else (None, None)
    except (TypeError, ValueError):
        return None, None


def _top_stacks(doc: dict) -> dict[str, dict[str, int]]:
    """{tile: {stack: count}} from a BENCH json's e2e_profile."""
    out: dict[str, dict[str, int]] = {}
    for tn, p in (doc.get("e2e_profile") or {}).items():
        out[tn] = {t["stack"]: int(t["count"])
                   for t in p.get("top", [])}
    return out


def diff_bench(old: dict, new: dict) -> dict:
    """Structured delta document (JSON-able): gated metric moves,
    per-hop link-budget deltas, and profile top-k churn."""
    metrics = {}
    for key, label in (*GATE_METRICS, *REPORT_METRICS):
        (ov, osrc), (nv, nsrc) = (_metric_src(old, key),
                                  _metric_src(new, key))
        rec = {"label": label, "old": ov, "new": nv,
               "old_src": osrc, "new_src": nsrc}
        if ov is not None and nv is not None and ov > 0:
            rec["frac"] = (nv - ov) / ov
        metrics[key] = rec
    # multichip layout choice (fdwitness multichip stage): a layout
    # flip between rounds is exactly the kind of silent change the
    # diff must surface
    multichip = None
    oc, nc = old.get("multichip_choice"), new.get("multichip_choice")
    if oc is not None or nc is not None:
        multichip = {"old": oc, "new": nc, "changed": oc != nc}
    links = {}
    ol = old.get("e2e_link_budget") or {}
    nl = new.get("e2e_link_budget") or {}
    for ln in sorted(set(ol) | set(nl)):
        o, n = ol.get(ln, {}), nl.get(ln, {})
        links[ln] = {k: {"old": o.get(k), "new": n.get(k)}
                     for k in ("pub", "lost", "backpressure",
                               "consume_p99_us")
                     if k in o or k in n}
    ot, nt = _top_stacks(old), _top_stacks(new)
    profile = {}
    for tn in sorted(set(ot) | set(nt)):
        o, n = ot.get(tn, {}), nt.get(tn, {})
        rows = {}
        for stack in sorted(set(o) | set(n)):
            if o.get(stack) != n.get(stack):
                rows[stack] = {"old": o.get(stack, 0),
                               "new": n.get(stack, 0)}
        if rows:
            profile[tn] = rows
    return {"metrics": metrics, "links": links, "profile": profile,
            "multichip": multichip}


def gate_regressions(diff: dict, threshold: float = 0.05,
                     keys=None) -> list[dict]:
    """Gated metrics whose fractional drop exceeds the threshold —
    non-empty means the gate fails (exit 1). `keys` restricts the
    gate to a metric subset (KNEE_METRICS for the implicit
    previous-round gate); None gates everything."""
    out = []
    for key, rec in diff["metrics"].items():
        if keys is not None and key not in keys:
            continue
        if key in _REPORT_ONLY:
            continue
        frac = rec.get("frac")
        if frac is not None and frac < -threshold:
            out.append({"metric": key, "label": rec["label"],
                        "old": rec["old"], "new": rec["new"],
                        "frac": frac})
    return out


def report_path_for(bench_path: str,
                    dir_fallback: bool = True) -> str | None:
    """The fdgui report artifact that belongs to a BENCH json, if one
    exists: `<base>.report.html` next to it, else (when dir_fallback)
    the directory's `report.html` — what bench.py writes under
    FDTPU_BENCH_REPORT. Callers comparing two rounds in the SAME
    directory must disable the fallback: one shared report.html holds
    only the latest run and would be misattributed to both rounds."""
    import os
    cands = [os.path.splitext(bench_path)[0] + ".report.html"]
    if dir_fallback:
        cands.append(os.path.join(
            os.path.dirname(bench_path) or ".", "report.html"))
    for cand in cands:
        if os.path.exists(cand):
            return cand
    return None


def render_text(diff: dict, regressions: list[dict],
                threshold: float, reports=None) -> str:
    lines = ["fdbench diff", "============"]
    for label, path in (reports or ()):
        if path:
            lines.append(f"report ({label}): {path}")
    # provenance badges (fdwitness): [wit] chain-stamped on a device,
    # [cpu] measured on the CPU backend, [fb] prior witnessed record
    # standing in — so a diff can never pass off a fallback or a smoke
    # number as a fresh chip measurement
    _BADGE = {"witnessed": "[wit]", "cpu": "[cpu]", "fallback": "[fb]",
              "measured": "", None: ""}
    for key, rec in diff["metrics"].items():
        ov, nv = rec["old"], rec["new"]
        if ov is None and nv is None:
            continue
        arrow = ""
        if rec.get("frac") is not None:
            arrow = f"  ({rec['frac']:+.1%})"
        ob = _BADGE.get(rec.get("old_src"), "")
        nb = _BADGE.get(rec.get("new_src"), "")
        lines.append(f"{rec['label']:<16} "
                     f"{ov if ov is not None else '-':>12}{ob:<5} -> "
                     f"{nv if nv is not None else '-':>12}{nb:<5}"
                     f"{arrow}")
    mc = diff.get("multichip")
    if mc:
        lines.append(f"multichip layout  "
                     f"{mc['old'] or '-'} -> {mc['new'] or '-'}"
                     + ("  (CHANGED)" if mc["changed"] else ""))
    if diff["links"]:
        lines.append("")
        lines.append(f"{'link':<18}{'pub':>16}{'lost':>12}"
                     f"{'bp':>12}{'p99us':>14}")
        for ln, rec in diff["links"].items():
            def cell(k):
                c = rec.get(k)
                if not c:
                    return "-"
                return f"{c['old'] if c['old'] is not None else '-'}" \
                       f"->{c['new'] if c['new'] is not None else '-'}"
            lines.append(f"{ln:<18}{cell('pub'):>16}{cell('lost'):>12}"
                         f"{cell('backpressure'):>12}"
                         f"{cell('consume_p99_us'):>14}")
    for tn, rows in diff["profile"].items():
        lines.append("")
        lines.append(f"profile {tn} (top-k sample-count deltas):")
        for stack, c in rows.items():
            lines.append(f"  {c['old']:>6} -> {c['new']:>6}  {stack}")
    lines.append("")
    if regressions:
        for r in regressions:
            lines.append(f"REGRESSION: {r['label']} {r['old']} -> "
                         f"{r['new']} ({r['frac']:+.1%}, threshold "
                         f"-{threshold:.0%})")
    else:
        lines.append(f"gate: clean (threshold -{threshold:.0%})")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="fdbench",
        description="diff two BENCH json files; --gate exits nonzero "
                    "on a regression beyond --threshold; --verify "
                    "checks a witnessed artifact's provenance chain")
    ap.add_argument("old")
    ap.add_argument("new", nargs="?", default=None)
    ap.add_argument("--gate", action="store_true")
    ap.add_argument("--threshold", type=float, default=0.05)
    ap.add_argument("--json", action="store_true",
                    help="emit the structured diff document instead")
    ap.add_argument("--verify", action="store_true",
                    help="single-file mode: verify the fdwitness "
                         "provenance hash chain of a "
                         "BENCH_r*_witnessed.json (exit 1 on tamper)")
    args = ap.parse_args(argv)
    if args.verify:
        # one definition of chain verification, shared with
        # `tools/fdwitness verify`
        from ..witness.cli import verify_artifact
        return verify_artifact(args.old)
    if args.new is None:
        ap.error("new is required unless --verify is given")
    old = load_bench(args.old)
    new = load_bench(args.new)
    d = diff_bench(old, new)
    regs = gate_regressions(d, threshold=args.threshold)
    # per-directory report.html is only attributable when the two
    # rounds live in different directories (per-round CI archives);
    # same-dir rounds share one file that holds only the latest run
    import os as _os
    fb = _os.path.dirname(_os.path.abspath(args.old)) \
        != _os.path.dirname(_os.path.abspath(args.new))
    reports = (("old", report_path_for(args.old, dir_fallback=fb)),
               ("new", report_path_for(args.new, dir_fallback=fb)))
    if args.json:
        json.dump({"diff": d, "regressions": regs,
                   "reports": dict(reports)},
                  sys.stdout, indent=2)
        sys.stdout.write("\n")
    else:
        # link each round's fdgui report artifact when one exists —
        # the diff names what moved, the reports show where
        sys.stdout.write(render_text(d, regs, args.threshold,
                                     reports=reports))
    return 1 if (args.gate and regs) else 0


if __name__ == "__main__":
    raise SystemExit(main())
