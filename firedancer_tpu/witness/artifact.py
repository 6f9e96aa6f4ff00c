"""Witnessed-artifact discovery + assembly.

One definition of "the latest witnessed round" shared by fdwitness
(next round number, previous-round diffing) and fdbench (witnessed-vs-fallback reporting) — replacing the
hardcoded `BENCH_r05_witnessed.json` filename that silently went stale
every round.
"""
from __future__ import annotations

import glob
import json
import os
import re

WITNESSED_RE = re.compile(r"BENCH_r(\d+)_witnessed\.json$")
ROUND_RE = re.compile(r"BENCH_r(\d+)(?:_witnessed)?\.json$")


def witnessed_rounds(root: str) -> list[tuple[int, str]]:
    """[(round, path)] of every BENCH_r*_witnessed.json under root,
    NUMERICALLY ordered (r10 beats r9 — lexicographic sort does not)."""
    out = []
    for p in glob.glob(os.path.join(root, "BENCH_r*_witnessed.json")):
        m = WITNESSED_RE.search(os.path.basename(p))
        if m:
            out.append((int(m.group(1)), p))
    return sorted(out)


def latest_witnessed(root: str, require_platform: str | None = "tpu"
                     ) -> tuple[str, dict] | None:
    """(path, record) of the newest readable witnessed artifact —
    newest round first, skipping unreadable files and (when
    require_platform is set) records measured on another backend (a
    cpu-smoke artifact must never stand in for the chip number)."""
    for _, path in reversed(witnessed_rounds(root)):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if not isinstance(doc, dict):
            continue
        if require_platform is not None and \
                doc.get("platform") != require_platform:
            continue
        return path, doc
    return None


def next_round(root: str) -> int:
    """The round number a fresh witnessed artifact belongs to: the
    latest BENCH_r*.json round (witnessed or not), so the artifact
    lands NEXT TO the driver round it witnesses; 1 when none exist."""
    rounds = [int(m.group(1))
              for p in glob.glob(os.path.join(root, "BENCH_r*.json"))
              if (m := ROUND_RE.search(os.path.basename(p)))]
    return max(rounds) if rounds else 1


# stage -> (exact result keys, key prefixes) merged into the artifact
# top level. The artifact keeps bench.py's bare-record shape so every
# existing reader (bench.py fallback, fdbench, fdgui trends) consumes
# it unchanged; the `witness` block rides alongside.
_MERGE_RULES = {
    "kernel_vps": (("metric", "value", "unit", "vs_baseline",
                    "platform", "kernel", "batch", "iters", "msg_len",
                    "p99_batch_ms", "compile_s", "rlc_bulk_vps",
                    "rlc_bulk_batch", "rlc_compile_s", "rlc_error"),
                   ()),
    "e2e_feed": ((), ("e2e_",)),
    "leader_knee": ((), ("e2e_leader",)),
    "exec_scale": ((), ("exec_scale",)),
    "flood_soak": (("rlc_prefilter_vps",), ("flood_",)),
    "catchup": (("replay_tps",), ("catchup_",)),
}


def stage_platform(ckpt: dict, result: dict) -> str:
    """What backend a stage's numbers were really measured on. A stage
    that names its platform is authoritative ('cpu (fallback)' is a
    cpu number wherever it ran); bench children that DON'T emit one
    (leader/flood — host-side loops driving device verify tiles) or
    that report the 'device' placeholder (the e2e parent must not
    init jax) inherit the probe stage's device fingerprint, which the
    runner stamps into every later checkpoint's provenance."""
    plat = str(result.get("platform") or "")
    if plat in ("", "device"):
        plat = str(((ckpt.get("provenance") or {}).get("device")
                    or {}).get("platform") or "")
    return plat


def merge_stages(stages: list[dict]) -> dict:
    """Stage checkpoints -> the flat witnessed record (bare bench.py
    shape) + the per-metric witnessed map."""
    rec: dict = {}
    witnessed: dict = {}
    for ckpt in stages:
        name, result = ckpt.get("stage"), ckpt.get("result")
        if not isinstance(result, dict):
            continue
        if ckpt.get("status") != "ok":
            # a failed/timed-out stage's parsed output stays in the
            # chain for diagnosis but must never surface as a headline
            # metric — a --keep-going artifact may carry gaps, not
            # clean-looking numbers from a failed run
            continue
        plat = stage_platform(ckpt, result)
        rule = _MERGE_RULES.get(name)
        if rule is not None:
            keys, prefixes = rule
            for k, v in result.items():
                if k in keys or k.startswith(prefixes):
                    rec[k] = v
                    witnessed[k] = {
                        "stage": name,
                        "witnessed": bool(plat)
                        and not plat.startswith("cpu"),
                    }
        elif name == "device_probe":
            rec.setdefault("platform", result.get("platform"))
        elif name == "mxu_fmul":
            rec["mxu_fmul"] = result
        elif name == "multichip":
            rec["multichip"] = result
            if "multichip_choice" in result:
                rec["multichip_choice"] = result["multichip_choice"]
    return {"record": rec, "witnessed": witnessed}


def assemble(run_doc: dict, stages: list[dict]) -> dict:
    """Run header + chained checkpoints -> the final self-describing
    artifact: flat record + `witnessed` per-metric map + full `witness`
    chain block."""
    merged = merge_stages(stages)
    art = dict(merged["record"])
    art["witnessed"] = merged["witnessed"]
    art["witness"] = {
        "v": 1,
        "run_id": run_doc.get("run_id"),
        "cpu_smoke": bool(run_doc.get("cpu_smoke")),
        "header": run_doc.get("header"),
        "genesis": run_doc.get("genesis"),
        "stages": stages,
        "head": stages[-1]["hash"] if stages else run_doc.get("genesis"),
        # the flat record (everything outside this block) is sealed
        # too: editing a headline number without re-deriving it from
        # the chained stage results is detectable
        "record_sha256": record_sha256(art),
    }
    return art


def record_sha256(doc: dict) -> str:
    """Recompute the flat-record seal of an artifact (everything
    outside the witness block) — compared against
    witness.record_sha256 by the verifiers."""
    import hashlib

    from .provenance import canonical
    return hashlib.sha256(
        canonical({k: v for k, v in doc.items()
                   if k != "witness"})).hexdigest()
