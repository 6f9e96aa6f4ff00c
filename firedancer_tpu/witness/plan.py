"""fdwitness stage plan: the [witness] schema + the ordered sweep.

The witnessed-run process used to be an oral tradition — ad-hoc
`/tmp/tpu_watch.sh` scripts, hand-run `bench.py` invocations, a
hardcoded fallback filename. This module is the committed replacement's
contract: ONE ordered catalog of gated stanzas (every number ROADMAP
item 1 queues behind a chip run), a validated `[witness]` config
section (the standard load/build/lint triple: app/config.py rejects a
typo at load with a did-you-mean, `build_plan` is the build gate, and
fdlint's `bad-witness` rule catches it at review), and the per-stage
subprocess specs the runner executes.

Stage catalog (plan order — the hash chain follows it):

    device_probe   hang-proof backend fingerprint (platform, device
                   kind, memory stats, device count) — the provenance
                   anchor every later stage is stamped with
    kernel_vps     bench.py kernel stage: strict Pallas `value` vps +
                   the `rlc_bulk_vps` Pallas-MSM bulk stanza
    mxu_fmul       tools/exp_mxu_fmul.py go/no-go (>2x over the VPU
                   control pays for radix-2^7)
    e2e_feed       bench.py e2e stage: feed-path tps + offered sweep +
                   knee (the r10 >=4x target)
    leader_knee    bench.py leader stage: full pack->bank->poh->shred
                   knee + saturating hop (r13)
    exec_scale     bench.py exec_scale stage: leader loop over the shm
                   funk store with resolv + an exec tile family —
                   measured tps per exec_tile_cnt and the hop snapshot
                   proving the knee moved off the bank (r16)
    flood_soak     bench.py flood stage: front-door survival goodput +
                   `rlc_prefilter_vps` at chip rate (r14)
    catchup        bench.py catchup stage: follower cold-start from a
                   ShmFunk snapshot racing live tail ingest — replay
                   over the exec family against the oracle's pinned
                   bank hashes, measured as replay_tps/catchup_s (r17)
    multichip      witness/multichip.py: the shard_map layout shootout
                   — per-chip rr tiles vs one mesh tile, measured side
                   by side with per-device memory/occupancy series
                   (the ROADMAP 1b layout decision, by measurement)

Every stage command prints its result as the LAST JSON-object line of
stdout (the bench.py child convention); the runner records it in a
checkpoint stamped with the provenance block and chained to the
previous stage's hash.
"""
from __future__ import annotations

import os
import sys

# ordered: the sweep runs (and the hash chain links) in this order
STAGES = ("device_probe", "kernel_vps", "mxu_fmul", "e2e_feed",
          "leader_knee", "exec_scale", "flood_soak", "catchup",
          "multichip")

# [witness] section keys (lint/registry.py WITNESS_SECTION_KEYS is the
# static mirror — tests/test_witness.py keeps it honest)
WITNESS_DEFAULTS = {
    "stages": None,            # ordered subset of STAGES (None = all)
    "out_dir": ".fdwitness",   # run/checkpoint dir (repo-root relative)
    "round": 0,                # artifact round (0 = latest BENCH_r*)
    "stage_timeout_s": 1800.0,  # default per-stage subprocess deadline
    "probe_timeout_s": 60.0,   # hang-proof backend-probe deadline
    "park_s": 30.0,            # watch-mode backoff floor
    "park_max_s": 360.0,       # watch-mode backoff ceiling
    "keep_going": False,       # continue the sweep past a failed stage
    "report": True,            # merged fdgui report next to the artifact
    "stage": None,             # per-stage override table (stage.<name>)
}

# [witness.stage.<name>] keys: per-stage enable/deadline and the
# command/env override (also the seam tests script failures through)
WITNESS_STAGE_KEYS = ("enable", "timeout_s", "cmd", "env")


def normalize_witness(spec: dict | None) -> dict:
    """Validate a [witness] table against the schema; returns the
    normalized dict (defaults applied). Raises ValueError with a
    did-you-mean on unknown keys/stage names — the same gate at config
    load (app/config.py), plan build (build_plan), and review
    (fdlint bad-witness)."""
    from ..lint.registry import suggest
    out = dict(WITNESS_DEFAULTS)
    spec = spec or {}
    bad = set(spec) - set(WITNESS_DEFAULTS)
    if bad:
        key = sorted(bad)[0]
        raise ValueError(f"unknown witness key(s) {sorted(bad)}"
                         + suggest(key, WITNESS_DEFAULTS))
    out.update(spec)
    if out["stages"] is not None:
        if not isinstance(out["stages"], (list, tuple)) or \
                not all(isinstance(s, str) for s in out["stages"]):
            raise ValueError("witness stages must be a list of stage "
                             f"names (subset of {list(STAGES)})")
        for s in out["stages"]:
            if s not in STAGES:
                raise ValueError(f"unknown witness stage {s!r}"
                                 + suggest(s, STAGES))
        # the sweep (and the hash chain) runs in catalog order
        out["stages"] = [s for s in STAGES if s in out["stages"]]
    for key in ("stage_timeout_s", "probe_timeout_s", "park_s",
                "park_max_s"):
        out[key] = float(out[key])
        if out[key] <= 0:
            raise ValueError(f"witness {key} must be > 0")
    if out["park_max_s"] < out["park_s"]:
        raise ValueError("witness park_max_s must be >= park_s")
    out["round"] = int(out["round"])
    if out["round"] < 0:
        raise ValueError("witness round must be >= 0")
    if not isinstance(out["out_dir"], str) or not out["out_dir"]:
        raise ValueError("witness out_dir must be a non-empty string")
    out["keep_going"] = bool(out["keep_going"])
    out["report"] = bool(out["report"])
    if out["stage"] is not None:
        if not isinstance(out["stage"], dict):
            raise ValueError("witness stage must be a table of "
                             "per-stage overrides")
        for sn, ov in out["stage"].items():
            if sn not in STAGES:
                raise ValueError(f"unknown witness stage {sn!r}"
                                 + suggest(sn, STAGES))
            if not isinstance(ov, dict):
                raise ValueError(f"witness stage {sn!r} override must "
                                 f"be a table")
            bad = set(ov) - set(WITNESS_STAGE_KEYS)
            if bad:
                key = sorted(bad)[0]
                raise ValueError(
                    f"witness stage {sn!r}: unknown key(s) "
                    f"{sorted(bad)}" + suggest(key, WITNESS_STAGE_KEYS))
            if "cmd" in ov and (
                    not isinstance(ov["cmd"], (list, tuple))
                    or not all(isinstance(c, str) for c in ov["cmd"])):
                raise ValueError(f"witness stage {sn!r}: cmd must be "
                                 f"an argv list of strings")
            if "env" in ov and (
                    not isinstance(ov["env"], dict)
                    or not all(isinstance(k, str) and isinstance(v, str)
                               for k, v in ov["env"].items())):
                raise ValueError(f"witness stage {sn!r}: env must be a "
                                 f"string -> string table")
            if "timeout_s" in ov and float(ov["timeout_s"]) <= 0:
                raise ValueError(f"witness stage {sn!r}: timeout_s "
                                 f"must be > 0")
    return out


# hang-proof backend fingerprint: the RUNNER bounds this subprocess
# with probe_timeout_s and kills it on hang (a device that cannot
# start may block jax.devices() forever) — the snippet itself
# just reports what it sees
PROBE_SNIPPET = """\
import json, os, sys
import jax
devs = jax.devices()
d0 = devs[0]
mem = {}
try:
    mem = d0.memory_stats() or {}
except Exception:
    pass
print(json.dumps({
    "platform": d0.platform,
    "device_kind": getattr(d0, "device_kind", ""),
    "device_count": len(devs),
    "local_device_count": jax.local_device_count(),
    "memory_stats": {k: int(v) for k, v in mem.items()},
    "jax_version": jax.__version__,
}))
"""

# cpu-smoke knob sets: the SAME stages, CPU-sized so a box with no
# accelerator can drill the whole orchestrator end to end (checkpoints,
# chain, artifact, report) in minutes. RLC is skipped by default — the
# jnp MSM graph costs minutes of compile on CPU (PERF.md); the chip
# sweep runs it for real.
_CPU_SMOKE_ENV = {
    "JAX_PLATFORMS": "cpu",
}
_CPU_SMOKE_STAGE_ENV = {
    "kernel_vps": {"FDTPU_BENCH_BATCH": "16", "FDTPU_BENCH_ITERS": "2",
                   "FDTPU_BENCH_MSG_LEN": "256",
                   "FDTPU_BENCH_SKIP_RLC": "1"},
    "e2e_feed": {"FDTPU_BENCH_E2E_COUNT": "8192",
                 "FDTPU_BENCH_E2E_UNIQUE": "128",
                 "FDTPU_BENCH_E2E_BATCH": "64",
                 "FDTPU_BENCH_E2E_SWEEP": "0.8"},
    "leader_knee": {"FDTPU_BENCH_LEADER_COUNT": "1024",
                    "FDTPU_BENCH_LEADER_UNIQUE": "256",
                    "FDTPU_BENCH_LEADER_BATCH": "16",
                    "FDTPU_BENCH_LEADER_SWEEP": "0.8",
                    "FDTPU_BENCH_LEADER_STANZA_S": "2.0"},
    "exec_scale": {"FDTPU_BENCH_EXEC_COUNT": "1024",
                   "FDTPU_BENCH_EXEC_UNIQUE": "256",
                   "FDTPU_BENCH_EXEC_BATCH": "16",
                   "FDTPU_BENCH_EXEC_SCALE_CNTS": "1,2"},
    "flood_soak": {"FDTPU_BENCH_FLOOD_S": "4",
                   "FDTPU_BENCH_FLOOD_PROBE_PPS": "40",
                   "FDTPU_BENCH_FLOOD_SYBILS": "8",
                   "FDTPU_BENCH_FLOOD_MULT": "3"},
    "catchup": {"FDTPU_BENCH_CATCHUP_COUNT": "96",
                "FDTPU_BENCH_CATCHUP_SLOTS": "8",
                "FDTPU_BENCH_CATCHUP_SNAP_SLOT": "3",
                "FDTPU_BENCH_CATCHUP_EXEC_TILES": "2"},
}


def default_stage_cmds(repo_root: str,
                       cpu_smoke: bool = False) -> dict[str, list[str]]:
    """stage name -> argv (cwd = repo_root for every stage)."""
    py = sys.executable
    bench = os.path.join(repo_root, "bench.py")
    mxu = [py, os.path.join(repo_root, "tools", "exp_mxu_fmul.py")]
    multi = [py, "-m", "firedancer_tpu.witness.multichip"]
    if cpu_smoke:
        mxu += ["--batch", "64", "--reps", "2"]
        multi += ["--devices", "2", "--batch", "16", "--iters", "2",
                  "--msg-len", "96"]
    return {
        "device_probe": [py, "-c", PROBE_SNIPPET],
        "kernel_vps": [py, bench],
        "mxu_fmul": mxu,
        "e2e_feed": [py, bench],
        "leader_knee": [py, bench],
        "exec_scale": [py, bench],
        "flood_soak": [py, bench],
        "catchup": [py, bench],
        "multichip": multi,
    }


# the bench.py stage-mux envs (main() dispatches on these)
_STAGE_CHILD_ENV = {
    "kernel_vps": {"FDTPU_BENCH_CHILD": "1"},
    "e2e_feed": {"FDTPU_BENCH_E2E_CHILD": "1"},
    "leader_knee": {"FDTPU_BENCH_LEADER_CHILD": "1"},
    "exec_scale": {"FDTPU_BENCH_EXEC_SCALE_CHILD": "1"},
    "flood_soak": {"FDTPU_BENCH_FLOOD_CHILD": "1"},
    "catchup": {"FDTPU_BENCH_CATCHUP_CHILD": "1"},
}


def build_plan(cfg: dict | None, repo_root: str,
               cpu_smoke: bool = False,
               stages: list[str] | None = None) -> list[dict]:
    """[witness] config (or None) -> the ordered, fully-resolved stage
    plan: [{name, cmd, env, timeout_s}]. This is the build-time gate of
    the load/build/lint triple — a bad table fails here before any
    stage runs. `stages` (CLI --stages) narrows further; order is
    always catalog order."""
    norm = normalize_witness(cfg)
    names = norm["stages"] or list(STAGES)
    if stages is not None:
        for s in stages:
            if s not in STAGES:
                from ..lint.registry import suggest
                raise ValueError(f"unknown witness stage {s!r}"
                                 + suggest(s, STAGES))
        names = [s for s in names if s in stages]
    cmds = default_stage_cmds(repo_root, cpu_smoke=cpu_smoke)
    overrides = norm["stage"] or {}
    plan = []
    for name in names:
        ov = overrides.get(name, {})
        if not ov.get("enable", True):
            continue
        env = {}
        if cpu_smoke:
            env.update(_CPU_SMOKE_ENV)
            env.update(_CPU_SMOKE_STAGE_ENV.get(name, {}))
        env.update(_STAGE_CHILD_ENV.get(name, {}))
        env.update(ov.get("env", {}))
        timeout = float(ov.get("timeout_s",
                               norm["probe_timeout_s"]
                               if name == "device_probe"
                               else norm["stage_timeout_s"]))
        plan.append({"name": name,
                     "cmd": list(ov.get("cmd", cmds[name])),
                     "env": env, "timeout_s": timeout})
    if not plan:
        raise ValueError("witness plan is empty (every stage disabled "
                         "or filtered out)")
    return plan
