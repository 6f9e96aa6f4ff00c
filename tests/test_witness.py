"""fdwitness: the witnessed-sweep orchestrator (firedancer_tpu/witness/).

Covers the ISSUE 11 test checklist: plan schema + did-you-mean (and the
load/build/lint triple for [witness]), checkpoint/resume after a
scripted mid-sweep stage failure, provenance hash-chain verification
(tamper detected — in a stage, in the flat record, and in a checkpoint
on disk), watch-mode probe timeout with a hanging fake backend, and a
fast end-to-end smoke through the real orchestrator producing a
verifiable artifact + merged report. The stage commands in the fast
tests are scripted JSON-printing children (the committed
[witness.stage.<name>] cmd seam); the slow half runs the REAL
--cpu-smoke stages.
"""
import json
import os
import subprocess
import sys
import time

import pytest

from firedancer_tpu.witness import (
    STAGES, WITNESS_DEFAULTS, WITNESS_STAGE_KEYS, WitnessRun,
    build_plan, latest_witnessed, next_round, normalize_witness,
    record_sha256, verify_chain, watch, witnessed_rounds,
)

pytestmark = pytest.mark.witness

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PY = sys.executable


def _ok_cmd(doc: dict) -> list:
    return [PY, "-c", f"import json;print(json.dumps({doc!r}))"]


def _scripted_cfg(extra=None, stages=None):
    """A full scripted plan: every stage a tiny JSON-printing child."""
    stage = {
        "device_probe": {"cmd": _ok_cmd(
            {"platform": "tpu", "device_kind": "fake v5",
             "device_count": 2})},
        "kernel_vps": {"cmd": _ok_cmd(
            {"metric": "ed25519_verifies_per_sec", "value": 402819.5,
             "unit": "verifies/s/chip", "platform": "tpu",
             "rlc_bulk_vps": 551000.0})},
        "mxu_fmul": {"cmd": _ok_cmd(
            {"platform": "tpu", "mxu_verdict": "NO-GO",
             "mxu_speedup_vs_vpu": 1.06})},
        "e2e_feed": {"cmd": _ok_cmd(
            {"platform": "tpu", "e2e_tps": 53000.0,
             "e2e_knee_tps": 51000.0})},
        "leader_knee": {"cmd": _ok_cmd(
            {"platform": "cpu", "e2e_leader_tps": 1234.0,
             "e2e_leader_knee_tps": 1200.0})},
        "exec_scale": {"cmd": _ok_cmd(
            {"platform": "cpu", "exec_scale_count": 1024,
             "exec_scale_tps": {"1": 900.0, "2": 1400.0},
             "exec_scale_tps_1": 900.0, "exec_scale_tps_2": 1400.0,
             "exec_scale_monotonic_1_2": True})},
        "flood_soak": {"cmd": _ok_cmd(
            {"platform": "tpu", "flood_goodput_tps": 900.0,
             "flood_pass": True, "rlc_prefilter_vps": 480000.0})},
        "multichip": {"cmd": _ok_cmd(
            {"platform": "tpu", "multichip_devices": 2,
             "layouts": {"one_mesh_tile": {"vps": 800000.0},
                         "rr_tiles": {"vps": 1010000.0}},
             "multichip_choice": "rr_tiles"})},
    }
    for name, ov in (extra or {}).items():
        stage[name] = ov
    cfg = {"stage": stage}
    if stages:
        cfg["stages"] = stages
    return cfg


# -- schema ------------------------------------------------------------------

def test_normalize_witness_defaults_and_validation():
    d = normalize_witness(None)
    assert d["stages"] is None and d["out_dir"] == ".fdwitness"
    assert d["park_max_s"] >= d["park_s"] > 0
    with pytest.raises(ValueError, match="did you mean 'stage'"):
        normalize_witness({"stagez": 1})
    with pytest.raises(ValueError, match="did you mean 'kernel_vps'"):
        normalize_witness({"stages": ["kernel_vp"]})
    with pytest.raises(ValueError, match="park_max_s"):
        normalize_witness({"park_s": 10.0, "park_max_s": 1.0})
    with pytest.raises(ValueError, match="probe_timeout_s"):
        normalize_witness({"probe_timeout_s": 0})
    with pytest.raises(ValueError, match="did you mean 'timeout_s'"):
        normalize_witness({"stage": {"kernel_vps": {"timeoutz_s": 1}}})
    with pytest.raises(ValueError, match="argv list"):
        normalize_witness({"stage": {"kernel_vps": {"cmd": "x y"}}})
    with pytest.raises(ValueError, match="string -> string"):
        normalize_witness({"stage": {"kernel_vps":
                                     {"env": {"A": 1}}}})
    # subsets normalize into CATALOG order (the chain order)
    got = normalize_witness({"stages": ["kernel_vps",
                                        "device_probe"]})["stages"]
    assert got == ["device_probe", "kernel_vps"]


def test_registry_mirrors_witness_keys():
    """The fdlint key registry's [witness] mirror must track the one
    validator's schema (the [trace]/[slo]/[prof]/[shed] honesty
    contract)."""
    from firedancer_tpu.lint import registry as reg
    assert set(reg.WITNESS_SECTION_KEYS) == set(WITNESS_DEFAULTS)
    assert set(reg.WITNESS_STAGE_KEYS) == set(WITNESS_STAGE_KEYS)


def test_build_plan_resolves_stages_and_overrides():
    plan = build_plan(None, REPO, cpu_smoke=True)
    assert [s["name"] for s in plan] == list(STAGES)
    kern = next(s for s in plan if s["name"] == "kernel_vps")
    assert kern["env"]["FDTPU_BENCH_CHILD"] == "1"
    assert kern["env"]["JAX_PLATFORMS"] == "cpu"
    # per-stage override wins; disabled stages drop out
    cfg = {"stage": {"kernel_vps": {"cmd": ["echo", "hi"],
                                    "timeout_s": 7.0},
                     "flood_soak": {"enable": False}}}
    plan = build_plan(cfg, REPO, stages=["kernel_vps", "flood_soak"])
    assert [s["name"] for s in plan] == ["kernel_vps"]
    assert plan[0]["cmd"] == ["echo", "hi"]
    assert plan[0]["timeout_s"] == 7.0
    with pytest.raises(ValueError, match="empty"):
        build_plan({"stage": {"kernel_vps": {"enable": False}}},
                   REPO, stages=["kernel_vps"])


def test_config_triple_gate(tmp_path):
    """[witness] gets the standard load/build/lint triple: a typo'd
    key fails topology build with a did-you-mean AND lands as a
    bad-witness fdlint finding; the clean section passes both."""
    from firedancer_tpu.app.config import build_topology, load_config
    from firedancer_tpu.lint.graph import lint_config_file
    bad = tmp_path / "bad.toml"
    bad.write_text("[witness]\nstagez = [\"kernel_vps\"]\n")
    with pytest.raises(ValueError, match="did you mean 'stage'"):
        build_topology(load_config(str(bad)))
    fs = lint_config_file(str(bad))
    assert [f.rule for f in fs] == ["bad-witness"]
    assert "did you mean" in fs[0].message
    good = tmp_path / "good.toml"
    good.write_text("[witness]\nstages = [\"device_probe\"]\n"
                    "park_s = 1.0\npark_max_s = 2.0\n"
                    "[witness.stage.device_probe]\ntimeout_s = 5.0\n")
    build_topology(load_config(str(good)))
    assert lint_config_file(str(good)) == []
    # a typo'd SECTION name is still rejected at parse (typo safety)
    typo = tmp_path / "typo.toml"
    typo.write_text("[witnes]\nx = 1\n")
    with pytest.raises(ValueError, match="unknown config sections"):
        load_config(str(typo))


# -- provenance chain --------------------------------------------------------

def test_chain_seal_and_tamper_detection():
    from firedancer_tpu.witness.provenance import chain_hash, seal
    header = {"git": {"sha": "abc", "dirty": False}}
    genesis = chain_hash("", header)
    c1 = seal({"stage": "a", "status": "ok", "result": {"v": 1}},
              genesis)
    c2 = seal({"stage": "b", "status": "ok", "result": {"v": 2}},
              c1["hash"])
    wit = {"header": header, "genesis": genesis,
           "stages": [c1, c2], "head": c2["hash"]}
    assert verify_chain(wit) == []
    # tamper a stage result -> content mismatch at that stage
    c1t = dict(c1)
    c1t["result"] = {"v": 999}
    errs = verify_chain({**wit, "stages": [c1t, c2]})
    assert any("'a'" in e and "tampered" in e for e in errs)
    # tamper the header -> genesis breaks
    errs = verify_chain({**wit,
                         "header": {"git": {"sha": "evil",
                                            "dirty": False}}})
    assert any("header tampered" in e for e in errs)
    # reorder/relink -> prev_hash breaks
    c2t = dict(c2)
    c2t["prev_hash"] = genesis
    c2t["hash"] = chain_hash(genesis,
                             {k: v for k, v in c2t.items()
                              if k != "hash"})
    errs = verify_chain({**wit, "stages": [c2t, c1]})
    assert any("broke the chain" in e for e in errs)


def test_provenance_block_shape():
    from firedancer_tpu.witness.provenance import provenance_block
    os.environ["FDTPU_BENCH_TESTKNOB"] = "7"
    try:
        b = provenance_block(REPO, extra_env={"FDTPU_BENCH_X": "1"})
    finally:
        del os.environ["FDTPU_BENCH_TESTKNOB"]
    assert len(b["git"]["sha"]) >= 7 and isinstance(b["git"]["dirty"],
                                                    bool)
    assert b["knobs"]["FDTPU_BENCH_TESTKNOB"] == "7"
    assert b["knobs"]["FDTPU_BENCH_X"] == "1"   # the env the stage SAW
    assert b["clock"]["monotonic_ns"] > 0
    assert "jax" in b["versions"]


# -- checkpoint / resume -----------------------------------------------------

def test_mid_sweep_failure_then_resume(tmp_path):
    """A scripted stage failure parks the sweep; rerunning the same
    run-id skips every completed stage (checkpoints untouched), reruns
    the failed one, finishes, and the chain verifies end to end."""
    marker = tmp_path / "flaky_marker"
    flaky = {"cmd": [PY, "-c", (
        "import json,os,sys\n"
        f"m = {str(marker)!r}\n"
        "if not os.path.exists(m):\n"
        "    open(m, 'w').close(); sys.exit(9)\n"
        "print(json.dumps({'platform': 'tpu', 'e2e_tps': 53000.0}))\n"
    )]}
    cfg = _scripted_cfg(extra={"e2e_feed": flaky})
    art = str(tmp_path / "BENCH_r97_witnessed.json")
    run = WitnessRun.create(REPO, run_id="flap", cfg=cfg,
                            out_dir=str(tmp_path), artifact_path=art,
                            log=lambda *a: None)
    assert run.run() == 1                     # parked at the failure
    assert not os.path.exists(art)
    ck = run.checkpoints()
    assert [c["status"] for c in ck] == ["ok", "ok", "ok", "failed"]
    kernel_hash = ck[1]["hash"]
    # resume: no run-id given -> the latest unfinalized run continues
    run2 = WitnessRun.create(REPO, cfg=cfg, out_dir=str(tmp_path),
                             artifact_path=art, log=lambda *a: None)
    assert run2.doc["run_id"] == "flap"
    assert run2.run() == 0
    ck = run2.checkpoints()
    assert [c["status"] for c in ck] == ["ok"] * len(STAGES)
    assert ck[1]["hash"] == kernel_hash       # completed: NOT rerun
    doc = json.load(open(art))
    assert verify_chain(doc["witness"]) == []
    assert doc["witness"]["record_sha256"] == record_sha256(doc)


def test_timeout_is_kill_hardened(tmp_path):
    """A hanging stage (a device that never answers) is killed
    at its deadline and checkpointed as `timeout`; resume reruns it."""
    cfg = _scripted_cfg(
        extra={"kernel_vps": {"cmd": [PY, "-c",
                                      "import time; time.sleep(60)"],
                              "timeout_s": 0.5}},
        stages=["device_probe", "kernel_vps"])
    run = WitnessRun.create(REPO, run_id="hang", cfg=cfg,
                            out_dir=str(tmp_path),
                            artifact_path=str(tmp_path / "a.json"),
                            log=lambda *a: None)
    t0 = time.monotonic()
    assert run.run() == 1
    assert time.monotonic() - t0 < 10
    ck = run.checkpoints()
    assert ck[-1]["status"] == "timeout"
    assert "deadline" in ck[-1]["result"]["error"]


def test_tampered_checkpoint_refuses_resume(tmp_path):
    """Editing a checkpoint on disk breaks the chain; the runner
    refuses to extend a tampered run (exit 2)."""
    cfg = _scripted_cfg(stages=["device_probe", "kernel_vps",
                                "e2e_feed"])
    # fail the LAST stage so there is something left to resume
    cfg["stage"]["e2e_feed"] = {"cmd": [PY, "-c",
                                        "import sys; sys.exit(3)"]}
    run = WitnessRun.create(REPO, run_id="tamper", cfg=cfg,
                            out_dir=str(tmp_path),
                            artifact_path=str(tmp_path / "a.json"),
                            log=lambda *a: None)
    assert run.run() == 1
    kp = os.path.join(run.run_dir, "01_kernel_vps.json")
    doc = json.load(open(kp))
    doc["result"]["value"] = 1.0
    json.dump(doc, open(kp, "w"))
    assert run.run() == 2


def test_nonzero_exit_with_json_line_is_failed(tmp_path):
    """A stage that exits nonzero is a failure even when it printed a
    structured JSON line (multichip's no-mesh error shape) — it must
    rerun on resume, not be skipped as completed."""
    cfg = _scripted_cfg(
        extra={"kernel_vps": {"cmd": [PY, "-c", (
            "import json,sys;"
            "print(json.dumps({'error': 'no mesh'}));sys.exit(1)")]}},
        stages=["device_probe", "kernel_vps"])
    run = WitnessRun.create(REPO, run_id="rcfail", cfg=cfg,
                            out_dir=str(tmp_path),
                            artifact_path=str(tmp_path / "a.json"),
                            log=lambda *a: None)
    assert run.run() == 1
    ck = run.checkpoints()
    assert ck[-1]["status"] == "failed"
    assert ck[-1]["result"]["stage_rc"] == 1


def test_witnessed_platform_falls_back_to_probe_fingerprint():
    """Stages that emit no platform (leader/flood children) or the
    'device' placeholder (the e2e parent) inherit the probe stage's
    fingerprint; an explicit 'cpu*' platform stays authoritative."""
    from firedancer_tpu.witness.artifact import merge_stages
    from firedancer_tpu.witness.provenance import seal

    def ck(stage, result, device, status="ok"):
        return seal({"stage": stage, "status": status,
                     "result": result,
                     "provenance": {"device": device}}, "p")
    tpu = {"platform": "tpu", "device_kind": "v5"}
    m = merge_stages([
        ck("flood_soak", {"flood_goodput_tps": 9.0}, tpu),   # no plat
        ck("e2e_feed", {"e2e_tps": 5.0, "platform": "device"}, tpu),
        ck("leader_knee", {"e2e_leader_tps": 2.0,
                           "platform": "cpu"}, tpu),  # explicit wins
    ])["witnessed"]
    assert m["flood_goodput_tps"]["witnessed"] is True
    assert m["e2e_tps"]["witnessed"] is True
    assert m["e2e_leader_tps"]["witnessed"] is False
    # no probe fingerprint at all -> never witnessed
    m = merge_stages([ck("e2e_feed", {"e2e_tps": 5.0,
                                      "platform": "device"}, {})])
    assert m["witnessed"]["e2e_tps"]["witnessed"] is False


def test_auto_resume_requires_matching_plan(tmp_path):
    """A leftover unfinalized run must not hijack an invocation with a
    different plan (e.g. --cpu-smoke after a parked full run); mutable
    execution knobs (--keep-going) DO follow the new invocation."""
    cfg = _scripted_cfg(stages=["device_probe", "kernel_vps"])
    cfg["stage"]["kernel_vps"] = {"cmd": [PY, "-c",
                                          "import sys; sys.exit(3)"]}
    run = WitnessRun.create(REPO, run_id="parked", cfg=cfg,
                            out_dir=str(tmp_path),
                            artifact_path=str(tmp_path / "a.json"),
                            log=lambda *a: None)
    assert run.run() == 1                       # parked at the failure
    # different stage list -> fresh run, not a hijacked resume
    other = WitnessRun.create(REPO, cfg=_scripted_cfg(
        stages=["device_probe"]), out_dir=str(tmp_path),
        artifact_path=str(tmp_path / "b.json"), log=lambda *a: None)
    assert other.doc["run_id"] != "parked"
    # same plan + keep_going override -> resumes AND keeps going past
    # the (still-failing) stage to finalize
    cfg2 = dict(cfg)
    cfg2["keep_going"] = True
    again = WitnessRun.create(REPO, cfg=cfg2, out_dir=str(tmp_path),
                              artifact_path=str(tmp_path / "a.json"),
                              log=lambda *a: None)
    assert again.doc["run_id"] == "parked"
    assert again.doc["keep_going"] is True
    assert again.run() == 0
    assert again.finalized()
    # the failed kernel stage is in the chain but contributes NO
    # headline metrics — a keep-going artifact carries gaps, not
    # clean-looking numbers from a failed run
    doc = json.load(open(tmp_path / "a.json"))
    assert "value" not in doc and "value" not in doc["witnessed"]
    assert [s["status"] for s in doc["witness"]["stages"]] \
        == ["ok", "failed"]


def test_cpu_record_never_clobbers_chip_artifact(tmp_path):
    """A cpu-measured run pointed (or defaulted) at an existing
    chip-witnessed artifact diverts into its run dir instead of
    overwriting the irreplaceable chip number; and a cpu-smoke run's
    DEFAULT artifact path never leaves the run dir at all."""
    target = tmp_path / "BENCH_r90_witnessed.json"
    target.write_text(json.dumps({"platform": "tpu",
                                  "value": 402819.5}))
    cfg = _scripted_cfg(stages=["device_probe", "kernel_vps"])
    cfg["stage"]["device_probe"] = {"cmd": _ok_cmd(
        {"platform": "cpu", "device_count": 1})}
    cfg["stage"]["kernel_vps"] = {"cmd": _ok_cmd(
        {"metric": "x", "value": 1.0, "platform": "cpu"})}
    run = WitnessRun.create(REPO, run_id="clobber", cfg=cfg,
                            out_dir=str(tmp_path),
                            artifact_path=str(target),
                            log=lambda *a: None)
    assert run.run() == 0
    assert json.load(open(target))["value"] == 402819.5   # intact
    diverted = os.path.join(run.run_dir, target.name)
    assert json.load(open(diverted))["platform"] == "cpu"
    # cpu-smoke default path: inside the run dir, never the repo root
    smoke = WitnessRun.create(REPO, run_id="smokeart",
                              cfg=_scripted_cfg(
                                  stages=["device_probe"]),
                              cpu_smoke=True, out_dir=str(tmp_path),
                              log=lambda *a: None)
    assert smoke.doc["artifact"].startswith(smoke.run_dir)


# -- watch mode --------------------------------------------------------------

def test_watch_parks_on_hanging_probe(tmp_path):
    """The probe child hangs forever; the watcher kills it at the
    deadline, parks with backoff, and gives up cleanly at max_probes
    without ever blocking."""
    cfg = _scripted_cfg(stages=["device_probe"])
    run = WitnessRun.create(REPO, run_id="park", cfg=cfg,
                            out_dir=str(tmp_path),
                            artifact_path=str(tmp_path / "a.json"),
                            log=lambda *a: None)
    t0 = time.monotonic()
    rc = watch(run, probe_timeout_s=0.5, park_s=0.05, park_max_s=0.1,
               max_probes=3,
               probe_cmd=[PY, "-c", "import time; time.sleep(60)"],
               log=lambda *a: None)
    assert rc == 3
    assert time.monotonic() - t0 < 10
    assert run.checkpoints() == []            # nothing ran


def test_watch_parks_on_cpu_then_runs_when_up(tmp_path):
    cfg = _scripted_cfg(stages=["device_probe", "kernel_vps"])
    art = str(tmp_path / "BENCH_r96_witnessed.json")
    run = WitnessRun.create(REPO, run_id="updown", cfg=cfg,
                            out_dir=str(tmp_path), artifact_path=art,
                            log=lambda *a: None)
    # cpu-only backend + require_accel -> parked
    rc = watch(run, probe_timeout_s=5, park_s=0.05, park_max_s=0.1,
               max_probes=2, probe_cmd=_ok_cmd({"platform": "cpu"}),
               log=lambda *a: None)
    assert rc == 3 and run.checkpoints() == []
    # device answers -> the sweep runs to the artifact
    rc = watch(run, probe_timeout_s=5, park_s=0.05, park_max_s=0.1,
               max_probes=2,
               probe_cmd=_ok_cmd({"platform": "tpu",
                                  "device_kind": "fake"}),
               log=lambda *a: None)
    assert rc == 0 and os.path.exists(art)


# -- artifact / report / discovery -------------------------------------------

@pytest.fixture(scope="module")
def sweep(tmp_path_factory):
    """One full scripted sweep shared by the artifact-facing tests."""
    tmp = tmp_path_factory.mktemp("sweep")
    art = str(tmp / "BENCH_r95_witnessed.json")
    run = WitnessRun.create(REPO, run_id="full", cfg=_scripted_cfg(),
                            out_dir=str(tmp), artifact_path=art,
                            log=lambda *a: None)
    assert run.run() == 0
    return {"tmp": tmp, "artifact": art,
            "report": os.path.splitext(art)[0] + ".report.html"}


def test_artifact_merges_all_stanzas(sweep):
    doc = json.load(open(sweep["artifact"]))
    # bare bench.py record shape: every reader consumes it unchanged
    assert doc["value"] == 402819.5 and doc["platform"] == "tpu"
    assert doc["rlc_bulk_vps"] == 551000.0
    assert doc["e2e_tps"] == 53000.0
    assert doc["e2e_leader_knee_tps"] == 1200.0
    assert doc["flood_pass"] is True
    assert doc["mxu_fmul"]["mxu_verdict"] == "NO-GO"
    assert doc["multichip_choice"] == "rr_tiles"
    # witnessed-vs-fallback is explicit per metric
    assert doc["witnessed"]["e2e_tps"]["witnessed"] is True
    assert doc["witnessed"]["e2e_leader_tps"]["witnessed"] is False
    # self-describing: chain + seal verify offline
    assert verify_chain(doc["witness"]) == []
    assert doc["witness"]["record_sha256"] == record_sha256(doc)
    # every stage stamped with provenance
    for ck in doc["witness"]["stages"]:
        assert ck["provenance"]["git"]["sha"]
        assert "knobs" in ck["provenance"]


def test_fdbench_verifies_and_detects_tamper(sweep, tmp_path):
    r = subprocess.run([PY, "-m", "firedancer_tpu.prof.bench_diff",
                        "--verify", sweep["artifact"]],
                       cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "chain intact" in r.stdout
    assert "[witnessed]" in r.stdout and "[cpu]" in r.stdout
    doc = json.load(open(sweep["artifact"]))
    doc["witness"]["stages"][1]["result"]["value"] = 1.0
    bad = tmp_path / "tampered.json"
    bad.write_text(json.dumps(doc))
    r = subprocess.run([PY, "-m", "firedancer_tpu.prof.bench_diff",
                        "--verify", str(bad)],
                       cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 1
    assert "TAMPERED" in r.stderr


def test_fdbench_diff_reports_witnessed_vs_fallback(sweep):
    """The diff names each number's provenance: [wit] chain-stamped,
    [cpu] smoke, [fb] carried-forward witnessed record."""
    from firedancer_tpu.prof.bench_diff import (diff_bench, load_bench,
                                                render_text)
    new = load_bench(sweep["artifact"])
    old = {"metric": "ed25519_verifies_per_sec", "value": 100.0,
           "platform": "cpu (fallback)", "e2e": "skipped",
           "witnessed_tpu": {"e2e_tps": 13273.8},
           "multichip_choice": "one_mesh_tile"}
    d = diff_bench(old, new)
    m = d["metrics"]
    assert m["value"]["old_src"] == "cpu"
    assert m["e2e_tps"]["old_src"] == "fallback"
    assert m["value"]["new_src"] == "witnessed"
    assert m["e2e_leader_knee_tps"]["new_src"] == "cpu"
    assert d["multichip"] == {"old": "one_mesh_tile",
                              "new": "rr_tiles", "changed": True}
    txt = render_text(d, [], 0.05)
    assert "[wit]" in txt and "[fb]" in txt and "[cpu]" in txt
    assert "multichip layout" in txt and "CHANGED" in txt


def test_report_carries_provenance_panel(sweep):
    html = open(sweep["report"]).read()
    assert "renderProv" in html          # the panel renderer shipped
    data = json.loads(html.split("window.FDGUI_DATA=", 1)[1]
                      .split("</script>", 1)[0].replace("<\\/", "</"))
    w = data["witness"]
    assert w["run_id"] == "full"
    assert len(w["git"]["sha"]) >= 7
    assert w["device"]["platform"] == "tpu"
    badges = {s["stage"]: s["witnessed"] for s in w["stages"]}
    assert badges["kernel_vps"] is True
    assert badges["leader_knee"] is False
    # the artifact itself is the trend page's last round
    assert data["bench"][-1]["file"].endswith("_witnessed.json")


def test_load_multichip_from_tail_and_fields(tmp_path):
    """The dryrun layout stanza is machine-readable from BOTH artifact
    shapes: a driver MULTICHIP json (stanza in the `tail` string) and
    a BENCH json persisting it as fields."""
    from firedancer_tpu.prof.bench_diff import load_multichip
    stanza = {"mesh": {"devices": 8}, "choose_by": "measurement"}
    mc = tmp_path / "MULTICHIP_r05.json"
    mc.write_text(json.dumps({
        "rc": 0, "tail": "noise\n"
        + json.dumps({"multichip_layout": stanza}) + "\n"}))
    assert load_multichip(str(mc)) == stanza
    be = tmp_path / "BENCH_r05.json"
    be.write_text(json.dumps({"multichip_layout": stanza}))
    assert load_multichip(str(be)) == stanza
    empty = tmp_path / "none.json"
    empty.write_text("{}")
    assert load_multichip(str(empty)) is None
    # the factored stanza bench.py persists matches what
    # dryrun_multichip prints (same function, pure data)
    sys.path.insert(0, REPO)
    from __graft_entry__ import multichip_layout_stanza
    s = multichip_layout_stanza(8)
    assert s["mesh"]["devices"] == 8
    assert s["rr_sharded_tiles"]["tile_cnt"] == 8


def test_latest_witnessed_numeric_discovery(tmp_path):
    """Glob-latest discovery orders rounds NUMERICALLY (r10 > r9) and
    honors the platform filter — the bench.py fallback contract that
    replaced the hardcoded filename."""
    for rnd, plat in ((4, "tpu"), (9, "tpu"), (10, "cpu")):
        (tmp_path / f"BENCH_r{rnd:02d}_witnessed.json").write_text(
            json.dumps({"platform": plat, "value": rnd}))
    assert [r for r, _ in witnessed_rounds(str(tmp_path))] == [4, 9, 10]
    path, doc = latest_witnessed(str(tmp_path))
    assert doc["value"] == 9                 # r10 is cpu: filtered
    path, doc = latest_witnessed(str(tmp_path), require_platform=None)
    assert doc["value"] == 10
    # corrupt latest -> falls back to the next readable round
    (tmp_path / "BENCH_r11_witnessed.json").write_text("{broken")
    assert latest_witnessed(str(tmp_path),
                            require_platform=None)[1]["value"] == 10
    assert next_round(str(tmp_path)) == 11


def test_dry_run_validates_without_running(tmp_path):
    r = subprocess.run([PY, "-m", "firedancer_tpu.witness", "run",
                        "--dry-run", "--cpu-smoke"],
                       cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout)
    assert doc["dry_run"] is True
    assert [s["name"] for s in doc["plan"]] == list(STAGES)
    assert doc["genesis"] and doc["header"]["git"]["sha"]
    # a broken [witness] config fails the dry run with the did-you-mean
    bad = tmp_path / "bad.toml"
    bad.write_text("[witness]\nstages = [\"kernel_vp\"]\n")
    r = subprocess.run([PY, "-m", "firedancer_tpu.witness", "run",
                        "--dry-run", "--config", str(bad)],
                       cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 2
    assert "did you mean 'kernel_vps'" in r.stderr


def test_status_lists_runs(sweep):
    r = subprocess.run([PY, "-m", "firedancer_tpu.witness", "status",
                        "--out-dir", str(sweep["tmp"])],
                       cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 0
    assert "full" in r.stdout and "[final]" in r.stdout
    assert "multichip=ok" in r.stdout


# -- the real thing (slow) ---------------------------------------------------

@pytest.mark.slow
def test_cpu_smoke_end_to_end(tmp_path):
    """The acceptance drill: `tools/fdwitness run --cpu-smoke` over the
    cheap real stages (probe + kernel + multichip — the ones that fit
    a test budget; the full sweep is the driver's run), producing a
    chain-verified artifact + merged report from real measurements."""
    art = str(tmp_path / "BENCH_r94_witnessed.json")
    r = subprocess.run(
        [os.path.join(REPO, "tools", "fdwitness"), "run", "--cpu-smoke",
         "--stages", "device_probe,kernel_vps,multichip",
         "--out-dir", str(tmp_path), "--artifact", art],
        cwd=REPO, capture_output=True, text=True, timeout=1500)
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-800:])
    doc = json.load(open(art))
    assert doc["platform"] == "cpu" and doc["value"] > 0
    assert doc["witnessed"]["value"]["witnessed"] is False  # cpu smoke
    assert doc["multichip"]["multichip_devices"] == 2
    assert set(doc["multichip"]["layouts"]) == {"one_mesh_tile",
                                                "rr_tiles"}
    assert doc["multichip_choice"] in ("one_mesh_tile", "rr_tiles")
    assert verify_chain(doc["witness"]) == []
    v = subprocess.run([PY, "-m", "firedancer_tpu.witness", "verify",
                        art], cwd=REPO, capture_output=True, text=True)
    assert v.returncode == 0, v.stderr
    assert os.path.exists(os.path.splitext(art)[0] + ".report.html")
