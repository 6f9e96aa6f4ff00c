"""perfbench's harness off the chip: a tiny CPU rehearsal of a cell end
to end (the device check steered to the CPU from the test's own
driver), the last line's keys, the refusals (no TPU, too few chips, an
unknown device kind, no repo), the trace reduction on a hand-built
trace, the control coming out not correct, and the reference agreeing
with the strict copy on chip_smoke's vector classes."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness, reference, tracereduce  # noqa: E402

DRIVER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "fault_driver.py")
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def drive(workload: str, fault: str = "none"):
    env = dict(os.environ, PERFBENCH_TEST_FAULT=fault, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, DRIVER, workload], cwd=ROOT,
                       env=env, capture_output=True, text=True,
                       timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1]), r.stderr


def test_cpu_rehearsal_end_to_end():
    out, err = drive("frontdoor.mainnet.60k")
    assert list(out) == KEYS                 # `checks` comes last
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 100 and out["failed"] == 0
    assert set(out["metrics"]) == {"verdict_p50_ms", "setup_s"}
    assert out["metrics"]["verdict_p50_ms"]["unit"] == "ms"
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    assert "memory_peak_bytes" in out["device"]
    tail = err.strip().splitlines()[-len(out["checks"]):]
    assert all(ln.startswith("check ") and "(limit 0)" in ln for ln in tail)


def test_refuses_without_a_tpu():
    """JAX_PLATFORMS=cpu: the plan would put verify on the host, so the
    run stops before it signs or boots anything, and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "perfbench/run.py", "--workload",
                        "frontdoor.mainnet.60k", "--seed", str(2 ** 33),
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "not 'tpu'" in r.stderr


@pytest.mark.parametrize("boot", [
    {"platform": "cpu", "kind": "cpu", "count": 1},
    {"platform": "tpu", "kind": "TPU v5 lite", "count": 0},
    {"platform": "tpu", "kind": "TPU v9 imaginary", "count": 1},
    {}])
def test_device_check_refuses(boot):
    with pytest.raises(harness.BenchError):
        harness.check_device(boot, chips=1)


def test_device_check_passes_a_v5e():
    harness.check_device({"platform": "tpu", "kind": "TPU v5 lite",
                          "count": 1}, chips=1)
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_boot_line_parse(tmp_path):
    log = tmp_path / "t.log"
    log.write_text("2026 NOTICE pb1:dedup:7 tile booting\n"
                   "2026 NOTICE pb1:verify:8 verify: jax platform=tpu "
                   "device_kind=TPU v5 lite devices=1 shards=1\n")
    assert harness.boot_line(str(log), "verify") == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_fails_alone_without_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    r = subprocess.run([sys.executable, "perfbench/run.py", "--workload",
                        "frontdoor.mainnet.60k", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout


def test_every_cell_names_files_that_exist():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        spec = harness.load_cell(w["name"])
        assert spec["cell"]["rate"] > 0
        for kind in ("end_to_end", "per_layer"):
            for m in spec["metrics"][kind]:
                assert callable(harness.reader(m["name"]))
        assert "setup_s" in {m["name"] for m in spec["metrics"]["end_to_end"]}


# -- the trace reduction ----------------------------------------------------

def _trace():
    ms = 1_000_000
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit__packed(1)", 0, 17 * ms],
                ["jit__packed(1)", 40 * ms, 19 * ms],
                ["jit_other", 70 * ms, 5 * ms]]},
            {"name": "XLA Ops", "events": [
                ["sha512", 0, 5 * ms], ["verify_kernel", 4 * ms, 13 * ms],
                ["sha512", 40 * ms, 5 * ms],
                ["verify_kernel", 45 * ms, 14 * ms],
                ["copy", 70 * ms, 5 * ms]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ["gather", 18 * ms, 20 * ms], ["parse", 60 * ms, 9 * ms],
                ["whole_run", 0, 100 * ms]]}]}]}


def test_trace_reduction_busy_idle_and_calls():
    red = tracereduce.reduce_trace(_trace(), window_ns=100_000_000)
    assert red["busy_s"] == pytest.approx(0.041)          # 17 + 19 + 5 ms
    assert red["window_s"] == pytest.approx(0.1)
    assert red["strict_calls"] == 2
    assert red["strict_call_ms"] == pytest.approx(18.0)
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0] == ["host: gather", pytest.approx(0.023)]
    assert gaps[1] == ["host: parse", pytest.approx(0.011)]
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["verify_kernel"] == pytest.approx(0.027)
    assert len(red["breakdown"]["device_ops"]) <= 10


def test_trace_reduction_without_a_device_plane_reads_nothing():
    doc = {"planes": [p for p in _trace()["planes"]
                      if p["name"].startswith("/host")]}
    assert tracereduce.reduce_trace(doc, 100_000_000) is None


def test_device_idle_reader_is_silent_untraced():
    r = harness.Run()
    r.trace = r.capture = None
    assert harness.reader("device_idle.tps")(r) is None
    assert harness.reader("strict_call_ms.tps")(r) is None
    r.trace = tracereduce.reduce_trace(_trace(), 100_000_000)
    assert harness.reader("device_idle.tps")(r) == pytest.approx(59.0)


# -- the reference and the comparison ------------------------------------

def _chip_smoke_vectors(n=30, seed=21):
    """chip_smoke.make_data's classes: valid; corrupt R, S, msg, A;
    S + l; small-order A and R."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import \
        Ed25519PrivateKey

    from perfbench.ed25519_strict import L
    rng = np.random.default_rng(seed)
    key = Ed25519PrivateKey.from_private_bytes(rng.bytes(32))
    pub = key.public_key().public_bytes_raw()
    small = (bytes([1]) + bytes(31), bytes([0xEC]) + b"\xff" * 30 + b"\x7f")
    out = []
    for i in range(n):
        msg = rng.bytes(int(rng.integers(0, 200)))
        sig = bytearray(key.sign(msg))
        p, m, c = bytearray(pub), bytearray(msg), i % 8
        if c == 1:
            sig[0] ^= 1
        elif c == 2:
            sig[33] ^= 1
        elif c == 3 and m:
            m[-1] ^= 0x80
        elif c == 4:
            p[3] ^= 1
        elif c == 5:
            s = int.from_bytes(bytes(sig[32:]), "little") + L
            sig[32:] = s.to_bytes(32, "little")
        elif c == 6:
            p = bytearray(small[0])
        elif c == 7:
            sig[:32] = small[1]
        out.append((bytes(sig), bytes(p), bytes(m), c))
    return out


def test_openssl_and_strict_agree_where_the_mixes_go():
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import \
        Ed25519PublicKey

    from perfbench.ed25519_strict import verify
    for sig, pub, msg, c in _chip_smoke_vectors():
        strict = verify(sig, pub, msg)
        try:
            Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
            ossl = True
        except (InvalidSignature, ValueError):
            ossl = False
        if c <= 5:                  # the classes the mixes can make
            assert strict == ossl == (c == 0 or (c == 3 and not msg)), c
        else:                       # small order: strict rejects
            assert strict is False


def test_judge_counts_each_fault():
    valid = np.array([1, 1, 1, 0, 1], bool)
    offered = np.array([1, 1, 1, 1, 0], bool)
    deliveries = np.array([1, 2, 0, 1, 0])
    idx = np.arange(4)
    checks = reference.judge(valid, offered, deliveries, 1, 2, idx,
                             valid[idx].copy())
    assert {k: v for k, (v, _) in checks.items()} == {
        "wrong_accept": 1, "missing": 1, "double": 1, "altered": 1,
        "unknown": 2, "label_disagree": 0}
    assert not reference.correct(checks)


def test_control_comes_out_not_correct():
    """The reference in the program's place, checking only each txn's
    first signature: forged later signatures get through."""
    from perfbench import control
    out = control.control_run(
        "frontdoor.mainnet.60k", 2 ** 36 + 3, 1.0, "first_sig_only",
        overrides={"cell": lambda c: dict(c, rate=3000, warmup_s=0.0),
                   "mix": lambda m: dict(m, forged_share=0.2)})
    assert out["correct"] is False
    assert out["checks"]["wrong_accept"][0] > 0
    assert out["checks"]["missing"][0] == 0
