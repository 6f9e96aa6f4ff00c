#!/usr/bin/env python3
"""Chip smoke: the sigverify hot path, once, on a TPU, through the repo's
own entry points — the quickest proof that the system still starts on
the chip.

    python3 chip_smoke.py             # one chip: build, kernel, pipeline
    python3 chip_smoke.py --chips 4   # the 4-chip mesh path only

Phases, one after another; each JAX phase is a child process, so exactly
one process holds the chip at any time and this parent never imports JAX:

  build     rebuild firedancer_tpu/native/libfdtpu.so from source
            (`make -B`: a library copied in from another host may use
            instructions this one lacks).
  kernel    at batch 8192 x msg_len 1232: the strict Pallas
            `pallas_ed.verify_batch` over a seeded mixed set (valid;
            corrupt R, S, msg, A; non-canonical S; small-order A and
            R), verdicts equal bit for bit to utils/ed25519_ref.py; the
            same call limited to its first LIVE lanes (`n_live`) gives
            those lanes the same verdicts and every later lane False;
            the verify tile's own staging sets and jit at 8192, 300 and
            1300 live lanes, in dispatches of its width (printed),
            verdicts equal to the oracle on the live lanes and False
            past each dispatch's count; the RLC prefilter
            `pallas_msm.rlc_verify_batch_tpu` passes an
            all-valid batch and fails one with a forged lane; the
            Mosaic lane/sublane rolls rotate like jnp.roll.
  pipeline  `python -m firedancer_tpu.app.run cfg/default.toml
            cfg/chip-smoke.toml`, the verify tile in bulk_prefilter
            mode without shedding (RLC warmup and equation, then the
            strict kernel):
            it runs on `tpu`, with cpu_fallback == 0 and
            device_errors == 0, and ran the RLC equation; every
            unique valid txn reaches bank0; every forged and torsion
            frame (each distinct) fails verify's signature check —
            verify_fail equals the hostile frame count and dedup_drop
            is 0.

With --chips 4 only the mesh path runs (after the build): the strict
kernel under shard_map over four chips, verdicts against the oracle,
one shard on each chip; then the pipeline with the verify tile's
`devices = 4` in strict mode (cfg/chip-smoke-mesh.toml), 2048 lanes
per chip.

The topology is named chipsmoke<pid>, so its /dev/shm workspace and
plan never meet those of another checkout's run on the same machine.

Exits non-zero, printing no result, if any phase fails — including when
JAX finds no TPU. On success the last stdout line is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
Compile and steady times are printed as information only.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, ".chip_smoke")         # logs (git-ignored)
BATCH, MSG_LEN, SEED = 8192, 1232, 21
LIVE = 1300                     # a part-full call: 6 of 32 grid programs
# the verify tile's dispatches: the whole set first, so the later ones
# find its bytes in their dead lanes
TILE_LIVE = (BATCH, 300, LIVE)
KERNEL_TIMEOUT_S = 900          # cold MSM compile alone is ~4 min
PIPELINE_BOOT_S = 600
PIPELINE_RUN_S = 300
PLATFORM = "tpu"                # tests/test_chip_smoke.py rehearses on cpu


class PhaseError(RuntimeError):
    pass


def say(msg: str):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# data: seeded signatures + the host oracle (no JAX)
# ---------------------------------------------------------------------------

_SMALL_ORDER = (bytes([1]) + bytes(31),                 # identity
                bytes([0xEC]) + b"\xff" * 30 + b"\x7f")  # (0, -1), order 2


def _oracle_chunk(args):
    from firedancer_tpu.utils.ed25519_ref import verify
    sig, pub, msg, ln = args
    return [verify(bytes(sig[i]), bytes(pub[i]), bytes(msg[i, :ln[i]]))
            for i in range(len(ln))]


def oracle(sig, pub, msg, ln, procs: int):
    """utils/ed25519_ref.py verdicts, fanned over a spawn pool (the
    workers import only the pure-python reference)."""
    import multiprocessing as mp

    import numpy as np
    step = -(-len(ln) // (procs * 4))
    chunks = [(sig[i:i + step], pub[i:i + step], msg[i:i + step],
               ln[i:i + step]) for i in range(0, len(ln), step)]
    with mp.get_context("spawn").Pool(procs) as pool:
        out = pool.map(_oracle_chunk, chunks)
    return np.array([v for c in out for v in c], bool)


def make_data(path: str, n: int, msg_len: int, seed: int):
    """Mixed strict set + RLC valid/forged batches, with the oracle's
    strict verdicts, saved to `path` (.npz)."""
    import numpy as np
    from cryptography.hazmat.primitives.asymmetric.ed25519 import \
        Ed25519PrivateKey

    from firedancer_tpu.utils.ed25519_ref import L

    rng = np.random.default_rng(seed)
    keys = [Ed25519PrivateKey.from_private_bytes(rng.bytes(32))
            for _ in range(64)]
    pubs = [k.public_key().public_bytes_raw() for k in keys]

    def signed():
        sig = np.zeros((n, 64), np.uint8)
        pub = np.zeros((n, 32), np.uint8)
        msg = rng.integers(0, 256, (n, msg_len), dtype=np.uint8)
        ln = rng.integers(0, msg_len + 1, n).astype(np.int32)
        ln[0], ln[1] = msg_len, 0
        for i in range(n):
            k = i % len(keys)
            sig[i] = np.frombuffer(
                keys[k].sign(msg[i, :ln[i]].tobytes()), np.uint8)
            pub[i] = np.frombuffer(pubs[k], np.uint8)
        return sig, pub, msg, ln

    # strict mixed set: lanes 0-4 of every 10 valid, then one class each
    sig, pub, msg, ln = signed()
    for i in range(n):
        c = i % 10
        if c == 5:
            sig[i, 0] ^= 1                                   # corrupt R
        elif c == 6:
            sig[i, 33] ^= 1                                  # corrupt S
        elif c == 7:
            if ln[i] == 0:
                ln[i] = 1
            msg[i, ln[i] - 1] ^= 0x80                        # corrupt msg
        elif c == 8:
            pub[i, 3] ^= 1                                   # corrupt A
        elif c == 9:
            edge = (i // 10) % 3
            if edge == 0:                                    # S + l
                s = int.from_bytes(sig[i, 32:].tobytes(), "little") + L
                sig[i, 32:] = np.frombuffer(s.to_bytes(32, "little"),
                                            np.uint8)
            elif edge == 1:
                pub[i] = np.frombuffer(_SMALL_ORDER[0], np.uint8)
            else:
                sig[i, :32] = np.frombuffer(_SMALL_ORDER[1], np.uint8)
    want = oracle(sig, pub, msg, ln, procs=max(1, min(16, os.cpu_count())))
    # RLC: an all-valid batch, and the same batch with one forged lane
    # (message bit flipped: structurally valid, fails the equation)
    rsig, rpub, rmsg, rln = signed()
    fmsg = rmsg.copy()
    fmsg[0, 0] ^= 1                     # lane 0 carries msg_len bytes
    z = rng.integers(0, 256, (n, 16), dtype=np.uint8)
    np.savez(path, sig=sig, pub=pub, msg=msg, ln=ln, want=want,
             rsig=rsig, rpub=rpub, rmsg=rmsg, rln=rln, fmsg=fmsg, z=z)
    classes = {"valid": int(want.sum()), "invalid": int((~want).sum())}
    say(f"data: {n} lanes x {msg_len} B, oracle verdicts {classes}")


# ---------------------------------------------------------------------------
# JAX children (the only processes that touch the chip)
# ---------------------------------------------------------------------------

def _timed(fn, *args, steady: int = 3):
    """(first call incl. compile s, mean steady s/call, result)."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steady):
        jax.block_until_ready(fn(*args))
    return first, (time.perf_counter() - t0) / steady, out


def _device_info():
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    say("device: " + json.dumps(info))
    if info["platform"] != PLATFORM:
        raise PhaseError(f"JAX found no TPU (platform "
                         f"{info['platform']!r})")
    return devs, info


def _check_rolls():
    """The kernels' Mosaic rolls rotate like jnp.roll — the stand-in the
    CPU tests swap in (pallas_ed._ROLL on sublanes, pallas_msm._roll_pt
    on lanes, negative shifts included)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from firedancer_tpu.ops import pallas_ed, pallas_msm

    def kern(x_ref, s_ref, l_ref):
        s_ref[...] = pallas_ed._ROLL(x_ref[...], shift=7, axis=0)
        l_ref[...] = pallas_msm._roll_pt((x_ref[...],), -3)[0]

    x = jnp.arange(40 * 256, dtype=jnp.int32).reshape(40, 256)
    sub, lane = pl.pallas_call(
        kern, out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)] * 2)(x)
    if not (bool((sub == jnp.roll(x, 7, axis=0)).all())
            and bool((lane == jnp.roll(x, -3, axis=1)).all())):
        raise PhaseError("Mosaic roll direction differs from jnp.roll")
    say("kernel: roll direction matches jnp.roll (sublane +7, lane -3)")


def kernel_child(data: str, mesh: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from firedancer_tpu.jaxcfg import use_compile_cache
    say(f"kernel: compile cache {use_compile_cache()}")
    devs, info = _device_info()
    d = np.load(data)
    args = [jnp.asarray(d[k]) for k in ("sig", "pub", "msg", "ln")]
    from firedancer_tpu.ops import pallas_ed

    if mesh:
        if len(devs) < mesh:
            raise PhaseError(f"need {mesh} chips, JAX sees {len(devs)}")
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        m = Mesh(np.array(devs[:mesh]), ("shard",))
        fn = jax.jit(jax.shard_map(
            pallas_ed.verify_batch, mesh=m, in_specs=(P("shard"),) * 4,
            out_specs=P("shard"), check_vma=False))
        args = [jax.device_put(a, NamedSharding(m, P("shard")))
                for a in args]
        first, steady, ok = _timed(fn, *args)
        held = {s.device.id for s in ok.addressable_shards}
        if len(held) != mesh:
            raise PhaseError(f"verdict shards on {len(held)} chips, "
                             f"want {mesh}")
        label = f"strict x{mesh} chips"
    else:
        _check_rolls()
        fn = jax.jit(pallas_ed.verify_batch)
        first, steady, ok = _timed(fn, *args)
        label = "strict"
    got = np.asarray(ok)
    bad = np.flatnonzero(got != d["want"])
    say(f"kernel: {label} compile+first call {first:.1f} s (info)")
    say(f"kernel: {label} steady {steady * 1e3:.1f} ms/call of "
        f"{len(got)} lanes (info)")
    if len(bad):
        raise PhaseError(f"{label}: {len(bad)} verdicts differ from "
                         f"the oracle, lanes {bad[:8].tolist()}")
    say(f"kernel: {label} verdicts == oracle on {len(got)} lanes "
        f"({int(got.sum())} valid)")
    if mesh:
        return info

    fn = jax.jit(lambda s, p, m, n, live: pallas_ed.verify_batch(
        s, p, m, n, n_live=live))
    first, steady, ok = _timed(fn, *args, np.int32(LIVE))
    part = np.asarray(ok)
    say(f"kernel: strict n_live={LIVE} steady {steady * 1e3:.1f} ms/call "
        f"(info)")
    if not (np.array_equal(part[:LIVE], got[:LIVE])
            and not part[LIVE:].any()):
        raise PhaseError(f"strict n_live={LIVE}: live verdicts differ from "
                         f"the full-width call, or a lane past the count "
                         f"reads True")
    say(f"kernel: strict n_live={LIVE} verdicts == full width on the live "
        f"lanes, False on the {len(part) - LIVE} past the count")

    check_tile_dispatch(d)

    from firedancer_tpu.ops.ed25519 import rlc_verify_fn
    rlc = rlc_verify_fn()       # the verify tile's resolver
    rargs = [jnp.asarray(d[k]) for k in
             ("rsig", "rpub", "rmsg", "rln", "z")]
    first, steady, (ok, pre) = _timed(rlc, *rargs)
    say(f"kernel: rlc compile+first call {first:.1f} s (info)")
    say(f"kernel: rlc steady {steady * 1e3:.1f} ms/call (info)")
    if not (bool(ok) and bool(np.asarray(pre).all())):
        raise PhaseError("rlc: the all-valid batch failed")
    rargs[2] = jnp.asarray(d["fmsg"])
    ok, _ = rlc(*rargs)
    if bool(ok):
        raise PhaseError("rlc: the batch with a forged lane passed")
    say("kernel: rlc passes the valid batch, fails the forged one")
    return info


def check_tile_dispatch(d, live=TILE_LIVE):
    """Dispatch through the verify tile's own staging sets and jit at
    each live count, in chunks of the tile's dispatch width as its
    assembler cuts them, dead lens zeroed as the assembler does.
    Verdicts equal the oracle on the live lanes and read False past
    each dispatch's count."""
    import jax
    import numpy as np

    from firedancer_tpu.runtime import Ring, Tcache, Workspace
    from firedancer_tpu.tiles.verify import VerifyTile
    n, max_len = d["msg"].shape
    w = Workspace(f"/fdtpu_smoke_{os.getpid()}", 1 << 22)
    try:
        t0 = time.perf_counter()
        tile = VerifyTile(Ring.create(w, depth=64, mtu=1280),
                          Ring.create(w, depth=64, mtu=1280),
                          Tcache(w, depth=64), batch=n, max_len=max_len)
        width = tile._width
        say(f"tile: width {width} of batch {n} warmed in "
            f"{time.perf_counter() - t0:.1f} s (info)")
        bs = tile._bufsets[0]
        for k in live:
            for lo in range(0, k, width):
                m = min(width, k - lo)
                for key in ("sig", "pub", "msg", "ln"):
                    getattr(bs, key)[:m] = d[key][lo:lo + m]
                bs.ln[m:] = 0
                got = np.asarray(jax.block_until_ready(
                    tile._device_verify(bs, m)))
                if len(got) != width or not np.array_equal(
                        got[:m], d["want"][lo:lo + m]) or got[m:].any():
                    raise PhaseError(
                        f"tile: lanes {lo}-{lo + m} of {k}: verdicts "
                        f"differ from the oracle, or a lane past the "
                        f"count reads True")
            say(f"tile: {k} live lanes in {-(-k // width)} dispatch(es) of "
                f"width {width}: verdicts == oracle on the live lanes "
                f"({int(d['want'][:k].sum())} valid), False past each count")
    finally:
        w.close()
        w.unlink()


def run_kernel_phase(data: str, mesh: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS=PLATFORM)
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child-kernel",
         data, "--chips", str(mesh or 1)],
        cwd=HERE, env=env, stdout=subprocess.PIPE, text=True,
        timeout=KERNEL_TIMEOUT_S)
    lines = r.stdout.splitlines()
    for ln in lines[:-1] if r.returncode == 0 else lines:
        say(ln)
    if r.returncode != 0:
        raise PhaseError(f"kernel child exited {r.returncode}")
    return json.loads(lines[-1])


# ---------------------------------------------------------------------------
# pipeline phase: the leader topology through app.run
# ---------------------------------------------------------------------------

def _expectations(layers: list[str]) -> dict:
    """What synth offers in the merged config: `count` unique valid
    txns plus the chaos plan's hostile frames, and the verify mode."""
    from firedancer_tpu.app.config import load_config
    cfg = load_config(*(os.path.join(HERE, p) for p in layers))
    synth = next(t for t in cfg["tile"] if t["name"] == "synth")
    verify = next(t for t in cfg["tile"] if t["name"] == "verify")
    if synth["unique"] != synth["count"]:
        raise PhaseError("chip smoke needs every synth txn unique")
    events = synth["chaos"]["events"]
    if any(e.get("pool", 0) < e["frames"] for e in events):
        raise PhaseError("chip smoke needs every hostile frame distinct "
                         "(pool = frames)")
    return {"count": synth["count"], "unique": synth["unique"],
            "attack": sum(e["frames"] for e in events),
            "mode": verify.get("mode", "strict")}


def run_pipeline_phase(layers: list[str], want_devices: int) -> None:
    from firedancer_tpu.disco.monitor import attach, full_snapshot

    exp = _expectations(layers)
    topo = f"chipsmoke{os.getpid()}"
    log_path = os.path.join(OUT, f"pipeline{want_devices}.log")
    env = dict(os.environ, JAX_PLATFORMS=PLATFORM)
    with open(log_path, "w") as logf:
        proc = subprocess.Popen(
            [sys.executable, "-m", "firedancer_tpu.app.run", *layers,
             "--name", topo, "--boot-timeout", str(PIPELINE_BOOT_S),
             "--interval", "3600"],
            cwd=HERE, env=env, stdout=logf, stderr=subprocess.STDOUT,
            start_new_session=True)
    snap, prev = None, None
    try:
        t_end = time.monotonic() + PIPELINE_BOOT_S + PIPELINE_RUN_S
        while time.monotonic() < t_end:
            time.sleep(2.0)
            if proc.poll() is not None:
                raise PhaseError(f"app.run exited {proc.returncode}")
            try:
                plan, wksp = attach(topo)
            except (OSError, ValueError, KeyError):
                continue                    # plan not written yet
            try:
                snap = full_snapshot(plan, wksp)["tiles"]
            finally:
                wksp.close()
            if any(t["state"] != "run" for t in snap.values()):
                continue
            m = {tn: snap[tn]["metrics"] for tn in
                 ("synth", "verify", "bank0")}
            # done once every frame is consumed and delivered, and the
            # counters held still across two polls (a tile flushes its
            # counters to shm between polls, so a busy one lags)
            done = m["verify"]["rx"] >= exp["count"] + exp["attack"] \
                and m["verify"]["tx"] >= exp["unique"] \
                and m["bank0"]["txns"] >= exp["unique"]
            if done and _brief(snap) == prev:
                break
            prev = _brief(snap) if done else None
        else:
            raise PhaseError("pipeline did not finish in time; last "
                             f"metrics {json.dumps(_brief(snap))}")
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGINT)
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
    _check_pipeline(snap, exp, log_path, want_devices)


def _brief(snap):
    if not snap:
        return None
    return {tn: {k: v for k, v in snap[tn]["metrics"].items()
                 if k in ("tx", "rx", "txns", "attack_tx", "attack_drop",
                          "verify_fail", "dedup_drop", "rlc_batches",
                          "rlc_pass", "rlc_lanes", "rlc_shed",
                          "cpu_fallback", "device_errors")}
            for tn in ("synth", "verify", "bank0") if tn in snap}


def _check_pipeline(snap, exp, log_path, want_devices):
    with open(log_path) as f:
        log = f.read()
    line = next((ln for ln in log.splitlines()
                 if "verify: jax platform=" in ln), "")
    say(f"pipeline: {line.split('verify: ', 1)[-1]}")
    syn, ver, bank = (snap[t]["metrics"] for t in
                      ("synth", "verify", "bank0"))
    say("pipeline: " + json.dumps(_brief(snap)))
    fails = []
    if f"platform={PLATFORM}" not in line:
        fails.append(f"the verify tile did not report "
                     f"platform={PLATFORM}")
    if f"devices={want_devices} shards={want_devices}" not in line:
        fails.append(f"verify verdicts not on {want_devices} chip(s)")
    if ver["cpu_fallback"] or ver["device_errors"]:
        fails.append(f"cpu_fallback={ver['cpu_fallback']} "
                     f"device_errors={ver['device_errors']}")
    if syn["tx"] != exp["count"] or syn["attack_tx"] != exp["attack"]:
        fails.append(f"synth offered {syn['tx']} + {syn['attack_tx']}, "
                     f"want {exp['count']} + {exp['attack']}")
    if ver["rx"] != exp["count"] + exp["attack"]:
        fails.append(f"verify consumed {ver['rx']} frames")
    if ver["tx"] != exp["unique"]:
        fails.append(f"verify passed {ver['tx']}, want exactly the "
                     f"{exp['unique']} unique valid txns (forged and "
                     f"torsion frames must all drop)")
    if ver["verify_fail"] != exp["attack"] or ver["dedup_drop"]:
        fails.append(f"verify_fail={ver['verify_fail']} "
                     f"dedup_drop={ver['dedup_drop']}, want every one "
                     f"of the {exp['attack']} distinct hostile frames "
                     f"failed by the signature check, none deduped")
    if exp["mode"] == "bulk_prefilter" and not ver["rlc_batches"]:
        fails.append("bulk_prefilter asked for, but the tile ran no "
                     "RLC batch equation")
    if bank["txns"] != exp["unique"]:
        fails.append(f"bank0 executed {bank['txns']} txns, want "
                     f"{exp['unique']}")
    if fails:
        raise PhaseError("pipeline: " + "; ".join(fails))
    say(f"pipeline: {exp['unique']} valid txns reached bank0; "
        f"{exp['attack']} distinct forged/torsion frames failed "
        f"verify's signature check (verify_fail), 0 deduped; "
        f"{exp['mode']}: rlc_batches={ver['rlc_batches']} "
        f"rlc_pass={ver['rlc_pass']} rlc_shed={ver['rlc_shed']}")


# ---------------------------------------------------------------------------

def build_phase():
    native = os.path.join(HERE, "firedancer_tpu", "native")
    t0 = time.perf_counter()
    subprocess.run(["make", "-B", "-s", "-C", native], check=True)
    say(f"build: libfdtpu.so rebuilt in {time.perf_counter() - t0:.1f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the 4-chip mesh path")
    ap.add_argument("--child-kernel", metavar="DATA",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    mesh = 4 if args.chips == 4 else 0
    if args.child_kernel:
        info = kernel_child(args.child_kernel, mesh)
        print(json.dumps(info), flush=True)
        return 0

    os.makedirs(OUT, exist_ok=True)
    sys.path.insert(0, HERE)
    build_phase()
    with tempfile.TemporaryDirectory() as tmp:
        data = os.path.join(tmp, "kernel.npz")
        make_data(data, BATCH, MSG_LEN, SEED)
        info = run_kernel_phase(data, mesh)
    layers = ["cfg/default.toml", "cfg/chip-smoke.toml"]
    if mesh:
        layers.append("cfg/chip-smoke-mesh.toml")
    run_pipeline_phase(layers, max(1, mesh))
    assert "jax" not in sys.modules, "the parent must stay off JAX"
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


def _sigterm(signum, frame):
    raise SystemExit(128 + signum)      # unwind: finally stops children


if __name__ == "__main__":
    signal.signal(signal.SIGTERM, _sigterm)
    try:
        sys.exit(main())
    except (PhaseError, subprocess.SubprocessError, OSError,
            ImportError) as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
