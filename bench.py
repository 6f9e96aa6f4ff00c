"""Headline benchmark: batched ed25519 sigverify throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Baseline: the reference's wiredancer FPGA sigverify tile sustains ~1M
verifies/s on one AWS-F1 card, vs ~30K/s per Skylake core for the C path
(ref: src/wiredancer/README.md:99-119). BASELINE.json's north star for this
rebuild is >= 1M ed25519 verifies/s on a single TPU chip, so
vs_baseline = verifies_per_sec / 1e6.

Methodology mirrors the reference's unit-test self-benchmarks
(ref: src/ballet/ed25519/test_ed25519.c:26-31 — print throughput from a
tight loop over pre-generated valid signatures): pre-generate distinct
signed messages host-side, tile to the microbatch size, jit-compile once,
then time steady-state iterations end-to-end (device dispatch + compute +
verdict readback). Per-iteration wall times give p99 dispatch latency.

The parent never touches JAX: each stage runs in a child with a bounded
deadline, so one process at a time holds the chip. The kernel stage must
run on a TPU: when it fails, or JAX resolves any other platform, bench.py
prints its record with the error and exits non-zero — it never retries on
the CPU and never stands an older record in for the current one. Every
record names the platform that actually ran.
"""
import hashlib
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BASELINE_VPS = 1.0e6


def _gen_vectors(n_unique: int, max_len: int, rng: np.random.Generator):
    from firedancer_tpu.utils.ed25519_ref import keypair, sign

    sig = np.zeros((n_unique, 64), np.uint8)
    pub = np.zeros((n_unique, 32), np.uint8)
    msg = np.zeros((n_unique, max_len), np.uint8)
    ln = np.zeros((n_unique,), np.int32)
    for i in range(n_unique):
        seed = hashlib.sha256(b"bench-key-%d" % (i % 8)).digest()
        m = rng.integers(0, 256, size=(int(rng.integers(32, max_len)),),
                         dtype=np.uint8).tobytes()
        _, _, pk = keypair(seed)
        s = sign(seed, m)
        sig[i] = np.frombuffer(s, np.uint8)
        pub[i] = np.frombuffer(pk, np.uint8)
        msg[i, :len(m)] = np.frombuffer(m, np.uint8)
        ln[i] = len(m)
    return sig, pub, msg, ln


def _child_bench():
    """Run the measurement on whatever backend this process resolves.

    Prints one JSON line on success; any exception propagates (the parent
    handles fallback + reporting)."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, HERE)
    from firedancer_tpu.jaxcfg import use_compile_cache
    from firedancer_tpu.ops import ed25519 as ed

    use_compile_cache()

    dev = jax.devices()[0]
    platform = dev.platform
    on_tpu = platform != "cpu"
    batch = int(os.environ.get("FDTPU_BENCH_BATCH",
                               "8192" if on_tpu else "64"))
    # MTU-realistic message length: the verify path must handle txn MTU
    # 1232 (ref: src/ballet/txn/fd_txn.h:102-104)
    max_len = int(os.environ.get("FDTPU_BENCH_MSG_LEN", "1232"))
    n_unique = min(batch, 256)

    rng = np.random.default_rng(42)
    sig, pub, msg, ln = _gen_vectors(n_unique, max_len, rng)
    reps = -(-batch // n_unique)
    sig = np.tile(sig, (reps, 1))[:batch]
    pub = np.tile(pub, (reps, 1))[:batch]
    msg = np.tile(msg, (reps, 1))[:batch]
    ln = np.tile(ln, reps)[:batch]

    if on_tpu:
        # fused Pallas kernels (ops/pallas_ed.py) — the production path
        from firedancer_tpu.ops import pallas_ed as ped
        fn = jax.jit(lambda s, p, m, l: ped.verify_batch(s, p, m, l))
        kernel = "pallas"
    else:
        fn = jax.jit(ed.verify_batch)
        kernel = "jnp"
    args = (jnp.asarray(sig), jnp.asarray(pub), jnp.asarray(msg),
            jnp.asarray(ln))
    t0 = time.perf_counter()
    out = fn(*args)
    out.block_until_ready()
    compile_s = time.perf_counter() - t0
    assert bool(np.asarray(out).all()), "bench vectors failed to verify"

    iters = int(os.environ.get("FDTPU_BENCH_ITERS", "16" if on_tpu else "2"))
    # per-dispatch (blocking) latency for p99
    lat = []
    for _ in range(max(4, iters // 4)):
        t1 = time.perf_counter()
        fn(*args).block_until_ready()
        lat.append(time.perf_counter() - t1)
    # steady-state throughput: pipelined dispatch (async queue, block at
    # the end) — how the verify tile actually drives the chip, and the
    # methodology that hides the per-dispatch latency
    t0 = time.perf_counter()
    outs = [fn(*args) for _ in range(iters)]
    jax.block_until_ready(outs)
    dt = time.perf_counter() - t0

    vps = batch * iters / dt
    out_rec = {
        "metric": "ed25519_verifies_per_sec",
        "value": round(vps, 1),
        "unit": "verifies/s/chip",
        "vs_baseline": round(vps / BASELINE_VPS, 4),
        "platform": platform,
        "kernel": kernel,
        "batch": batch,
        "iters": iters,
        "msg_len": max_len,
        "p99_batch_ms": round(sorted(lat)[min(len(lat) - 1,
                                              -(-len(lat) * 99 // 100) - 1)]
                              * 1e3, 2),
        "compile_s": round(compile_s, 1),
    }

    if os.environ.get("FDTPU_BENCH_SKIP_RLC") != "1":
        # bulk pre-filter path: RLC batch verification (cofactored
        # semantics — ops/pallas_msm.py docstring), the ROADMAP 1b
        # rlc_bulk_vps stanza. The hardware run doubles as the
        # kernel's correctness gate: the all-valid batch must pass,
        # and a forged lane must fail it. On CPU the jnp limb kernel
        # runs a SMALL batch (the MSM graph compiles in minutes and
        # verifies a few hundred lanes/s — the number is recorded for
        # the platform it ran on) so a CPU drill still exercises the
        # stanza.
        try:
            rfn = ed.rlc_verify_fn()   # shared platform dispatch
            if on_tpu:
                rbatch, rargs = batch, args
            else:
                rbatch = min(batch, int(os.environ.get(
                    "FDTPU_BENCH_RLC_CPU_BATCH", "16")))
                rargs = tuple(a[:rbatch] for a in args)
            zrng = np.random.default_rng(7)
            z = jnp.asarray(zrng.integers(0, 256, (rbatch, 16),
                                          dtype=np.uint8))
            t0 = time.perf_counter()
            ok, pre = rfn(*rargs, z)
            jax.block_until_ready((ok, pre))
            rlc_compile_s = time.perf_counter() - t0
            assert bool(ok) and bool(np.asarray(pre).all()), \
                "rlc: valid batch failed"
            bad_msg = np.array(msg[:rbatch])
            bad_msg[3, 0] ^= 0x01          # forge lane 3's message:
            ok2, _ = rfn(rargs[0], rargs[1],  # prechecks still pass,
                         jnp.asarray(bad_msg),  # the equation must not
                         rargs[3], z)
            assert not bool(ok2), \
                "rlc: forged lane not caught by the batch equation"
            riters = iters if on_tpu else max(2, iters)
            t0 = time.perf_counter()
            outs = [rfn(*rargs, z) for _ in range(riters)]
            jax.block_until_ready(outs)
            rdt = time.perf_counter() - t0
            out_rec["rlc_bulk_vps"] = round(rbatch * riters / rdt, 1)
            out_rec["rlc_bulk_batch"] = rbatch
            out_rec["rlc_compile_s"] = round(rlc_compile_s, 1)
        except Exception as e:  # noqa: BLE001 — annotate, don't break
            out_rec["rlc_error"] = f"{e!r}"[:200]

    print(json.dumps(out_rec))
    sys.stdout.flush()


def _bench_flight(stage: str):
    """[flight] cfg for a bench stage topology, or None. Under
    FDTPU_BENCH_FLIGHT_DIR each stage archives its telemetry history
    to <dir>/<stage>, so report.html's history tab (and fdflight
    post-mortems) cover the bench run itself. Off by default — the
    recorder is reader-side only, but the bench path stays untouched
    unless asked."""
    root = os.environ.get("FDTPU_BENCH_FLIGHT_DIR")
    if not root:
        return None
    return {"dir": os.path.join(root, stage)}


def _e2e_run(count: int, unique: int, batch: int,
             rate_tps: float = 0.0, coalesce_us: float = 0.0,
             profile: bool = True):
    """One synth -> verify -> dedup -> sink topology run; returns the
    measured record (tps, stage budget, link budget, and — with
    profile=True — the fdprof per-stage attribution digest). rate_tps
    > 0 paces the synth (the offered axis of the sweep); 0 lets it rip
    (capacity measurement)."""
    from firedancer_tpu.disco import Topology, TopologyRunner
    from firedancer_tpu.disco.metrics import (link_lag, merge_hists,
                                              quantile_ns, read_hists,
                                              read_link_metrics)

    # bench observatory (fdprof): low prime sampling rate so the
    # profile rides every bench round at negligible overhead (the
    # tier-1 overhead test bounds the sampler; 29 Hz against ~20 us
    # polls is noise) — override/disable with FDTPU_BENCH_PROF_HZ
    prof_hz = float(os.environ.get("FDTPU_BENCH_PROF_HZ", "29"))
    prof_cfg = {"enable": True, "hz": prof_hz} \
        if profile and prof_hz > 0 else None
    flight_cfg = _bench_flight("e2e")
    topo = (
        Topology(f"bench{os.getpid()}", wksp_size=1 << 26,
                 prof=prof_cfg, flight=flight_cfg)
        .link("ingest", depth=8192, mtu=1280)
        .link("verify_dedup", depth=8192, mtu=1280)
        .link("dedup_sink", depth=8192, mtu=1280)
        .tcache("verify_tc", depth=8192)
        .tcache("dedup_tc", depth=8192)
        .tile("synth", "synth", outs=["ingest"], count=count,
              unique=unique, burst=1024, seed=17, rate_tps=rate_tps)
        .tile("verify", "verify", ins=["ingest"], outs=["verify_dedup"],
              batch=batch, tcache="verify_tc", coalesce_us=coalesce_us)
        .tile("dedup", "dedup", ins=["verify_dedup"], outs=["dedup_sink"],
              tcache="dedup_tc", batch=1024)
        .tile("sink", "sink", ins=["dedup_sink"], batch=1024)
    )
    if flight_cfg:
        topo.tile("flight", "flight")
    runner = TopologyRunner(topo.build()).start()
    try:
        runner.wait_running(timeout_s=840)   # includes verify compile
        t0 = time.perf_counter()
        runner.wait_idle("sink", "rx", unique, timeout_s=600)
        runner.wait_idle("verify", "rx", count, timeout_s=600)
        wall = time.perf_counter() - t0
        hists = read_hists(runner.wksp, runner.plan, "verify")
        p99_ms = quantile_ns(hists.get("work", {"count": 0}), 0.99) / 1e6 \
            if hists else 0.0
        # stage-by-stage latency/occupancy budget (VERDICT r4 item 2):
        # per tile, p50/p99 of busy poll iterations and the fraction of
        # wall time spent working vs waiting on the ring
        budget = {}
        for t in ("synth", "verify", "dedup", "sink"):
            h = read_hists(runner.wksp, runner.plan, t)
            if not h:
                continue
            work, wait = h.get("work"), h.get("wait")
            tot_work = work["sum_ns"] if work else 0
            tot_wait = wait["sum_ns"] if wait else 0
            busy = tot_work / (tot_work + tot_wait) \
                if tot_work + tot_wait else 0.0
            budget[t] = {
                "work_p50_us": round(quantile_ns(work, 0.50) / 1e3, 1)
                if work else 0,
                "work_p99_us": round(quantile_ns(work, 0.99) / 1e3, 1)
                if work else 0,
                "occupancy": round(busy, 3),
            }
        # per-link attribution (fdmetrics v2): WHERE the hot-path time
        # and backpressure went, hop by hop — published/consumed (loss
        # per hop), producer backpressure ticks, and the consumer-side
        # queue-wait quantiles — so the bench trajectory records
        # which hop throttles end-to-end TPS, not just the number
        link_budget = {}
        for ln, rec in read_link_metrics(runner.wksp,
                                         runner.plan).items():
            cons = rec["consumers"]
            # link-level quantiles over ALL consumers (rr-sharded
            # verify), loss = the shared per-consumer lag definition
            h = merge_hists(c["hist"] for c in cons.values())
            link_budget[ln] = {
                "pub": rec["pub"],
                "consumed": sum(c["consumed"] for c in cons.values()),
                "lost": sum(link_lag(rec, tn) for tn in cons),
                "backpressure": rec["backpressure"],
                "consume_p50_us": round(quantile_ns(h, 0.50) / 1e3, 1)
                if h else 0,
                "consume_p99_us": round(quantile_ns(h, 0.99) / 1e3, 1)
                if h else 0,
            }
        out = {
            "e2e_tps": round(count / wall, 1),
            "e2e_count": count,
            "e2e_wall_s": round(wall, 2),
            "e2e_verify_work_p99_ms": round(p99_ms, 2),
            "e2e_stage_budget": budget,
            "e2e_link_budget": link_budget,
        }
        if prof_cfg:
            # per-stage profile digest (fdprof): top-k frames with
            # stem-state attribution, device occupancy (tpu time /
            # wall), compile counts — the WHY next to every number,
            # diffable across rounds by tools/fdbench
            from firedancer_tpu.prof import profile_summary
            prof = profile_summary(runner.plan, runner.wksp)
            vh = read_hists(runner.wksp, runner.plan, "verify")
            tpu = vh.get("tpu", {"sum_ns": 0})
            vm = runner.metrics("verify")
            prof["verify_device"] = {
                "occupancy": round(tpu["sum_ns"] / 1e9 / wall, 3)
                if wall else 0.0,
                "compiles": vm.get("tpu_jit_compiles", 0),
                "cache_miss": vm.get("tpu_jit_cache_miss", 0),
                "compile_s": round(
                    vm.get("tpu_compile_ns", 0) / 1e9, 2),
            }
            out["e2e_profile"] = prof
        return out
    finally:
        runner.halt()
        runner.close()


def _saturating_hop(rec: dict):
    """Attribute a sweep point's bottleneck: the highest-occupancy tile
    and the first link (in hop order) showing producer backpressure —
    the two answers 'which hop saturates first' decomposes into."""
    budget = rec.get("e2e_stage_budget", {})
    top_tile = max(budget, key=lambda t: budget[t]["occupancy"]) \
        if budget else None
    links = rec.get("e2e_link_budget", {})
    bp_link = None
    for ln in ("ingest", "verify_dedup", "dedup_sink"):
        if links.get(ln, {}).get("backpressure", 0) > 0:
            bp_link = ln
            break
    return top_tile, bp_link


def _e2e_bench():
    """End-to-end tile pipeline TPS on the resolved backend: synth ->
    verify(device) -> dedup -> sink across four OS processes over shm
    rings (BASELINE config 3/4 — the verify-tile replay measurement;
    ref: src/app/shared_dev/commands/bench/ bencho TPS observation).

    Prints one JSON line: {"e2e_tps", "e2e_count", "e2e_wall_s",
    "e2e_verify_work_p99_ms", "e2e_offered_sweep", "e2e_knee_tps",
    "platform"}. TPS counts frags INGESTED by the verify tile (rx,
    incl. dup drops — the tile's real workload); the clock starts when
    every tile reaches RUN (compile excluded) and stops when the last
    unique txn reaches the sink.

    The offered-load sweep (r10) re-runs the topology with the synth
    paced at fractions of the measured capacity and records, per
    point, achieved-vs-offered plus which hop saturated first (top
    occupancy tile, first backpressured link). The knee — the highest
    offered load still served at >= 90% — is the number future PRs
    must move, and the per-point hop attribution says what to fix.

    NOTE: this process must NOT initialize the jax backend — the verify
    tile's process owns the chip; the plan names the platform it gets."""
    sys.path.insert(0, HERE)
    # sizing: throughput ceiling ~= batch * inflight / dispatch latency;
    # the ingest ring must hold several in-flight batches or the batch
    # can never fill (VERDICT r4 item 2)
    count = int(os.environ.get("FDTPU_BENCH_E2E_COUNT", "65536"))
    unique = int(os.environ.get("FDTPU_BENCH_E2E_UNIQUE", "256"))
    batch = int(os.environ.get("FDTPU_BENCH_E2E_BATCH", "2048"))
    coalesce_us = float(os.environ.get("FDTPU_BENCH_E2E_COALESCE_US",
                                       "500"))
    os.environ.setdefault("FDTPU_VERIFY_INFLIGHT", "3")
    out = _e2e_run(count, unique, batch, coalesce_us=coalesce_us)
    from firedancer_tpu.jaxcfg import plan_platform
    out["platform"] = plan_platform()

    # offered-load sweep: fractions of the measured capacity (override:
    # FDTPU_BENCH_E2E_SWEEP="0.5,0.8,1.1" — empty string disables)
    fracs_env = os.environ.get("FDTPU_BENCH_E2E_SWEEP", "0.5,0.8,1.2")
    fracs = [float(f) for f in fracs_env.split(",") if f.strip()]
    if fracs:
        cap = out["e2e_tps"]
        sweep = []
        for frac in fracs:
            offered = cap * frac
            # ~2 s of traffic per point, floored so the batch pipeline
            # actually engages; compile is warm from the first run
            n_pt = int(max(8192, min(count, offered * 2)))
            try:
                # sweep points keep only achieved/hop attribution —
                # skip the per-point profiling the capacity run did
                rec = _e2e_run(n_pt, unique, batch, rate_tps=offered,
                               coalesce_us=coalesce_us, profile=False)
            except Exception as e:  # noqa: BLE001 — annotate the point
                sweep.append({"offered_tps": round(offered, 1),
                              "error": f"{e!r}"[:200]})
                continue
            top_tile, bp_link = _saturating_hop(rec)
            sweep.append({
                "offered_tps": round(offered, 1),
                "achieved_tps": rec["e2e_tps"],
                "served_frac": round(rec["e2e_tps"] / offered, 3)
                if offered else 0.0,
                "top_occupancy_tile": top_tile,
                "first_backpressured_link": bp_link,
            })
        out["e2e_offered_sweep"] = sweep
        served = [p for p in sweep if p.get("served_frac", 0) >= 0.9]
        # no point served >= 90% (all errored, or pacing never kept
        # up): the knee is UNKNOWN-BAD, reported null — falling back
        # to raw capacity would report the most optimistic number
        # exactly when the sweep proved no offered load is sustained
        knee = max((p["achieved_tps"] for p in served), default=None)
        out["e2e_knee_tps"] = round(knee, 1) if knee is not None else None
    print(json.dumps(out))
    sys.stdout.flush()


def _leader_topology(count, unique, batch, verify_tiles, rate_tps,
                     tcache_depth=None):
    """The FULL leader loop: synth -> verify(xN, rr-sharded) -> dedup
    -> pack -> bank(svm device waves) -> poh -> shred(leader, signed
    merkle FEC sets) -> shredsink. Tcache depths sit BELOW the unique
    txn pool so replayed frames re-verify and re-execute instead of
    dedup-dropping — the loop sees `count` txns of real work from a
    pool it can afford to pre-sign at boot. Verify shards get a core
    each (cpu0+i) when the host has cores to spare."""
    from firedancer_tpu.disco import Topology
    if tcache_depth is None:
        # the wraparound trick needs a replay's tag EVICTED by the
        # time its copy is queried. Eviction happens at insert
        # (finalize) but queries happen at dispatch, so the effective
        # window is depth + the verify in-flight window (~batch x
        # (inflight+1) lanes) — and rr sharding divides the replay
        # distance by tile_cnt. depth ~ unique/16 leaves comfortable
        # margin for all of that; below 16 the tcache degenerates
        tcache_depth = max(16, 1 << (max(64, int(unique)).bit_length()
                                     - 4))
    cpus = os.cpu_count() or 1
    cpu0 = 1 if cpus >= verify_tiles + 6 else None
    vd = [f"vd{i}" for i in range(verify_tiles)]
    flight_cfg = _bench_flight("leader")
    topo = (
        Topology(f"ldr{os.getpid()}", wksp_size=1 << 27,
                 flight=flight_cfg)
        .link("ingest", depth=4096, mtu=1280)
        .link("dedup_pack", depth=4096, mtu=1280)
        .link("pack_bank0", depth=256, mtu=16384)
        .link("bank0_done", depth=256, mtu=64)
        .link("bank0_poh", depth=256, mtu=16448)
        .link("poh_entries", depth=512, mtu=16640)
        .link("poh_slots", depth=64, mtu=64)
        .link("shreds_mirror", depth=4096, mtu=1280)
        .link("shred_req", depth=32, mtu=1280)
        .link("sign_resp", depth=32, mtu=128)
        .tcache("dedup_tc", depth=tcache_depth)
        .tile("synth", "synth", outs=["ingest"], count=count,
              unique=unique, burst=512, seed=17, rate_tps=rate_tps)
        .tile("dedup", "dedup", ins=vd, outs=["dedup_pack"],
              tcache="dedup_tc", batch=1024)
        .tile("pack", "pack",
              ins=["dedup_pack", "bank0_done", "poh_slots"],
              outs=["pack_bank0"], txn_in="dedup_pack",
              bank_links=["pack_bank0"], done_links=["bank0_done"],
              slot_in="poh_slots", max_txn_per_microblock=31,
              wave=4, batch=256)
        .tile("bank0", "bank", ins=["pack_bank0"],
              outs=["bank0_done", "bank0_poh"], exec="svm", wave=8,
              poh_link="bank0_poh", forward_payloads=True,
              genesis_synth=unique)
        .tile("poh", "poh", ins=["bank0_poh"],
              outs=["poh_entries", "poh_slots"],
              slot_link="poh_slots", hashes_per_tick=64,
              ticks_per_slot=8)
        .tile("shred", "shred", mode="leader",
              ins=["poh_entries", ("sign_resp", False)],
              outs=["shred_req", "shreds_mirror"], req="shred_req",
              resp="sign_resp", shreds_link="shreds_mirror",
              identity_hex="03a107bff3ce10be1d70dd18e74bc09967e4d63"
                           "09ba50d5f1ddc8664125531b8",
              cluster=[{"pubkey_hex": "55" * 32, "stake": 100,
                        "addr": "127.0.0.1:9"}])
        .tile("sign", "sign", ins=[("shred_req", False)],
              outs=["sign_resp"],
              seed="000102030405060708090a0b0c0d0e0f10111213141516"
                   "1718191a1b1c1d1e1f",
              clients=[{"role": "leader", "req": "shred_req",
                        "resp": "sign_resp"}])
        .tile("shredsink", "sink", ins=["shreds_mirror"]))
    if flight_cfg:
        topo.tile("flight", "flight")
    for i in range(verify_tiles):
        topo.link(vd[i], depth=4096, mtu=1280)
        topo.tcache(f"vtc{i}", depth=tcache_depth)
    topo.sharded_tile(
        "verify", "verify", verify_tiles, ins=["ingest"], outs=vd,
        batch=batch, coalesce_us=500, cpu0=cpu0,
        tcache=[f"vtc{i}" for i in range(verify_tiles)])
    return topo


_LEADER_TILES = ("synth", "dedup", "pack", "bank0", "poh", "shred",
                 "shredsink")
_LEADER_LINKS = ("ingest", "dedup_pack", "pack_bank0", "bank0_poh",
                 "poh_entries", "shreds_mirror")


def _leader_hop_snapshot(runner, verify_tiles, tiles_extra=()):
    """Cumulative per-tile work/wait sums + per-link backpressure —
    diffed per sweep stanza to attribute the saturating hop.
    tiles_extra: additional tile names beyond the canonical leader set
    (the r16 exec-family loop adds resolv + exec shards)."""
    from firedancer_tpu.disco.metrics import (read_hists,
                                              read_link_metrics)
    tiles = {}
    names = list(_LEADER_TILES) + [f"verify{i}"
                                   for i in range(verify_tiles)] \
        + list(tiles_extra)
    for t in names:
        h = read_hists(runner.wksp, runner.plan, t)
        if not h:
            continue
        tiles[t] = (h.get("work", {}).get("sum_ns", 0),
                    h.get("wait", {}).get("sum_ns", 0))
    links = {ln: rec["backpressure"]
             for ln, rec in read_link_metrics(runner.wksp,
                                              runner.plan).items()}
    return {"tiles": tiles, "links": links}


def _leader_hop(prev, cur, verify_tiles, links_extra=()):
    """(top occupancy tile, first backpressured link) over a stanza
    window, from two cumulative snapshots."""
    occ = {}
    for t, (w1, i1) in cur["tiles"].items():
        w0, i0 = prev["tiles"].get(t, (0, 0))
        dw, di = w1 - w0, i1 - i0
        occ[t] = dw / (dw + di) if dw + di else 0.0
    top = max(occ, key=occ.get) if occ else None
    link_order = ["ingest"] + [f"vd{i}" for i in range(verify_tiles)] \
        + [ln for ln in _LEADER_LINKS if ln != "ingest"] \
        + list(links_extra)
    bp = next((ln for ln in link_order
               if cur["links"].get(ln, 0)
               - prev["links"].get(ln, 0) > 0), None)
    return top, bp


def _leader_wait_drained(runner, count, verify_tiles,
                         timeout_s=600.0, resolv=False):
    """Block until every synth txn reached a TERMINAL outcome
    (executed by the bank, or dropped at a named hop — conservation
    accounting, so a still-chewing pipeline is never mistaken for a
    drained one) and pack has retired every outstanding microblock.
    resolv=True adds the r16 resolv tile's drop counters to the
    conservation sum (the exec-family loop runs it ahead of pack)."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        runner.check_failures()
        p = runner.metrics("pack")
        b = runner.metrics("bank0")
        dropped = runner.metrics("dedup")["dup"] + p["parse_fail"]
        if resolv:
            r = runner.metrics("resolv")
            dropped += r["parse_fail"] + r["alut_fail"] \
                + r["fee_fail"] + r["oversz"]
        for i in range(verify_tiles):
            v = runner.metrics(f"verify{i}")
            dropped += v["parse_fail"] + v["dedup_drop"] \
                + v["verify_fail"]
        if b["txns"] + dropped >= count \
                and p["completions"] == p["microblocks"]:
            return
        time.sleep(0.1)
    raise TimeoutError(f"leader loop never drained: pack={p} bank={b}")


def _leader_bench():
    """Leader-loop sweep stage (r13): measure the knee of the WHOLE
    leader loop — the number that has to survive millions of users —
    not just synth->verify->dedup->sink.

    Two boots: (1) unpaced capacity (the loop's ceiling, bank-executed
    txns per wall second from RUN to drained); (2) ONE ramped boot for
    every sweep point (the synth's rate_tps ramp schedule holds each
    offered load for a fixed stanza), recording per stanza the
    achieved rate and the saturating hop (top-occupancy tile + first
    link showing fresh backpressure). Knee = highest offered load
    still served at >= 90%.

    Prints one JSON line with e2e_leader_tps / e2e_leader_sweep /
    e2e_leader_knee_tps / e2e_leader_hop. The parent process must not
    touch jax — the verify tile processes own the device."""
    sys.path.insert(0, HERE)
    from firedancer_tpu.disco import TopologyRunner
    count = int(os.environ.get("FDTPU_BENCH_LEADER_COUNT", "4096"))
    unique = int(os.environ.get("FDTPU_BENCH_LEADER_UNIQUE", "768"))
    batch = int(os.environ.get("FDTPU_BENCH_LEADER_BATCH", "32"))
    # one device tile per plan (disco/topo.py refuses more); multi-chip
    # scale-out is the verify tile's `devices`
    tiles = 1
    out = {"e2e_leader_verify_tiles": tiles}

    # --- boot 1: capacity -------------------------------------------------
    runner = TopologyRunner(
        _leader_topology(count, unique, batch, tiles,
                         rate_tps=0.0).build()).start()
    try:
        runner.wait_running(timeout_s=840)
        t0 = time.perf_counter()
        runner.wait_idle("synth", "tx", count, timeout_s=600)
        _leader_wait_drained(runner, count, tiles)
        wall = time.perf_counter() - t0
        txns = runner.metrics("bank0")["txns"]
        out["e2e_leader_tps"] = round(txns / wall, 1) if wall else 0.0
        out["e2e_leader_count"] = txns
        out["e2e_leader_wall_s"] = round(wall, 2)
    finally:
        runner.halt()
        runner.close()

    # --- boot 2: one ramped boot for the whole sweep ----------------------
    fracs_env = os.environ.get("FDTPU_BENCH_LEADER_SWEEP",
                               "0.5,0.8,1.2")
    fracs = [float(f) for f in fracs_env.split(",") if f.strip()]
    cap = out["e2e_leader_tps"]
    if fracs and cap > 0:
        dur = float(os.environ.get("FDTPU_BENCH_LEADER_STANZA_S",
                                   "3.0"))
        # a warmup stanza primes the pipeline (verify latency + fill)
        # so the first MEASURED stanza isn't half cold-start; it is
        # excluded from the sweep output
        warmup = max(2.0, dur)
        ramp = [[warmup, round(cap * 0.4, 1)]] \
            + [[dur, round(cap * f, 1)] for f in fracs]
        n_ramp = int(sum(d * r for d, r in ramp)) + 64
        runner = TopologyRunner(
            _leader_topology(n_ramp, unique, batch, tiles,
                             rate_tps=ramp).build()).start()
        sweep = []
        try:
            runner.wait_running(timeout_s=840)
            # stanza clock starts when the synth's token bucket does
            # (its first publish) — poll fast for the first frag
            deadline = time.monotonic() + 60
            while runner.metrics("synth")["tx"] == 0 \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            snap = _leader_hop_snapshot(runner, tiles)
            base_txns = runner.metrics("bank0")["txns"]
            t_next = time.monotonic()
            for si, (d, offered) in enumerate(ramp):
                t_next += d
                while time.monotonic() < t_next:
                    runner.check_failures()
                    time.sleep(0.02)
                cur = _leader_hop_snapshot(runner, tiles)
                txns = runner.metrics("bank0")["txns"]
                achieved = (txns - base_txns) / d
                top, bp = _leader_hop(snap, cur, tiles)
                if si > 0:              # stanza 0 is the warmup
                    sweep.append({
                        "offered_tps": offered,
                        "achieved_tps": round(achieved, 1),
                        "served_frac": round(achieved / offered, 3)
                        if offered else 0.0,
                        "top_occupancy_tile": top,
                        "first_backpressured_link": bp,
                    })
                snap, base_txns = cur, txns
        finally:
            runner.halt()
            runner.close()
        out["e2e_leader_sweep"] = sweep
        served = [p for p in sweep if p.get("served_frac", 0) >= 0.9]
        knee = max((p["achieved_tps"] for p in served), default=None)
        out["e2e_leader_knee_tps"] = round(knee, 1) \
            if knee is not None else None
        # the saturating hop: attribution at the first point past the
        # knee (where the loop stopped keeping up), else at the top
        # offered point — the "what to fix next" pointer
        past = next((p for p in sweep
                     if p.get("served_frac", 1.0) < 0.9), None)
        at = past or (sweep[-1] if sweep else None)
        if at:
            out["e2e_leader_hop"] = {
                "top_occupancy_tile": at["top_occupancy_tile"],
                "first_backpressured_link":
                    at["first_backpressured_link"],
            }
    print(json.dumps(out))
    sys.stdout.flush()


def _exec_leader_topology(count, unique, batch, verify_tiles,
                          exec_cnt, rate_tps, tcache_depth=None):
    """The r16 leader loop: the _leader_topology shape with execution
    scaled OUT of the bank — a resolv tile ahead of pack (RESOLVED
    frames: account sets + cost precomputed) and `exec_cnt` exec tiles
    pulling conflict-free waves over rings, all sharing the shm funk
    store. The bank keeps wave scheduling / commit ordering / PoH
    handoff. pack and bank consume their feedback links unreliably —
    the exec fan-out adds a bank0->exec->bank0 leg that would
    otherwise close a reliable-consumption cycle."""
    from firedancer_tpu.disco import Topology
    if tcache_depth is None:
        tcache_depth = max(16, 1 << (max(64, int(unique)).bit_length()
                                     - 4))
    cpus = os.cpu_count() or 1
    cpu0 = 1 if cpus >= verify_tiles + exec_cnt + 7 else None
    vd = [f"vd{i}" for i in range(verify_tiles)]
    disp = [f"exec_disp{i}" for i in range(exec_cnt)]
    done = [f"exec_done{i}" for i in range(exec_cnt)]
    topo = (
        Topology(f"exl{os.getpid()}", wksp_size=1 << 27,
                 funk={"backend": "shm", "heap_mb": 16})
        .link("ingest", depth=4096, mtu=1280)
        .link("dedup_resolv", depth=4096, mtu=1280)
        .link("resolv_pack", depth=4096, mtu=2048)
        .link("pack_bank0", depth=256, mtu=16384)
        .link("bank0_done", depth=256, mtu=64)
        .link("bank0_poh", depth=256, mtu=16448)
        .link("poh_entries", depth=512, mtu=16640)
        .link("poh_slots", depth=64, mtu=64)
        .link("shreds_mirror", depth=4096, mtu=1280)
        .link("shred_req", depth=32, mtu=1280)
        .link("sign_resp", depth=32, mtu=128)
        .tcache("dedup_tc", depth=tcache_depth)
        .tile("synth", "synth", outs=["ingest"], count=count,
              unique=unique, burst=512, seed=17, rate_tps=rate_tps)
        .tile("dedup", "dedup", ins=vd, outs=["dedup_resolv"],
              tcache="dedup_tc", batch=1024)
        .tile("resolv", "resolv", ins=["dedup_resolv"],
              outs=["resolv_pack"], batch=256, fee_payer_check=False)
        .tile("pack", "pack",
              ins=["resolv_pack", ("bank0_done", False),
                   ("poh_slots", False)],
              outs=["pack_bank0"], txn_in="resolv_pack",
              resolved_in=True, bank_links=["pack_bank0"],
              done_links=["bank0_done"], slot_in="poh_slots",
              max_txn_per_microblock=31, wave=4, batch=256)
        .tile("bank0", "bank",
              ins=["pack_bank0"] + [(ln, False) for ln in done],
              outs=["bank0_done", "bank0_poh"] + disp,
              exec="svm", wave=8, poh_link="bank0_poh",
              forward_payloads=True, genesis_synth=unique,
              exec_links=disp, exec_done=done)
        .tile("poh", "poh", ins=["bank0_poh"],
              outs=["poh_entries", "poh_slots"],
              slot_link="poh_slots", hashes_per_tick=64,
              ticks_per_slot=8)
        .tile("shred", "shred", mode="leader",
              ins=["poh_entries", ("sign_resp", False)],
              outs=["shred_req", "shreds_mirror"], req="shred_req",
              resp="sign_resp", shreds_link="shreds_mirror",
              identity_hex="03a107bff3ce10be1d70dd18e74bc09967e4d63"
                           "09ba50d5f1ddc8664125531b8",
              cluster=[{"pubkey_hex": "55" * 32, "stake": 100,
                        "addr": "127.0.0.1:9"}])
        .tile("sign", "sign", ins=[("shred_req", False)],
              outs=["sign_resp"],
              seed="000102030405060708090a0b0c0d0e0f10111213141516"
                   "1718191a1b1c1d1e1f",
              clients=[{"role": "leader", "req": "shred_req",
                        "resp": "sign_resp"}])
        .tile("shredsink", "sink", ins=["shreds_mirror"]))
    for ln in disp:
        topo.link(ln, depth=64, mtu=4096)
    for ln in done:
        topo.link(ln, depth=64, mtu=64)
    for i in range(verify_tiles):
        topo.link(vd[i], depth=4096, mtu=1280)
        topo.tcache(f"vtc{i}", depth=tcache_depth)
    topo.sharded_tile(
        "verify", "verify", verify_tiles, ins=["ingest"], outs=vd,
        batch=batch, coalesce_us=500, cpu0=cpu0,
        tcache=[f"vtc{i}" for i in range(verify_tiles)])
    topo.sharded_tile("exec", "exec", exec_cnt, ins=[disp], outs=done,
                      batch=8)
    return topo


def _exec_scale_bench():
    """Execution scale-out stage (r16): one unpaced capacity boot of
    the exec-family leader loop per exec_tile_cnt — the measurement
    behind "execution scales with tile count". Per count: bank-executed
    txns per wall second (RUN -> drained) and the run's saturating-hop
    attribution (top-occupancy tile + first backpressured link) so the
    record shows WHO the bottleneck is once the bank stops executing.

    Prints one JSON line with exec_scale_tps {cnt: tps},
    exec_scale_hop {cnt: hop}, flat exec_scale_tps_N gate metrics, and
    exec_scale_leader_hop — the post-refactor leader-loop hop at 2
    exec tiles. The parent process must not touch jax."""
    sys.path.insert(0, HERE)
    from firedancer_tpu.disco import TopologyRunner
    count = int(os.environ.get("FDTPU_BENCH_EXEC_COUNT", "4096"))
    unique = int(os.environ.get("FDTPU_BENCH_EXEC_UNIQUE", "768"))
    batch = int(os.environ.get("FDTPU_BENCH_EXEC_BATCH", "32"))
    vtiles = 1      # one device tile per plan, as in the leader stage
    cnts = [int(c) for c in os.environ.get(
        "FDTPU_BENCH_EXEC_SCALE_CNTS", "1,2,4").split(",")
        if c.strip()]
    out = {"exec_scale_tps": {}, "exec_scale_hop": {},
           "exec_scale_count": count}
    for cnt in cnts:
        tiles_extra = ["resolv"] + [f"exec{i}" for i in range(cnt)]
        links_extra = ["dedup_resolv", "resolv_pack"] \
            + [f"exec_disp{i}" for i in range(cnt)] \
            + [f"exec_done{i}" for i in range(cnt)]
        runner = TopologyRunner(
            _exec_leader_topology(count, unique, batch, vtiles, cnt,
                                  rate_tps=0.0).build()).start()
        try:
            runner.wait_running(timeout_s=840)
            snap0 = _leader_hop_snapshot(runner, vtiles, tiles_extra)
            t0 = time.perf_counter()
            runner.wait_idle("synth", "tx", count, timeout_s=600)
            _leader_wait_drained(runner, count, vtiles, resolv=True)
            wall = time.perf_counter() - t0
            snap1 = _leader_hop_snapshot(runner, vtiles, tiles_extra)
            txns = runner.metrics("bank0")["txns"]
            top, bp = _leader_hop(snap0, snap1, vtiles, links_extra)
            tps = round(txns / wall, 1) if wall else 0.0
            out["exec_scale_tps"][str(cnt)] = tps
            out[f"exec_scale_tps_{cnt}"] = tps
            out["exec_scale_hop"][str(cnt)] = {
                "top_occupancy_tile": top,
                "first_backpressured_link": bp,
            }
        finally:
            runner.halt()
            runner.close()
    tps = out["exec_scale_tps"]
    if "1" in tps and "2" in tps:
        out["exec_scale_monotonic_1_2"] = tps["2"] >= tps["1"]
    hop_cnt = "2" if "2" in out["exec_scale_hop"] \
        else (str(cnts[-1]) if cnts else None)
    if hop_cnt:
        out["exec_scale_leader_hop"] = out["exec_scale_hop"][hop_cnt]
    print(json.dumps(out))
    sys.stdout.flush()


def _catchup_fixture(tmp, count, unique, n_slots, snap_slot):
    """Leader-side oracle: replay `n_slots` slots of signed transfers
    in-process, write the shm-format snapshot at `snap_slot` (atomic
    v2 checkpoint with slot + bank hash meta), archive the tail slices
    for playback, and return the per-slot bank hashes the follower
    must reproduce, and the txn count it replayed."""
    import struct as _struct
    from firedancer_tpu.jaxcfg import use_compile_cache
    use_compile_cache()
    from firedancer_tpu.disco.tiles import _synth_genesis
    from firedancer_tpu.funk.funk import Funk
    from firedancer_tpu.tiles.replay import InlineFanout, ReplayCore
    from firedancer_tpu.tiles.shred import pack_slice
    from firedancer_tpu.tiles.synth import make_signed_txns
    from firedancer_tpu.utils.checkpt import (CheckptWriter,
                                              snapshot_write_atomic)
    gen = _synth_genesis(unique)
    funk = Funk()
    oracle = ReplayCore(genesis=gen, verify_poh=False, funk=funk,
                        fanout=InlineFanout(funk))
    txns = make_signed_txns(count, seed=23)
    per = max(1, count // n_slots)
    slices = {}
    for s in range(1, n_slots + 1):
        batch = b""
        chunk = txns[(s - 1) * per:s * per]
        tip = hashlib.sha256(b"cu-tip-%d" % s).digest()
        batch += _struct.pack("<I", 1) + tip \
            + _struct.pack("<I", len(chunk))
        for t in chunk:
            batch += _struct.pack("<H", len(t)) + t
        slices[s] = pack_slice(s, 0, True, batch)
    snap_path = os.path.join(tmp, "snap.ckpt")
    for s in range(1, n_slots + 1):
        oracle.on_slice(slices[s])
        if s == snap_slot:
            snapshot_write_atomic(
                snap_path, oracle.funk, slot=s,
                bank_hash=oracle.bank_hash_of[s])
    tail_path = os.path.join(tmp, "tail.arch")
    with open(tail_path, "wb") as fp:
        w = CheckptWriter(fp, compress=True)
        for i, s in enumerate(range(snap_slot + 1, n_slots + 1)):
            payload = slices[s]
            w.frame(_struct.pack("<QQHI", i, s, 0, len(payload))
                    + payload)
        w.fini()
    expected = {str(s): oracle.bank_hash_of[s].hex()
                for s in range(snap_slot + 1, n_slots + 1)}
    return snap_path, tail_path, expected, oracle.metrics["txns"]


def _pin_cpu():
    """Pool initializer: this worker's jax runs on the host."""
    os.environ["JAX_PLATFORMS"] = "cpu"


def _follower_topology(snap_path, tail_path, expected, snap_slot,
                       exec_cnt):
    """The catch-up race under measurement: snapld->snapin restoring
    the shm store while playback floods the slice tail at full speed —
    the replay tile buffers behind the restore gate, then catches up
    over `exec_cnt` exec shards with the leader's bank hashes pinned."""
    from firedancer_tpu.disco import Topology
    disp = [f"exec_disp{i}" for i in range(exec_cnt)]
    done = [f"exec_done{i}" for i in range(exec_cnt)]
    topo = (
        Topology(f"cu{os.getpid()}", wksp_size=1 << 26,
                 funk={"backend": "shm", "heap_mb": 16},
                 snapshot={"path": snap_path, "min_slot": snap_slot,
                           "chunk": 4096})
        .link("snap_stream", depth=256, mtu=1 << 16)
        .link("shred_slices", depth=256, mtu=1 << 16)
        .link("replay_tower", depth=128, mtu=128)
        .tile("snapld", "snapld", outs=["snap_stream"])
        .tile("snapin", "snapin", ins=["snap_stream"])
        .tile("playback", "playback", outs=["shred_slices"],
              path=tail_path)
        .tile("replay", "replay",
              ins=["shred_slices"] + [(ln, False) for ln in done],
              outs=["replay_tower"] + disp,
              exec_links=disp, exec_done=done, wait_restore=True,
              expected=expected, verify_poh=False)
        .tile("towersink", "sink", ins=["replay_tower"]))
    for ln in disp:
        topo.link(ln, depth=64, mtu=4096)
    for ln in done:
        topo.link(ln, depth=64, mtu=64)
    topo.sharded_tile("exec", "exec", exec_cnt, ins=[disp], outs=done,
                      batch=8)
    return topo


def _catchup_bench():
    """Catch-up stage (r17): cold-start a follower from a ShmFunk
    snapshot while the slice tail streams in live, replay the tail
    over the exec tile family, and measure snapshot-load + replay
    against the in-process oracle's pinned bank hashes.

    Prints one JSON line with replay_tps (gate metric: replayed txns
    per wall second from boot to caught-up), catchup_s, the restore
    slot, and the divergence counter (must be 0). The parent process
    must not touch jax: the oracle (it hashes every slot's account
    delta with jax) runs in a CPU-pinned child."""
    import multiprocessing as mp
    import shutil

    sys.path.insert(0, HERE)
    from firedancer_tpu.disco import TopologyRunner
    count = int(os.environ.get("FDTPU_BENCH_CATCHUP_COUNT", "192"))
    unique = int(os.environ.get("FDTPU_BENCH_CATCHUP_UNIQUE", "16"))
    n_slots = int(os.environ.get("FDTPU_BENCH_CATCHUP_SLOTS", "12"))
    snap_slot = int(os.environ.get("FDTPU_BENCH_CATCHUP_SNAP_SLOT",
                                   "4"))
    exec_cnt = int(os.environ.get("FDTPU_BENCH_CATCHUP_EXEC_TILES",
                                  "2"))
    tmp = tempfile.mkdtemp(prefix="fdtpu_catchup_")
    with mp.get_context("spawn").Pool(1, initializer=_pin_cpu) as pool:
        snap_path, tail_path, expected, oracle_txns = pool.apply(
            _catchup_fixture, (tmp, count, unique, n_slots, snap_slot))
    target = n_slots - snap_slot
    runner = TopologyRunner(_follower_topology(
        snap_path, tail_path, expected, snap_slot,
        exec_cnt).build()).start()
    out = {"catchup_slots": target,
           "catchup_count": oracle_txns}
    try:
        runner.wait_running(timeout_s=840)
        t0 = time.perf_counter()
        deadline = t0 + 600
        m = {}
        while time.perf_counter() < deadline:
            m = runner.metrics("replay")
            if m.get("slots_replayed", 0) >= target:
                break
            time.sleep(0.05)
        wall = time.perf_counter() - t0
        if m.get("slots_replayed", 0) < target:
            raise RuntimeError(
                f"follower never caught up: "
                f"{m.get('slots_replayed', 0)}/{target} slots in "
                f"{wall:.1f}s (divergent_slot="
                f"{m.get('divergent_slot', 0)})")
        out["catchup_s"] = round(wall, 3)
        out["replay_tps"] = round(m["txns"] / wall, 1) if wall else 0.0
        out["catchup_restore_slot"] = m.get("restore_slot", 0)
        out["catchup_divergent_slot"] = m.get("divergent_slot", 0)
        out["catchup_exec_waves"] = m.get("exec_waves", 0)
    finally:
        runner.halt()
        runner.close()
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(out))
    sys.stdout.flush()


def _flood_topology(shed_stakes: dict, slo_floor: float | None,
                    pool: int, rate_pps: float = 300.0):
    """The front-door topology the adversarial soak attacks: a real
    UDP sock door (per-peer policing + stake-weighted shedding,
    disco/shed.py) feeding a bulk_prefilter verify tile (RLC batch
    equation ahead of strict — tiles/verify.py r14), dedup, sink, and
    the metric tile whose SLO engine is the pass/fail judge."""
    from firedancer_tpu.disco import Topology
    slo = None
    if slo_floor is not None:
        # the judge: staked goodput at the sink must hold the floor.
        # burn_fast 1.0 = a breach means the floor was missed for the
        # WHOLE fast window — boot/drain edges and the attack-onset
        # transient (the ring briefly fills with garbage before the
        # watermark flips the door to stake-weighted shedding) don't
        # page, a SUSTAINED collapse does. The window is cpu-scaled
        # (the r11 wedge_timeout_s precedent): on a 1-2 core CI box
        # the floor is ~25 txns/window and scheduler-descheduling a
        # healthy 6-process topology for a second dents a 4 s window
        # ~20% — so small boxes judge at attack length (the criterion
        # is literally "goodput over the attack >= 80% of clean"),
        # real hosts keep the stricter 4 s acuity.
        fast_s = 4.0 if (os.cpu_count() or 1) >= 4 else 8.0
        slo = {"fast_window_s": fast_s, "slow_window_s": 20.0,
               "burn_fast": 1.0, "burn_slow": 0.5,
               "target": [{"name": "flood_goodput",
                           "expr": f"sink.rx rate > {slo_floor}/s"}]}
    topo = (
        Topology(f"flood{os.getpid()}", wksp_size=1 << 26,
                 slo=slo, flight=_bench_flight("flood"),
                 shed={"rate_pps": float(os.environ.get(
                           "FDTPU_BENCH_FLOOD_RATE_PPS", "0"))
                       or rate_pps,
                       # burst bounds the bucket-funded onset spike: a
                       # Sybil swarm's FIRST packets all ride fresh
                       # buckets (token buckets cannot police a peer
                       # that brings a new identity per burst — that
                       # is the overload gate's job), so sybils*burst
                       # is garbage the door admits before the
                       # watermark trips, every frame of it strict-
                       # kernel work stolen from staked traffic
                       "burst": 4, "max_peers": 64, "min_stake": 1,
                       # the hold is the overload duty cycle: each
                       # expiry is a recovery probe that re-admits one
                       # bucket-funded burst before the watermark
                       # re-trips, so floor the hold at attack length
                       # — ONE admission window per soak; recovery
                       # latency is bounded by the same expiry either
                       # way (the drain phase asserts it)
                       "overload_hold_s": 8.0,
                       "stakes": shed_stakes})
        # the ingest ring is deliberately SHALLOW: queued garbage is
        # latency the staked traffic pays behind it, and the sock
        # watermark (shed armed, credits <= depth/2) flips to
        # stake-weighted shedding while there is still room — a deep
        # ring would just buy the flood a bigger backlog to age in
        # (and every queued garbage frame is a strict dispatch the
        # verify tile owes before staked traffic behind it moves)
        .link("sock_verify", depth=32, mtu=1280)
        .link("verify_dedup", depth=1024, mtu=1280)
        .link("dedup_sink", depth=1024, mtu=1280)
        .tcache("verify_tc", depth=max(8192, 2 * pool))
        .tcache("dedup_tc", depth=max(8192, 2 * pool))
        .tile("sock", "sock", outs=["sock_verify"], port=0, batch=32)
        .tile("verify", "verify", ins=["sock_verify"],
              outs=["verify_dedup"], batch=16, tcache="verify_tc",
              # coalesce paced trickles toward full chunks: a strict
              # dispatch costs the same fixed-shape kernel whatever
              # the fill, and the prefilter engages on FULL chunks
              coalesce_us=150000,
              mode="bulk_prefilter")
        .tile("dedup", "dedup", ins=["verify_dedup"],
              outs=["dedup_sink"], tcache="dedup_tc", batch=256)
        .tile("sink", "sink", ins=["dedup_sink"], batch=256))
    if slo is not None:
        topo.tile("metric", "metric", port=0)
    if _bench_flight("flood"):
        topo.tile("flight", "flight")
    return topo


class _PacedSender:
    """Daemon thread pacing datagrams at aggregate `pps`, rotating
    round-robin over one or more bound sockets (each socket = one peer
    identity at the door). The staked client is a single socket; the
    Sybil swarm is ONE thread over `sybils` sockets — same identities
    and aggregate rate as a thread per Sybil, but without handing the
    scheduler dozens of competing sender threads on a small CI box
    (the soak judges the FRONT DOOR, not harness-side contention)."""

    def __init__(self, frames: list, port: int, pps: float,
                 sock=None, nsocks: int = 1):
        import socket as socket_mod
        import threading
        if sock is not None:
            self.socks = [sock]
        else:
            self.socks = [socket_mod.socket(socket_mod.AF_INET,
                                            socket_mod.SOCK_DGRAM)
                          for _ in range(nsocks)]
        self.frames, self.port, self.pps = frames, port, pps
        self.sent = 0
        self._stop = threading.Event()
        self._thr = threading.Thread(target=self._run, daemon=True)

    def start(self):
        self._thr.start()
        return self

    def _run(self):
        t0 = time.perf_counter()
        while not self._stop.is_set():
            budget = int((time.perf_counter() - t0) * self.pps)
            while self.sent < budget and not self._stop.is_set():
                self.socks[self.sent % len(self.socks)].sendto(
                    self.frames[self.sent % len(self.frames)],
                    ("127.0.0.1", self.port))
                self.sent += 1
            time.sleep(0.002)

    def stop(self):
        self._stop.set()
        self._thr.join(timeout=5)
        for s in self.socks:
            s.close()
        return self.sent


def _flood_bench():
    """Adversarial flood soak (r14, ROADMAP item 4): boot the
    front-door topology, measure clean staked goodput, then attack it
    with a seeded forged-sig flood at >= FLOOD_MULT x the clean rate
    from a Sybil swarm of unstaked peers — with the SLO engine as the
    judge (goodput floor 80% of clean), zero watchdog trips, and the
    per-peer table bounded. Prints one JSON line with the flood_* +
    rlc_prefilter_vps record.

    CPU note: the jnp RLC kernel bounds the whole soak at a few
    hundred tps — the numbers are small
    but the DYNAMICS (door shedding, overload duty cycle, prefilter
    chunk shedding, SLO hold) are the same ones the chip run sees;
    only a chip run gives the TPU-scale numbers."""
    import socket as socket_mod

    sys.path.insert(0, HERE)
    from firedancer_tpu.disco import TopologyRunner
    from firedancer_tpu.tiles.synth import make_signed_txns
    from firedancer_tpu.utils.chaos import attack_frames

    probe_pps = float(os.environ.get("FDTPU_BENCH_FLOOD_PROBE_PPS",
                                     "80"))
    mult = float(os.environ.get("FDTPU_BENCH_FLOOD_MULT", "4"))
    attack_s = float(os.environ.get("FDTPU_BENCH_FLOOD_S", "8"))
    sybils = int(os.environ.get("FDTPU_BENCH_FLOOD_SYBILS", "24"))
    clean_s = 6.0
    pool = int(probe_pps * (clean_s + attack_s + 40))
    txns = make_signed_txns(pool, seed=23)
    forged = attack_frames("flood_forged", 64, seed=29)

    # the staked identity binds first so its "ip:port" key can be in
    # the topology's [shed.stakes] table
    staked_sock = socket_mod.socket(socket_mod.AF_INET,
                                    socket_mod.SOCK_DGRAM)
    staked_sock.bind(("127.0.0.1", 0))
    skey = f"127.0.0.1:{staked_sock.getsockname()[1]}"
    out = {}

    def _port(runner):
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            p = runner.metrics("sock").get("port")
            if p:
                return int(p)
            time.sleep(0.05)
        raise TimeoutError("sock port never published")

    # --- boot 1: capacity probe (the clean knee of this box) --------------
    # saturating paced run: achieved goodput == the pipeline's capacity
    # on this host (on a 1-core CI box the whole 5-process topology
    # shares one CPU, so this is tens of tps; on real hosts/TPU it is
    # the strict-kernel rate — the protocol is host-relative by design)
    runner = TopologyRunner(
        _flood_topology({skey: 1000}, None, pool,
                        rate_pps=2 * probe_pps).build()).start()
    try:
        runner.wait_running(timeout_s=840)
        port = _port(runner)
        sender = _PacedSender(txns, port, probe_pps,
                              sock=staked_sock).start()
        time.sleep(2.0)                  # pipeline fill excluded
        rx0 = runner.metrics("sink")["rx"]
        t0 = time.perf_counter()
        time.sleep(clean_s)
        cap_tps = (runner.metrics("sink")["rx"] - rx0) \
            / (time.perf_counter() - t0)
        sender._stop.set()
        sender._thr.join(timeout=5)

        # staked offered rate sits WELL UNDER capacity so the clean
        # run is unsaturated (a goodput baseline measured at the knee
        # would just re-measure capacity) and the attack must steal
        # headroom to breach; the flood itself is sized against
        # CAPACITY (>= mult x the clean knee per the protocol).
        # Capped by the pre-rendered txn pool: the staked sender signs
        # host-side from a FINITE pool, and on a host fast enough that
        # 0.33*capacity outruns it the sender would wrap — every
        # replayed frame dedup-drops and the judged goodput collapses
        # for a harness reason, not a front-door one. ~120 s covers
        # the worst-case remaining protocol (clean ref + SLO wait +
        # baseline + attack + drain + exercise).
        clean_pps = max(4.0, float(os.environ.get(
            "FDTPU_BENCH_FLOOD_CLEAN_PPS", "0")) or 0.33 * cap_tps)
        clean_pps = min(clean_pps, (pool - sender.sent) / 120.0)

        # unsaturated clean REFERENCE on the same boot: the SLO floor
        # is 80% of what this host actually DELIVERS at clean_pps, not
        # 80% of the offered rate — on a loaded CI box achieved runs a
        # few % under offered and that gap would silently tighten the
        # judge's bar past the acceptance criterion ("80% of clean-run
        # goodput"). 8 s drains the saturated probe's backlog first
        # (ring + verify in-flight hold ~100 frames; at a small-box
        # capacity of ~20 tps that tail would otherwise inflate the
        # reference measurement).
        sent_clean = sender.sent
        sender = _PacedSender(txns[sent_clean:], port, clean_pps,
                              sock=staked_sock).start()
        time.sleep(8.0)
        rx0 = runner.metrics("sink")["rx"]
        t0 = time.perf_counter()
        time.sleep(4.0)
        clean_ref = (runner.metrics("sink")["rx"] - rx0) \
            / (time.perf_counter() - t0)
        sender._stop.set()
        sender._thr.join(timeout=5)
        sent_clean += sender.sent
    finally:
        runner.halt()
        runner.close()
    out["flood_capacity_tps"] = round(cap_tps, 1)
    out["flood_clean_ref_tps"] = round(clean_ref, 1)
    floor = round(0.8 * min(clean_ref, clean_pps), 1)
    attack_pps = max(mult * cap_tps, 4 * clean_pps)

    # --- boot 2: the attack, judged by the SLO engine ---------------------
    txns_b = txns[sent_clean:]
    runner = TopologyRunner(
        _flood_topology({skey: 1000}, floor, pool,
                        rate_pps=max(20.0, 3 * clean_pps))
        .build()).start()
    senders, flood = [], []
    try:
        runner.wait_running(timeout_s=840)
        port = _port(runner)
        sender = _PacedSender(txns_b, port, clean_pps,
                              sock=staked_sock).start()
        senders.append(sender)
        # let the engine see the clean floor held before attacking
        # (the boot window legitimately starts breached: rate 0)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if runner.metrics("metric")["slo_evals"] > 0 \
                    and runner.metrics("metric")["slo_breach"] == 0:
                break
            time.sleep(0.2)
        assert runner.metrics("metric")["slo_breach"] == 0, \
            "clean staked traffic never satisfied the SLO floor"
        pre_breaches = runner.metrics("metric")["slo_breaches"]
        # the clean-run goodput baseline, measured unsaturated
        rx0 = runner.metrics("sink")["rx"]
        t0 = time.perf_counter()
        time.sleep(4.0)
        clean_tps = (runner.metrics("sink")["rx"] - rx0) \
            / (time.perf_counter() - t0)
        out["flood_clean_tps"] = round(clean_tps, 1)

        rx0 = runner.metrics("sink")["rx"]
        t0 = time.perf_counter()
        flood = [_PacedSender(forged, port, attack_pps,
                              nsocks=sybils).start()]
        peers_peak, breach_ticks = 0, 0
        while time.perf_counter() - t0 < attack_s:
            runner.check_failures()
            m = runner.metrics("sock")
            peers_peak = max(peers_peak, m["peers"])
            if runner.metrics("metric")["slo_breach"]:
                breach_ticks += 1
            time.sleep(0.2)
        wall = time.perf_counter() - t0
        goodput = (runner.metrics("sink")["rx"] - rx0) / wall
        attack_sent = sum(s.stop() for s in flood)
        flood = []
        # drain: the attack is over (the staked sender keeps running —
        # the engine's rate floor should judge recovery under normal
        # traffic, and the exercise phase below still needs it); the
        # engine must CLEAR (recovery is part of the overload
        # contract) and no ring may be wedged
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            runner.check_failures()
            if runner.metrics("metric")["slo_breach"] == 0:
                break
            time.sleep(0.2)
        sockm = runner.metrics("sock")
        trips = sum(runner.metrics(t).get("sup_watchdog_trips", 0)
                    for t in ("sock", "verify", "dedup", "sink",
                              "metric"))
        # the ratio denominator is capped at the OFFERED clean rate:
        # the in-place clean window starts right after the boot fill,
        # so its measurement can catch queued backlog draining through
        # and read a few % above what the sender actually paced — an
        # inflated baseline would demand goodput the staked client
        # never even offered
        clean_eff = min(clean_tps, clean_pps)
        out.update({
            "flood_goodput_tps": round(goodput, 1),
            "flood_goodput_ratio": round(goodput / clean_eff, 3)
            if clean_eff else 0.0,
            "flood_offered_attack_pps": round(attack_sent / wall, 1),
            "flood_attack_mult": round(attack_sent / wall / clean_tps,
                                       2) if clean_tps else 0.0,
            "flood_shed_pct": round(100.0 * sockm["shed"]
                                    / max(1, sockm["shed"]
                                          + sockm["rx"]), 1),
            "flood_peers_peak": peers_peak,
            "flood_peers_bound": sockm["peers"] <= 64,
            "flood_slo_breaches": runner.metrics("metric")
            ["slo_breaches"] - pre_breaches,
            "flood_slo_breach_final": runner.metrics("metric")
            ["slo_breach"],
            "flood_watchdog_trips": trips,
        })

        # --- prefilter exercise (rlc_prefilter_vps) -----------------------
        # the judged numbers above are FROZEN; now deterministically
        # exercise the WIRED RLC path for its throughput stanza. A
        # well-tuned door sheds the whole soak at the socket (the
        # desired outcome!) and a PACED flood never piles the ring
        # high enough for full chunks — so after each overload hold
        # expires, BLAST one back-to-back burst from fresh Sybil
        # identities (fresh buckets admit until the ring is full,
        # ~depth frames in under a millisecond): the verify gathers go
        # full, chunks assemble at `batch` lanes, and every one of
        # them must cross the RLC equation.
        import socket as socket_mod
        for _ in range(2):
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                runner.check_failures()
                if runner.metrics("sock")["overload"] == 0:
                    break
                time.sleep(0.2)
            blast = [socket_mod.socket(socket_mod.AF_INET,
                                       socket_mod.SOCK_DGRAM)
                     for _ in range(sybils)]
            sent_b = 0
            for _ in range(4):           # ~4*sybils frames, instantly
                for s in blast:
                    s.sendto(forged[sent_b % len(forged)],
                             ("127.0.0.1", port))
                    sent_b += 1
            time.sleep(4.0)              # let verify chew the chunks
            for s in blast:
                s.close()
        runner.check_failures()
        verifym = runner.metrics("verify")
        out.update({
            "flood_rlc_shed": verifym["rlc_shed"],
            "flood_rlc_batches": verifym["rlc_batches"],
            "flood_rlc_lanes": verifym["rlc_lanes"],
            "flood_rlc_pass": verifym["rlc_pass"],
            "flood_verify_fail": verifym["verify_fail"],
        })
        if verifym["rlc_ns"] and verifym["rlc_lanes"] >= 32:
            # only a real measurement (attack + exercise combined):
            # two full chunks minimum — compile happened at boot and
            # every call rides the one pinned shape, so the ratio is
            # steady-state kernel time, not warmup noise; the chip run
            # sees far more lanes through the same counters
            out["rlc_prefilter_vps"] = round(
                verifym["rlc_lanes"] * 1e9 / verifym["rlc_ns"], 1)
        sender._stop.set()
        sender._thr.join(timeout=5)
        # zero falsely-accepted frags: everything at the sink is a
        # staked txn (forged/shed traffic must never land) — asserted
        # across clean + attack + drain + exercise
        assert runner.metrics("sink")["rx"] <= sender.sent + 1, \
            "forged frags reached the sink"
    finally:
        for s in flood:
            s.stop()
        runner.halt()
        runner.close()
    staked_sock.close()
    out["flood_pass"] = (out.get("flood_slo_breaches", 1) == 0
                         and out.get("flood_watchdog_trips", 1) == 0
                         and out.get("flood_peers_bound", False)
                         and out.get("flood_goodput_ratio", 0) >= 0.8)
    print(json.dumps(out))
    sys.stdout.flush()


def _run_child(env_extra: dict, timeout_s: float,
               require_key: str | None = "metric"):
    """Spawn bench.py as a child with extra env; return the last JSON
    object line of its stdout (containing require_key, if given)."""
    env = dict(os.environ)
    env.update(env_extra)
    env.setdefault("FDTPU_BENCH_CHILD", "1")
    r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       capture_output=True, text=True, timeout=timeout_s,
                       cwd=HERE, env=env)
    for line in reversed(r.stdout.strip().splitlines()):
        try:
            d = json.loads(line)
            if isinstance(d, dict) and (require_key is None
                                        or require_key in d):
                return d
        except json.JSONDecodeError:
            continue
    raise RuntimeError(
        f"child rc={r.returncode}: {(r.stderr or r.stdout)[-300:]}")


def main():
    if os.environ.get("FDTPU_BENCH_E2E_CHILD") == "1":
        _e2e_bench()
        return
    if os.environ.get("FDTPU_BENCH_LEADER_CHILD") == "1":
        _leader_bench()
        return
    if os.environ.get("FDTPU_BENCH_FLOOD_CHILD") == "1":
        _flood_bench()
        return
    if os.environ.get("FDTPU_BENCH_EXEC_SCALE_CHILD") == "1":
        _exec_scale_bench()
        return
    if os.environ.get("FDTPU_BENCH_CATCHUP_CHILD") == "1":
        _catchup_bench()
        return
    if os.environ.get("FDTPU_BENCH_CHILD") == "1":
        _child_bench()
        return

    result: dict = {}
    try:
        result = _run_child(
            {}, float(os.environ.get("FDTPU_BENCH_TPU_TIMEOUT", "900")))
    except Exception as e:  # noqa: BLE001 — reported, then exit 1
        result["error"] = f"kernel stage: {e!r}"[:300]
    if result.get("platform") != "tpu":
        # a measurement path that finds no chip fails: no CPU number
        # under a device metric's name, no older record in its place
        print(json.dumps({"error": result.get("error") or (
            f"kernel stage ran on {result.get('platform')!r}, not tpu"),
            "platform": result.get("platform")}))
        sys.exit(1)

    # second stage: end-to-end tile pipeline TPS (VERDICT r2 item 2)
    if os.environ.get("FDTPU_BENCH_SKIP_E2E") == "1":
        result["e2e"] = "skipped"
    else:
        try:
            e2e = _run_child(
                {"FDTPU_BENCH_E2E_CHILD": "1"},
                float(os.environ.get("FDTPU_BENCH_E2E_TIMEOUT", "1500")),
                require_key=None)
            for k, v in e2e.items():
                if k.startswith("e2e_"):
                    result[k] = v
        except Exception as e3:  # noqa: BLE001
            result["e2e_error"] = f"{e3!r}"[:300]

    # leader-loop sweep (r13): the full pack->bank->poh->shred knee,
    # CPU-measured by design (the leader hops are host code) — runs on
    # every platform unless skipped. Failures annotate, never break.
    if os.environ.get("FDTPU_BENCH_SKIP_LEADER") != "1":
        try:
            env = {"FDTPU_BENCH_LEADER_CHILD": "1"}
            ldr = _run_child(
                env,
                float(os.environ.get("FDTPU_BENCH_LEADER_TIMEOUT",
                                     "1200")),
                require_key="e2e_leader_tps")
            for k, v in ldr.items():
                if k.startswith("e2e_leader"):
                    result[k] = v
        except Exception as e4:  # noqa: BLE001
            result["e2e_leader_error"] = f"{e4!r}"[:300]

    # adversarial flood soak (r14): the front-door topology under a
    # seeded forged-sig flood, SLO engine as judge — runs on every
    # platform (CPU numbers are small but the shedding/overload/
    # prefilter dynamics are identical).
    if os.environ.get("FDTPU_BENCH_SKIP_FLOOD") != "1":
        try:
            env = {"FDTPU_BENCH_FLOOD_CHILD": "1"}
            fl = _run_child(
                env,
                float(os.environ.get("FDTPU_BENCH_FLOOD_TIMEOUT",
                                     "1200")),
                require_key="flood_goodput_tps")
            for k, v in fl.items():
                if k.startswith("flood_") or k.startswith("rlc_"):
                    result[k] = v
        except Exception as e5:  # noqa: BLE001
            result["flood_error"] = f"{e5!r}"[:300]

    # execution scale-out (r16): the shm-funk leader loop with the
    # resolv + exec tile family, one capacity boot per exec_tile_cnt —
    # the proof that execution scales with tile count, plus the
    # post-refactor leader-hop attribution. CPU-measured by design
    # (the exec hops are host code). Failures annotate, never break.
    if os.environ.get("FDTPU_BENCH_SKIP_EXEC_SCALE") != "1":
        try:
            env = {"FDTPU_BENCH_EXEC_SCALE_CHILD": "1"}
            ex = _run_child(
                env,
                float(os.environ.get("FDTPU_BENCH_EXEC_SCALE_TIMEOUT",
                                     "1800")),
                require_key="exec_scale_tps")
            for k, v in ex.items():
                if k.startswith("exec_scale"):
                    result[k] = v
        except Exception as e6:  # noqa: BLE001
            result["exec_scale_error"] = f"{e6!r}"[:300]

    # follower catch-up (r17): cold-start from a ShmFunk snapshot
    # while the slice tail streams live, replay over the exec family
    # against the oracle's pinned bank hashes — the "become a
    # follower" throughput record. CPU-measured by design (restore +
    # replay hops are host code). Failures annotate, never break.
    if os.environ.get("FDTPU_BENCH_SKIP_CATCHUP") != "1":
        try:
            env = {"FDTPU_BENCH_CATCHUP_CHILD": "1"}
            cu = _run_child(
                env,
                float(os.environ.get("FDTPU_BENCH_CATCHUP_TIMEOUT",
                                     "1500")),
                require_key="replay_tps")
            for k, v in cu.items():
                if k.startswith("catchup_") or k == "replay_tps":
                    result[k] = v
        except Exception as e7:  # noqa: BLE001
            result["catchup_error"] = f"{e7!r}"[:300]

    # multichip layout stanza (ROADMAP 1b): the same machine-readable
    # candidate-layout record dryrun_multichip prints into the
    # MULTICHIP tail, persisted as FIELDS of this round's BENCH json
    # so fdwitness/fdbench can diff layout choices round over round
    # (the measured choice itself comes from the fdwitness multichip
    # stage and rides the witnessed artifact as `multichip_choice`)
    try:
        sys.path.insert(0, HERE)
        from __graft_entry__ import multichip_layout_stanza
        # mesh size mirrors dryrun_multichip's 8-device default (this
        # parent must not touch jax to count devices itself) so the
        # BENCH field diffs cleanly against the MULTICHIP tail record
        n_dev = int(os.environ.get("FDTPU_BENCH_MULTICHIP_DEVICES",
                                   "8"))
        result["multichip_layout"] = multichip_layout_stanza(n_dev)
    except Exception as e:  # noqa: BLE001 — annotate, don't break
        result["multichip_layout_error"] = f"{e!r}"[:200]

    # bench-trend gate (fdbench): compare this round against the
    # previous BENCH json — kernel vps / e2e tps / knee regressions
    # beyond the threshold fail the run, and the printed diff says
    # which hop/frames moved (tools/fdbench for the standalone CLI).
    # With FDTPU_BENCH_PREV unset the gate defaults to the LATEST
    # committed BENCH_r*.json round and gates only the knee metrics
    # (the r13 contract: the knee never goes backwards; kernel/raw-tps
    # noise across heterogeneous rounds stays report-only).
    trend_rc = 0
    prev = os.environ.get("FDTPU_BENCH_PREV")
    knee_only = False
    if not prev:
        import glob as _glob
        rounds = sorted(_glob.glob(os.path.join(HERE, "BENCH_r*.json")))
        rounds = [r for r in rounds
                  if "witnessed" not in os.path.basename(r)]
        if rounds:
            prev = rounds[-1]
            knee_only = True
    if prev:
        try:
            from firedancer_tpu.prof.bench_diff import (
                KNEE_METRICS, diff_bench, gate_regressions, load_bench,
                render_text)
            old = load_bench(prev)
            thr = float(os.environ.get("FDTPU_BENCH_GATE_PCT", "0.05"))
            d = diff_bench(old, result)
            regs = gate_regressions(
                d, threshold=thr,
                keys=KNEE_METRICS if knee_only else None)
            print(render_text(d, regs, thr), file=sys.stderr)
            result["bench_gate"] = {
                "prev": prev, "threshold": thr,
                "knee_only": knee_only,
                "regressions": regs,
            }
            trend_rc = 1 if regs else 0
        except Exception as e:  # noqa: BLE001 — annotate, don't break
            result["bench_gate"] = {"prev": prev,
                                    "error": f"{e!r}"[:200]}
    # flight archive provenance (r19): the round's record names the
    # archive dir its stage topologies recorded into, so fdflight /
    # fdgui --archive can post-mortem the exact run behind the numbers
    if os.environ.get("FDTPU_BENCH_FLIGHT_DIR"):
        result["flight_dir"] = os.environ["FDTPU_BENCH_FLIGHT_DIR"]

    _emit_report(result)
    print(json.dumps(result))
    sys.stdout.flush()
    sys.exit(_gate_rc(result, os.environ.get("FDTPU_BENCH_GATE_E2E"))
             or trend_rc)


def _emit_report(result: dict):
    """Per-round report artifact (fdgui): FDTPU_BENCH_REPORT=<out.html>
    (any other truthy value means ./report.html) renders the
    bench-trend dashboard over every BENCH_r*.json round plus THIS
    round's record — so every CI/bench run leaves an openable report
    next to its json. Annotates `result` (report / report_error),
    never breaks the JSON line."""
    rep = os.environ.get("FDTPU_BENCH_REPORT")
    if not rep:
        return
    try:
        import glob as _glob
        out_path = rep if rep.endswith(".html") \
            else os.path.join(HERE, "report.html")
        cur = os.path.join(tempfile.gettempdir(),
                           f"BENCH_current.{os.getpid()}.json")
        with open(cur, "w") as f:
            json.dump(result, f)
        try:
            from firedancer_tpu.gui.report import report_from_bench
            rounds = sorted(_glob.glob(
                os.path.join(HERE, "BENCH_r*.json")))
            # witnessed artifacts chart through their own reports (and
            # the fallback embed) — as trend rounds they would double
            # up with the driver round they witness
            rounds = [r for r in rounds
                      if "witnessed" not in os.path.basename(r)]
            # bench_series preserves caller order, so THIS round is
            # the trajectory's last point wherever tempdir sorts
            report_from_bench(rounds + [cur], out_path)
        finally:
            os.unlink(cur)
        result["report"] = out_path
    except Exception as e:  # noqa: BLE001 — annotate, don't break
        result["report_error"] = f"{e!r}"[:200]


def _gate_rc(result: dict, floor: str | None) -> int:
    """Regression gate: nonzero when an e2e floor is set and this
    run's measured e2e_tps is below it. No number at all under a floor
    is itself a failure (a gate that silently passes on a broken bench
    gates nothing)."""
    if not floor:
        return 0
    tps = result.get("e2e_tps")
    return 0 if tps is not None and float(tps) >= float(floor) else 1


if __name__ == "__main__":
    main()
