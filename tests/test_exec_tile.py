"""Exec tile family + resolv tile + shm funk store (r16).

The bank's execution stage moves out-of-process: the bank partitions
each gathered wave into account-disjoint conflict groups, ships them
over dedicated rings to N exec tiles that execute against the
shm-resident funk store at the fork the bank prepared, and publishes
the fork only after every dispatch frame completed. These suites pin:

* the [funk] registry mirror (lint/registry.py vs funk/shmfunk.py),
* the conflict-group partition invariants,
* byte-identity of the fan-out path's poh/done egress vs the
  in-process svm wave path (same frames in, same bytes out),
* cross-tile conflict isolation on the wire (no account appears in
  two tiles' dispatch frames),
* the supervision drill: an exec tile dying mid-wave (its frames
  lost) leads to cancel + whole-wave redispatch under a fresh fork —
  exactly-once application, no wedged producer,
* the resolv tile's RESOLVED egress vs pack's meta_from_payload.
"""
import hashlib
import os
import struct
import time
from types import SimpleNamespace

import pytest

from firedancer_tpu.runtime import Ring, Store, Workspace

pytestmark = pytest.mark.exec



# ---------------------------------------------------------------------------
# registry mirror + conflict groups
# ---------------------------------------------------------------------------

def test_funk_registry_mirrors_defaults():
    """lint/registry.py FUNK_SECTION_KEYS is the static mirror of
    funk/shmfunk.py FUNK_DEFAULTS (the bad-funk rule and the config
    gate both trust it)."""
    from firedancer_tpu.funk.shmfunk import (FUNK_BACKENDS,
                                             FUNK_DEFAULTS,
                                             normalize_funk)
    from firedancer_tpu.lint.registry import FUNK_SECTION_KEYS
    assert set(FUNK_SECTION_KEYS) == set(FUNK_DEFAULTS)
    assert FUNK_DEFAULTS["backend"] in FUNK_BACKENDS
    with pytest.raises(ValueError, match="did you mean"):
        normalize_funk({"bakend": "shm"})
    with pytest.raises(ValueError, match="backend"):
        normalize_funk({"backend": "sm"})
    cfg = normalize_funk({"backend": "shm", "heap_mb": 4})
    assert cfg["rec_max"] == FUNK_DEFAULTS["rec_max"]


def test_conflict_groups_partition():
    """Union-find partition: transitively-linked transfers share one
    group (in original order); groups are pairwise account-disjoint."""
    from firedancer_tpu.disco.tiles import _conflict_groups
    from firedancer_tpu.svm.executor import SystemTxn
    k = [bytes([i]) * 32 for i in range(8)]
    txns = [
        SystemTxn(src=k[0], dst=k[1], amount=1, fee=0),   # g0
        SystemTxn(src=k[2], dst=k[3], amount=2, fee=0),   # g1
        SystemTxn(src=k[1], dst=k[4], amount=3, fee=0),   # g0 (via k1)
        SystemTxn(src=k[5], dst=k[6], amount=4, fee=0),   # g2
        SystemTxn(src=k[4], dst=k[0], amount=5, fee=0),   # g0 (via k4)
        SystemTxn(src=k[6], dst=k[7], amount=6, fee=0),   # g2 (via k6)
    ]
    groups = _conflict_groups(txns)
    assert sorted(len(g) for g in groups) == [1, 2, 3]
    accts = [set(x for t in g for x in (t.src, t.dst)) for g in groups]
    for i in range(len(groups)):
        for j in range(i + 1, len(groups)):
            assert not (accts[i] & accts[j])
    big = next(g for g in groups if len(g) == 3)
    assert [t.amount for t in big] == [1, 3, 5]   # original order kept


# ---------------------------------------------------------------------------
# in-process harness: bank + N exec adapters over real rings
# ---------------------------------------------------------------------------

def _mk_family(wksp, n_exec=2, redispatch_s=5.0, genesis=None,
               disp_mtu=4096):
    from firedancer_tpu.disco.tiles import BankAdapter, ExecAdapter
    st = Store(wksp, rec_max=4096, txn_max=64, heap_sz=1 << 20)
    funk_plan = {"backend": "shm", "rec_max": 4096, "txn_max": 64,
                 "heap_mb": 1, "off": st.off, "heap_sz": 1 << 20}
    links = {"pack_bank0": {"mtu": 1 << 15},
             "bank0_done": {"mtu": 64},
             "bank0_poh": {"mtu": 1 << 16}}
    for i in range(n_exec):
        links[f"exec_disp{i}"] = {"mtu": disp_mtu}
        links[f"exec_done{i}"] = {"mtu": 64}
    rings = {ln: Ring.create(wksp, depth=64, mtu=li["mtu"])
             for ln, li in links.items()}
    plan = {"links": links, "funk": funk_plan}
    bank_ctx = SimpleNamespace(
        tile_name="bank0", plan=plan, wksp=wksp,
        in_rings={"pack_bank0": rings["pack_bank0"],
                  **{f"exec_done{i}": rings[f"exec_done{i}"]
                     for i in range(n_exec)}},
        out_rings={"bank0_done": rings["bank0_done"],
                   "bank0_poh": rings["bank0_poh"],
                   **{f"exec_disp{i}": rings[f"exec_disp{i}"]
                      for i in range(n_exec)}},
        out_fseqs={ln: [] for ln in links},
        in_seq0={})
    bank = BankAdapter(bank_ctx, {
        "exec": "svm", "wave": 8, "poh_link": "bank0_poh",
        "exec_links": [f"exec_disp{i}" for i in range(n_exec)],
        "exec_done": [f"exec_done{i}" for i in range(n_exec)],
        "genesis": genesis or {}, "forward_payloads": True,
        "redispatch_s": redispatch_s})
    execs = []
    for i in range(n_exec):
        ctx = SimpleNamespace(
            tile_name=f"exec{i}", plan=plan, wksp=wksp,
            in_rings={f"exec_disp{i}": rings[f"exec_disp{i}"]},
            out_rings={f"exec_done{i}": rings[f"exec_done{i}"]},
            out_fseqs={f"exec_done{i}": []},
            in_seq0={})
        execs.append(ExecAdapter(ctx, {"batch": 8}))
    return bank, execs, rings


def _microblocks(txns, per=6, slot=3):
    frames = []
    for mb_id in range(0, len(txns), per):
        chunk = txns[mb_id:mb_id + per]
        body = b"".join(struct.pack("<H", len(p)) + p for p in chunk)
        frames.append(struct.pack("<HHQQ", 0, len(chunk),
                                  mb_id // per, slot) + body)
    return frames


def _synth_genesis(n=16):
    from firedancer_tpu.tiles.synth import synth_signer_seed
    from firedancer_tpu.utils.ed25519_ref import keypair
    return {keypair(synth_signer_seed(i))[-1].hex(): 1 << 44
            for i in range(n)}


def _drain(ring, seq=0):
    out = []
    while True:
        rc, frag = ring.consume(seq)
        if rc != 0:
            break
        out.append((bytes(ring.payload(frag)), frag.sig))
        seq += 1
    return out, seq


@pytest.fixture()
def wksp():
    w = Workspace(f"/fdtpu_ext_{os.getpid()}", 1 << 24)
    yield w
    w.close()
    w.unlink()


def test_exec_family_byte_identity_vs_in_process(wksp):
    """Same microblock frames through (a) the in-process svm wave path
    and (b) the exec fan-out over 2 tiles: every poh frame, every done
    frag, and every touched balance is IDENTICAL — the fan-out is a
    pure throughput change."""
    from firedancer_tpu.disco.tiles import BankAdapter
    from firedancer_tpu.tiles.synth import make_signed_txns
    genesis = _synth_genesis()
    frames = _microblocks(make_signed_txns(18, seed=77), per=6)

    # (a) in-process oracle bank
    links = {"pb": {"mtu": 1 << 15}, "dn": {"mtu": 64},
             "ph": {"mtu": 1 << 16}}
    rings_a = {ln: Ring.create(wksp, depth=64, mtu=li["mtu"])
               for ln, li in links.items()}
    ctx_a = SimpleNamespace(
        tile_name="bankA", plan={"links": links},
        in_rings={"pb": rings_a["pb"]},
        out_rings={"dn": rings_a["dn"], "ph": rings_a["ph"]},
        out_fseqs={"dn": [], "ph": []}, in_seq0={})
    bank_a = BankAdapter(ctx_a, {
        "exec": "svm", "wave": 8, "poh_link": "ph",
        "genesis": genesis, "forward_payloads": True})
    for i, f in enumerate(frames):
        rings_a["pb"].publish(f, sig=i)
    bank_a.poll_once()
    bank_a.poll_once()            # drain-on-idle retires the wave

    # (b) the exec tile family
    bank, execs, rings = _mk_family(wksp, n_exec=2, genesis=genesis)
    for i, f in enumerate(frames):
        rings["pack_bank0"].publish(f, sig=i)
    bank.poll_once()
    assert bank.fanout.wave is not None \
        and bank.fanout.wave["remaining"] >= 1
    for e in execs:
        e.poll_once()
    bank.poll_once()
    assert bank.fanout.wave is None

    assert bank.m["transfers"] == bank_a.m["transfers"] > 0
    assert bank.m["exec_fail"] == bank_a.m["exec_fail"]
    got_poh, _ = _drain(rings["bank0_poh"])
    want_poh, _ = _drain(rings_a["ph"])
    assert got_poh == want_poh           # bytes AND sigs, in order
    got_dn, _ = _drain(rings["bank0_done"])
    want_dn, _ = _drain(rings_a["dn"])
    assert got_dn == want_dn
    for hex_key in genesis:
        k = bytes.fromhex(hex_key)
        assert bank.funk.rec_query(None, k) \
            == bank_a.funk.rec_query(None, k)
    # both exec tiles actually carried work
    assert all(e.m["txns"] > 0 for e in execs)


def test_exec_cross_tile_conflict_isolation(wksp):
    """On the wire: no account key appears in two different tiles'
    dispatch frames (conflict groups are account-disjoint across
    tiles), a conflict CHAIN lands on one tile in order, and the final
    balances match the serial oracle despite the cross-frame
    conflicts."""
    from firedancer_tpu.svm.executor import execute_block_serial
    keys = [hashlib.sha256(b"ct%d" % i).digest() for i in range(9)]
    genesis = {k.hex(): 1_000_000 for k in keys}
    bank, execs, rings = _mk_family(wksp, n_exec=2, genesis=genesis)
    # chain: k0->k1->k2->k3 (conflicting, order-sensitive) + disjoint
    # pairs k4->k5, k6->k7, k8->k8
    from firedancer_tpu.svm.executor import SystemTxn
    txns = [
        SystemTxn(src=keys[0], dst=keys[1], amount=900_000, fee=0),
        SystemTxn(src=keys[1], dst=keys[2], amount=1_800_000, fee=0),
        SystemTxn(src=keys[2], dst=keys[3], amount=2_000_000, fee=0),
        SystemTxn(src=keys[4], dst=keys[5], amount=5, fee=7),
        SystemTxn(src=keys[6], dst=keys[7], amount=11, fee=0),
        SystemTxn(src=keys[8], dst=keys[8], amount=13, fee=0),
    ]
    # inject directly at the scheduler layer (the wire carries raw
    # payloads; here the partition itself is under test)
    bank.fanout.dispatch(txns, tag=[])
    per_tile_accts = []
    chain_frames = []
    from firedancer_tpu.disco.tiles import (_EXEC_HDR, _EXEC_TXN,
                                            _EXEC_TXN_SZ)
    for i in range(2):
        accts = set()
        frames, _ = _drain(rings[f"exec_disp{i}"])
        for frame, _sig in frames:
            ws, xid, cnt = _EXEC_HDR.unpack_from(frame, 0)
            off = _EXEC_HDR.size
            for _ in range(cnt):
                src = frame[off:off + 32]
                dst = frame[off + 32:off + 64]
                amt, _fee = _EXEC_TXN.unpack_from(frame, off + 64)
                accts |= {src, dst}
                if src in keys[:4]:
                    chain_frames.append((i, amt))
                off += _EXEC_TXN_SZ
        per_tile_accts.append(accts)
    assert per_tile_accts[0] and per_tile_accts[1]
    assert not (per_tile_accts[0] & per_tile_accts[1])
    # the whole chain went to ONE tile, in original order
    assert len({t for t, _ in chain_frames}) == 1
    assert [a for _, a in chain_frames] \
        == [900_000, 1_800_000, 2_000_000]
    for e in execs:
        e.poll_once()
    bank.poll_once()
    assert bank.fanout.wave is None
    oracle = {k: 1_000_000 for k in keys}
    execute_block_serial(oracle, txns)
    for k in keys:
        assert bank.funk.rec_query(None, k) == oracle[k]


def test_exec_tile_death_redispatch_drill(wksp):
    """Supervision drill, in-process: the exec tile 'dies' mid-wave —
    its dispatch frames are never executed (a supervised restart
    rejoins at the ring TAIL, skipping them) — so the bank times out,
    CANCELS the fork (store back to pre-wave state) and re-dispatches
    the whole wave under a fresh fork. The restarted tile abandons any
    stale frames it does see (cancelled fork -> no completion) and
    completes the fresh ones: exactly-once application, no wedge."""
    from firedancer_tpu.disco.tiles import ExecAdapter
    from firedancer_tpu.svm.executor import execute_block_serial
    from firedancer_tpu.tiles.synth import make_signed_txns
    genesis = _synth_genesis()
    bank, execs, rings = _mk_family(wksp, n_exec=2,
                                    redispatch_s=30.0,
                                    genesis=genesis)
    txns = make_signed_txns(12, seed=91)
    for i, f in enumerate(_microblocks(txns, per=6)):
        rings["pack_bank0"].publish(f, sig=i)
    bank.poll_once()
    assert bank.fanout.wave is not None
    xid1 = bank.fanout.wave["xid"]
    # tile 0 'dies': nobody drains exec_disp0. Tile 1 completes its
    # share — the wave must NOT publish on a partial completion set.
    execs[1].poll_once()
    bank.poll_once()
    assert bank.fanout.wave is not None \
        and bank.fanout.wave["xid"] == xid1
    # mid-wave store state is invisible at the root
    root0 = {bytes.fromhex(k): bank.funk.rec_query(
        None, bytes.fromhex(k)) for k in genesis}
    assert root0 == {bytes.fromhex(k): v for k, v in genesis.items()}
    # timeout (forced, no wall-clock flake) -> cancel + redispatch
    # under a fresh fork
    bank.fanout.wave["deadline"] = time.monotonic() - 1
    bank.poll_once()
    assert bank.m["exec_redispatch"] == 1
    assert bank.fanout.wave is not None \
        and bank.fanout.wave["xid"] != xid1
    assert not bank.funk.txn_is_prepared(xid1)
    # 'restart': fresh adapters from seq 0 — they see the STALE frames
    # first (cancelled fork -> abandoned, no completion), then the
    # fresh ones
    stale = 0
    for i in range(2):
        ctx = SimpleNamespace(
            tile_name=f"exec{i}r", plan=execs[i].ctx.plan, wksp=wksp,
            in_rings={f"exec_disp{i}": rings[f"exec_disp{i}"]},
            out_rings={f"exec_done{i}": rings[f"exec_done{i}"]},
            out_fseqs={f"exec_done{i}": []},
            in_seq0={})
        e = ExecAdapter(ctx, {"batch": 16})
        e.poll_once()
        stale += e.m["stale_xid"]
    assert stale >= 1      # cancelled-fork frames replayed, abandoned
    deadline = time.monotonic() + 10
    while bank.fanout.wave is not None \
            and time.monotonic() < deadline:
        bank.poll_once()
    assert bank.fanout.wave is None        # not wedged
    assert bank.m["exec_redispatch"] == 1
    # exactly-once: balances match ONE serial application
    all_t = []
    for f in _microblocks(txns, per=6):
        t, _ = bank._parse_transfers(f, struct.unpack_from(
            "<HHQQ", f)[1])
        all_t.extend(t)
    oracle = {bytes.fromhex(k): v for k, v in genesis.items()}
    execute_block_serial(oracle, all_t)
    for k, v in oracle.items():
        assert bank.funk.rec_query(None, k) == v
    # done + poh flushed exactly once per microblock
    assert rings["bank0_done"].seq == 2
    assert rings["bank0_poh"].seq == 2


# ---------------------------------------------------------------------------
# resolv tile
# ---------------------------------------------------------------------------

def _mk_resolv(wksp, funk_plan=None, **args):
    from firedancer_tpu.disco.tiles import ResolvAdapter
    links = {"dr": {"mtu": 1280}, "rp": {"mtu": 2048}}
    rings = {ln: Ring.create(wksp, depth=64, mtu=li["mtu"])
             for ln, li in links.items()}
    plan = {"links": links}
    if funk_plan:
        plan["funk"] = funk_plan
    ctx = SimpleNamespace(
        tile_name="resolv", plan=plan, wksp=wksp,
        in_rings={"dr": rings["dr"]}, out_rings={"rp": rings["rp"]},
        out_fseqs={"rp": []}, in_seq0={})
    return ResolvAdapter(ctx, args), rings


def test_resolv_resolved_frames_match_meta_from_payload(wksp):
    """For legacy txns the resolv tile's RESOLVED frame decodes (via
    pack's meta_from_resolved) to the SAME scheduling inputs
    meta_from_payload computes from the raw payload — account sets,
    cost, reward, vote flag."""
    from firedancer_tpu.pack.scheduler import (meta_from_payload,
                                               meta_from_resolved)
    from firedancer_tpu.tiles.synth import make_signed_txns
    tile, rings = _mk_resolv(wksp)
    assert tile.db is None and not tile.fee_check
    txns = make_signed_txns(8, seed=13)
    for i, p in enumerate(txns):
        rings["dr"].publish(p, sig=i)
    tile.poll_once()
    assert tile.m["resolved"] == len(txns)
    frames, _ = _drain(rings["rp"])
    assert len(frames) == len(txns)
    for (frame, _sig), payload in zip(frames, txns):
        got = meta_from_resolved(frame)
        want = meta_from_payload(payload)
        assert got.payload == want.payload == payload
        assert got.writes == want.writes
        assert got.reads == want.reads
        assert (got.cost, got.reward, got.is_vote) \
            == (want.cost, want.reward, want.is_vote)


def test_resolv_fee_payer_gate_and_junk(wksp):
    """With the shm store joined, a fee payer below the signature fee
    drops (fee_fail); funded payers pass; junk bytes count
    parse_fail."""
    from firedancer_tpu.pack.scheduler import FEE_PER_SIGNATURE
    from firedancer_tpu.tiles.synth import make_signed_txns
    st = Store(wksp, rec_max=512, txn_max=16, heap_sz=1 << 18)
    funk_plan = {"backend": "shm", "rec_max": 512, "txn_max": 16,
                 "heap_mb": 1, "off": st.off, "heap_sz": 1 << 18}
    tile, rings = _mk_resolv(wksp, funk_plan=funk_plan)
    assert tile.db is not None and tile.fee_check
    txns = make_signed_txns(4, seed=29)
    # fund the first two txns' fee payers only
    from firedancer_tpu.protocol.txn import parse_txn
    for p in txns[:2]:
        t = parse_txn(p)
        tile.db.funk.rec_write(None, t.account_keys(p)[0],
                               FEE_PER_SIGNATURE * 4)
    for i, p in enumerate(txns):
        rings["dr"].publish(p, sig=i)
    rings["dr"].publish(b"\x00junk", sig=99)
    tile.poll_once()
    assert tile.m["parse_fail"] == 1
    assert tile.m["resolved"] + tile.m["fee_fail"] == len(txns)
    assert tile.m["fee_fail"] >= 1
    frames, _ = _drain(rings["rp"])
    assert len(frames) == tile.m["resolved"]


# ---------------------------------------------------------------------------
# the full topology: sharded exec family, resolv ahead of pack,
# supervised exec restart under fire (the process-level drill)
# ---------------------------------------------------------------------------

def _family_topology(name, n=24, exec_cnt=2, chaos0=None,
                     redispatch_s=1.0):
    from firedancer_tpu.disco import Topology
    genesis = _synth_genesis()
    topo = (
        Topology(name, wksp_size=1 << 26,
                 funk={"backend": "shm", "heap_mb": 4})
        .link("ingest", depth=128, mtu=1280)
        .link("vd0", depth=128, mtu=1280)
        .link("dedup_resolv", depth=128, mtu=1280)
        .link("resolv_pack", depth=128, mtu=2048)
        .link("pack_bank0", depth=32, mtu=1 << 15)
        .link("bank0_done", depth=32, mtu=64)
        .link("bank0_poh", depth=64, mtu=1 << 16)
        .link("poh_entries", depth=256, mtu=(1 << 16) + 128)
        .link("poh_slots", depth=64, mtu=64)
        .tcache("vtc0", depth=4096).tcache("dedup_tc", depth=4096)
        # unique == count: every frame distinct (the deep tcaches would
        # dedup pool replays — this drill counts executed transfers,
        # not dedup behavior); signer seeds cycle mod 16, so the
        # 16-key genesis still funds every fee payer
        .tile("synth", "synth", outs=["ingest"], count=n, unique=n,
              seed=6)
        .tile("verify0", "verify", ins=["ingest"], outs=["vd0"],
              batch=16, tcache="vtc0")
        .tile("dedup", "dedup", ins=["vd0"], outs=["dedup_resolv"],
              tcache="dedup_tc")
        .tile("resolv", "resolv", ins=["dedup_resolv"],
              outs=["resolv_pack"], fee_payer_check=False)
        .tile("pack", "pack",
              ins=["resolv_pack", ("bank0_done", False),
                   ("poh_slots", False)],
              outs=["pack_bank0"], txn_in="resolv_pack",
              resolved_in=True, bank_links=["pack_bank0"],
              done_links=["bank0_done"], slot_in="poh_slots",
              max_txn_per_microblock=8, wave=4))
    disp = [f"exec_disp{i}" for i in range(exec_cnt)]
    done = [f"exec_done{i}" for i in range(exec_cnt)]
    for ln in disp:
        topo.link(ln, depth=64, mtu=4096)
    for ln in done:
        topo.link(ln, depth=64, mtu=64)
    topo.tile("bank0", "bank",
              ins=["pack_bank0"] + [(ln, False) for ln in done],
              outs=["bank0_done", "bank0_poh"] + disp,
              exec="svm", wave=4, poh_link="bank0_poh",
              exec_links=disp, exec_done=done, genesis=genesis,
              redispatch_s=redispatch_s)
    exec_args = {}
    if chaos0 is not None:
        exec_args["chaos"] = chaos0
        exec_args["supervise"] = {"policy": "restart",
                                  "backoff_s": 0.05,
                                  "max_restarts": 3, "window_s": 60.0}
    topo.sharded_tile("exec", "exec", exec_cnt, ins=[disp],
                      outs=done, batch=8, **exec_args)
    topo.tile("poh", "poh", ins=["bank0_poh"],
              outs=["poh_entries", "poh_slots"],
              slot_link="poh_slots", hashes_per_tick=16,
              ticks_per_slot=4)
    topo.tile("entsink", "sink", ins=["poh_entries"])
    return topo, genesis


def test_family_topology_builds_and_lints():
    """Topology-level wiring: sharded exec tiles get ONE dispatch ring
    each (per-shard ins distribution), topo.build carves the shm store
    into the plan, and the static linter accepts the model with zero
    errors."""
    topo, _ = _family_topology(f"efb{os.getpid()}", exec_cnt=2)
    for i in range(2):
        t = topo.tiles[f"exec{i}"]
        assert [x["link"] for x in t.ins] == [f"exec_disp{i}"]
        assert t.outs == [f"exec_done{i}"]
        assert t.args["rr_cnt"] == 2 and t.args["rr_idx"] == i
    from firedancer_tpu.lint.graph import lint_topology
    assert not [f for f in lint_topology(topo)
                if f.severity == "error"]
    plan = topo.build()
    try:
        assert plan["funk"]["backend"] == "shm"
        assert plan["funk"]["off"] > 0
        assert plan["funk"]["heap_sz"] == 4 << 20
    finally:
        from firedancer_tpu.runtime import Workspace as _W
        _W(plan["wksp"]["name"], plan["wksp"]["size"]).unlink()


@pytest.mark.slow
def test_exec_family_leader_loop_with_supervised_kill():
    """The process-level supervision drill: the full leader loop with
    resolv + exec_tile_cnt=2, where exec0 CRASHES mid-stream (seeded
    chaos) and the restart policy respawns it. The bank's redispatch
    path re-runs any wave the dead tile dropped: every funded transfer
    applies exactly once (balances match the serial oracle), the loop
    drains completely, and nobody wedges."""
    from firedancer_tpu.disco import TopologyRunner
    from firedancer_tpu.svm.executor import execute_block_serial
    n = 24
    topo, genesis = _family_topology(
        f"efk{os.getpid()}", n=n, exec_cnt=2,
        chaos0=[{"seed": 1, "events": [{"action": "crash",
                                        "at_rx": 1}]},
                None])
    runner = TopologyRunner(topo.build()).start()
    try:
        runner.wait_running(timeout_s=540)
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            runner.check_failures()
            b = runner.metrics("bank0")
            if b["transfers"] >= n and runner.metrics("poh")["mixins"] \
                    == b["microblocks"] and b["microblocks"] > 0:
                break
            time.sleep(0.05)
        b = runner.metrics("bank0")
        assert b["transfers"] == n and b["exec_fail"] == 0
        e0 = runner.metrics("exec0")
        assert e0["sup_restarts"] >= 1         # the drill actually fired
        assert runner.metrics("resolv")["resolved"] == n
        assert runner.metrics("pack")["inserted"] == n
    finally:
        runner.halt()
        runner.close()


@pytest.mark.slow
def test_exec_family_leader_loop_clean():
    """exec_tile_cnt=2, no faults: the full loop executes every funded
    transfer exactly once and BOTH exec shards carry traffic."""
    from firedancer_tpu.disco import TopologyRunner
    n = 24
    # generous redispatch: a cold exec tile's first wave can take
    # seconds on a loaded 1-core box, and this test asserts ZERO
    # redispatches — only the kill drill wants a twitchy deadline
    topo, _ = _family_topology(f"efc{os.getpid()}", n=n, exec_cnt=2,
                               redispatch_s=60.0)
    runner = TopologyRunner(topo.build()).start()
    try:
        runner.wait_running(timeout_s=540)
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            runner.check_failures()
            b = runner.metrics("bank0")
            if b["transfers"] >= n and runner.metrics("poh")["mixins"] \
                    == b["microblocks"] and b["microblocks"] > 0:
                break
            time.sleep(0.05)
        b = runner.metrics("bank0")
        assert b["transfers"] == n and b["exec_fail"] == 0
        assert b["exec_redispatch"] == 0
        ex = [runner.metrics(f"exec{i}") for i in range(2)]
        assert sum(e["txns"] for e in ex) >= n
        assert all(e["stale_xid"] == 0 for e in ex)
    finally:
        runner.halt()
        runner.close()


# ---------------------------------------------------------------------------
# the leader adapters' parameters: read once from the tile's args
# ---------------------------------------------------------------------------

def _args_plan(tag, args):
    """pack -> bank -> (exec family) wiring, built but never launched:
    the adapters are constructed in this process from the plan."""
    from firedancer_tpu.disco import Topology
    topo = (
        Topology(f"targs{tag}{os.getpid()}", wksp_size=1 << 24,
                 funk={"backend": "shm", "heap_mb": 1})
        .link("dedup_pack", depth=32, mtu=1280)
        .link("pack_bank0", depth=32, mtu=1 << 15)
        .link("bank0_done", depth=32, mtu=64)
        .link("exec_disp0", depth=32, mtu=4096)
        .link("exec_done0", depth=32, mtu=64)
        .tile("synth", "synth", outs=["dedup_pack"], count=8, unique=8)
        .tile("pack", "pack", ins=["dedup_pack", ("bank0_done", False)],
              outs=["pack_bank0"], txn_in="dedup_pack",
              bank_links=["pack_bank0"], done_links=["bank0_done"],
              **args.get("pack", {}))
        .tile("bank0", "bank", ins=["pack_bank0"], outs=["bank0_done"],
              exec="stub", **args.get("bank0", {}))
        .tile("dsynth", "synth", outs=["exec_disp0"], count=8, unique=8)
        .tile("exec0", "exec", ins=["exec_disp0"], outs=["exec_done0"],
              **args.get("exec0", {}))
        .tile("dsink", "sink", ins=["exec_done0"]))
    return topo.build()


# (tile, its arg, the adapter attribute it sets, the value; None = the
# arg left out, so the adapter's own default)
ARG_CASES = {
    "pack_wave": ("pack", "wave", "wave", 3),
    "pack_wave_wide": ("pack", "wave", "wave", 9),
    "pack_wave_default": ("pack", "wave", "wave", None),
    "bank_wave": ("bank0", "wave", "wave", 2),
    "bank_wave_wide": ("bank0", "wave", "wave", 11),
    "bank_wave_default": ("bank0", "wave", "wave", None),
    "exec_depth": ("exec0", "batch", "batch", 5),
    "exec_depth_wide": ("exec0", "batch", "batch", 13),
    "exec_depth_default": ("exec0", "batch", "batch", None),
}
ARG_DEFAULTS = {"pack": 4, "bank0": 8, "exec0": 8}


@pytest.mark.parametrize("tile,arg,attr,value", list(ARG_CASES.values()),
                         ids=list(ARG_CASES))
def test_leader_adapter_params_come_from_tile_args(tile, arg, attr,
                                                   value):
    """pack's and the bank's `wave` and the exec tile's dispatch depth
    are read from the plan's tile args at construction, and stay as
    read while the stem runs its housekeeping passes."""
    from firedancer_tpu.disco.stem import Stem
    from firedancer_tpu.disco.tiles import REGISTRY
    from firedancer_tpu.disco.topo import TileCtx
    plan = _args_plan(f"{tile}{value}",
                      {tile: {arg: value}} if value is not None else {})
    ctx = None
    try:
        ctx = TileCtx(plan, tile)
        spec = plan["tiles"][tile]
        adapter = REGISTRY[spec["kind"]](ctx, spec["args"])
        want = ARG_DEFAULTS[tile] if value is None else value
        assert getattr(adapter, attr) == want
        Stem(ctx, adapter, hk_interval_s=0.0).run(max_iters=8)
        assert getattr(adapter, attr) == want
    finally:
        if ctx is not None:
            ctx.close()
        Workspace.unlink_name(plan["wksp"]["name"])
