"""Front-door survival: the WIRED bulk RLC prefilter path and the
policed ingest tiles under adversarial traffic (ROADMAP item 4).

tests/test_rlc.py pins the RLC kernel's semantics (cofactored, the
torsion divergence class); this suite pins the TOPOLOGY WIRING on top:

  * a torsion-point batch that passes the naive cofactored equation is
    still rejected by the deployed prefilter -> strict-re-verify path
    (zero falsely-accepted frags),
  * an all-garbage forged-sig chunk is shed at MSM cost under ingest
    saturation, while a mixed chunk never loses legitimate traffic,
  * the sock/quic/gossip doors police hostile traffic (token buckets,
    bounded Sybil tables, malformed frames dying in the parser),
  * the chaos traffic plans flow through the stem -> on_chaos hook in
    a live topology.

The tier-1 half drives the wired path with a HOST-ARITHMETIC naive-RLC
oracle injected as the tile's _rlc_fn (the MSM graph's CPU compile is
~100 s/shape — the kernel itself is already pinned by test_rlc); the
`slow` half runs the identical drills through the real jitted kernel.
"""
import hashlib
import os
import socket
import time

import numpy as np
import pytest

from firedancer_tpu.runtime import Fseq, Ring, Tcache, Workspace
from firedancer_tpu.tiles.synth import SynthTile, make_signed_txns
from firedancer_tpu.utils import ed25519_ref as ref
from firedancer_tpu.utils.chaos import attack_frames

pytestmark = pytest.mark.flood

BATCH = 32          # matches test_verify_tile: one shared strict jit


@pytest.fixture(scope="module")
def wksp():
    w = Workspace(f"/fdtpu_fl_{os.getpid()}", 1 << 24)
    yield w
    w.close()
    w.unlink()


# -- host-arithmetic naive-RLC oracle ---------------------------------------

def _pt_neg(p):
    return (ref.P - p[0], p[1], p[2], ref.P - p[3])


def _pt_is_identity(p):
    zi = pow(p[2], ref.P - 2, ref.P)
    return (p[0] * zi % ref.P, p[1] * zi % ref.P) == (0, 1)


def host_rlc(sig, pub, msg, ln, z):
    """The naive cofactored RLC batch equation in reference
    arithmetic: sum_i z_i * ([S_i]B - [k_i]A_i - R_i) == identity,
    prechecked lanes only — verdict-compatible with
    ops/ed25519.rlc_verify_batch (which tests/test_rlc.py pins),
    including the torsion acceptance when z_i ≡ 0 (mod 8)."""
    sig, pub, msg = map(np.asarray, (sig, pub, msg))
    ln, z = np.asarray(ln), np.asarray(z)
    n = sig.shape[0]
    pre = np.zeros(n, bool)
    acc = (0, 1, 1, 0)
    for i in range(n):
        sb, pb = bytes(sig[i]), bytes(pub[i])
        m = bytes(msg[i, :int(ln[i])])
        s = int.from_bytes(sb[32:], "little")
        a = ref.pt_decompress(pb)
        r = ref.pt_decompress(sb[:32])
        pre[i] = (s < ref.L and a is not None and r is not None
                  and not ref.is_small_order(a)
                  and not ref.is_small_order(r))
        zi = int.from_bytes(bytes(z[i]), "little")
        if not pre[i] or not zi:
            continue
        k = int.from_bytes(
            hashlib.sha512(sb[:32] + pb + m).digest(), "little") % ref.L
        resid = ref.pt_add(
            ref.pt_mul(s, ref.BASEPOINT),
            ref.pt_add(ref.pt_mul(k, _pt_neg(a)), _pt_neg(r)))
        acc = ref.pt_add(acc, ref.pt_mul(zi, resid))
    return _pt_is_identity(acc), pre


def _mk_prefilter_tile(wksp, monkeypatch, rlc_fn=host_rlc, depth=128):
    """A bulk_prefilter VerifyTile wired to an injected RLC backend
    (warmup skipped — the injection replaces the lazy kernel resolve,
    everything downstream of _rlc_fn is the deployed path)."""
    from firedancer_tpu.tiles.verify import VerifyTile
    monkeypatch.setenv("FDTPU_VERIFY_SKIP_RLC_WARMUP", "1")
    in_ring = Ring.create(wksp, depth=depth, mtu=1280)
    out_ring = Ring.create(wksp, depth=depth, mtu=1280)
    tc = Tcache(wksp, depth=512)
    tile = VerifyTile(in_ring, out_ring, tc, batch=BATCH,
                      mode="bulk_prefilter")
    tile._rlc_fn = rlc_fn
    return tile, in_ring, out_ring


def _drain(tile):
    while tile.poll_once():
        pass
    tile.flush()


def _collect(out_ring):
    got, seq = [], 0
    while True:
        rc, frag = out_ring.consume(seq)
        if rc != 0:
            return got
        got.append(bytes(out_ring.payload(frag)))
        seq += 1


def _rig_z(tile, val=8):
    """Pin the z draw to a constant ≡ 0 (mod 8): the cofactored batch
    equation cannot see a pure-8-torsion residual under this draw —
    the strongest position an RLC-evasion attacker can be in."""
    def draw(n):
        z = np.zeros((n, 16), np.uint8)
        z[:, 0] = val
        return z
    tile._draw_z = draw


# -- the wired evasion path -------------------------------------------------

def test_torsion_batch_passes_naive_rlc_but_wired_path_rejects_all(
        wksp, monkeypatch):
    """THE acceptance drill: a torsion-point batch (R* = rB + T,
    S = r + k·a) passes the naive cofactored equation when the z draw
    cooperates — the deployed prefilter must still forward it to the
    strict kernel, which rejects every lane. Zero falsely-accepted
    frags, no shedding of the batch (it LOOKED clean)."""
    tile, in_ring, out_ring = _mk_prefilter_tile(wksp, monkeypatch)
    _rig_z(tile)
    tile._hot_until = 1 << 62   # saturation window: the filter engages
    frames = attack_frames("flood_torsion", 8, seed=21)
    assert len(set(frames)) == 8
    # oracle sanity: under the rigged draw the naive equation ACCEPTS
    # the torsion batch — this is exactly the evasion being attempted
    for i, f in enumerate(frames):
        in_ring.publish(f, sig=i)
    _drain(tile)
    m = tile.metrics
    assert m["rlc_batches"] >= 1 and m["rlc_pass"] >= 1, \
        "the evasion batch must PASS the naive prefilter equation"
    assert m["rlc_shed"] == 0          # it looked clean: no shedding
    assert m["verify_fail"] == 8       # strict caught every lane
    assert m["tx"] == 0
    assert _collect(out_ring) == []    # zero falsely-accepted frags

    # and the same rigged tile still forwards honest traffic
    txns = make_signed_txns(6, seed=31)
    SynthTile(in_ring, txns).run(len(txns))
    _drain(tile)
    assert tile.metrics["tx"] == 6
    assert _collect(out_ring) == txns


def test_forged_flood_sheds_garbage_chunks_mixed_never_collateral(
        wksp, monkeypatch):
    """Forged-sig flood under ingest saturation: an all-garbage chunk
    sheds at (oracle) MSM cost without a strict dispatch; a chunk
    shared with honest traffic always proceeds to strict and the
    honest txns land."""
    tile, in_ring, out_ring = _mk_prefilter_tile(wksp, monkeypatch)
    forged = attack_frames("flood_forged", 8, seed=3)
    for i, f in enumerate(forged):
        in_ring.publish(f, sig=i)
    tile._hot_until = 1 << 62          # saturation window forced open
    _drain(tile)
    m = tile.metrics
    assert m["rlc_shed"] == 8, "all-garbage chunk must shed whole"
    assert m["tx"] == 0 and _collect(out_ring) == []
    shed_before = m["rlc_shed"]

    # mixed chunk: forged + honest gathered together
    txns = make_signed_txns(4, seed=41)
    for i, f in enumerate(attack_frames("flood_forged", 4, seed=5)):
        in_ring.publish(f, sig=100 + i)
    SynthTile(in_ring, txns).run(len(txns))
    tile._hot_until = 1 << 62
    _drain(tile)
    assert tile.metrics["rlc_shed"] == shed_before, \
        "a mixed chunk must never shed (bisect saw a clean half)"
    assert tile.metrics["tx"] == 4
    assert _collect(out_ring) == txns

    # off-hot (peacetime): a sub-full chunk skips the equation
    # entirely and the garbage dies in the strict kernel as usual —
    # fail-closed, nothing shed, the filter idle
    lanes_before = tile.metrics["rlc_lanes"]
    for i, f in enumerate(attack_frames("flood_forged", 8, seed=7)):
        in_ring.publish(f, sig=200 + i)
    tile._hot_until = 0
    _drain(tile)
    assert tile.metrics["rlc_shed"] == shed_before
    assert tile.metrics["rlc_lanes"] == lanes_before   # filter idle
    assert tile.metrics["tx"] == 4     # nothing new forwarded


def test_prefilter_saturation_is_the_gathers_not_a_chunks(
        wksp, monkeypatch):
    """Dispatched in chunks narrower than the batch (the Pallas path's
    width): a full chunk from a sub-full gather in peacetime skips the
    equation, as a sub-full batch does; a full gather gates each of its
    chunks, each equation padded to the width, not the batch."""
    import jax.numpy as jnp
    width = 8
    widths = []

    def rlc(sig, *rest):
        widths.append(len(sig))
        return host_rlc(sig, *rest)
    tile, in_ring, out_ring = _mk_prefilter_tile(wksp, monkeypatch,
                                                 rlc_fn=rlc)
    monkeypatch.setattr(tile, "_counted", True)
    monkeypatch.setattr(tile, "_width", width)
    monkeypatch.setattr(tile, "_bufsets", tile._stage_sets())
    monkeypatch.setattr(tile, "_bufset_fut", [None] * len(tile._bufsets))
    monkeypatch.setattr(tile, "_fn", lambda flat, n: jnp.zeros(width, bool))
    for i, f in enumerate(attack_frames("flood_forged", width, seed=11)):
        in_ring.publish(f, sig=i)
    tile._hot_until = 0
    _drain(tile)
    m = tile.metrics
    assert m["batches"] == 1 and m["lanes"] == width   # one full chunk
    assert m["rlc_lanes"] == 0 and m["rlc_shed"] == 0  # filter idle
    assert m["verify_fail"] == width

    for i, f in enumerate(attack_frames("flood_forged", BATCH, seed=13,
                                         pool=BATCH)):
        in_ring.publish(f, sig=100 + i)
    _drain(tile)                       # one full gather: 4 chunks
    assert m["rlc_lanes"] == BATCH and m["rlc_shed"] == BATCH
    assert m["batches"] == 1           # every chunk shed, none strict
    assert widths and set(widths) == {width}
    assert m["tx"] == 0 and _collect(out_ring) == []


def test_duplicate_storm_earns_no_device_work(wksp, monkeypatch):
    """flood_dup: one valid txn replayed — every copy past the first
    dies in ha-dedup / the in-flight reservation, and the storm never
    fills a chunk, so the prefilter stays idle too."""
    tile, in_ring, out_ring = _mk_prefilter_tile(wksp, monkeypatch)
    frames = attack_frames("flood_dup", 64, seed=9)
    assert len(set(frames)) == 1
    for i, f in enumerate(frames):
        in_ring.publish(f, sig=i)
    _drain(tile)
    assert tile.metrics["tx"] == 1
    assert tile.metrics["dedup_drop"] == 63
    assert tile.metrics["rlc_lanes"] <= 2


# -- sock door --------------------------------------------------------------

def _send_from(socks, port, payload=b"x" * 64, rounds=1):
    for _ in range(rounds):
        for s in socks:
            s.sendto(payload, ("127.0.0.1", port))


def _drain_sock(tile, spins=200):
    tot = 0
    for _ in range(spins):
        n = tile.poll_once()
        tot += n
        if not n:
            time.sleep(0.002)
    return tot


def test_sock_batch_grain_bytes_exact_and_credit_bounded(wksp):
    """r14 satellite: the sock tile drains a burst into ONE
    publish_batch — frames land byte-identical and in order, jumbos
    drop, and with no shed policy a full ring leaves packets in the
    kernel buffer (the seed behavior)."""
    from firedancer_tpu.tiles.sock import SockTile
    out = Ring.create(wksp, depth=8, mtu=512)
    fseq = Fseq(wksp)
    tile = SockTile(out, [fseq], port=0, batch=16, mtu=256)
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    frames = [bytes([i]) * (20 + i) for i in range(6)]
    for f in frames:
        tx.sendto(f, ("127.0.0.1", tile.port))
    tx.sendto(b"J" * 300, ("127.0.0.1", tile.port))   # jumbo: dropped
    deadline = time.monotonic() + 5
    while tile.metrics["rx"] < 6 and time.monotonic() < deadline:
        tile.poll_once()
        time.sleep(0.002)
    assert tile.metrics["rx"] == 6
    assert tile.metrics["oversz"] == 1
    assert _collect(out) == frames     # byte-exact, in order

    # ring full + consumer frozen + no shed: backpressure counts,
    # packets stay queued in the kernel (nothing lost, nothing wedged)
    for i in range(12):
        tx.sendto(b"q%d" % i, ("127.0.0.1", tile.port))
    deadline = time.monotonic() + 5
    while tile.metrics["rx"] < 8 and time.monotonic() < deadline:
        tile.poll_once()
        time.sleep(0.002)
    assert tile.metrics["rx"] == 8     # depth 8, fseq never advanced
    tile.poll_once()                   # one poll against the full ring
    assert tile.metrics["backpressure"] > 0
    fseq.update(6)                     # consumer catches up
    deadline = time.monotonic() + 5
    while tile.metrics["rx"] < 14 and time.monotonic() < deadline:
        tile.poll_once()
        time.sleep(0.002)
    assert tile.metrics["rx"] == 14    # kernel queue preserved the rest
    tile.close()
    tx.close()


def test_sock_shed_flood_drops_newest_staked_lands(wksp):
    """Forged-sig flood drill at the sock door: with the shed armed, a
    full ring drain-and-DROPS hostile bursts (never wedges, never
    grows), shed counters tick, and a staked peer's traffic still
    lands once pressure clears."""
    from firedancer_tpu.tiles.sock import SockTile
    staked_tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    staked_tx.bind(("127.0.0.1", 0))
    skey = f"127.0.0.1:{staked_tx.getsockname()[1]}"
    out = Ring.create(wksp, depth=16, mtu=512)
    fseq = Fseq(wksp)
    tile = SockTile(out, [fseq], port=0, batch=16, mtu=256,
                    shed={"rate_pps": 500.0, "burst": 4,
                          "max_peers": 8, "min_stake": 1,
                          "overload_hold_s": 5.0,
                          "stakes": {skey: 1000}})
    floods = []
    for i in range(20):
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.bind(("127.0.0.1", 0))
        floods.append(s)
    _send_from(floods, tile.port, rounds=8)     # 160 hostile datagrams
    _drain_sock(tile, spins=400)
    m = dict(tile.metrics)             # snapshot (metrics is live)
    assert m["shed"] > 0, "flood must tick shed counters"
    assert m["peers"] <= 8             # bounded Sybil table
    # ring full (depth 16, frozen consumer) + shed armed: overload
    # trips and everything arriving is dropped-newest at the door
    assert m["overload"] == 1
    before = m["rx"]
    _send_from(floods, tile.port, rounds=4)
    _drain_sock(tile, spins=200)
    assert tile.metrics["rx"] == before          # nothing admitted
    assert tile.metrics["shed"] > m["shed"]      # ...everything counted
    # consumer drains -> credits return; the STAKED peer (token budget
    # intact, above min_stake) lands through the still-open overload
    fseq.update(16)
    for i in range(3):
        staked_tx.sendto(b"staked-%d" % i, ("127.0.0.1", tile.port))
    deadline = time.monotonic() + 5
    while tile.metrics["rx"] < before + 3 \
            and time.monotonic() < deadline:
        tile.poll_once()
        time.sleep(0.002)
    assert tile.metrics["rx"] >= before + 3
    payloads = []
    seq = before                       # the flood's 16 filled the ring
    while True:
        rc, frag = out.consume(seq)
        if rc != 0:
            break
        payloads.append(bytes(out.payload(frag)))
        seq += 1
    assert b"staked-0" in payloads and b"staked-2" in payloads
    tile.close()
    staked_tx.close()
    for s in floods:
        s.close()


def test_sock_staked_waiting_room_survives_full_door(wksp):
    """A garbage burst that saturates the ring must not take the
    staked trickle down with it: staked datagrams caught in the full
    door's drain-and-drop park in the bounded waiting room (memory
    O(batch*mtu)) and re-enter through the normal admission gate when
    credits return, in arrival order; unstaked burst-mates are
    dropped-newest as before."""
    from firedancer_tpu.tiles.sock import SockTile
    staked_tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    staked_tx.bind(("127.0.0.1", 0))
    skey = f"127.0.0.1:{staked_tx.getsockname()[1]}"
    out = Ring.create(wksp, depth=8, mtu=512)
    fseq = Fseq(wksp)
    tile = SockTile(out, [fseq], port=0, batch=8, mtu=256,
                    shed={"rate_pps": 500.0, "burst": 16,
                          "max_peers": 8, "min_stake": 1,
                          "overload_hold_s": 30.0,
                          "stakes": {skey: 1000}})
    junk_tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    junk_tx.bind(("127.0.0.1", 0))
    # saturate the ring against a frozen consumer
    for i in range(8):
        junk_tx.sendto(b"fill-%d" % i, ("127.0.0.1", tile.port))
    _drain_sock(tile, spins=100)
    assert tile.metrics["rx"] == 8
    # full door: the staked trickle arrives mixed into a junk burst
    for i in range(3):
        staked_tx.sendto(b"held-%d" % i, ("127.0.0.1", tile.port))
        junk_tx.sendto(b"junk-%d" % i, ("127.0.0.1", tile.port))
    _drain_sock(tile, spins=100)
    assert tile.metrics["rx"] == 8             # ring still full
    assert len(tile._staked_hold) == 3, "staked must park, not drop"
    assert tile.metrics["shed"] >= 3           # junk dropped-newest
    # hold is BOUNDED at batch frames whatever the staked peer sends
    for i in range(2 * tile.batch):
        staked_tx.sendto(b"over-%d" % i, ("127.0.0.1", tile.port))
    _drain_sock(tile, spins=200)
    assert len(tile._staked_hold) <= tile.batch
    # credits return: the waiting room drains FIRST, byte-exact and
    # in arrival order, through the same admission gate
    fseq.update(8)
    deadline = time.monotonic() + 5
    while tile.metrics["rx"] < 11 and time.monotonic() < deadline:
        tile.poll_once()
        time.sleep(0.002)
    payloads = []
    seq = 8
    while True:
        rc, frag = out.consume(seq)
        if rc != 0:
            break
        payloads.append(bytes(out.payload(frag)))
        seq += 1
    assert payloads[:3] == [b"held-0", b"held-1", b"held-2"]
    tile.close()
    staked_tx.close()
    junk_tx.close()


# -- quic door --------------------------------------------------------------

def test_quic_malformed_flood_dies_in_parser_zero_txns(wksp):
    """flood_malformed_quic: garbage wearing QUIC long headers must
    die as bad_pkts — never a crash, never a published txn frag — and
    the Sybil source addresses stay inside the bounded peer table."""
    pytest.importorskip("cryptography")
    from firedancer_tpu.tiles.quic import QuicTile
    out = Ring.create(wksp, depth=64, mtu=1280)
    tile = QuicTile(out, [], port=0, batch=16,
                    shed={"rate_pps": 1000.0, "max_peers": 8})
    frames = attack_frames("flood_malformed_quic", 48, seed=13)
    for i, f in enumerate(frames):
        tile.inject(f, (f"203.0.113.{i % 32 + 1}", 4000 + i))
    tile.poll_once()                   # flush server metrics
    m = tile.metrics
    assert m["txns"] == 0              # zero falsely-accepted frags
    assert m["bad_pkts"] > 0 or m["shed"] > 0
    assert m["peers"] <= 8
    assert _collect(out) == []
    tile.close()


# -- gossip door ------------------------------------------------------------

def test_crds_spam_bounded_table_and_overload_shed(wksp):
    """flood_crds_spam: validly signed values from throwaway unstaked
    origins. The second policing axis (CRDS sender identity) keeps the
    peer table bounded, and overload sheds the spam at the door while
    a staked origin still lands."""
    from firedancer_tpu.tiles.gossip import GossipTile
    staked_seed = hashlib.sha256(b"staked-origin").digest()
    _, _, staked_pub = ref.keypair(staked_seed)
    tile = GossipTile(
        hashlib.sha256(b"node").digest(), port=0,
        shed={"rate_pps": 1000.0, "burst": 64, "max_peers": 8,
              "min_stake": 1, "overload_hold_s": 30.0,
              "stakes": {staked_pub.hex(): 500,
                         "127.0.0.1:65000": 500}})
    spam = attack_frames("flood_crds_spam", 24, seed=17)
    for i, d in enumerate(spam):
        tile.inject(d, (f"198.51.100.{i % 16 + 1}", 3000 + i))
    assert tile.shed.counters()["peers"] <= 8    # bounded Sybil table
    values_peacetime = len(tile.node.crds.values)
    assert values_peacetime > 0        # peacetime: spam is admitted...

    tile.shed.trip_overload()          # ...until pressure trips
    more = attack_frames("flood_crds_spam", 24, seed=18)
    shed0 = tile.shed.shed_total
    for i, d in enumerate(more):
        tile.inject(d, (f"198.51.100.{i % 16 + 101}", 5000 + i))
    assert tile.shed.shed_total > shed0
    assert len(tile.node.crds.values) == values_peacetime, \
        "overloaded door must not grow the CRDS store with spam"
    assert tile.shed.counters()["peers"] <= 8

    # the staked origin's validly signed value still lands, from a
    # staked socket address, through the same overloaded door
    from firedancer_tpu.flamenco import gossip_wire as gw
    from firedancer_tpu.gossip.crds import CrdsValue, KIND_NODE_INSTANCE
    data = staked_pub + (1).to_bytes(8, "little") + b"\x07" * 16
    v = CrdsValue(staked_pub, KIND_NODE_INSTANCE, 0, 1, data)
    sv = CrdsValue(staked_pub, KIND_NODE_INSTANCE, 0, 1, data,
                   ref.sign(staked_seed, v.signable()))
    pkt = gw.encode_container(gw.MSG_PUSH, staked_pub, [sv.to_wire()])
    tile.inject(pkt, ("127.0.0.1", 65000))
    assert len(tile.node.crds.values) == values_peacetime + 1
    tile.close()


def test_repair_door_polices_requests_and_responses():
    """The repair port is internet-facing too (r16): every datagram —
    signed request or shred response — pays one PeerGate admission
    BEFORE the ed25519 verify / shred parse, so a flood dies at the
    cheapest layer; out-ring backpressure trips stake-weighted
    overload and a staked repair peer still lands through the
    overloaded door."""
    from firedancer_tpu.repair.policy import REQ_LEN
    from firedancer_tpu.shred import format as fmt
    from firedancer_tpu.tiles.repair import RepairCore
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.bind(("127.0.0.1", 0))
    sock.setblocking(False)
    core = RepairCore(
        b"\x01" * 32, lambda p: None, sock,
        shed={"rate_pps": 1000.0, "burst": 8, "max_peers": 8,
              "min_stake": 1, "overload_hold_s": 30.0,
              "stakes": {"127.0.0.1:65001": 500}})
    junk_req = hashlib.sha256(b"junk").digest() * 3  # 96B payload..
    junk_req = (junk_req + junk_req)[:REQ_LEN + 64]  # ..+ garbage sig
    # peacetime Sybil flood: admitted per-peer, dies in sigverify as
    # reqs_refused, and the table never exceeds max_peers
    for i in range(64):
        core.on_datagram(junk_req, (f"203.0.113.{i % 32 + 1}", 4000 + i))
    assert core.metrics["reqs_refused"] == 64
    assert core.shed.counters()["peers"] <= 8
    # pressure trips: the same flood now sheds AT THE DOOR — the
    # refused counter freezes because no signature verify ever runs
    core.shed.trip_overload()
    refused0 = core.metrics["reqs_refused"]
    shed0 = core.shed.shed_total
    for i in range(64):
        core.on_datagram(junk_req, (f"198.51.100.{i % 32 + 1}", 7000 + i))
    assert core.metrics["reqs_refused"] == refused0
    assert core.shed.shed_total >= shed0 + 64
    # shred-sized response spam from an unstaked peer: shed the same
    # way, never counted as a response
    resp = b"\x00" * fmt.SHRED_MIN_SZ
    assert core.on_datagram(resp, ("9.9.9.9", 1)) == 0
    assert core.metrics["resps_in"] == 0
    # the staked repair peer's response still lands
    assert core.on_datagram(resp, ("127.0.0.1", 65001)) == 1
    assert core.metrics["resps_in"] == 1
    sock.close()


def test_repair_backpressure_trips_overload():
    """A stalled FEC-resolver consumer (zero out-ring credits) must
    latch the repair door into overload — the same pressure->shed
    coupling the sock door has, on the response-forward path."""
    from firedancer_tpu.shred import format as fmt
    from firedancer_tpu.tiles.repair import RepairCore

    class _StubRing:
        def __init__(self):
            self.calls = 0
            self.pub = []

        def credits(self, fseqs):
            self.calls += 1
            return 0 if self.calls == 1 else 1   # stalled, then drained

        def publish(self, data, sig=0):
            self.pub.append(bytes(data))

    ring = _StubRing()
    core = RepairCore(
        b"\x02" * 32, lambda p: None, sock=None,
        out_ring=ring, out_fseqs=[object()],
        shed={"rate_pps": 1000.0, "burst": 64, "max_peers": 8,
              "min_stake": 1, "overload_hold_s": 30.0})
    assert not core.shed.overloaded()
    resp = b"\x00" * fmt.SHRED_MIN_SZ
    assert core.on_datagram(resp, ("10.0.0.7", 9)) == 1
    assert core.shed.overloaded()        # pressure latched the door
    assert len(ring.pub) == 1            # ...but the response still went


def test_repair_adapter_declares_shed_slots_and_lint_allows():
    """The adapter exports the shed counters as metric slots (the
    prometheus renderer + flood bench judge off them) and fdlint's
    dead-config check knows repair has an ingest door to police."""
    from firedancer_tpu.disco.tiles import RepairAdapter
    from firedancer_tpu.lint.graph import SHED_KINDS
    assert {"shed", "shed_unstaked", "peers",
            "overload"} <= set(RepairAdapter.METRICS)
    assert {"peers", "overload"} <= set(RepairAdapter.GAUGES)
    assert "repair" in SHED_KINDS


# -- gossvf bulk mode -------------------------------------------------------

def test_gossvf_bulk_wiring_matches_individual(monkeypatch):
    """mode='bulk' verdicts == mode='individual' verdicts for both an
    all-valid packet (bulk accept) and a packet with a corrupt value
    (bulk equation fails -> strict re-verify of survivors)."""
    from firedancer_tpu.gossip import gossvf
    from firedancer_tpu.gossip.crds import CrdsValue, KIND_NODE_INSTANCE

    def oracle(sig, pub, msg, ln, z):
        ok, pre = host_rlc(sig, pub, msg, ln, z)
        return np.bool_(ok), pre
    monkeypatch.setattr(gossvf, "_RLC_FN", oracle)

    vals = []
    for i in range(4):
        seed = hashlib.sha256(b"gv-%d" % i).digest()
        _, _, pub = ref.keypair(seed)
        data = pub + i.to_bytes(8, "little") + bytes(8)
        v = CrdsValue(pub, KIND_NODE_INSTANCE, 0, i, data)
        vals.append(CrdsValue(pub, KIND_NODE_INSTANCE, 0, i, data,
                              ref.sign(seed, v.signable())))
    assert gossvf.batch_verify(vals, mode="bulk") == [True] * 4
    # corrupt one signature: bulk must fall back to strict and agree
    bad = CrdsValue(vals[1].origin, KIND_NODE_INSTANCE, 0, 1,
                    vals[1].data, b"\x01" * 64)
    mixed = [vals[0], bad, vals[2]]
    assert gossvf.batch_verify(mixed, mode="bulk") \
        == gossvf.batch_verify(mixed, mode="individual") \
        == [True, False, True]
    with pytest.raises(ValueError, match="unknown gossvf mode"):
        gossvf.batch_verify(vals, mode="warp")


# -- traffic plans through a live topology ----------------------------------

def test_synth_attack_plan_floods_through_stem_hook():
    """A seeded traffic plan on the synth tile: the stem records the
    injection (EV_CHAOS with the flood action id) and the on_chaos
    hook floods the rendered frames into the out ring — the sink sees
    legit traffic + the attack burst."""
    from firedancer_tpu.disco import Topology, TopologyRunner
    n, frames = 64, 48
    topo = (
        Topology(f"atk{os.getpid()}", wksp_size=1 << 22,
                 trace={"enable": True, "depth": 512, "sample": 1})
        .link("a_b", depth=256, mtu=1280)
        .tile("a", "synth", outs=["a_b"], count=n, unique=16, burst=16,
              chaos={"events": [{"action": "flood_dup", "at_iter": 4,
                                 "frames": frames, "seed": 5}]})
        .tile("b", "sink", ins=["a_b"]))
    runner = TopologyRunner(topo.build()).start()
    try:
        runner.wait_running(timeout_s=60)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            runner.check_failures()
            # the synth's counters reach shm at its next housekeeping
            # flush, which may trail the sink's
            a = runner.metrics("a")
            if runner.metrics("b")["rx"] >= n + frames \
                    and a["attack_tx"] + a["attack_drop"] == frames:
                break
            time.sleep(0.02)
        assert a["attack_tx"] + a["attack_drop"] == frames
        assert runner.metrics("b")["rx"] >= n + a["attack_tx"]
        # the injection is on the flight recorder, named
        from firedancer_tpu.trace import read_rings
        from firedancer_tpu.trace.events import CHAOS_ACTION_IDS
        evs = read_rings(runner.plan, runner.wksp)["a"]
        chaos = [e for e in evs if e["ev"] == "chaos"]
        assert chaos and chaos[0]["count"] == \
            CHAOS_ACTION_IDS["flood_dup"]
    finally:
        runner.halt()
        runner.close()


# -- the real kernel (slow) -------------------------------------------------

@pytest.mark.slow
def test_real_kernel_prefilter_flood_and_torsion(wksp, monkeypatch):
    """The identical torsion + forged-flood drills through the REAL
    jitted RLC kernel (CPU limb kernel here, Pallas MSM on
    accelerators) — pinning that the host oracle the tier-1 half used
    is verdict-faithful to the deployed kernel on the wired path."""
    monkeypatch.setenv("FDTPU_VERIFY_SKIP_RLC_WARMUP", "1")
    from firedancer_tpu.tiles.verify import VerifyTile
    in_ring = Ring.create(wksp, depth=128, mtu=1280)
    out_ring = Ring.create(wksp, depth=128, mtu=1280)
    tc = Tcache(wksp, depth=512)
    tile = VerifyTile(in_ring, out_ring, tc, batch=16,
                      mode="bulk_prefilter")
    _rig_z(tile)
    tile._hot_until = 1 << 62
    for i, f in enumerate(attack_frames("flood_torsion", 8, seed=21)):
        in_ring.publish(f, sig=i)
    _drain(tile)
    assert tile.metrics["rlc_pass"] >= 1     # naive equation evaded
    assert tile.metrics["verify_fail"] == 8  # strict caught all
    assert tile.metrics["tx"] == 0 and _collect(out_ring) == []

    tile._draw_z = VerifyTile._draw_z.__get__(tile)   # honest draw back
    for i, f in enumerate(attack_frames("flood_forged", 8, seed=3)):
        in_ring.publish(f, sig=100 + i)
    tile._hot_until = 1 << 62
    _drain(tile)
    assert tile.metrics["rlc_shed"] == 8
    assert tile.metrics["tx"] == 0

    txns = make_signed_txns(4, seed=51)
    SynthTile(in_ring, txns).run(len(txns))
    _drain(tile)
    assert tile.metrics["tx"] == 4
    assert _collect(out_ring) == txns
