"""P2 horizontal sharding tests (ref: src/disco/verify/fd_verify_tile.c:
49-53 — N verify tiles round-robin one ingest link by seq % cnt — and
the TPU-native form: shard_map over the device mesh inside one tile)."""
import os

import pytest

pytestmark = pytest.mark.slow

from firedancer_tpu.disco import Topology, TopologyRunner

N = 32


def test_two_verify_tiles_round_robin_one_link():
    """Both verify tiles consume the SAME ingest link; ownership is
    disjoint by seq parity; dedup fans both outs into one stream. Every
    unique txn arrives exactly once — nothing dropped, nothing doubled."""
    topo = (
        Topology(f"rr{os.getpid()}", wksp_size=1 << 24)
        .link("ingest", depth=64, mtu=1280)
        .link("v0_dedup", depth=64, mtu=1280)
        .link("v1_dedup", depth=64, mtu=1280)
        .link("dedup_sink", depth=128, mtu=1280)
        .tcache("v0_tc", depth=4096)
        .tcache("v1_tc", depth=4096)
        .tcache("dedup_tc", depth=4096)
        .tile("synth", "synth", outs=["ingest"], count=N, unique=N, seed=11)
        .tile("v0", "verify", ins=["ingest"], outs=["v0_dedup"],
              batch=16, tcache="v0_tc", rr_cnt=2, rr_idx=0)
        .tile("v1", "verify", ins=["ingest"], outs=["v1_dedup"],
              batch=16, tcache="v1_tc", rr_cnt=2, rr_idx=1)
        .tile("dedup", "dedup", ins=["v0_dedup", "v1_dedup"],
              outs=["dedup_sink"], tcache="dedup_tc")
        .tile("sink", "sink", ins=["dedup_sink"])
    )
    runner = TopologyRunner(topo.build()).start()
    try:
        runner.wait_running(timeout_s=540)
        runner.wait_idle("sink", "rx", N, timeout_s=540)
        # shm metrics flush one housekeeping interval behind the frag
        # flow — poll the upstream counters, don't snapshot
        runner.wait_idle("dedup", "rx", N, timeout_s=60)
        v0, v1 = runner.metrics("v0"), runner.metrics("v1")
        # disjoint ownership: each tile verified its share, no overlap
        assert v0["tx"] + v1["tx"] == N
        assert v0["tx"] > 0 and v1["tx"] > 0, (v0, v1)
        assert v0["verify_fail"] == 0 and v1["verify_fail"] == 0
        d = runner.metrics("dedup")
        assert d["rx"] == N and d["dup"] == 0 and d["tx"] == N
        assert runner.metrics("sink")["rx"] == N
    finally:
        runner.halt()
        runner.close()


def test_verify_tile_shard_map_multidevice():
    """One verify tile sharding its batch over the 8-device virtual CPU
    mesh (conftest forces xla_force_host_platform_device_count=8):
    verdicts must match the single-device kernel exactly."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device platform")
    import numpy as np

    from firedancer_tpu.runtime import Ring, Tcache, Workspace
    from firedancer_tpu.tiles.synth import make_signed_txns
    from firedancer_tpu.tiles.verify import VerifyTile

    w = Workspace(f"/fdtpu_sh{os.getpid()}", 1 << 23)
    try:
        in_ring = Ring.create(w, depth=64, mtu=1280)
        out_ring = Ring.create(w, depth=64, mtu=1280)
        tc = Tcache(w, depth=4096)
        tile = VerifyTile(in_ring, out_ring, tc, batch=16,
                          devices=len(jax.devices()))
        assert tile.devices >= 2
        assert tile._width == 16          # the mesh dispatches the batch
        txns = make_signed_txns(12, seed=3)
        for i, t in enumerate(txns):
            in_ring.publish(t, sig=i)
        # corrupt one more txn's signature: the sharded kernel must
        # reject it on whichever device shard it lands
        bad = bytearray(txns[0])
        bad[10] ^= 1
        in_ring.publish(bytes(bad), sig=99)
        got = 0
        for _ in range(8):
            got += tile.poll_once()
            if got >= 13:
                break
        # r5 async pipelining: verdicts publish at drain, not inside
        # poll_once — retire every in-flight batch before asserting
        tile.flush()
        assert tile.metrics["tx"] == 12
        # the corrupted copy fails verify (same first-sig tag would have
        # been dedup-dropped only AFTER verify; corruption hits earlier)
        assert tile.metrics["verify_fail"] + tile.metrics["dedup_drop"] >= 1
        n, _, buf, sizes, sigs, _ = out_ring.gather(0, 32, 1280)
        assert n == 12
    finally:
        w.close()
        w.unlink()
