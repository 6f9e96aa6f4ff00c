"""Staged device transfers + adaptive microbatch coalescing (r10).

Three contracts from the kernel<->pipeline gap work:

  * double-buffered staging: each dispatch is ONE transfer of the
    packed staging buffer, and dispatching batch k+1 never blocks on
    batch k's readback (scripted-future fake backend, the same style
    as the chaos degraded-path tests);
  * coalescing window: sub-full gathers are held until the lane budget
    fills, the deadline expires, or ingest idles with nothing in
    device flight — and held frags are never dropped or reordered;
  * drain-on-idle: batches already in device flight retire when ingest
    goes quiet mid-coalesce (queued verdicts never wait on traffic).
"""
import os

import numpy as np
import pytest

from firedancer_tpu.runtime import Ring, Tcache, Workspace
from firedancer_tpu.tiles.synth import make_signed_txns
from firedancer_tpu.tiles.verify import VerifyTile

pytestmark = pytest.mark.coalesce

BATCH = 16


@pytest.fixture(scope="module")
def wksp():
    w = Workspace(f"/fdtpu_co_{os.getpid()}", 1 << 24)
    yield w
    w.close()
    w.unlink()


@pytest.fixture(scope="module")
def txns():
    return make_signed_txns(24, seed=3)


@pytest.fixture(scope="module")
def _shared_tile(wksp):
    """ONE compiled VerifyTile for the whole module (tile warmup
    traces+compiles the packed verify jit — ~30 s on the 1-core CI
    box; per-test tiles would blow the tier-1 budget). Tests get it
    re-pointed at fresh rings/tcache via _mk_tile."""
    tile = VerifyTile(Ring.create(wksp, depth=256, mtu=1280),
                      Ring.create(wksp, depth=256, mtu=1280),
                      Tcache(wksp, depth=512), batch=BATCH,
                      coalesce_us=1.0)        # hold buffer allocated
    tile._real_fn = tile._fn
    return tile


def _mk_tile(wksp, coalesce_us: float = 0.0, _tile=None, **kw):
    """Reset the shared tile onto fresh rings + tcache with the given
    coalescing window — state-equivalent to a new VerifyTile without
    re-tracing the jit."""
    from collections import deque
    tile = _tile
    in_ring = Ring.create(wksp, depth=256, mtu=1280)
    out_ring = Ring.create(wksp, depth=256, mtu=1280)
    tile.in_ring, tile.out_ring = in_ring, out_ring
    tile.tcache = Tcache(wksp, depth=512)
    tile.seq = 0
    tile._fn = tile._real_fn
    tile._pending = deque()
    tile._bufset_fut = [None] * len(tile._bufsets)
    tile._disp = 0
    tile._deferred, tile._deferred_n = {}, 0
    tile.degraded, tile._consec_fail = False, 0
    tile.metrics = {k: 0 for k in tile.metrics}
    tile._coalesce_ns = max(0, int(float(coalesce_us) * 1e3))
    tile._hold_n, tile._hold_deadline = 0, 0
    return tile, in_ring, out_ring


def _collect(out_ring):
    got, seq = [], 0
    while True:
        rc, frag = out_ring.consume(seq)
        if rc != 0:
            break
        got.append(bytes(out_ring.payload(frag)))
        seq += 1
    return got


class ScriptedFut:
    """Fake device verdict future: is_ready() is test-scripted (a
    manual flag, or auto-ready after N polls — a device whose verdicts
    land mid-wait), and forcing it before the script says ready is the
    failure the staging contract forbids."""

    def __init__(self, verdicts):
        self.v = np.asarray(verdicts, bool)
        self.ready = False
        self.ready_after = None          # is_ready calls until ready
        self.polls = 0
        self.forced = 0

    def is_ready(self):
        self.polls += 1
        if self.ready_after is not None and self.polls >= self.ready_after:
            self.ready = True
        return self.ready

    def __array__(self, dtype=None, copy=None):
        assert self.ready, "verdict readback forced before scripted ready"
        self.forced += 1
        return self.v


def _script_backend(tile, counts=None, shipped=None):
    """Swap the tile's jit for a scripted fake AFTER warmup: records
    each dispatch's transfer shape (and, given `counts`, its live-lane
    count; given `shipped`, a copy of the bytes it shipped) and returns
    a ScriptedFut."""
    futs = []
    shapes = []
    flat_len = tile._bufsets[0].flat.shape[0]

    def fake_fn(flat, n):
        shapes.append(tuple(np.asarray(flat).shape))
        if counts is not None:
            counts.append(n)
        if shipped is not None:
            shipped.append(np.array(flat, np.uint8))
        fut = ScriptedFut(np.ones(tile.batch, bool))
        futs.append(fut)
        return fut

    tile._fn = fake_fn
    return futs, shapes, flat_len


def test_dispatch_k1_does_not_block_on_readback_of_k(wksp, txns, _shared_tile):
    """Acceptance: with two batches' verdicts scripted unresolved, the
    second dispatch completes without forcing the first readback, each
    dispatch ships exactly ONE packed transfer (the whole staging
    buffer), and verdicts retire oldest-first once ready."""
    tile, in_ring, out_ring = _mk_tile(wksp, _tile=_shared_tile)
    assert tile.inflight >= 2
    futs, shapes, flat_len = _script_backend(tile)

    for t in txns[:4]:
        in_ring.publish(t, sig=1)
    tile.poll_once()                      # batch k dispatched
    assert len(futs) == 1 and len(tile._pending) == 1
    for t in txns[4:8]:
        in_ring.publish(t, sig=2)
    tile.poll_once()                      # batch k+1: must not block
    assert len(futs) == 2 and len(tile._pending) == 2
    assert futs[0].forced == 0            # k's readback never forced
    # single staged transfer per dispatch: the packed flat buffer
    # (len|sig|pub|msg lanes back to back), not four per-array copies
    assert shapes == [(flat_len,), (flat_len,)]
    for f in futs:
        f.ready = True
    tile.flush()
    assert not tile._pending
    assert tile.metrics["tx"] == 8
    assert _collect(out_ring) == [bytes(t) for t in txns[:8]]


def test_dispatch_passes_its_live_lane_count(wksp, txns, _shared_tile,
                                             monkeypatch):
    """Each dispatch hands the kernel exactly the lanes it assembled, as
    an int32 scalar (one traced shape for every count), and
    `kernel_programs` sums ceil(lanes / grid width) over dispatches.
    The batch here is one 16-lane program at the kernel's own width, so
    the grid width is narrowed to 8 lanes to make the rounding show,
    and the tile is made to count as on the Pallas path (the CPU's jnp
    path takes no count)."""
    from firedancer_tpu.ops.pallas_ed import DEFAULT_TB
    assert _shared_tile._grain == DEFAULT_TB
    monkeypatch.setattr(_shared_tile, "_grain", 8)
    monkeypatch.setattr(_shared_tile, "_counted", True)
    tile, in_ring, out_ring = _mk_tile(wksp, _tile=_shared_tile)
    counts = []
    futs, _, _ = _script_backend(tile, counts)
    for t in txns[:16]:                   # one full batch (single-signer)
        in_ring.publish(t, sig=1)
    tile.poll_once()
    for t in txns[16:21]:                 # then 5 lanes
        in_ring.publish(t, sig=2)
    tile.poll_once()
    assert counts == [16, 5]
    assert all(isinstance(n, np.int32) for n in counts)
    for f in futs:
        f.ready = True
    tile.flush()
    assert tile.metrics["lanes"] == 21
    assert tile.metrics["kernel_programs"] == 2 + 1   # ceil(16/8) + ceil(5/8)
    assert _collect(out_ring) == [bytes(t) for t in txns[:21]]


@pytest.mark.parametrize("path", ["uncounted", "host_verdicts"])
def test_kernel_programs_whole_batch_where_nothing_skips(
        wksp, txns, _shared_tile, monkeypatch, path):
    """A dispatch that carries no count (the CPU jnp path, the mesh)
    runs every program, and one answered with host verdicts (degraded)
    skipped nothing on the device: each adds the whole batch's programs
    to `kernel_programs`, never a skip that did not happen, and the
    whole batch to `staged_lanes`, never a width that did not ship."""
    monkeypatch.setattr(_shared_tile, "_grain", 8)
    if path == "host_verdicts":
        _narrow(_shared_tile, monkeypatch, width=8)
    else:
        monkeypatch.setattr(_shared_tile, "_counted", False)
    tile, in_ring, out_ring = _mk_tile(wksp, _tile=_shared_tile)
    futs, _, _ = _script_backend(tile)
    tile.degraded = path == "host_verdicts"
    for t in txns[:5]:
        in_ring.publish(t, sig=1)
    tile.poll_once()
    assert len(futs) == (path == "uncounted")
    for f in futs:
        f.ready = True
    tile.flush()
    assert tile.metrics["lanes"] == 5
    assert tile.metrics["kernel_programs"] == BATCH // 8
    assert tile.metrics["staged_lanes"] == BATCH
    assert _collect(out_ring) == [bytes(t) for t in txns[:5]]


# -- dispatch width: chunks narrower than the batch ---------------------------

WIDTH = 8


def _narrow(tile, monkeypatch, width=WIDTH):
    """Make the shared tile dispatch as the Pallas path does below a
    larger batch: staging sets and the assembler's cap at `width`."""
    monkeypatch.setattr(tile, "_counted", True)
    monkeypatch.setattr(tile, "_width", width)
    monkeypatch.setattr(tile, "_bufsets", tile._stage_sets())


def _lanes(flat, width):
    """(lens, sigs) of a shipped transfer laid out at `width` lanes."""
    ln = flat[:4 * width].view(np.int32)
    sig = flat[4 * width:68 * width].reshape(width, 64)
    return ln, sig


def _multisig(i: int, m: int) -> bytes:
    """A parseable txn with m signatures (bytes only: the scripted
    backend decides its verdict)."""
    from firedancer_tpu.protocol.txn import build_message, build_txn
    signers = [bytes([i, j + 1]) + bytes(30) for j in range(m)]
    msg = build_message(signers, [bytes(32)], b"\x11" * 32,
                        [(m, bytes([0]), b"x")])
    return build_txn([bytes([i, j]) + bytes(62) for j in range(m)], msg)


def test_dispatch_width_from_the_batch():
    """One SHA-512 program's lanes below a larger multiple of them,
    else the batch."""
    from firedancer_tpu.ops.pallas_sha import TBATCH
    from firedancer_tpu.tiles.verify import dispatch_width
    assert TBATCH == 1024
    assert dispatch_width(8192, 256, TBATCH) == 1024
    assert dispatch_width(3072, 256, TBATCH) == 1024
    assert dispatch_width(1024, 256, TBATCH) == 1024   # the batch itself
    assert dispatch_width(2500, 256, TBATCH) == 2500   # not a multiple
    assert dispatch_width(8192, 2048, TBATCH) == 8192  # off the grain
    assert dispatch_width(16, 256, TBATCH) == 16


@pytest.mark.parametrize("k", [1, 5, WIDTH])
def test_dispatch_ships_its_width_with_the_live_count(
        wksp, txns, _shared_tile, monkeypatch, k):
    """A counted dispatch of k live lanes ships its whole staging set,
    width x row bytes laid out for that width (lens, then signatures),
    with every lane past k dead, and passes n = k."""
    _narrow(_shared_tile, monkeypatch)
    tile, in_ring, out_ring = _mk_tile(wksp, _tile=_shared_tile)
    counts, shipped = [], []
    futs, shapes, _ = _script_backend(tile, counts, shipped)
    for t in txns[:k]:                    # single-signer: one lane each
        in_ring.publish(t, sig=1)
    tile.poll_once()
    row = tile._bufsets[0].flat.shape[0] // WIDTH
    assert shapes == [(WIDTH * row,)] and counts == [k]
    ln, sig = _lanes(shipped[0], WIDTH)
    assert ln[:k].tolist() == [len(t) - 65 for t in txns[:k]]
    assert not ln[k:].any()
    assert [bytes(s) for s in sig[:k]] == [bytes(t[1:65]) for t in txns[:k]]
    for f in futs:
        f.ready = True
    tile.flush()
    assert tile.metrics["staged_lanes"] == WIDTH
    assert _collect(out_ring) == [bytes(t) for t in txns[:k]]


def test_lanes_past_the_width_ship_in_chunks_of_whole_txns(
        wksp, _shared_tile, monkeypatch):
    """Candidates that need more lanes than the width ship in several
    dispatches of that width, each holding whole txns in order (no
    txn's signatures split), all from one gather; `staged_lanes` sums
    the widths shipped."""
    _narrow(_shared_tile, monkeypatch)
    tile, in_ring, out_ring = _mk_tile(wksp, _tile=_shared_tile)
    sigs = [3, 3, 3, 3, 3, 2, 3]          # 20 lanes: 6, 6, then 3 + 2 + 3
    batch = [_multisig(i + 1, m) for i, m in enumerate(sigs)]
    counts, shipped = [], []
    futs, shapes, _ = _script_backend(tile, counts, shipped)
    for t in batch:
        in_ring.publish(t, sig=1)
    tile.poll_once()
    row = tile._bufsets[0].flat.shape[0] // WIDTH
    assert shapes == [(WIDTH * row,)] * 3
    assert counts == [6, 6, 8]
    want = [t[1 + 64 * j:1 + 64 * (j + 1)]
            for t, m in zip(batch, sigs) for j in range(m)]
    got = []
    for flat, n in zip(shipped, counts):
        _, sig = _lanes(flat, WIDTH)
        got += [bytes(s) for s in sig[:n]]
    assert got == want                    # whole txns, in order
    for f in futs:
        f.ready = True
    tile.flush()
    assert tile.metrics["batches"] == 3 and tile.metrics["lanes"] == 20
    assert tile.metrics["staged_lanes"] == 3 * WIDTH
    assert _collect(out_ring) == batch


def test_chunks_of_the_batches_in_flight_never_wait_on_each_other(
        wksp, _shared_tile, monkeypatch):
    """With `inflight` batches of chunks scripted unresolved, every
    chunk still finds a free staging set: no dispatch waits on another
    chunk's verdicts, and all retire once ready."""
    _narrow(_shared_tile, monkeypatch)
    tile, in_ring, out_ring = _mk_tile(wksp, _tile=_shared_tile)
    monkeypatch.setattr(tile, "device_timeout_s", 0.05)
    assert tile.inflight == 2 and len(tile._bufsets) == 6
    futs, _, _ = _script_backend(tile)
    gathers = [[_multisig(i, 3) for i in range(g, g + 5)]
               for g in (1, 6)]           # 15 lanes: chunks of 6, 6, 3
    for batch in gathers:
        for t in batch:
            in_ring.publish(t, sig=1)
        tile.poll_once()
    assert len(futs) == 6 and len(tile._pending) == 2
    assert not tile.degraded and tile.metrics["device_errors"] == 0
    for f in futs:
        f.ready = True
    tile.flush()
    assert not tile._pending
    assert _collect(out_ring) == gathers[0] + gathers[1]


def test_boot_warms_the_dispatch_width(_shared_tile, monkeypatch):
    """The boot warmup ships one full staging set at the dispatch
    width, so nothing compiles mid-run."""
    import jax.numpy as jnp
    _narrow(_shared_tile, monkeypatch)
    bs = _shared_tile._bufsets[0]
    row = bs.flat.shape[0] // WIDTH
    seen = []

    def fake_fn(flat, n):
        seen.append((flat.shape, int(n)))
        return jnp.zeros(flat.shape[0] // row, bool)
    monkeypatch.setattr(_shared_tile, "_fn", fake_fn)
    assert _shared_tile._warmup_once(bs) is None
    assert seen == [((WIDTH * row,), WIDTH)]


@pytest.mark.parametrize("devices", [1, 8])
def test_uncounted_paths_dispatch_the_whole_batch(wksp, monkeypatch,
                                                  devices):
    """The CPU's jnp path and the mesh take no count and dispatch the
    whole batch, whatever width the batch would give a counted path."""
    import jax

    def warm(self, bs):
        self._warm_shards = 1
        return None
    monkeypatch.setattr(VerifyTile, "_warmup_once", warm)
    tile = VerifyTile(Ring.create(wksp, depth=64, mtu=1280),
                      Ring.create(wksp, depth=64, mtu=1280),
                      Tcache(wksp, depth=64), batch=2048,
                      devices=devices)
    assert tile.devices == min(devices, len(jax.devices()))
    assert not tile._counted
    assert tile._width == 2048
    assert all(len(bs.txn) == 2048 for bs in tile._bufsets)


def _skip_warmup(monkeypatch):
    """Construct VerifyTiles without tracing the verify jit."""
    def warm(self, bs):
        self._warm_shards = 1
        return None
    monkeypatch.setattr(VerifyTile, "_warmup_once", warm)


@pytest.mark.parametrize("coalesce_us", [0.0, 0.0004, 1.0, 200.0])
def test_hold_buffer_only_with_a_window(wksp, monkeypatch, coalesce_us):
    """The coalescing window is fixed at construction: a tile whose
    window rounds to 0 ns dispatches every gather as-is and never
    allocates the hold buffer; any other window gets one batch of
    rows up front."""
    _skip_warmup(monkeypatch)
    tile = VerifyTile(Ring.create(wksp, depth=64, mtu=1280),
                      Ring.create(wksp, depth=64, mtu=1280),
                      Tcache(wksp, depth=64), batch=BATCH,
                      coalesce_us=coalesce_us)
    ns = int(coalesce_us * 1e3)
    assert tile._coalesce_ns == ns
    if ns:
        assert tile._hold_buf.shape == (BATCH, tile.max_len)
    else:
        assert tile._hold_buf is None


@pytest.mark.parametrize("coalesce_us", [0, 200])
def test_verify_adapter_window_fixed_across_housekeeping(monkeypatch,
                                                         coalesce_us):
    """The verify adapter takes the window from the tile's args and
    its housekeeping passes leave the window and the hold buffer as
    construction made them."""
    from firedancer_tpu.disco import Topology
    from firedancer_tpu.disco.tiles import REGISTRY
    from firedancer_tpu.disco.topo import TileCtx
    _skip_warmup(monkeypatch)
    plan = (Topology(f"cohk{coalesce_us}_{os.getpid()}",
                     wksp_size=1 << 22)
            .link("a_b", depth=64).link("b_c", depth=64)
            .tcache("vtc", depth=64)
            .tile("a", "synth", outs=["a_b"], count=8, unique=8)
            .tile("b", "verify", ins=["a_b"], outs=["b_c"],
                  batch=BATCH, tcache="vtc", coalesce_us=coalesce_us)
            .tile("c", "sink", ins=["b_c"])).build()
    ctx = TileCtx(plan, "b")
    try:
        adapter = REGISTRY["verify"](ctx, plan["tiles"]["b"]["args"])
        tile = adapter.tile
        hold = tile._hold_buf
        assert tile._coalesce_ns == coalesce_us * 1_000
        assert (hold is not None) == (coalesce_us > 0)
        for _ in range(3):
            adapter.housekeeping()
        assert tile._coalesce_ns == coalesce_us * 1_000
        assert tile._hold_buf is hold
    finally:
        ctx.close()
        Workspace.unlink_name(plan["wksp"]["name"])


def test_coalesce_holds_subfull_until_lane_budget_fills(wksp, txns, _shared_tile):
    """With a long window, a sub-full gather dispatches nothing; the
    window flushes the instant the lane budget (one compiled batch)
    fills, with held + new frags forwarded in order."""
    tile, in_ring, out_ring = _mk_tile(wksp, coalesce_us=10_000_000, _tile=_shared_tile)
    for t in txns[:5]:
        in_ring.publish(t, sig=1)
    assert tile.poll_once() == 5          # consumed...
    assert tile.metrics["batches"] == 0   # ...but held, not dispatched
    assert tile._hold_n == 5
    for t in txns[5:16]:
        in_ring.publish(t, sig=2)
    tile.poll_once()                      # 5 + 11 == BATCH: flush
    assert tile._hold_n == 0
    assert tile.metrics["batches"] >= 1
    tile.flush()
    assert _collect(out_ring) == [bytes(t) for t in txns[:16]]


def test_coalesce_flushes_on_idle_when_device_idle(wksp, txns, _shared_tile):
    """Idle ingest with NO batch in device flight flushes the hold
    immediately — an idle device is never kept waiting for a fuller
    batch, whatever the deadline says."""
    tile, in_ring, out_ring = _mk_tile(wksp, coalesce_us=10_000_000, _tile=_shared_tile)
    for t in txns[:3]:
        in_ring.publish(t, sig=1)
    tile.poll_once()
    assert tile._hold_n == 3 and not tile._pending
    tile.poll_once()                      # idle poll: flush now
    assert tile._hold_n == 0 and tile.metrics["batches"] == 1
    tile.flush()
    assert tile.metrics["tx"] == 3


def test_coalesce_deadline_flush_under_trickle(wksp, txns, _shared_tile):
    """Trickling ingest never goes idle, so the DEADLINE is what bounds
    held-frag latency: once it expires the window dispatches even
    sub-full."""
    import time
    tile, in_ring, out_ring = _mk_tile(wksp, coalesce_us=50_000, _tile=_shared_tile)
    in_ring.publish(txns[0], sig=1)
    tile.poll_once()
    in_ring.publish(txns[1], sig=2)
    tile.poll_once()                      # trickle: still inside window
    assert tile.metrics["batches"] == 0 and tile._hold_n == 2
    time.sleep(0.06)                      # cross the 50 ms deadline
    in_ring.publish(txns[2], sig=3)
    tile.poll_once()
    assert tile.metrics["batches"] == 1 and tile._hold_n == 0
    tile.flush()
    assert _collect(out_ring) == [bytes(t) for t in txns[:3]]


def test_drain_on_idle_retires_inflight_mid_coalesce(wksp, txns, _shared_tile):
    """Ingest goes quiet while a window is held AND a batch is in
    device flight: the idle poll must retire the in-flight batch
    (drain-on-idle — queued verdicts never wait for more traffic), and
    must NOT flush the held window while the device is busy and the
    deadline is live."""
    tile, in_ring, out_ring = _mk_tile(wksp, coalesce_us=10_000_000, _tile=_shared_tile)
    futs, _, _ = _script_backend(tile)
    for t in txns[:16]:                   # fill one lane budget
        in_ring.publish(t, sig=1)
    tile.poll_once()                      # dispatches batch A
    assert len(tile._pending) == 1
    for t in txns[16:19]:
        in_ring.publish(t, sig=2)
    tile.poll_once()                      # sub-full window held
    assert tile._hold_n == 3
    # A's verdicts land only mid-wait: the idle poll's snapshot order
    # is checked — hold NOT flushed (device busy, deadline live), but
    # the in-flight batch still retires before the poll returns
    futs[0].ready_after = futs[0].polls + 2
    tile.poll_once()                      # IDLE: A retires, hold stays
    assert not tile._pending              # drain-on-idle
    assert tile._hold_n == 3              # device was busy: hold lives
    assert tile.metrics["tx"] == 16
    tile.poll_once()                      # idle again, device now idle
    assert tile._hold_n == 0
    for f in futs:
        f.ready = True
    tile.flush()
    assert _collect(out_ring) == [bytes(t) for t in txns[:19]]


def test_flush_dispatches_held_window_on_halt(wksp, txns, _shared_tile):
    """The halt path must not drop held ingest: flush() dispatches the
    window and retires it."""
    tile, in_ring, out_ring = _mk_tile(wksp, coalesce_us=10_000_000, _tile=_shared_tile)
    for t in txns[:7]:
        in_ring.publish(t, sig=1)
    tile.poll_once()
    assert tile._hold_n == 7
    tile.flush()
    assert tile._hold_n == 0 and not tile._pending
    assert _collect(out_ring) == [bytes(t) for t in txns[:7]]


def test_publish_batch_backpressure_resume(wksp):
    """Ring.publish_batch under a slow reliable consumer: stop_row < n
    means credits ran out; the producer heartbeats, the consumer
    advances its fseq a little, and the publish RESUMES from stop_row —
    across several stalls — with every masked row delivered exactly
    once, in order, byte-identical."""
    from firedancer_tpu.runtime import Fseq
    ring = Ring.create(wksp, depth=4, mtu=128)
    fs = Fseq(wksp)
    n = 11
    buf = np.zeros((n, 128), np.uint8)
    for i in range(n):
        buf[i, :8] = i + 1
    sizes = np.full(n, 8, np.uint32)
    sigs = np.arange(n, dtype=np.uint64)
    mask = np.ones(n, np.uint8)
    mask[2] = 0                          # hole must not publish
    start = pub_total = 0
    seq = 0
    got = []
    rounds = 0
    while start < n:
        start, pub = ring.publish_batch(buf, sizes, sigs, mask,
                                        fseqs=[fs], start=start)
        pub_total += pub
        rounds += 1
        assert rounds < 32               # no livelock
        # consumer side: drain what's there, publish progress (the
        # "heartbeat" step between stalls)
        while True:
            rc, frag = ring.consume(seq)
            if rc != 0:
                break
            got.append(bytes(ring.payload(frag)))
            seq += 1
        fs.update(seq)
    assert rounds > 1                    # backpressure actually engaged
    assert pub_total == n - 1
    while True:
        rc, frag = ring.consume(seq)
        if rc != 0:
            break
        got.append(bytes(ring.payload(frag)))
        seq += 1
    want = [bytes(buf[i, :8]) for i in range(n) if mask[i]]
    assert got == want


# -- stage accounting (tiles/verify.py StageClock, residence counters) ------

class _StemCtx:
    """The least a Stem needs to run a bare VerifyTile in-process."""

    def __init__(self, wksp):
        from firedancer_tpu.runtime import Cnc
        self.cnc = Cnc(wksp)
        self.spec = {"args": {}}
        self.trace = self.prof = None

    def hist_view(self):
        return None


def _forged(txn: bytes) -> bytes:
    b = bytearray(txn)
    b[1 + 10] ^= 0x01                     # a bit of the first signature
    return bytes(b)


def test_stage_ns_within_stem_time_and_lanes_counted(wksp, txns,
                                                     _shared_tile):
    """Under a real Stem, the verify stages' summed host ns stay within
    the stem's poll time (work + wait): each stage is timed inside a
    poll, never twice. `lanes` counts the lanes dispatched (one per
    single-signer txn), and every txn that reached a lane is timed
    through hold, device and egress."""
    from firedancer_tpu.disco.stem import Stem
    from firedancer_tpu.trace.events import VERIFY_STAGES
    tile, in_ring, out_ring = _mk_tile(wksp, coalesce_us=200.0,
                                       _tile=_shared_tile)
    futs, _, _ = _script_backend(tile)
    real_fn = tile._fn

    def ready_fn(flat, n):                # verdicts land after 2 polls
        fut = real_fn(flat, n)
        fut.ready_after = 2
        return fut
    tile._fn = ready_fn
    ns0 = list(tile.stages.ns)
    for t in txns[:20]:
        in_ring.publish(t, sig=1)
    stem = Stem(_StemCtx(wksp), tile, idle_sleep_s=0)
    stem.run(max_iters=100)               # full batch, then the held 4
    assert not tile._pending and tile._hold_n == 0 and len(futs) == 2
    assert tile.metrics["tx"] == 20
    from firedancer_tpu.trace.events import STAGE_IDS
    stage_ns = sum(tile.stages.ns[STAGE_IDS[s]] - ns0[STAGE_IDS[s]]
                   for s in VERIFY_STAGES)
    poll_ns = stem._hists["work"].sum_ns + stem._hists["wait"].sum_ns
    assert 0 < stage_ns <= poll_ns
    assert tile.metrics["lanes"] == 20 and tile.metrics["batches"] >= 2
    m = tile.metrics
    assert m["txn_timed"] == 20
    assert m["txn_hold_ns"] > 0 and m["txn_device_ns"] > 0 \
        and m["txn_egress_ns"] > 0
    # the three residences of a txn fit inside the whole run
    assert m["txn_hold_ns"] + m["txn_device_ns"] + m["txn_egress_ns"] \
        <= 20 * (stage_ns + poll_ns)
    assert stem._hists["stall"].count == 0


@pytest.mark.parametrize("forged,raced,parked,host_verifies", [
    (True, True, 1, 1),      # the reserver failed: its twin re-verified
    (False, True, 1, 0),     # the reserver passed: the twin is dropped
    (True, False, 0, 0),     # no race: the twin goes to the device
])
def test_deferred_host_verifies_only_for_raced_forged_twins(
        wksp, txns, _shared_tile, forged, raced, parked, host_verifies):
    """A duplicate parks only while its twin is in device flight; the
    host reference re-verifies it only if that twin FAILED — a forged
    txn whose duplicate raced it. Scripted verdicts: the device says
    what the txn is."""
    tile, in_ring, out_ring = _mk_tile(wksp, _tile=_shared_tile)
    futs = []

    def fake_fn(flat, n):
        futs.append(ScriptedFut(np.full(tile.batch, not forged)))
        return futs[-1]
    tile._fn = fake_fn
    txn = _forged(txns[0]) if forged else txns[0]
    in_ring.publish(txn, sig=1)
    tile.poll_once()                      # the original, in flight
    if not raced:
        futs[0].ready = True
        tile.poll_once()                  # ...and finalized
    in_ring.publish(txn, sig=2)
    tile.poll_once()                      # its twin
    for f in futs:
        f.ready = True
    tile.flush()
    assert tile.metrics["deferred_parked"] == parked
    assert tile.metrics["deferred_host_verifies"] == host_verifies
    assert tile.metrics["tx"] == (0 if forged else 1)
