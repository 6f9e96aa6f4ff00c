"""Verify tile: the TPU microbatch bridge.

Re-expression of the reference's verify tile + wiredancer offload pattern
(ref: src/disco/verify/fd_verify_tile.h:60-111 — parse, ha-dedup on first
sig, ed25519 batch verify; src/wiredancer/README.md:106-121 — async
req/resp offload behind the ring ABI):

  in ring (txn payloads) --C++ gather--> microbatch arrays
    --jit(verify_batch) on device--> verdicts
    --tcache dedup on first sig--> out ring (payload + PASS sig)

Batch assembly keeps ONE compiled shape, the dispatch WIDTH (short
chunks are padded with dead lanes, masked after), so XLA never
recompiles in steady state. On the single-device Pallas path the width
is one SHA-512 grid program, 1,024 lanes, below a larger batch
(`dispatch_width`): a gather of up to `batch` frames is assembled,
shipped and run in chunks of that width, so a dispatch of ~300 live
lanes moves 1.4 MB, not the 10.9 MB of 8192 x 1232, and runs one SHA
program, not eight. One width, not several: every compiled width costs
each boot its trace, lowering and load of the strict kernel (~15-20 s
on a v5e host, compile cache or not). The live-lane count rides each
dispatch as a traced scalar: the strict kernel skips its 256-lane grid
programs that hold only padding (counted in `kernel_programs`). The
jnp path on the CPU (no grid) and the multi-device mesh (which shards
the batch axis) dispatch the whole batch and take no count. A txn with
k signatures occupies k lanes and passes only if all k verify (the
reference loops sigs the same way, fd_verify_tile.h:94); its lanes
never split across chunks.

Device staging (r10): each rotating buffer set is ONE contiguous host
buffer (len|sig|pub|msg lanes packed back to back — the native
assembler writes straight into views of it), so a dispatch is a single
async `device_put` of width x (100 + max_len) bytes followed by the
jit, which splits the lanes back out on-device (donated on real
accelerators, though the bool verdicts cannot reuse the uint8 buffer,
so the donation only frees it after the call). Because
the put is async and each in-flight batch owns its own staging set,
the host->device transfer of batch k overlaps the device compute of
batch k-1 instead of serializing four little `jnp.asarray` copies
per dispatch.

Adaptive microbatch coalescing (r10): under steady load, dispatching
whatever `gather` returned burns full fixed-shape compiled batches on
mostly-padding lanes. With `coalesce_us` > 0 the tile HOLDS sub-full
gathers in a staging window and dispatches when (a) the lane budget
(one compiled batch) fills, (b) the window deadline expires while
traffic trickles, or (c) ingest goes idle with no batch in device
flight — an idle device is never kept waiting for a fuller batch, and
the drain-on-idle rule below still retires every in-flight batch when
ingest goes quiet mid-coalesce. Window config rides [tile.verify]
(coalesce_us, validated by the fdlint key registry).

Dedup ordering matches the reference (tag = per-boot seeded hash over
the FULL 64-byte first signature, fd_verify_tile.h:82; queried BEFORE
verify, inserted into the tcache only AFTER the signature verifies,
fd_verify_tile.h:98-101 — so an attacker-crafted garbage txn carrying a
victim's signature cannot poison the dedup window and censor the
legitimate transaction), EXTENDED with a dispatch-time reservation:
with up to `inflight` async device batches pending, a duplicate
arriving inside the pipeline window would pass the tcache query and be
forwarded twice (ADVICE r5). Candidate tags are therefore
query-and-RESERVED against the pending records' tag window at dispatch
(one vectorized membership test per batch — the window IS the pending
queue, so reservations release themselves at finalize); a
duplicate of an in-flight tag is DEFERRED (its payload parked, no
device lanes spent) and decided when the reserving txn's verdict
lands: reserver passed -> the deferred copy is a true duplicate,
dropped; reserver FAILED -> the deferred copy is re-verified on the
host reference path and forwarded if genuine — so a garbage txn
carrying a victim's signature can neither poison the tcache NOR censor
the victim through the reservation. The deferral pool is
capacity-bounded (overflow drops are counted); the host-local set is
sound because ha-dedup tcaches are per-tile and round-robin frag
ownership is disjoint.

Bulk RLC pre-filter (r14, `[tile.verify] mode = "bulk_prefilter"`):
each chunk of a FULL gather (its candidates need a whole batch of
lanes) — or any chunk while the ingest-saturation window is open — is
gated by ONE random-linear-combination batch equation padded to the
dispatch width (ops/ed25519.rlc_verify_batch on CPU, ops/pallas_msm on
accelerators, secret per-chunk z) BEFORE the strict dispatch — the
flood front door ROADMAP item 4 names. The chunks of a sub-full
gather in peacetime skip the equation entirely: the filter's economics
only work at batch grain (a trickle pays less running the strict
kernel directly), and a flood by definition fills gathers. The strict
kernel stays the
final accept authority (rlc is cofactored, NOT a consensus drop-in —
tests/test_rlc.py pins the torsion divergence class), so a batch that
slips the filter is still judged strictly and zero frags are ever
falsely accepted. What the filter buys is the flood path: a chunk that
FAILS the batch equation while ingest is saturated — a FULL gather is
its own saturation proof, the hot window covers partial gathers during
a sustained burst — is bisected, and if BOTH halves fail too (an
all-garbage chunk — a forged-sig flood at line rate) the whole chunk
is dropped at MSM cost without spending a strict dispatch; a chunk
with one clean half proceeds to strict. The halves are the only test:
a chunk with garbage in BOTH halves is shed whole, its valid lanes
with it (the chip smoke saw 4,096 valid txns shed from one chunk of
1,024 valid + 4,096 forged/torsion + 3,072 valid, PR 21), so
`prefilter_shed = False` — the filter runs, never sheds — is the
setting that keeps every valid txn. A failing chunk of a sub-full
gather off-hot just proceeds to strict (fail-closed, zero behavior
change in peacetime beyond the one batch check). rlc_* metrics count
batches/lanes/sheds and accumulate kernel time for the
rlc_prefilter_vps bench stanza.

Device robustness: a device that cannot warm up fails the tile at
boot. After a healthy boot, dispatch is wrapped in bounded retry,
readback in a timeout; a persistent device failure (consecutive
errors >= device_fail_limit, or a readback timeout) degrades the tile to the CPU
reference ed25519 path (utils/ed25519_ref.py — byte-identical verdicts)
with the `cpu_fallback` metrics flag raised, so sigverify survives a
lost TPU rather than killing the topology.

Publishing is credit-gated: when downstream reliable consumers' fseqs are
attached, the tile spins for credits instead of silently lapping them
(ref: src/tango/fctl/fd_fctl.h:4-10).

Stage accounting: each batch's path is timed stage by stage
(trace/events.py STAGE_IDS: gather, parse, dedup, assemble, dispatch,
readback, finalize, deferred, publish) with one clock read per stage
per batch — cumulative ns per stage (`stages.ns`, the ns_<stage> metric
slots) and, when traced, one EV_STAGE span each. A txn that reaches a
device lane is also timed through the tile: txn_hold_ns (gather ->
dispatch), txn_device_ns (dispatch -> the tile sees its verdict ready)
and txn_egress_ns (verdict seen -> published), summed over txn_timed
txns from the few gather timestamps of each batch, never per txn.
"""
from __future__ import annotations

import ctypes as ct
import os
import time

import numpy as np

from ..disco.metrics import HistAccum
from ..protocol.txn import MTU
from ..runtime import Ring, Tcache
from ..runtime.tango import lib as _lib
from ..trace.events import STAGE_IDS
from ..trace.recorder import StageClock
from ..utils.tempo import monotonic_ns

_GATHER, _PARSE, _DEDUP, _ASSEMBLE, _DISPATCH, _READBACK, _FINALIZE, \
    _DEFERRED, _PUBLISH = (STAGE_IDS[s] for s in (
        "gather", "parse", "dedup", "assemble", "dispatch", "readback",
        "finalize", "deferred", "publish"))

_u8p = ct.POINTER(ct.c_uint8)
_i32p = ct.POINTER(ct.c_int32)
_u32p = ct.POINTER(ct.c_uint32)
_u64p = ct.POINTER(ct.c_uint64)


def parse_batch(buf: np.ndarray, sizes: np.ndarray, seed: bytes):
    """Native batched txn parse + seeded dedup-tag hash.

    buf (n, stride) uint8, sizes (n,) uint32 -> (meta (n,8) int32,
    tags (n,) uint64). meta[:,0] is the parse-ok flag; layout per
    native/fdtpu.h::fdtpu_txn_parse_batch."""
    n, stride = buf.shape
    buf = np.ascontiguousarray(buf)
    sizes = np.ascontiguousarray(sizes, np.uint32)
    meta = np.zeros((n, 8), np.int32)
    tags = np.zeros((n,), np.uint64)
    s0 = int.from_bytes(seed[:8], "little")
    s1 = int.from_bytes(seed[8:16], "little")
    _lib.fdtpu_txn_parse_batch(
        buf.ctypes.data_as(_u8p), sizes.ctypes.data_as(_u32p), n, stride,
        s0, s1, meta.ctypes.data_as(_i32p), tags.ctypes.data_as(_u64p))
    return meta, tags


# process-local compiled-dispatch cache: the jitted packed-verify fn
# is a pure function of (batch, max_len, devices, platform), but
# jax.jit caches per CLOSURE — so N same-shape tiles in one process
# (rr shards, test suites) would each pay the full strict-kernel
# compile. Sharing the jit is safe: it holds no tile state.
_FN_CACHE: dict = {}


def dispatch_width(batch: int, grain: int, program: int) -> int:
    """Lanes of one counted device dispatch: one SHA-512 grid program
    (`program`, ops/pallas_sha.TBATCH = 1,024) where the batch is a
    larger multiple of it and it is a multiple of the strict kernel's
    `grain`, else the whole batch."""
    if batch > program and batch % program == 0 and program % grain == 0:
        return program
    return batch


class _StageBuf:
    """One rotating staging set: a single contiguous host buffer whose
    lane regions (len|sig|pub|msg) are numpy views the native assembler
    fills in place — the whole set ships to the device as ONE transfer.
    `txn` (lane -> txn row map) is host-only bookkeeping and stays off
    the wire."""

    __slots__ = ("flat", "ln", "sig", "pub", "msg", "txn")

    def __init__(self, batch: int, max_len: int):
        self.flat = np.zeros(batch * (4 + 64 + 32 + max_len), np.uint8)
        o = 4 * batch                      # int32 lens first: 4B-aligned
        self.ln = self.flat[:o].view(np.int32)
        self.sig = self.flat[o:o + 64 * batch].reshape(batch, 64)
        o += 64 * batch
        self.pub = self.flat[o:o + 32 * batch].reshape(batch, 32)
        o += 32 * batch
        self.msg = self.flat[o:].reshape(batch, max_len)
        self.txn = np.zeros(batch, np.int32)


class VerifyTile:
    def __init__(self, in_ring: Ring, out_ring: Ring, tcache: Tcache,
                 batch: int = 256, max_len: int = MTU,
                 backend: str = "jax", out_fseqs=None,
                 dedup_seed: bytes | None = None,
                 rr_cnt: int = 1, rr_idx: int = 0, devices: int = 1,
                 device_retries: int = 2,
                 device_timeout_s: float | None = None,
                 device_fail_limit: int = 3, chaos: dict | None = None,
                 trace=None, trace_link: int = 0,
                 trace_link_in: int = 0, coalesce_us: float = 0.0,
                 mode: str = "strict", prefilter_shed: bool = True):
        self.in_ring, self.out_ring, self.tcache = in_ring, out_ring, tcache
        # horizontal sharding: N verify tiles consume the SAME ingest
        # link; tile rr_idx owns frags with seq % rr_cnt == rr_idx
        # (P2, ref: src/disco/verify/fd_verify_tile.c:49-53)
        if not 0 <= rr_idx < rr_cnt:
            raise ValueError(f"rr_idx {rr_idx} out of range {rr_cnt}")
        self.rr_cnt, self.rr_idx = rr_cnt, rr_idx
        # a txn's sig lanes never split across device chunks, so the
        # chunk must hold the max per-txn signature count (SIG_MAX=12,
        # protocol/txn.py) or a 13-lane txn could wedge lane assembly
        if batch < 12:
            raise ValueError(f"verify batch {batch} < max sig_cnt 12")
        self.batch, self.max_len = batch, max_len
        self.out_fseqs = list(out_fseqs or [])
        # per-boot random seed: tags are unpredictable to senders
        self.dedup_seed = dedup_seed if dedup_seed is not None \
            else os.urandom(16)
        self.seq = 0
        self._cnc = None
        self.metrics = {
            "rx": 0, "parse_fail": 0, "dedup_drop": 0, "verify_fail": 0,
            "tx": 0, "overruns": 0, "batches": 0, "backpressure": 0,
            "device_errors": 0, "cpu_fallback": 0,
            # bulk RLC pre-filter (mode="bulk_prefilter"): equation
            # runs / passes / lanes checked / lanes shed / kernel ns
            "rlc_batches": 0, "rlc_pass": 0, "rlc_lanes": 0,
            "rlc_shed": 0, "rlc_ns": 0,
            # stage accounting: strict lanes dispatched, parked
            # duplicates and their host reference re-verifies, and the
            # residence of the txns that reached a device lane
            "lanes": 0, "deferred_parked": 0, "deferred_host_verifies": 0,
            "txn_hold_ns": 0, "txn_device_ns": 0, "txn_egress_ns": 0,
            "txn_timed": 0,
            # strict-kernel grid programs run: ceil(lanes / 256) per
            # counted device dispatch, batch / 256 (all) otherwise; and
            # the lanes each such dispatch shipped, the batch otherwise
            "kernel_programs": 0, "staged_lanes": 0,
        }
        if mode not in ("strict", "bulk_prefilter"):
            raise ValueError(f"unknown verify mode {mode!r} "
                             f"(strict | bulk_prefilter)")
        self.mode = mode
        self.prefilter_shed = bool(prefilter_shed)
        self._rlc_fn = None
        # per-tile secret RLC coefficient stream: the batch equation's
        # soundness lives in z being unpredictable to txn senders
        # (tests rig _draw_z to pin the torsion divergence class)
        self._rlc_rng = np.random.default_rng(
            int.from_bytes(os.urandom(16), "little"))
        # ingest-saturation clock: a full gather means the ring is
        # outpacing us — the prefilter may shed all-garbage chunks
        # only inside this window (drop-newest under pressure, never
        # in peacetime)
        self._hot_until = 0
        self._hot_hold_ns = 100_000_000
        # graceful degradation: bounded retry around dispatch, timeout
        # around readback; persistent failure flips to the CPU reference
        # path instead of killing the tile (the watchdog-visible metric
        # is cpu_fallback; ISSUE r6 tentpole 3)
        self.device_retries = int(device_retries)
        self.device_timeout_s = device_timeout_s if device_timeout_s \
            is not None else float(os.environ.get(
                "FDTPU_VERIFY_TIMEOUT_S", "60"))
        self.device_fail_limit = max(1, int(device_fail_limit))
        self.degraded = False
        self._consec_fail = 0
        # duplicates inside the async pipeline window are deferred and
        # decided by the reserving txn's verdict (no device lanes
        # spent, no censorship through a failed reserver). The window
        # itself is the pending records' `reserved` tag arrays — a
        # record's tags leave the window the instant it pops for
        # finalize, so there is no separate set to keep in sync, and
        # membership tests run as one vectorized np.isin per batch.
        self._deferred: dict[int, list[bytes]] = {}
        self._deferred_n = 0
        self._deferred_cap = 256          # bounds attacker-driven parking
        # adaptive coalescing window (0 = dispatch every gather as-is):
        # sub-full gathers accumulate here until the lane budget fills,
        # the deadline expires, or ingest idles with the device idle
        self._coalesce_ns = max(0, int(float(coalesce_us) * 1e3))
        self._hold_buf = np.zeros((batch, max_len), np.uint8) \
            if self._coalesce_ns else None
        self._hold_sizes = np.zeros(batch, np.uint32)
        self._hold_n = 0
        self._hold_deadline = 0
        # (first hold row, gather time) of each gather in the hold (reset
        # when a hold starts): the txns' gather -> dispatch residence
        self._hold_segs: list[tuple[int, int]] = []
        self._chaos = None
        if chaos:
            from ..utils.chaos import ChaosPlan
            self._chaos = ChaosPlan(chaos)
        # fdtrace flight recorder (None = untraced, zero hot-path cost:
        # every hook below is one attribute check). The stage spans and
        # the fallback transition are the events the host-side trace
        # exists for.
        self._trace = trace
        self.stages = StageClock(trace)
        self._trace_link = trace_link
        self._trace_link_in = trace_link_in
        # TPU-time attribution (fdmetrics v2): dispatch + readback
        # durations accumulate here regardless of tracing (two
        # monotonic_ns reads per BATCH, not per frag) and the stem
        # flushes it into the tile's `tpu` histogram slot — the
        # device-side half of the wait/work split
        self.tpu_hist = HistAccum()
        if backend == "jax":
            import jax
            from ..ops.pallas_ed import DEFAULT_TB as tb
            devs = jax.devices()
            # `counted`: vb takes the live-lane count (the Pallas grid
            # of `tb`-lane programs)
            counted = devs[0].platform != "cpu"
            if not counted:
                from ..ops.ed25519 import verify_batch as vb
            else:
                # fused Pallas kernels on accelerator backends
                from ..ops.pallas_ed import verify_batch as vb
                from ..ops.pallas_sha import TBATCH
            ndev = min(int(devices), len(devs))
            if ndev > 1:
                # shard the batch axis over the device mesh: the
                # TPU-native form of adding verify tiles (P2 over ICI
                # instead of cores; ref SURVEY §2.10, fd_verify_tile.c
                # round-robin -> shard_map). Verdicts stay sharded and
                # gather back on the host readback.
                from jax.sharding import Mesh, PartitionSpec as P
                if batch % ndev:
                    raise ValueError(f"batch {batch} % devices {ndev}")
                mesh = Mesh(np.array(devs[:ndev]), ("shard",))
                # carries start as constants (sha IV / identity point)
                # and become axis-varying in the loop body — disable
                # the varying-manual-axes check. Each shard runs its
                # whole slice: no live-lane count on the mesh path
                vb = jax.shard_map(
                    vb, mesh=mesh, in_specs=(P("shard"),) * 4,
                    out_specs=P("shard"), check_vma=False)
                counted = False
            self.devices = ndev
            self._counted, self._grain = counted, tb
            # only a counted dispatch narrows: the jnp path and the
            # mesh keep the whole batch
            self._width = dispatch_width(batch, tb, TBATCH) if counted \
                else batch
            # staged dispatch: the jit consumes ONE packed uint8 buffer
            # (the whole staging set) and splits the lanes on-device —
            # host->device is a single transfer per dispatch — plus the
            # live-lane count, an int32 scalar (traced: no retrace). The
            # transfer buffer is donated on real accelerators (each
            # dispatch device_puts a fresh copy, so the computation may
            # consume it); CPU device_put can alias host memory, where
            # donation would hand XLA the live staging array.
            bsz, mlen = self._width, max_len
            o_sig, o_pub = 4 * bsz, (4 + 64) * bsz
            o_msg = o_pub + 32 * bsz
            fn_key = (bsz, mlen, ndev, devs[0].platform)
            if fn_key in _FN_CACHE:
                self._fn = _FN_CACHE[fn_key]
            else:
                def _packed(flat, n):
                    import jax.numpy as jnp
                    lb = flat[:o_sig].reshape(bsz, 4).astype(jnp.int32)
                    ln = (lb[:, 0] | (lb[:, 1] << 8) | (lb[:, 2] << 16)
                          | (lb[:, 3] << 24))
                    lanes = (flat[o_sig:o_pub].reshape(bsz, 64),
                             flat[o_pub:o_msg].reshape(bsz, 32),
                             flat[o_msg:].reshape(bsz, mlen), ln)
                    return (vb(*lanes, n_live=n, tb=tb) if counted
                            else vb(*lanes))

                donate = (0,) if devs[0].platform != "cpu" else ()
                self._fn = _FN_CACHE[fn_key] = jax.jit(
                    _packed, donate_argnums=donate)
        else:
            raise ValueError(backend)
        # pipelined dispatch: keep up to `inflight` device batches in
        # flight so the per-dispatch latency overlaps the NEXT batch's
        # host work instead of serializing with it (the wiredancer
        # offload queue pattern, ref src/wiredancer/README.md:106-121;
        # VERDICT r4 item 2).
        # Each dispatch assembles into its own rotating lane-buffer set
        # so an in-flight transfer never reads a reused host buffer.
        self.inflight = max(1, int(os.environ.get(
            "FDTPU_VERIFY_INFLIGHT", "2")))
        self._bufsets = self._stage_sets()
        self._bufset_fut = [None] * len(self._bufsets)
        self._disp = 0
        from collections import deque
        self._pending: deque = deque()
        # warm the compile NOW, before the stem declares RUN — tile
        # startup gates on it (the reference does privileged/slow init
        # before signaling the cnc, src/disco/topo/fd_topo_run.c), so
        # the first real batch never stalls a minute inside poll_once.
        # A device that cannot warm up — by raising OR by hanging — fails
        # the tile at boot (fail_fast then stops the topology): a tile
        # that starts on the host reference path would hide a device
        # that never ran. The runtime degrade below (_degrade) is only
        # for a device lost after a healthy boot, and stays visible as
        # cpu_fallback. The deadline is generous: a cold compile of the
        # strict kernel and the RLC kernel takes minutes.
        self.warmup_timeout_s = float(os.environ.get(
            "FDTPU_VERIFY_WARMUP_TIMEOUT_S", "900"))
        # fdprof: warmup compile wall time, surfaced as the
        # tpu_compile_ns gauge (fdtpu_tile_tpu_compile_ns) — the
        # compile-time attribution the bench observatory records
        warmup_t0 = monotonic_ns()
        for attempt in range(self.device_retries + 1):
            err = self._warmup_once(self._bufsets[0])
            if err is None:
                break
            self.metrics["device_errors"] += 1
        else:
            raise RuntimeError(f"verify: device warmup failed: {err}")
        # the device this tile resolved, in its boot log (chip_smoke.py
        # reads it): platform, kind, mesh size, chips holding a shard
        from ..utils import log
        log.notice(f"verify: jax platform={devs[0].platform} "
                   f"device_kind={devs[0].device_kind} "
                   f"devices={self.devices} shards={self._warm_shards}")
        if self.mode == "bulk_prefilter" and os.environ.get(
                "FDTPU_VERIFY_SKIP_RLC_WARMUP") != "1":
            # pre-compile the prefilter's ONE shape NOW (BOOT is
            # watchdog-exempt; a mid-run compile would starve
            # heartbeats and get a healthy tile killed). The prefilter
            # was asked for, so a kernel that cannot warm up fails the
            # boot like the strict one. The skip env is for tests that
            # inject a host-oracle _rlc_fn (tracing + compiling the MSM
            # graph costs minutes on CPU — tier-1 exercises the wiring
            # against the oracle, the slow suite runs the real kernel).
            self._rlc_ok(self._bufsets[0], 0, 2)
        self.compile_ns = monotonic_ns() - warmup_t0

    def _stage_sets(self) -> list:
        """`inflight` + 1 batches of staging sets at the dispatch width,
        so the chunks of the batches in flight never wait on each
        other's verdicts."""
        n = (self.inflight + 1) * (self.batch // self._width)
        return [_StageBuf(self._width, self.max_len) for _ in range(n)]

    def _warmup_once(self, bs: _StageBuf) -> str | None:
        """One warmup attempt on a daemon thread with a deadline (a
        hung warmup must not hold the tile in BOOT forever). Returns
        None on success, else what went wrong."""
        import queue
        import threading
        q: "queue.Queue" = queue.Queue(maxsize=1)

        def _worker():
            try:
                import jax
                # the full width: boot runs every grid program once
                out = jax.block_until_ready(
                    self._device_verify(bs, self._width))
                self._warm_shards = len(
                    {s.device for s in out.addressable_shards})
                q.put(None)
            except Exception as e:     # noqa: BLE001 — reported to boot
                q.put(f"{type(e).__name__}: {e}")

        threading.Thread(target=_worker, daemon=True).start()
        try:
            return q.get(timeout=self.warmup_timeout_s)
        except queue.Empty:
            return f"no result within {self.warmup_timeout_s:.0f} s"

    def _degrade(self, why: str):
        """Permanent TPU->CPU fallback: every subsequent verify runs the
        reference ed25519 verifier on host (byte-identical verdicts)."""
        if not self.degraded:
            self.degraded = True
            self.metrics["cpu_fallback"] = 1
            if self._trace is not None:
                from ..trace.events import EV_CPU_FALLBACK
                self._trace.event(EV_CPU_FALLBACK)
            from ..utils import log
            log.warning(f"verify: degrading to CPU reference path ({why})")

    def _device_verify(self, bs: _StageBuf, lanes: int):
        """Async staged dispatch: ONE host->device transfer of the
        packed staging buffer (device_put starts the copy and returns;
        the jit splits lanes on-device; `lanes` live ones, the rest
        padding the kernel skips), then the verdict future —
        never forced, callers pipeline and block later. The staging
        set stays untouched until its future resolves (_bufset_fut
        guard), so the async transfer always reads stable memory."""
        import jax
        return self._fn(jax.device_put(bs.flat), np.int32(lanes))

    def _draw_z(self, n: int) -> np.ndarray:
        """Secret per-chunk RLC coefficients (n,16) u8. A method so the
        evasion tests can rig the draw into the documented divergence
        class (z ≡ 0 mod 8 keeps a torsion residual invisible to the
        cofactored equation — tests/test_rlc.py)."""
        return self._rlc_rng.integers(0, 256, (n, 16), dtype=np.uint8)

    def _rlc_ok(self, bs: _StageBuf, start: int, stop: int) -> bool:
        """One cofactored RLC batch equation over assembled lanes
        [start, stop), padded to the dispatch width (so the jit only
        ever sees ONE shape: tracing the MSM graph costs minutes on
        CPU and a mid-run retrace would starve heartbeats and trip the
        wedge watchdog; bisect halves just ride the full width with
        dead lanes). Pad lanes carry z = 0, which zeroes
        every one of their scalar terms — an identity contribution to
        the sum regardless of what the stale lane bytes decode to.
        Platform-dispatched like gossvf: the Pallas MSM kernel on
        accelerators, the jnp limb kernel on CPU — identical verdict
        semantics (tests/test_pallas_msm.py).

        Lanes failing structural prechecks are masked OUT of the sum,
        so a chunk where every live lane is structural garbage passes
        the equation vacuously — that counts as a FAILURE here
        (nothing survived the prechecks, the all-garbage-flood
        signature), while a mixed chunk keeps its masked pass and
        proceeds to strict."""
        if self._rlc_fn is None:
            from ..ops.ed25519 import rlc_verify_fn
            self._rlc_fn = rlc_verify_fn()
        import jax.numpy as jnp
        k, width = stop - start, self._width
        sig = np.zeros((width, 64), np.uint8)
        pub = np.zeros((width, 32), np.uint8)
        msg = np.zeros((width, self.max_len), np.uint8)
        ln = np.zeros(width, np.int32)
        sig[:k] = bs.sig[start:stop]
        pub[:k] = bs.pub[start:stop]
        msg[:k] = bs.msg[start:stop]
        ln[:k] = bs.ln[start:stop]
        z = np.zeros((width, 16), np.uint8)
        z[:k] = self._draw_z(k)
        ok, pre = self._rlc_fn(jnp.asarray(sig), jnp.asarray(pub),
                               jnp.asarray(msg), jnp.asarray(ln),
                               jnp.asarray(z))
        return bool(ok) and bool(np.asarray(pre)[:k].any())

    def _rlc_prefilter(self, bs: _StageBuf, lanes: int) -> bool:
        """The flood front door: one RLC batch equation per assembled
        chunk, BEFORE the strict dispatch. Returns False only when the
        chunk should be SHED (equation failed while ingest is
        saturated — a full gather, or the hot window open — AND both
        bisection halves fail too — an all-garbage
        chunk, the forged-sig-flood signature); True always proceeds
        to the strict kernel, which remains the sole accept authority
        (rlc is cofactored — tests/test_rlc.py pins the divergence)."""
        t0 = monotonic_ns()
        self.metrics["rlc_batches"] += 1
        self.metrics["rlc_lanes"] += lanes
        ok = self._rlc_ok(bs, 0, lanes)
        keep = True
        if ok:
            self.metrics["rlc_pass"] += 1
        elif self.prefilter_shed and lanes >= 2:
            # the caller already attested saturation (full gather, or
            # the hot window open at assembly) — deliberately NOT
            # re-sampled here: the equation above costs real wall time
            # on slow backends (~175ms on the CPU jnp kernel) and the
            # shed decision must reflect the ingest state the chunk
            # ARRIVED under, not whether the window survived the
            # filter's own latency
            # bisect: a chunk with either half clean goes to strict;
            # garbage in both halves sheds the chunk, valid lanes
            # included (see the module docstring)
            h = lanes // 2
            self.metrics["rlc_batches"] += 2
            keep = self._rlc_ok(bs, 0, h) or self._rlc_ok(bs, h, lanes)
        self.metrics["rlc_ns"] += monotonic_ns() - t0
        return keep

    def _hb_tick(self, i: int):
        """Heartbeat every few host verifies: a pure-Python ed25519
        verify costs ~5-20ms, so a big degraded batch would otherwise
        starve the heartbeat and get the tile killed by the very wedge
        watchdog the CPU fallback exists to survive."""
        if i % 8 == 0 and self._cnc is not None:
            self._cnc.heartbeat()

    def _cpu_verify_lanes(self, bs: _StageBuf, lanes: int):
        """Reference-verifier verdicts for assembled lanes (fallback
        path — lane buffers are only valid at dispatch time)."""
        from ..utils.ed25519_ref import verify as _ref_verify
        out = np.zeros(bs.sig.shape[0], bool)
        for i in range(int(lanes)):
            self._hb_tick(i)
            mlen = int(bs.ln[i])
            out[i] = _ref_verify(bytes(bs.sig[i]), bytes(bs.pub[i]),
                                 bytes(bs.msg[i, :mlen]))
        return out

    def _dispatch(self, bs: _StageBuf, lanes: int):
        """Guarded device dispatch: bounded retry, chaos injection, and
        CPU fallback. Returns either an async device array or a numpy
        verdict array (already final)."""
        if self.degraded:
            return self._cpu_verify_lanes(bs, lanes)
        from ..utils.chaos import ChaosDeviceError
        for attempt in range(self.device_retries + 1):
            try:
                if self._chaos is not None and \
                        self._chaos.take_dispatch_failure():
                    if self._trace is not None:
                        from ..trace import chaos_event
                        chaos_event(self._trace, "fail_dispatch")
                    raise ChaosDeviceError("injected dispatch failure")
                t0 = monotonic_ns()
                fut = self._device_verify(bs, lanes)
                self.tpu_hist.add(monotonic_ns() - t0)
                return fut
            except Exception:
                self.metrics["device_errors"] += 1
        self._consec_fail += 1
        if self._consec_fail >= self.device_fail_limit:
            self._degrade(f"{self._consec_fail} consecutive dispatch "
                          f"failures")
        return self._cpu_verify_lanes(bs, lanes)

    def _read_verdicts(self, fut):
        """Readback with timeout: numpy (CPU-fallback) verdicts pass
        through; device arrays block, bounded by device_timeout_s. A
        timeout is the wedged-device signature — degrade immediately,
        and once degraded never wait on the device again (remaining
        in-flight futures fail fast into the CPU re-verify path)."""
        if isinstance(fut, np.ndarray):
            return fut
        if self.degraded:
            # the device already proved wedged: never trust or wait on
            # a device future again — an abandoned transfer may have
            # read REUSED lane buffers, so even a late-resolving "ready"
            # verdict is poisoned (fail-closed into CPU re-verify)
            raise TimeoutError("device degraded; verdicts abandoned")
        try:
            if fut.is_ready():       # resolved: return without waiting
                return np.asarray(fut)
        except AttributeError:
            return np.asarray(fut)   # backend without is_ready: block
        if self.device_timeout_s and self.device_timeout_s > 0:
            # deadline spin on is_ready — no thread per readback on the
            # steady-state drain path, nothing leaked on a timeout.
            # Heartbeat while waiting (like _wait_credits) so an armed
            # wedge watchdog doesn't kill the tile during a legitimate
            # device wait and preempt the degradation path.
            deadline = time.perf_counter() + self.device_timeout_s
            spins = 0
            while time.perf_counter() < deadline:
                if fut.is_ready():
                    return np.asarray(fut)
                spins += 1
                if spins % 256 == 0 and self._cnc is not None:
                    self._cnc.heartbeat()
                time.sleep(0.0005)
            self.metrics["device_errors"] += 1
            self._degrade("device readback timeout")
            raise TimeoutError("device readback timeout")
        return np.asarray(fut)

    def poll_once(self) -> int:
        """Gather -> (coalesce) -> parse -> ha-dedup -> async device
        verify -> (queue) -> publish.

        The whole host side is batched: one native call parses + tags the
        gathered frame set (fdtpu_txn_parse_batch), one native call per
        device chunk assembles lanes (fdtpu_verify_assemble), tcache
        query/insert run as native batch loops, the in-flight dedup
        reservation is one vectorized membership test, trace lineage
        lands via frag_batch, and the egress copies + credit checks are
        one native call (fdtpu_ring_publish_batch) — no per-txn Python
        on the hot path (the reference's host path is C for the same
        reason, src/disco/verify/fd_verify_tile.h:60-111; enforced by
        fdlint's per-frag-loop rule).

        Device dispatch is ASYNC with up to `inflight` batches queued:
        verdict readback of batch k overlaps gather/parse/dispatch of
        batch k+1, hiding the per-dispatch latency.
        Returns number of frags CONSUMED (0 only when the ring was idle)."""
        self._drain(block=False)
        want = self.batch - self._hold_n
        t_g = monotonic_ns()
        n, self.seq, buf, sizes, sigs, ovr, seqs = self.in_ring.gather(
            self.seq, want, self.max_len, want_seqs=True)
        self.metrics["overruns"] += ovr
        if self.mode != "strict" and (n >= want or ovr):
            # a full gather (or an overrun) means ingest is outpacing
            # us: open the prefilter's shed window for the hold —
            # refreshed while saturation persists, expires on its own
            self._hot_until = monotonic_ns() + self._hot_hold_ns
        if not n:
            # idle ingest: a held sub-batch dispatches now rather than
            # waiting for traffic that may never come — unless batches
            # are still in device flight, in which case holding is free
            # (the device isn't idle) until the window deadline. And
            # in-flight batches ALWAYS retire: queued verdicts must
            # never wait on more traffic arriving (drain-on-idle).
            if self._hold_n and (not self._pending or
                                 monotonic_ns() >= self._hold_deadline):
                self._flush_hold()
            if self._pending:
                self._drain(block=True)
            return 0
        consumed = n
        if self.rr_cnt > 1:
            # keep only our round-robin share; the siblings consume the
            # same frags from their own cursors (dedup is unnecessary
            # here — ownership is disjoint by construction)
            mine = (seqs[:n] % self.rr_cnt) == self.rr_idx
            buf, sizes, sigs = buf[:n][mine], sizes[:n][mine], sigs[:n][mine]
            n = int(mine.sum())
            if not n:
                return consumed
        else:
            buf, sizes, sigs = buf[:n], sizes[:n], sigs[:n]
        self.metrics["rx"] += n
        if self._trace is not None:
            # ingest lineage anchors (sampled, one vectorized append):
            # the upstream producer's sig, so synth/quic -> verify
            # hand-offs correlate too
            from ..trace.events import EV_CONSUME
            self._trace.frag_batch(EV_CONSUME, sigs,
                                   link=self._trace_link_in)
        if not self._coalesce_ns:
            self.stages.add(_GATHER, t_g)
            self._process_batch(buf, sizes, n, [(0, t_g)])
            return consumed
        # adaptive coalescing: accumulate sub-full gathers into the
        # hold window; dispatch when one compiled batch's lane budget
        # fills or the window deadline expires under a trickle. A FULL
        # gather with nothing held bypasses the window entirely — under
        # saturation the fresh gather buffer dispatches directly, never
        # paying the stage-into-hold + recycle-copy that exists only to
        # keep sub-full remainders alive across polls
        if not self._hold_n and n >= self.batch:
            self.stages.add(_GATHER, t_g)
            self._process_batch(buf, sizes, n, [(0, t_g)])
            return consumed
        if not self._hold_n:
            self._hold_deadline = monotonic_ns() + self._coalesce_ns
            self._hold_segs = []
        self._hold_buf[self._hold_n:self._hold_n + n] = buf
        self._hold_sizes[self._hold_n:self._hold_n + n] = sizes
        self._hold_segs.append((self._hold_n, t_g))
        self._hold_n += n
        t = self.stages.add(_GATHER, t_g)
        if self._hold_n >= self.batch or t >= self._hold_deadline:
            self._flush_hold()
        return consumed

    def _flush_hold(self):
        """Dispatch the coalesced window. The hold buffer is recycled
        for the next window, so the record keeps its own copy (one bulk
        memcpy per dispatched batch — the price of a fresh gather
        buffer, paid once per BATCH instead of once per gather)."""
        n, self._hold_n = self._hold_n, 0
        self._process_batch(self._hold_buf[:n].copy(),
                            self._hold_sizes[:n].copy(), n,
                            self._hold_segs)

    def _process_batch(self, buf, sizes, n: int, segs):
        """Parse -> tag -> ha-dedup + batched in-flight reservation ->
        fixed-shape device chunks, dispatched async (the verify
        pipeline behind the gather/coalesce stage). `segs` holds the
        (first row, gather time) of each gather the rows came from."""
        st = self.stages
        t = monotonic_ns()
        buf = np.ascontiguousarray(buf[:n])
        sizes = np.ascontiguousarray(sizes[:n], np.uint32)
        meta, tags = parse_batch(buf, sizes, self.dedup_seed)
        ok = meta[:, 0] != 0
        self.metrics["parse_fail"] += int(n - ok.sum())
        t = st.add(_PARSE, t)

        # ha-dedup query BEFORE spending device lanes; tcache insert
        # stays AFTER verify (ref order: fd_verify_tile.h:84-101), and
        # the in-flight reservation closes the async pipeline window:
        # a duplicate of a txn still in device flight spends no lanes
        # here — it parks in the deferral pool and is decided by the
        # reserving txn's verdict at finalize (ADVICE r5; see module
        # docstring for why it must not be dropped outright). The
        # reservation is BATCHED: one np.isin against the pending
        # records' reserved-tag window + a first-occurrence mask for
        # intra-batch twins; only the rare raced duplicates fall to the
        # python parking loop.
        hit = self.tcache.query_batch(tags, mask=ok.astype(np.uint8))
        dup_pre = ok & (hit != 0)
        self.metrics["dedup_drop"] += int(dup_pre.sum())
        cand_idx = np.nonzero(ok & ~dup_pre)[0]
        reserved = np.zeros(0, np.uint64)
        if cand_idx.size:
            ctags = tags[cand_idx]
            window = [r["reserved"] for r in self._pending
                      if len(r["reserved"])]
            infl = np.isin(ctags, np.concatenate(window)) if window \
                else np.zeros(len(ctags), bool)
            first = np.zeros(len(ctags), bool)
            first[np.unique(ctags, return_index=True)[1]] = True
            res_m = first & ~infl
            reserved = ctags[res_m]
            defer = cand_idx[~res_m]
            if defer.size:
                dup_pre[defer] = True    # twins still in flight: defer
                for i in defer:
                    if self._deferred_n < self._deferred_cap:
                        self._deferred.setdefault(int(tags[i]), []) \
                            .append(bytes(buf[i, :sizes[i]]))
                        self._deferred_n += 1
                        self.metrics["deferred_parked"] += 1
                    else:
                        self.metrics["dedup_drop"] += 1  # pool overflow
        skip = np.ascontiguousarray(~ok | dup_pre).astype(np.uint8)
        cand = ok & ~dup_pre
        t = st.add(_DEDUP, t)
        if not cand.any():
            return

        # device verify in fixed-shape chunks (native lane assembly),
        # dispatched async. FAIL-CLOSED: a candidate txn counts as
        # verified only if every one of its signature lanes ran on the
        # device AND passed; any txn the assembler skips (over-MTU msg)
        # or cannot place is dropped, never forwarded unverified.
        chunks = []
        cursor = ct.c_int64(0)
        # ingest saturation is the gather's to attest, not a chunk's: a
        # chunk of the width fills long before a batch of lanes does
        full = int(meta[cand, 1].sum()) >= self.batch
        while cursor.value < n:
            k = self._disp % len(self._bufsets)
            if self._bufset_fut[k] is not None:
                # this buffer set still feeds an in-flight transfer;
                # the timeout-guarded wait keeps a wedged device from
                # hanging poll_once forever
                try:
                    self._read_verdicts(self._bufset_fut[k])
                except Exception:
                    pass              # degraded inside _read_verdicts
                self._bufset_fut[k] = None
                t = st.add(_READBACK, t)
            bs = self._bufsets[k]
            lanes = _lib.fdtpu_verify_assemble(
                buf.ctypes.data_as(_u8p),
                sizes.ctypes.data_as(_u32p),
                meta.ctypes.data_as(_i32p), skip.ctypes.data_as(_u8p),
                n, buf.shape[1], ct.byref(cursor), self._width,
                self.max_len,
                bs.sig.ctypes.data_as(_u8p),
                bs.pub.ctypes.data_as(_u8p),
                bs.msg.ctypes.data_as(_u8p),
                bs.ln.ctypes.data_as(_i32p),
                bs.txn.ctypes.data_as(_i32p))
            t = st.add(_ASSEMBLE, t)
            if not lanes:
                break
            if self.mode == "bulk_prefilter" and not self.degraded \
                    and (full or monotonic_ns() < self._hot_until) \
                    and not self._rlc_prefilter(bs, lanes):
                # all-garbage chunk under ingest saturation: shed the
                # whole chunk at MSM cost — an all-False verdict array
                # (never forwarded) instead of a strict dispatch. The
                # strict kernel stays the accept authority for every
                # chunk that is NOT shed, so nothing is ever accepted
                # on the cofactored equation alone.
                self.metrics["rlc_shed"] += lanes
                chunks.append((np.zeros(self.batch, bool),
                               bs.txn[:lanes].copy()))
                t = st.add(_DISPATCH, t)
                continue
            fut = self._dispatch(bs, lanes)
            if not isinstance(fut, np.ndarray):
                self._bufset_fut[k] = fut
            self._disp += 1
            self.metrics["batches"] += 1
            self.metrics["lanes"] += lanes
            # only a counted device dispatch skips programs; the jnp
            # path, the mesh and host verdicts count the whole batch
            dev = self._counted and not isinstance(fut, np.ndarray)
            live, width = (lanes, self._width) if dev \
                else (self.batch, self.batch)
            self.metrics["kernel_programs"] += -(-live // self._grain)
            self.metrics["staged_lanes"] += width
            chunks.append((fut, bs.txn[:lanes].copy()))
            t = st.add(_DISPATCH, t)
        # residence of the txns that reached a device lane: each waited
        # from its gather's time to this dispatch
        on_dev = np.zeros(n, bool)
        for fut, live in chunks:
            if not isinstance(fut, np.ndarray):
                on_dev[live] = True
        per_seg = np.add.reduceat(on_dev, [r for r, _ in segs],
                                  dtype=np.int64)
        self._pending.append(
            {"chunks": chunks, "buf": buf, "sizes": sizes,
             "tags": tags, "cand": cand, "n": n, "reserved": reserved,
             "t_disp": t, "timed": int(per_seg.sum()),
             "hold_ns": sum(int(c) * (t - tg)
                            for c, (_, tg) in zip(per_seg, segs))})
        while len(self._pending) > self.inflight:
            self._drain(block=True, max_sets=1)

    @staticmethod
    def _chunk_ready(fut) -> bool:
        if isinstance(fut, np.ndarray):
            return True                  # CPU-fallback verdicts: final
        try:
            return fut.is_ready()
        except AttributeError:           # backend without is_ready()
            return False

    def _drain(self, block: bool, max_sets: int | None = None):
        """Retire pending device batches: oldest-first, stopping at the
        first unresolved one when block=False."""
        done = 0
        while self._pending and (max_sets is None or done < max_sets):
            rec = self._pending[0]
            if not block and not all(self._chunk_ready(f)
                                     for f, _ in rec["chunks"]):
                return
            self._pending.popleft()
            self._finalize(rec)
            done += 1

    def _host_verify_payload(self, p: bytes) -> bool:
        """Reference-path verdict for ONE raw txn payload, with the
        same fail-closed rules as the device lane assembler: parse must
        succeed, over-MTU messages are dropped, every signature must
        verify. The single source of truth for both the record-recovery
        and deferred-duplicate slow paths."""
        from ..protocol.txn import parse_txn
        from ..utils.ed25519_ref import verify as _ref_verify
        try:
            t = parse_txn(p)
        except Exception:
            return False
        msg = t.message(p)
        if len(msg) > self.max_len:
            return False                 # assembler drops over-MTU too
        return all(_ref_verify(sig, pub, msg)
                   for sig, pub in zip(t.signatures(p),
                                       t.signer_pubkeys(p)))

    def _cpu_verify_record(self, rec) -> np.ndarray:
        """Re-verify a whole record's candidate txns on the host from
        the ORIGINAL frames (the lane buffers may already be reused by
        later dispatches) — the readback-failure recovery path."""
        buf, sizes, cand = rec["buf"], rec["sizes"], rec["cand"]
        ok = np.zeros(rec["n"], bool)
        for k, i in enumerate(np.nonzero(cand)[0]):
            self._hb_tick(k)
            ok[i] = self._host_verify_payload(bytes(buf[i, :sizes[i]]))
        return ok

    def _finalize(self, rec):
        """Readback verdicts and batch-publish one record (tags were
        already reserved at dispatch)."""
        st = self.stages
        n, cand = rec["n"], rec["cand"]
        txn_ok = cand.copy()
        covered = np.zeros(n, bool)
        rb_t0 = monotonic_ns()
        # the readback stage (and the tile's TPU-attributed time) closes
        # at the end of the device-verdict wait — never around the CPU
        # re-verify fallback, which would blame the device for host work
        try:
            had_device = False
            for fut, live in rec["chunks"]:
                had_device |= not isinstance(fut, np.ndarray)
                lane_ok = self._read_verdicts(fut)
                covered[live] = True
                # a txn passes only if ALL its signature lanes verified
                failed = live[~lane_ok[:len(live)]]
                txn_ok[failed] = False
            txn_ok &= covered
            t_ready = st.add(_READBACK, rb_t0)
            if had_device:
                self._consec_fail = 0    # a healthy device round-trip
                self.tpu_hist.add(t_ready - rb_t0)
        except Exception:
            # lost verdicts (device died mid-flight / readback timeout):
            # recompute the whole record on the CPU reference path — the
            # batch still serves rather than dropping or crashing. The
            # readback span closes HERE (the device wait up to the
            # failure), before the CPU re-verify starts.
            t_ready = st.add(_READBACK, rb_t0)
            self.tpu_hist.add(t_ready - rb_t0)
            self.metrics["device_errors"] += 1
            self._consec_fail += 1
            if self._consec_fail >= self.device_fail_limit:
                self._degrade("readback failures")
            txn_ok = self._cpu_verify_record(rec)
        self.metrics["verify_fail"] += int((cand & ~txn_ok).sum())

        # the dispatch-time reservations released themselves when this
        # record popped off _pending (the window IS the pending queue);
        # tcache insert happens only for txns whose signatures VERIFIED
        # (ref order, poisoning resistance). A racing duplicate between
        # query and insert is dropped here (insert returns "already
        # present").
        dup_post = self.tcache.insert_batch(rec["tags"],
                                            mask=txn_ok.astype(np.uint8))
        late = txn_ok & (dup_post != 0)
        self.metrics["dedup_drop"] += int(late.sum())
        txn_ok &= dup_post == 0
        t = st.add(_FINALIZE, t_ready)
        self._resolve_deferred(rec["reserved"])
        t = st.add(_DEFERRED, t)

        mask = txn_ok.astype(np.uint8)
        start, fwd = 0, 0
        while True:
            start, pub = self.out_ring.publish_batch(
                rec["buf"], rec["sizes"], rec["tags"], mask,
                fseqs=self.out_fseqs, start=start)
            fwd += pub
            if start >= n:
                break
            # out of downstream credits mid-batch
            if not self._wait_credits():
                break               # halted while backpressured
        t_pub = st.add(_PUBLISH, t)
        self.metrics["tx"] += fwd
        m = rec["timed"]
        if m:
            self.metrics["txn_timed"] += m
            self.metrics["txn_hold_ns"] += rec["hold_ns"]
            self.metrics["txn_device_ns"] += m * (t_ready - rec["t_disp"])
            self.metrics["txn_egress_ns"] += m * (t_pub - t_ready)
        if self._trace is not None and fwd:
            # frag-lineage anchors: (sampled) publish records keyed by
            # dedup tag — the sig the downstream consume hooks carry,
            # so one microbatch is followable verify -> dedup -> pack
            # across rings; one vectorized append for the whole batch
            from ..trace.events import EV_PUBLISH
            self._trace.frag_batch(EV_PUBLISH, rec["tags"][mask != 0],
                                   link=self._trace_link)

    def _resolve_deferred(self, released_tags):
        """Decide duplicates parked while their tag was in flight: the
        reserver PASSED (tag now in the tcache) -> true duplicates,
        dropped; the reserver FAILED -> each parked copy is re-verified
        on the host reference path and forwarded if genuine (the
        censorship-resistance half of the reservation contract). The
        slow path only runs for dups that raced the pipeline window."""
        hb = 0
        # deferred-duplicate recovery: bounded by _deferred_cap, runs
        # only for dups that raced the in-flight window, never on the
        # batched ingest/egress path
        # fdlint: disable=per-frag-loop — bounded raced-dup slow path
        for t in np.asarray(released_tags, np.uint64).tolist():
            for p in self._deferred.pop(t, ()):
                self._hb_tick(hb)
                hb += 1
                self._deferred_n -= 1
                if self.tcache.query(t):
                    self.metrics["dedup_drop"] += 1
                    continue
                self.metrics["deferred_host_verifies"] += 1
                if not self._host_verify_payload(p):
                    self.metrics["verify_fail"] += 1
                    continue
                if self.tcache.insert(t):
                    self.metrics["dedup_drop"] += 1
                    continue
                if self._wait_credits():
                    self.out_ring.publish(p, sig=t)
                    self.metrics["tx"] += 1

    def _wait_credits(self) -> bool:
        """Block until the out ring has credits. Counts one backpressure
        event (not one per spin), keeps heartbeating, and aborts — returns
        False — if the tile is halted while waiting, so a dead downstream
        consumer can never wedge the tile (the reference's stance: stall
        visibly under fctl backpressure, never lap a reliable consumer,
        src/tango/fctl/fd_fctl.h:4-10)."""
        if not self.out_fseqs or self.out_ring.credits(self.out_fseqs) > 0:
            return True
        self.metrics["backpressure"] += 1
        bp_t0 = 0
        if self._trace is not None:
            bp_t0 = monotonic_ns()
        spins = 0
        while self.out_ring.credits(self.out_fseqs) <= 0:
            spins += 1
            if spins % 256 == 0:
                if self._cnc is not None:
                    self._cnc.heartbeat()
                    from ..runtime import CNC_RUN
                    if self._cnc.state != CNC_RUN:
                        return False
                time.sleep(50e-6)
        if self._trace is not None:
            # backpressure-wait attribution: the whole credit stall as
            # ONE span on the out link (not one event per spin)
            from ..trace.events import EV_BACKPRESSURE
            self._trace.span(EV_BACKPRESSURE, bp_t0,
                             link=self._trace_link)
        return True

    def flush(self):
        """Dispatch a held coalesce window, then retire every in-flight
        batch (halt path — verdicts already dispatched must still
        publish, and held ingest must not be dropped)."""
        if self._hold_n:
            self._flush_hold()
        self._drain(block=True)

    def on_halt(self):
        self.flush()

    def run(self, cnc, spin_limit: int | None = None):
        """Stem-style loop: poll until cnc leaves RUN (or spin budget)."""
        from ..runtime import CNC_RUN
        spins = 0
        self._cnc = cnc
        cnc.state = CNC_RUN
        while cnc.state == CNC_RUN:
            if not self.poll_once():
                spins += 1
                if spin_limit and spins > spin_limit:
                    break
            else:
                spins = 0
            cnc.heartbeat()
        self.flush()
