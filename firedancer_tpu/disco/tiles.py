"""Tile adapters + registry: kind string -> runnable tile object.

The reference's equivalent is the fd_topo_run_tile_t vtable each tile
exports (ref: src/disco/topo/fd_topo.h:664-684) and the main()-side
registry of tiles (ref: src/app/fdctl/main.c:20-117). An adapter is
constructed inside the tile process from (TileCtx, args) and supplies
the stem callbacks (poll_once / housekeeping / metrics_items / in_seqs).
"""
from __future__ import annotations

import json
import os
import struct
import time

import numpy as np

from ..protocol.txn import MTU
from ..trace.events import STAGE_IDS, VERIFY_STAGES

REGISTRY: dict[str, type] = {}

# the verify tile's ns_<stage> slots: its batch stages + capture
_VERIFY_NS = VERIFY_STAGES + ("capture",)
_STAGE_CAPTURE = STAGE_IDS["capture"]


def register(kind: str):
    def deco(cls):
        REGISTRY[kind] = cls
        cls.kind = kind
        return cls
    return deco


def _single(d: dict, what: str, tile: str):
    if len(d) != 1:
        raise ValueError(f"tile {tile}: expected exactly one {what}, "
                        f"got {list(d)}")
    return next(iter(d.values()))


def _gather_all(ctx, seqs: dict, mtus: dict, batch: int, handle,
                m: dict) -> int:
    """Shared multi-in-link poll loop: gather each ring, count
    overruns into m['overruns'], dispatch every frame to handle.
    With tracing on, each gathered batch leaves its (sampled) lineage
    records via ONE vectorized frag_batch append — the downstream half
    of the cross-tile frag-lineage chain, with no per-frag Python on
    the traced path. The per-frame `handle` dispatch remains: callers
    of this helper (shred/tower/…) do inherently frame-granular work
    (parse + state machine per frame), not batchable ring I/O."""
    tr = getattr(ctx, "trace", None)
    total = 0
    for ln, ring in ctx.in_rings.items():
        if ln not in seqs:
            continue
        n, seqs[ln], buf, sizes, sigs, ovr = ring.gather(
            seqs[ln], batch, mtus[ln])
        m["overruns"] += ovr
        if tr is not None and n:
            from ..trace.events import EV_CONSUME
            tr.frag_batch(EV_CONSUME, sigs[:n], link=tr.link_id(ln))
        for i in range(n):
            handle(bytes(buf[i, :sizes[i]]))
        total += n
    return total


def publish_wave(out, fseqs, frames, cnc=None, on_stall=None) -> int:
    """THE batched wave egress: one credit-gated publish_batch over
    (sig, payload) rows with stop-row resume on a mid-wave stall.
    Stalls are visible (`on_stall` per stall tick) and heartbeat; a
    tile that leaves RUN while backpressured ABORTS the wave instead
    of spinning forever (the verify `_wait_credits` contract — a dead
    or halting consumer must never wedge a producer's halt path).
    Returns rows published. Shared by pack/bank/poh/shred so the
    stall policy lives in one place."""
    k = len(frames)
    if not k:
        return 0
    wb = np.zeros((k, max(len(f) for _, f in frames)), np.uint8)
    sz = np.zeros(k, np.uint32)
    ids = np.zeros(k, np.uint64)
    for i, (sig, f) in enumerate(frames):
        wb[i, :len(f)] = np.frombuffer(f, np.uint8)
        sz[i] = len(f)
        ids[i] = sig
    start, total = 0, 0
    while True:
        stop, pub = out.publish_batch(
            wb, sz, ids, np.ones(k, np.uint8), fseqs=fseqs,
            start=start)
        total += pub
        start = stop
        if start >= k:
            return total
        if on_stall is not None:
            on_stall()
        if cnc is not None:
            cnc.heartbeat()
            from ..runtime import CNC_RUN
            if cnc.state != CNC_RUN:
                return total      # halted while backpressured: abort
        time.sleep(20e-6)


def _synth_genesis(n: int) -> dict:
    """Fund the deterministic synth signer pool (wraps mod its size):
    the ONE genesis map both the leader bank and non-leader replay
    derive from a config count."""
    from ..tiles.synth import synth_signer_seed
    from ..utils.ed25519_ref import keypair
    out = {}
    seen = set()
    for i in range(n):
        seed = synth_signer_seed(i)
        if seed in seen:
            break
        seen.add(seed)
        out[keypair(seed)[-1]] = 1 << 44
    return out


@register("synth")
class SynthAdapter:
    """Load generator (the reference's benchg tile,
    ref: src/app/shared_dev/commands/bench/fd_benchg_tile.c).
    args: count (total txns), seed, burst, rate_tps (0 = unpaced;
    token-bucket pacing for bench.py's offered-load sweep). rate_tps
    may also be a RAMP SCHEDULE — a list of (duration_s, tps) stanzas
    — so one topology boot serves a whole offered-load sweep (one
    stanza per sweep point; past the schedule's end the last stanza's
    rate holds, so a long tail never silently unpaces)."""

    METRICS = ["tx", "backpressure", "attack_tx", "attack_drop"]

    def __init__(self, ctx, args):
        import numpy as np

        from ..tiles.synth import make_signed_txns
        self.ctx = ctx
        self.count = int(args.get("count", 1024))
        # adversarial traffic plans (utils/chaos.py TRAFFIC_ACTIONS):
        # the stem fires the plan's events into on_chaos below; the
        # synth renders + floods the frames into its out ring
        self.attack_tx = 0
        self.attack_drop = 0
        self._attack_sig = 1 << 48       # ring sigs clear of tx range
        self.burst = int(args.get("burst", 32))
        rt = args.get("rate_tps", 0.0)
        if isinstance(rt, (list, tuple)) and rt:
            self.ramp = [(float(d), float(r)) for d, r in rt]
            self.rate_tps = self.ramp[0][1]
        else:
            # an EMPTY ramp list means unpaced, same as rate_tps=0
            self.ramp = None
            self.rate_tps = 0.0 if isinstance(rt, (list, tuple)) \
                else float(rt)
        self._t0 = None               # pacing clock starts on first poll
        n_unique = min(self.count, int(args.get("unique", 64)))
        txns = make_signed_txns(n_unique, seed=int(args.get("seed", 0)))
        self.out = _single(ctx.out_rings, "out link", ctx.tile_name)
        self.fseqs = _single(ctx.out_fseqs, "out link", ctx.tile_name)
        # pre-render the unique-frame pool ONCE into one padded buffer
        # and replay it: each burst is a native credit-gated batch
        # publish, never a per-txn Python loop (the benchg hot loop is
        # C for the same reason)
        stride = max((len(t) for t in txns), default=1)
        self._buf = np.zeros((n_unique, stride), np.uint8)
        self._sizes = np.zeros(n_unique, np.uint32)
        for i, t in enumerate(txns):
            self._buf[i, :len(t)] = np.frombuffer(t, np.uint8)
            self._sizes[i] = len(t)
        self._n_unique = n_unique
        self.sent = 0
        self.bp = 0

    def poll_once(self) -> int:
        import numpy as np
        if self.sent >= self.count or not self._n_unique:
            return 0
        b = min(self.burst, self.count - self.sent)
        if self.ramp is not None or self.rate_tps > 0:
            # offered-load pacing: publish no faster than the token
            # budget elapsed wall time has earned (the sweep's offered
            # axis; an unpaced synth measures capacity, not the knee)
            if self._t0 is None:
                self._t0 = time.perf_counter()
            b = min(b, self._earned(time.perf_counter() - self._t0)
                    - self.sent)
            if b <= 0:
                return 0
        idx = np.arange(self.sent, self.sent + b) % self._n_unique
        stop, pub = self.out.publish_batch(
            self._buf[idx], self._sizes[idx],
            np.arange(self.sent, self.sent + b, dtype=np.uint64),
            np.ones(b, np.uint8), fseqs=self.fseqs)
        if stop < b:
            self.bp += 1
        tr = getattr(self.ctx, "trace", None)
        if tr is not None and pub:
            from ..trace.events import EV_PUBLISH
            tr.frag_batch(
                EV_PUBLISH,
                np.arange(self.sent, self.sent + pub, dtype=np.uint64),
                link=tr.link_id(next(iter(self.ctx.out_rings))))
        self.sent += pub
        return pub

    def on_chaos(self, ev: dict):
        """Adversarial traffic plan hook (stem hands TRAFFIC_ACTIONS
        events here AFTER recording the EV_CHAOS injection): render
        the attack pool and flood the out ring at line rate. The flood
        is credit-gated like all egress but NEVER spins: rows the ring
        refuses are dropped-newest (attack_drop) — hostile traffic
        must not be able to wedge the attacker tile's own halt path
        either."""
        import numpy as np

        from ..utils.chaos import TRAFFIC_ACTIONS, attack_frames
        if ev["action"] not in TRAFFIC_ACTIONS:
            return
        frames = attack_frames(ev["action"], ev["frames"],
                               seed=ev["seed"], pool=ev["pool"])
        if not frames:
            return
        k = len(frames)
        wb = np.zeros((k, max(len(f) for f in frames)), np.uint8)
        sz = np.zeros(k, np.uint32)
        for i, f in enumerate(frames):
            wb[i, :len(f)] = np.frombuffer(f, np.uint8)
            sz[i] = len(f)
        sigs = np.arange(self._attack_sig, self._attack_sig + k,
                         dtype=np.uint64)
        self._attack_sig += k
        start = 0
        stalls = 0
        while start < k:
            stop, pub = self.out.publish_batch(
                wb, sz, sigs, np.ones(k, np.uint8),
                fseqs=self.fseqs, start=start)
            self.attack_tx += pub
            if stop == start:
                stalls += 1
                if stalls >= 2:          # no credits twice: drop rest
                    self.attack_drop += k - start
                    break
                time.sleep(50e-6)
                continue
            stalls = 0
            start = stop

    def _earned(self, dt: float) -> int:
        """Token budget earned after dt seconds: flat rate, or the
        ramp schedule's integral (holding the last stanza's rate past
        the end)."""
        if self.ramp is None:
            return int(dt * self.rate_tps)
        total = 0.0
        for d, r in self.ramp:
            if dt <= d:
                return int(total + dt * r)
            total += d * r
            dt -= d
        return int(total + dt * self.ramp[-1][1])

    def metrics_items(self):
        return {"tx": self.sent, "backpressure": self.bp,
                "attack_tx": self.attack_tx,
                "attack_drop": self.attack_drop}


@register("verify")
class VerifyAdapter:
    """TPU sigverify bridge tile (ref: src/disco/verify/fd_verify_tile.h).
    args: batch, max_len, tcache (name), device_retries,
    device_timeout_s, device_fail_limit, chaos (fault plan),
    mode ("strict" | "bulk_prefilter" — the r14 RLC flood front door),
    prefilter_shed (allow shedding all-garbage chunks under
    saturation; False = filter observes but never drops)."""

    # the plan's one accelerator owner (disco/topo.py): this tile's
    # process resolves JAX pinned to the device platform
    DEVICE = True
    METRICS = ["rx", "parse_fail", "dedup_drop", "verify_fail", "tx",
               "overruns", "batches", "backpressure", "device_errors",
               "cpu_fallback",
               # bulk RLC pre-filter (mode="bulk_prefilter"): equation
               # runs / passes / lanes / lanes shed / kernel ns — the
               # rlc_prefilter_vps bench stanza reads lanes & ns
               "rlc_batches", "rlc_pass", "rlc_lanes", "rlc_shed",
               "rlc_ns",
               # device telemetry (fdmetrics v2): promoted by the
               # prometheus renderer to fdtpu_tile_tpu_* series
               "tpu_jit_compiles", "tpu_jit_cache_miss",
               "tpu_inflight", "tpu_mem_bytes",
               # fdprof: warmup compile wall time + device-capture
               # windows served (the observability of the profiler)
               "tpu_compile_ns", "prof_captures",
               # stage accounting (tiles/verify.py): host ns per stage
               # (trace/events.py STAGE_IDS; capture = a device
               # capture's own start/stop), strict lanes dispatched,
               # parked duplicates and their host re-verifies, and the
               # summed residence of the txns that reached a device lane,
               # the strict kernel's grid programs run and the lanes each
               # device dispatch shipped
               "ns_gather", "ns_parse", "ns_dedup", "ns_assemble",
               "ns_dispatch", "ns_readback", "ns_finalize",
               "ns_deferred", "ns_publish", "ns_capture",
               "lanes", "deferred_parked", "deferred_host_verifies",
               "txn_hold_ns", "txn_device_ns", "txn_egress_ns",
               "txn_timed", "kernel_programs", "staged_lanes"]
    GAUGES = ["cpu_fallback", "tpu_jit_compiles", "tpu_jit_cache_miss",
              "tpu_inflight", "tpu_mem_bytes", "tpu_compile_ns"]
    # declared (not name-sniffed) device-telemetry slots: the renderer
    # promotes these to first-class fdtpu_tile_<name> families
    DEVICE_SERIES = ["tpu_jit_compiles", "tpu_jit_cache_miss",
                     "tpu_inflight", "tpu_mem_bytes",
                     "tpu_compile_ns"]

    def __init__(self, ctx, args):
        from ..tiles.verify import VerifyTile
        self.ctx = ctx
        in_ring = _single(ctx.in_rings, "in link", ctx.tile_name)
        out_ring = _single(ctx.out_rings, "out link", ctx.tile_name)
        tc_name = args.get("tcache")
        tc = ctx.tcaches[tc_name] if tc_name \
            else _single(ctx.tcaches, "tcache", ctx.tile_name)
        seed = bytes.fromhex(ctx.plan["seed"]) if "seed" in ctx.plan \
            else None
        kw = {}
        if "device_timeout_s" in args:
            kw["device_timeout_s"] = float(args["device_timeout_s"])
        out_ln = next(iter(ctx.out_rings))
        self.tile = VerifyTile(
            in_ring, out_ring, tc,
            batch=int(args.get("batch", 256)),
            max_len=int(args.get("max_len", MTU)),
            out_fseqs=_single(ctx.out_fseqs, "out link", ctx.tile_name),
            dedup_seed=seed,
            rr_cnt=int(args.get("rr_cnt", 1)),
            rr_idx=int(args.get("rr_idx", 0)),
            devices=int(args.get("devices", 1)),
            device_retries=int(args.get("device_retries", 2)),
            device_fail_limit=int(args.get("device_fail_limit", 3)),
            coalesce_us=float(args.get("coalesce_us", 0.0)),
            mode=args.get("mode", "strict"),
            prefilter_shed=bool(args.get("prefilter_shed", True)),
            chaos=args.get("chaos"),
            trace=ctx.trace,
            trace_link=(ctx.trace.link_id(out_ln)
                        if ctx.trace is not None else 0),
            trace_link_in=(ctx.trace.link_id(next(iter(ctx.in_rings)))
                           if ctx.trace is not None else 0), **kw)
        self.tile._cnc = ctx.cnc
        self.in_link = next(iter(ctx.in_rings))
        self.tile.seq = ctx.in_seq0.get(self.in_link, 0)
        # device-time attribution: the stem flushes this accumulator
        # into the tile's third (tpu) histogram slot; a stall names the
        # stage the tile's clock saw take longest
        self.tpu_hist = self.tile.tpu_hist
        self.stages = self.tile.stages
        # fdprof device side: compile-event watch (EV_COMPILE + a
        # manifest when profiled) and the capture doorbell handler —
        # both polled at housekeeping cadence, never in the hot loop
        from ..prof.device import CompileWatch, DeviceCapture
        prof = getattr(ctx, "prof", None)
        self._compile_watch = CompileWatch(
            ctx.plan, ctx.tile_name, self._jit_compiles,
            trace=ctx.trace, mem_fn=self._device_mem,
            manifest=prof is not None)
        self._capture = DeviceCapture(
            ctx.plan, ctx.tile_name, prof,
            trace=ctx.trace) if prof is not None else None

    def poll_once(self) -> int:
        return self.tile.poll_once()

    def housekeeping(self):
        self._compile_watch.poll()
        if self._capture is not None:
            # a window opening, closing or landing on disk is the
            # capture stage: the profiler's own start/stop, not the
            # tile's work
            from ..utils.tempo import monotonic_ns
            was, t0 = self._capture.active, monotonic_ns()
            landed = self._capture.poll()
            if self._capture.active != was or landed:
                self.stages.add(_STAGE_CAPTURE, t0)

    def on_halt(self):
        if self._capture is not None:
            self._capture.flush()   # never leave the doorbell hanging
        self.tile.flush()      # publish verdicts already in flight

    def in_seqs(self):
        return {self.in_link: self.tile.seq}

    def _jit_compiles(self) -> int:
        """Compiled-variant count of the verify jit (the steady-state
        contract is ONE shape — anything past the warmed entry is a
        recompile the padding discipline should have prevented)."""
        try:
            return int(self.tile._fn._cache_size())
        except Exception:                # noqa: BLE001 — jax-version API
            return 0

    def _device_mem(self) -> int:
        """Device bytes in use via memory_stats(); gracefully 0 on
        backends (CPU) that expose none."""
        try:
            import jax
            st = jax.local_devices()[0].memory_stats()
            return int(st.get("bytes_in_use", 0)) if st else 0
        except Exception:                # noqa: BLE001
            return 0

    def metrics_items(self):
        m = dict(self.tile.metrics)
        compiles = self._jit_compiles()
        m["tpu_jit_compiles"] = compiles
        m["tpu_jit_cache_miss"] = max(0, compiles - 1)
        m["tpu_inflight"] = len(self.tile._pending)
        m["tpu_mem_bytes"] = self._device_mem()
        m["tpu_compile_ns"] = self.tile.compile_ns
        m["prof_captures"] = self._capture.captures \
            if self._capture is not None else 0
        ns = self.tile.stages.ns
        for name in _VERIFY_NS:
            m[f"ns_{name}"] = ns[STAGE_IDS[name]]
        return m


@register("dedup")
class DedupAdapter:
    """Global dedup stage across verify outs
    (ref: src/disco/dedup/fd_dedup_tile.c:9-20 — one tcache over all
    verify tile outputs; tags were computed upstream with the shared
    per-boot seed, carried in the frag sig field).
    args: tcache (name), batch."""

    METRICS = ["rx", "dup", "tx", "overruns", "backpressure"]

    def __init__(self, ctx, args):
        self.ctx = ctx
        self.batch = int(args.get("batch", 64))
        tc_name = args.get("tcache")
        self.tcache = ctx.tcaches[tc_name] if tc_name \
            else _single(ctx.tcaches, "tcache", ctx.tile_name)
        self.out = _single(ctx.out_rings, "out link", ctx.tile_name)
        self.out_fseqs = _single(ctx.out_fseqs, "out link", ctx.tile_name)
        self.seqs = ctx.in_seqs0()
        self.mtu = max(ctx.plan["links"][ln]["mtu"] for ln in ctx.in_rings)
        self.m = {k: 0 for k in self.METRICS}
        # trace link ids resolved ONCE — the per-frag hook below must
        # stay a bare method call on the traced path
        self._tr = getattr(ctx, "trace", None)
        if self._tr is not None:
            out_ln = next(iter(ctx.out_rings))
            self._tr_out = self._tr.link_id(out_ln)
            self._tr_ins = {ln: self._tr.link_id(ln)
                            for ln in ctx.in_rings}

    def poll_once(self) -> int:
        tr = self._tr
        if tr is not None:
            from ..trace.events import EV_CONSUME, EV_PUBLISH
        total = 0
        for ln, ring in self.ctx.in_rings.items():
            n, self.seqs[ln], buf, sizes, sigs, ovr = ring.gather(
                self.seqs[ln], self.batch, self.mtu)
            self.m["overruns"] += ovr
            if not n:
                continue
            total += n
            self.m["rx"] += n
            if tr is not None:
                tr.frag_batch(EV_CONSUME, sigs[:n],
                              link=self._tr_ins[ln])
            # the whole gather dedups as ONE native insert-or-dup call
            # and forwards as credit-gated native batch publishes — no
            # per-frag Python on the global dedup stage (the reference
            # dedup hot loop is C, src/disco/dedup/fd_dedup_tile.c)
            dup = self.tcache.insert_batch(sigs[:n])
            self.m["dup"] += int(dup.sum())
            mask = (dup == 0).astype(np.uint8)
            start = 0
            while True:
                stop, pub = self.out.publish_batch(
                    buf[:n], sizes[:n], sigs[:n], mask,
                    fseqs=self.out_fseqs, start=start)
                self.m["tx"] += pub
                if tr is not None and pub:
                    live = sigs[start:stop][mask[start:stop] != 0]
                    tr.frag_batch(EV_PUBLISH, live, link=self._tr_out)
                start = stop
                if start >= n:
                    break
                # out of downstream credits mid-batch: stall visibly,
                # resume from the stop row (fd_fctl discipline)
                self.m["backpressure"] += 1
                time.sleep(20e-6)
        return total

    def in_seqs(self):
        return dict(self.seqs)

    def metrics_items(self):
        return dict(self.m)


@register("pack")
class PackAdapter:
    """Leader scheduler tile (ref: src/disco/pack/fd_pack_tile.c):
    inserts txns from the dedup stage, emits non-conflicting
    microblocks to parallel bank tiles, retires account locks on bank
    completion frags.

    Microblock wire format (one frag): u16 bank | u16 txn_cnt |
    u64 microblock_id | u64 slot | (u16 len | payload)*.
    Completion frag: u64 microblock_id (per-bank dedicated link).

    Wave discipline (r13): up to `wave` microblocks are outstanding
    per bank (the scheduler's FIFO), the whole per-poll wave for a
    bank ships as ONE credit-gated publish_batch on its link, and
    completion frags drain as one gather pass per done link — no
    per-microblock Python publish on the egress path (the reference's
    pack hot loop is C, src/disco/pack/fd_pack_tile.c).

    args: txn_in (link), bank_links (ordered list), done_links (ordered
    list, one per bank), max_txn_per_microblock, wave (max outstanding
    microblocks per bank), and the slot boundary source: slot_in (link
    carrying PoH slot frags — the production path, ref fd_poh.h leader
    slot handoff) or slot_ms (wall-clock fallback for poh-less
    topologies)."""

    METRICS = ["rx", "parse_fail", "inserted", "scheduled", "microblocks",
               "completions", "blocks", "backpressure", "overruns",
               "bundles", "bundle_rejects"]

    def __init__(self, ctx, args):
        from ..pack import PackScheduler, PackLimits
        from ..pack.scheduler import (meta_from_payload,
                                      meta_from_resolved)
        self._meta_from_payload = meta_from_payload
        # resolved_in: txn_in carries RESOLVED frames from a resolv
        # tile (account sets + cost precomputed upstream, the
        # reference's resolv->pack seam); bundles stay raw payloads
        self._meta_txn_in = (meta_from_resolved
                            if args.get("resolved_in")
                            else meta_from_payload)
        self.ctx = ctx
        self.txn_in = args["txn_in"]
        self.bank_links = list(args["bank_links"])
        self.done_links = list(args["done_links"])
        assert len(self.bank_links) == len(self.done_links)
        n_banks = len(self.bank_links)
        mtu = min(ctx.plan["links"][ln]["mtu"] for ln in self.bank_links)
        self.sched = PackScheduler(
            bank_cnt=n_banks,
            limits=PackLimits(
                max_txn_per_microblock=int(
                    args.get("max_txn_per_microblock", 31)),
                max_data_bytes_per_microblock=mtu - 20))
        self.slot_in = args.get("slot_in")
        self.bundle_in = args.get("bundle_in")
        self.slot_ms = float(args.get("slot_ms", 400.0))
        self._slot_t0 = time.monotonic()
        self.batch = int(args.get("batch", 64))
        self.wave = max(1, int(args.get("wave", 4)))
        self.seqs = ctx.in_seqs0()
        self.in_mtu = ctx.plan["links"][self.txn_in]["mtu"]
        from collections import deque
        # outstanding microblock ids per bank, FIFO (wave depth deep;
        # the scheduler holds the matching lock masks in its own queue)
        self.busy = [deque() for _ in range(n_banks)]
        self._next_mb = 0
        self.cur_slot = 0                 # advanced by PoH slot frags
        self.m = {k: 0 for k in self.METRICS}

    def _serialize(self, bank: int, mb_id: int, metas) -> bytes:
        out = bytearray(struct.pack("<HHQQ", bank, len(metas), mb_id,
                                    self.cur_slot))
        for m in metas:
            out += struct.pack("<H", len(m.payload)) + m.payload
        return bytes(out)

    def poll_once(self) -> int:
        total = 0
        # 1) retire completions in batch (frees account locks first —
        # matches the reference's poll order so banks never starve):
        # each done link's gather drains as one pass over the sig
        # array; completions arrive in the bank's FIFO execution order,
        # so retiring matches the scheduler's oldest-first queue
        for bank, ln in enumerate(self.done_links):
            ring = self.ctx.in_rings[ln]
            n, self.seqs[ln], buf, sizes, sigs, ovr = ring.gather(
                self.seqs[ln], self.batch, 64)
            self.m["overruns"] += ovr
            q = self.busy[bank]
            for mb_id in sigs[:n].tolist():
                if q and q[0] == mb_id:
                    q.popleft()
                    self.sched.microblock_done(bank)
                    self.m["completions"] += 1
            total += n
        # 2) ingest new txns
        ring = self.ctx.in_rings[self.txn_in]
        n, self.seqs[self.txn_in], buf, sizes, sigs, ovr = ring.gather(
            self.seqs[self.txn_in], self.batch, self.in_mtu)
        self.m["overruns"] += ovr
        for i in range(n):
            try:
                self.sched.insert(
                    self._meta_txn_in(bytes(buf[i, :sizes[i]])))
                self.m["inserted"] += 1
            except Exception:
                self.m["parse_fail"] += 1
        self.m["rx"] += n
        total += n
        # 2a) bundle ingest (ordered atomic groups from the bundle
        # tile; wire: u8 count | count x (u16 len | payload))
        if self.bundle_in:
            ring = self.ctx.in_rings[self.bundle_in]
            k, self.seqs[self.bundle_in], buf, sizes, sigs, ovr = \
                ring.gather(self.seqs[self.bundle_in], 8,
                            self.ctx.plan["links"][self.bundle_in]["mtu"])
            self.m["overruns"] += ovr
            for i in range(k):
                frame = bytes(buf[i, :sizes[i]])
                try:
                    metas = []
                    cnt = frame[0]
                    off = 1
                    for _ in range(cnt):
                        (ln2,) = struct.unpack_from("<H", frame, off)
                        off += 2
                        metas.append(self._meta_from_payload(
                            frame[off:off + ln2]))
                        off += ln2
                    self.sched.insert_bundle(metas)
                    self.m["bundles"] += 1
                    self.m["inserted"] += cnt
                except Exception:
                    self.m["bundle_rejects"] += 1
            total += k
        # 2b) PoH slot boundaries (tick-count-driven, not wall clock)
        if self.slot_in:
            ring = self.ctx.in_rings[self.slot_in]
            k, self.seqs[self.slot_in], buf, sizes, sigs, ovr = \
                ring.gather(self.seqs[self.slot_in], 4, 16)
            self.m["overruns"] += ovr
            for i in range(k):
                self.sched.end_block()
                self.m["blocks"] += 1
                # slot frag payload = u64 completed slot (poh tile)
                (done_slot,) = struct.unpack_from("<Q", buf[i], 0)
                self.cur_slot = done_slot + 1
            total += k
        # 3) fill banks in WAVES: schedule up to the per-bank wave
        # budget (bounded by the link's credit window so the batched
        # publish below cannot stall mid-wave against a live
        # consumer), serialize the whole wave into one buffer, and
        # ship it as ONE credit-gated publish_batch per bank link —
        # batch-grain egress, zero per-microblock Python publish
        for bank, ln in enumerate(self.bank_links):
            out = self.ctx.out_rings[ln]
            fseqs = self.ctx.out_fseqs[ln]
            room = self.wave - len(self.busy[bank])
            if room <= 0:
                continue
            if fseqs:
                cr = out.credits(fseqs)
                if cr <= 0:
                    self.m["backpressure"] += 1
                    continue
                room = min(room, cr)
            frames = []
            while len(frames) < room:
                metas = self.sched.schedule_microblock(bank)
                if not metas:
                    break
                mb_id = self._next_mb
                self._next_mb += 1
                frames.append((mb_id,
                               self._serialize(bank, mb_id, metas)))
                self.busy[bank].append(mb_id)
                self.m["scheduled"] += len(metas)
                self.m["microblocks"] += 1
            if not frames:
                continue

            # the credit pre-check bounds the wave, so a mid-wave
            # stall can only mean a consumer rewound its fseq: stall
            # visibly, resume from the stop row, abort on halt
            def bp():
                self.m["backpressure"] += 1
            publish_wave(out, fseqs, frames,
                         cnc=getattr(self.ctx, "cnc", None),
                         on_stall=bp)
            total += len(frames)
        return total

    def housekeeping(self):
        # wall-clock slot fallback, only when no PoH slot link is wired
        if not self.slot_in and \
                (time.monotonic() - self._slot_t0) * 1e3 >= self.slot_ms:
            self.sched.end_block()
            self._slot_t0 = time.monotonic()
            self.m["blocks"] += 1
            self.cur_slot += 1

    def in_seqs(self):
        return dict(self.seqs)

    def metrics_items(self):
        return dict(self.m)


# exec-family wire (r16): bank -> exec dispatch frame is
# u64 wave_seq | u64 xid | u16 txn_cnt, then txn_cnt x
# (32B src | 32B dst | u64 amount | u64 fee); exec -> bank completion
# frag is u64 wave_seq | u32 ok | u32 fail
_EXEC_HDR = struct.Struct("<QQH")
_EXEC_TXN = struct.Struct("<QQ")
_EXEC_TXN_SZ = 64 + _EXEC_TXN.size
_EXEC_DONE = struct.Struct("<QII")


def _conflict_groups(txns):
    """Union-find partition of a wave's transfers into account-disjoint
    conflict groups, each group in original txn order. Groups can run
    concurrently on different exec tiles without breaking the serial
    fiction; txns INSIDE a group must execute in order on one tile
    (pack only prevents conflicts against OTHER banks' outstanding
    microblocks — same-bank microblocks may conflict pairwise)."""
    parent = {}

    def find(k):
        while parent[k] != k:
            parent[k] = parent[parent[k]]
            k = parent[k]
        return k

    for t in txns:
        parent.setdefault(t.src, t.src)
        parent.setdefault(t.dst, t.dst)
        ra, rb = find(t.src), find(t.dst)
        if ra != rb:
            parent[ra] = rb
    groups = {}
    for t in txns:
        groups.setdefault(find(t.src), []).append(t)
    return list(groups.values())


class ExecFanout:
    """Sharded exec-family wave scheduler — the r16 bank fan-out
    machinery, factored out in r17 so the replay tile catches up over
    the SAME engine the leader executes with. Owns the per-shard
    dispatch/completion rings, the one-fork-per-attempt discipline
    (wave_seq == xid: one monotonic counter identifies both the fork
    and the attempt, so a cancelled attempt's late completions can
    never alias the retry's), the conflict-group round-robin across
    shards (groups are account-disjoint across tiles; a group bigger
    than a link frame splits into consecutive frames on the SAME ring,
    executed in order at the fork layer), and timeout cancel +
    whole-wave redispatch when a shard dies mid-wave — exactly-once
    application, no wedged producer.

    The OWNER supplies on_commit(tag, xid, ok, fail), called when a
    wave fully completes: the bank publishes the fork immediately and
    flushes its poh/done frames; replay folds the fork's delta into
    the bank-hash lattice FIRST, then publishes. xid is None when the
    wave carried no transfers (no fork was prepared). `m` is the
    owner's metrics dict (needs exec_waves/exec_redispatch/overruns)."""

    def __init__(self, ctx, funk, exec_links, exec_done, m,
                 on_commit=None, redispatch_s=2.0):
        self.ctx = ctx
        self.funk = funk
        self.m = m
        self.on_commit = on_commit
        self.redispatch_s = float(redispatch_s)
        self.exec_links = list(exec_links)
        self.exec_done = list(exec_done)
        if len(self.exec_links) != len(self.exec_done):
            raise ValueError(
                f"{ctx.tile_name}: exec_links/exec_done must pair up, "
                f"got {self.exec_links} / {self.exec_done}")
        self._exec_out = [(ctx.out_rings[ln], ctx.out_fseqs[ln])
                          for ln in self.exec_links]
        self._done_rings = [ctx.in_rings[ln] for ln in self.exec_done]
        self.done_seq = {ln: ctx.in_seq0.get(ln, 0)
                         for ln in self.exec_done}
        self._exec_cap = []
        for ln in self.exec_links:
            cap = (ctx.plan["links"][ln]["mtu"] - _EXEC_HDR.size) \
                // _EXEC_TXN_SZ
            if cap < 1:
                raise ValueError(
                    f"{ctx.tile_name}: exec link {ln} mtu "
                    f"{ctx.plan['links'][ln]['mtu']} can't carry one "
                    f"dispatch txn ({_EXEC_HDR.size + _EXEC_TXN_SZ}B)")
            self._exec_cap.append(cap)
        self._next_xid = 1
        self.wave = None               # in-flight wave state

    @property
    def busy(self) -> bool:
        return self.wave is not None

    def dispatch(self, txns, tag=None):
        """Start a wave (exactly ONE outstanding — waves stay serial,
        so cross-wave conflicts need no tracking at all). `tag` rides
        the wave untouched and comes back in on_commit."""
        assert self.wave is None, "one wave outstanding"
        self.wave = {"tag": tag, "txns": list(txns), "xid": None,
                     "wave_seq": None, "remaining": 0, "ok": 0,
                     "fail": 0, "deadline": None}
        self._send()

    def _send(self):
        """(Re-)dispatch the in-flight wave under a FRESH fork:
        conflict groups round-robin across the exec tiles, each group
        intact and in order on ONE tile."""
        w = self.wave
        if not w["txns"]:
            self._commit()
            return
        xid = self._next_xid
        self._next_xid += 1
        self.funk.txn_prepare(None, xid)
        per_tile = [[] for _ in self.exec_links]
        for gi, g in enumerate(_conflict_groups(w["txns"])):
            per_tile[gi % len(per_tile)].extend(g)
        cnc = getattr(self.ctx, "cnc", None)
        sent = 0
        for ti, tl in enumerate(per_tile):
            if not tl:
                continue
            out, fseqs = self._exec_out[ti]
            cap = self._exec_cap[ti]
            frames = []
            for i in range(0, len(tl), cap):
                chunk = tl[i:i + cap]
                body = b"".join(
                    t.src + t.dst + _EXEC_TXN.pack(t.amount, t.fee)
                    for t in chunk)
                frames.append(
                    (xid, _EXEC_HDR.pack(xid, xid, len(chunk)) + body))
            publish_wave(out, fseqs, frames, cnc=cnc)
            sent += len(frames)
        w.update(xid=xid, wave_seq=xid, remaining=sent, ok=0, fail=0,
                 deadline=time.monotonic() + self.redispatch_s)
        self.m["exec_waves"] += 1

    def poll(self, allow_redispatch=True) -> int:
        """Drain completion frags; commit the wave when every dispatch
        frame completed, cancel + re-dispatch whole under a fresh fork
        on deadline (an exec tile died mid-wave and its ring rejoin
        skipped the frames) — the store stays consistent, the owner
        never wedges."""
        total = 0
        for ln, ring in zip(self.exec_done, self._done_rings):
            n, self.done_seq[ln], buf, sizes, _sigs, ovr = \
                ring.gather(self.done_seq[ln], 64, 64)
            self.m["overruns"] += ovr
            total += n
            for i in range(n):
                ws, ok, fail = _EXEC_DONE.unpack_from(
                    bytes(buf[i, :sizes[i]]), 0)
                w = self.wave
                if w is None or ws != w["wave_seq"]:
                    continue       # a cancelled attempt's leftovers
                w["remaining"] -= 1
                w["ok"] += ok
                w["fail"] += fail
        w = self.wave
        if w is not None and w["wave_seq"] is not None:
            if w["remaining"] <= 0:
                self._commit()
            elif allow_redispatch \
                    and time.monotonic() > w["deadline"]:
                self.m["exec_redispatch"] += 1
                self.funk.txn_cancel(w["xid"])
                self._send()
        return total

    def _commit(self):
        w = self.wave
        self.wave = None
        if self.on_commit is not None:
            self.on_commit(w["tag"], w["xid"], w["ok"], w["fail"])

    def halt(self):
        """Bounded drain, then cancel: a wave already dispatched gets
        redispatch_s to complete (exec tiles are halting too); after
        the window the fork is cancelled — no partial commits in the
        store, no on_commit for a wave that never finished."""
        t0 = time.monotonic()
        while self.wave is not None \
                and time.monotonic() - t0 < self.redispatch_s:
            self.poll(allow_redispatch=False)
            if self.wave is not None:
                time.sleep(0.001)
        if self.wave is not None:
            if self.wave["xid"] is not None:
                self.funk.txn_cancel(self.wave["xid"])
            self.wave = None


@register("bank")
class BankAdapter:
    """Execution stage (ref: src/discoh/bank/fd_bank_tile.c shape:
    consume microblock, execute, emit completion; execution entry
    src/flamenco/runtime/fd_runtime.h:254-266).

    exec="svm": parse each txn, execute system-program transfers
    through the wave executor (svm/executor.py — conflict-DAG waves as
    one lax.scan) against a process-local funk fork per microblock,
    and forward the executed microblock (with a PoH mixin hash) on the
    optional poh link. Multi-bank topologies share no account state yet
    (the shm-resident accdb is a future component), so use one bank
    tile with exec="svm".

    exec="general": the FULL host SVM per microblock — every txn runs
    through TxnExecutor (system incl. seed/nonce, vote, stake, ALUT,
    precompiles, deployed sBPF with CPI), staged through the conflict
    DAG in wave order (serial fiction preserved); this is the real
    execution stage, svm's wave path remains the device-batched
    transfer fast lane.

    exec="stub": count txns and ack (ring-plumbing tests).

    Device-wave execution (r13): the tile gathers up to `wave`
    microblocks per poll and executes them as ONE device dispatch —
    conflict tables for the whole wave are lane-assembled into one
    packed staging buffer (svm/executor.py WaveExecutor, the verify
    tile's _StageBuf discipline) whose balance-independent transfer is
    issued BEFORE the previous wave retires, so it overlaps that
    wave's compute; the previous wave then commits and its poh-mixin
    frames + completion frags publish as one credit-gated
    publish_batch per link. Serial fiction holds across waves because
    balances are read only after the prior wave's commit, and the
    conflict DAG orders intra-wave dependencies.

    Exec tile fan-out (r16): with `exec_links`/`exec_done` the bank
    keeps only wave scheduling, commit ordering and the PoH handoff —
    execution moves to the exec tile family over the shm funk store
    (plan["funk"], backend "shm"). The gathered wave's transfers are
    partitioned into CONFLICT GROUPS (union-find over account keys —
    pack only guarantees non-conflict against OTHER banks' outstanding
    microblocks, so same-bank waves may conflict internally and rely
    on ordered execution); each group ships intact, in order, to one
    exec tile as dispatch frames under ONE funk fork the bank
    prepared. Groups are account-disjoint across tiles, so concurrent
    execution preserves the serial fiction. The bank publishes the
    fork only after every dispatch frame completed; a wave that
    doesn't complete within `redispatch_s` (an exec tile died
    mid-wave and its ring rejoin skipped the frames) is CANCELLED —
    dropping every partial commit — and re-dispatched whole under a
    fresh fork, so a supervised exec restart never wedges the leader
    loop or leaves the store half-written.

    Dispatch frame wire: u64 wave_seq | u64 xid | u16 txn_cnt |
    txn_cnt x (32B src | 32B dst | u64 amount | u64 fee).
    Completion frag: u64 wave_seq | u32 ok | u32 fail.

    args: exec, wave (microblocks per device wave), poh_link (optional
    out link name), exec_links/exec_done (ordered per-exec-shard
    dispatch/completion links), redispatch_s, done link = the
    remaining out link."""

    METRICS = ["microblocks", "txns", "transfers", "exec_skip",
               "exec_fail", "overruns", "rpc_port", "ws_port",
               "rewards_paid", "exec_waves", "exec_redispatch"]
    GAUGES = ["rpc_port", "ws_port"]

    def __init__(self, ctx, args):
        self.ctx = ctx
        self.exec_links = list(args.get("exec_links") or [])
        self.exec_done = list(args.get("exec_done") or [])
        if len(self.exec_links) != len(self.exec_done):
            raise ValueError(
                f"bank {ctx.tile_name}: exec_links/exec_done must "
                f"pair up, got {self.exec_links} / {self.exec_done}")
        non_done = [ln for ln in ctx.in_rings
                    if ln not in self.exec_done]
        if len(non_done) != 1:
            raise ValueError(f"bank tile {ctx.tile_name}: one in link")
        self.in_link = non_done[0]
        self.ring = ctx.in_rings[self.in_link]
        self.exec_mode = args.get("exec", "stub")
        self.poh_link = args.get("poh_link")
        if self.poh_link:
            self.poh_out = ctx.out_rings[self.poh_link]
            self.poh_fseqs = ctx.out_fseqs[self.poh_link]
            done = [ln for ln in ctx.out_rings
                    if ln != self.poh_link and ln not in self.exec_links]
            assert len(done) == 1, done
            self.out = ctx.out_rings[done[0]]
            self.out_fseqs = ctx.out_fseqs[done[0]]
        else:
            self.poh_out = None
            done = [ln for ln in ctx.out_rings
                    if ln not in self.exec_links]
            assert len(done) == 1, done
            self.out = ctx.out_rings[done[0]]
            self.out_fseqs = ctx.out_fseqs[done[0]]
        self.m = {k: 0 for k in self.METRICS}
        self.slot = 0                  # highest slot seen in microblocks
        self._rewards_epoch = None     # lazily read from the marker
        self.fwd_payloads = bool(args.get("forward_payloads", False))
        self.slots_per_epoch = int(args.get("slots_per_epoch", 432_000))
        if self.fwd_payloads and self.poh_out is not None:
            # fail at BOOT, not mid-flight: the poh frame re-wraps the
            # microblock txn section (micro hdr 20 -> poh hdr 42), so
            # the poh link must absorb the worst-case in-frame
            need = ctx.plan["links"][self.in_link]["mtu"] - 20 + 42
            have = ctx.plan["links"][self.poh_link]["mtu"]
            if have < need:
                raise ValueError(
                    f"bank {ctx.tile_name}: forward_payloads needs "
                    f"poh link mtu >= {need}, got {have}")
        self.wave = max(1, int(args.get("wave", 8)))
        self._pending = None           # svm: dispatched, uncommitted wave
        if self.exec_mode in ("svm", "general"):
            from ..svm.executor import WaveExecutor
            self._wx = WaveExecutor()
            from ..funk.funk import Funk
            # genesis checkpoint: restore the WHOLE boot state (funded
            # users + vote/stake accounts from app/genesis.py) — the
            # dev command's wiring; production restores from snapshot
            if self.exec_links:
                if self.exec_mode != "svm":
                    raise ValueError(
                        f"bank {ctx.tile_name}: exec_links need "
                        f"exec=\"svm\"")
                if args.get("genesis_ckpt"):
                    raise ValueError(
                        f"bank {ctx.tile_name}: genesis_ckpt is "
                        f"process-funk only, not exec fan-out")
                fk = ctx.plan.get("funk") or {}
                if fk.get("backend") != "shm" or "off" not in fk:
                    raise ValueError(
                        f"bank {ctx.tile_name}: exec_links need "
                        f"[funk] backend=\"shm\"")
                from ..funk.shmfunk import WireFunk
                self.funk = WireFunk.from_plan(ctx.wksp, fk)
            elif args.get("genesis_ckpt"):
                from ..utils.checkpt import funk_restore
                with open(args["genesis_ckpt"], "rb") as gf:
                    self.funk = funk_restore(Funk, gf)
            else:
                self.funk = Funk()
            self.xid = None            # published root
            self._next_xid = 1
            # genesis balances: airdropped synth accounts (tests inject
            # via args; production restores from snapshot)
            from ..funk.funk import key32
            for acct_hex, bal in args.get("genesis", {}).items():
                self.funk.rec_write(None, key32(bytes.fromhex(acct_hex)),
                                    int(bal))
            # genesis_synth = N: fund the deterministic synth signers
            # (config-file convenience — TOML can't derive pubkeys; the
            # committed default topology uses this). The synth signer
            # pool wraps mod 16, so fund each UNIQUE pubkey once.
            if args.get("genesis_synth"):
                for pub, bal in _synth_genesis(
                        int(args["genesis_synth"])).items():
                    self.funk.rec_write(None, key32(pub), bal)
            if self.exec_mode == "general":
                from ..svm import AccDb, TxnExecutor
                from ..svm.accdb import Account as _Acct
                # the general executor needs TYPED genesis accounts
                for key, val in list(self.funk.root_items().items()):
                    if isinstance(val, int):
                        self.funk.rec_write(None, key32(key),
                                            _Acct(lamports=val))
                self.db = AccDb(self.funk)
                self.executor = TxnExecutor(self.db)
            # optional JSON-RPC surface over this bank's state (the
            # rpc-tile seam; production would read a shared accdb,
            # ref src/discof/rpc/fd_rpc_tile.c)
            self.rpc = None
            if args.get("rpc_port") is not None:
                from ..rpc import RpcServer
                self.rpc = RpcServer(
                    lambda: {"funk": self.funk,
                             "slot": self.slot,
                             "txn_count": self.m["transfers"]},
                    port=int(args["rpc_port"]))
                self.m["rpc_port"] = self.rpc.port
            # websocket pub-sub surface (slot + account notifications,
            # ref: the rpc tile's subscription API)
            self.ws = None
            self._ws_last_slot = -1
            if args.get("ws_port") is not None:
                from ..rpc.ws import WsServer
                self.ws = WsServer(port=int(args["ws_port"]))
                self.m["ws_port"] = self.ws.port
        self.seq = ctx.in_seq0.get(self.in_link, 0)
        self.mtu = ctx.plan["links"][self.in_link]["mtu"]
        self.fanout = None             # exec-family wave scheduler
        if self.exec_links:
            self.fanout = ExecFanout(
                ctx, self.funk, self.exec_links, self.exec_done,
                self.m, on_commit=self._ef_commit,
                redispatch_s=float(args.get("redispatch_s", 2.0)))

    def _parse_payloads(self, frame, txn_cnt):
        """THE microblock frame walker (header 20, u16-framed
        payloads): -> (payloads, parsed ParsedTxns, sha256 mixin over
        first signatures). Both exec modes consume this, so the frame
        format and mixin rule live in ONE place."""
        import hashlib

        from ..protocol.txn import parse_txn
        payloads, parsed, sigs = [], [], []
        off = 20
        for _ in range(txn_cnt):
            (ln,) = struct.unpack_from("<H", frame, off)
            off += 2
            p = bytes(frame[off:off + ln])
            off += ln
            try:
                t = parse_txn(p)
                sigs.append(t.signatures(p)[0])
                payloads.append(p)
                parsed.append(t)
            except Exception:
                self.m["exec_skip"] += 1
        return payloads, parsed, hashlib.sha256(b"".join(sigs)).digest()

    def _wave_order(self, payloads, parsed, xid):
        """Conflict-DAG wave order over the microblock (pack already
        guarantees intra-microblock non-conflict, but the DAG is the
        execution contract — replay uses the identical staging).
        Resolution runs at the SAME slot the executor will use, so the
        two call sites can never disagree on table activeness."""
        from ..replay.rdisp import ConflictDag
        from ..svm.alut import AlutResolveError, resolve_loaded_keys
        dag = ConflictDag()
        for p, t in zip(payloads, parsed):
            keys = t.account_keys(p)
            flags = [t.is_writable(i) for i in range(t.acct_cnt)]
            if t.version == 0 and t.aluts:
                try:
                    lk, lw = resolve_loaded_keys(
                        self.db, xid, t, slot=self.executor.slot)
                    keys, flags = keys + lk, flags + lw
                except AlutResolveError:
                    pass              # executor fails it cleanly
            dag.add_txn([k for k, w in zip(keys, flags) if w],
                        [k for k, w in zip(keys, flags) if not w])
        for wave in dag.waves():
            for i in wave:
                yield payloads[i], parsed[i]

    def _parse_transfers(self, frame, txn_cnt):
        """Microblock frame -> (SystemTxn list — one per system-program
        Transfer instruction, in instruction order, fee charged on each
        txn's first only —, sha256 mixin over concatenated first
        signatures)."""
        import hashlib

        from ..pack.cost import SYSTEM_PROGRAM_ID
        from ..pack.scheduler import FEE_PER_SIGNATURE
        from ..protocol.txn import parse_txn
        from ..svm.executor import SystemTxn
        txns, sigs = [], []
        off = 20
        for _ in range(txn_cnt):
            (ln,) = struct.unpack_from("<H", frame, off)
            off += 2
            payload = bytes(frame[off:off + ln])
            off += ln
            try:
                t = parse_txn(payload)
            except Exception:
                self.m["exec_skip"] += 1
                continue
            sigs.append(t.signatures(payload)[0])
            keys = t.account_keys(payload)
            matched = 0
            for ins in t.instrs:
                data = payload[ins.data_off:ins.data_off + ins.data_sz]
                # system-program Transfer: u32 discriminant 2 + u64
                # lamports (fd_system_program.c transfer instruction);
                # every transfer instruction executes, fee once per txn
                if (keys[ins.prog_idx] == SYSTEM_PROGRAM_ID
                        and len(data) == 12
                        and data[:4] == b"\x02\x00\x00\x00"
                        and len(ins.acct_idxs) >= 2):
                    amt = int.from_bytes(data[4:12], "little")
                    txns.append(SystemTxn(
                        src=keys[ins.acct_idxs[0]],
                        dst=keys[ins.acct_idxs[1]], amount=amt,
                        fee=0 if matched
                        else FEE_PER_SIGNATURE * t.sig_cnt))
                    matched += 1
            if not matched:
                self.m["exec_skip"] += 1
        mixin = hashlib.sha256(b"".join(sigs)).digest()
        return txns, mixin

    def poll_once(self) -> int:
        if self.exec_links:
            return self._poll_exec_family()
        n, self.seq, buf, sizes, sigs, ovr = self.ring.gather(
            self.seq, self.wave, self.mtu)
        self.m["overruns"] += ovr
        if not n:
            # drain-on-idle: a dispatched wave always retires — queued
            # completions never wait on more microblocks arriving
            if self._pending is not None:
                self._finalize_wave()
            return 0
        # decode the wave (header walk: host control-plane, no ring
        # API per frame — every publish below is batch-grain)
        frames = []
        slots_seen = []
        for i in range(n):
            frame = bytes(buf[i, :sizes[i]])
            _bank, txn_cnt, mb_id, slot = struct.unpack_from(
                "<HHQQ", frame, 0)
            self.slot = max(self.slot, slot)
            slots_seen.append(slot)
            self.m["txns"] += txn_cnt
            self.m["microblocks"] += 1
            frames.append((frame, txn_cnt, mb_id))
        if self.exec_mode in ("svm", "general") \
                and self.ws is not None:
            # every NEW slot the wave crossed notifies, in order — a
            # slotSubscribe client must not skip intermediate slots
            for s in sorted({s for s in slots_seen
                             if s > self._ws_last_slot}):
                self._ws_last_slot = s
                self.ws.publish_slot(s)
        if self.exec_mode == "svm":
            self._wave_svm(frames)
        elif self.exec_mode == "general":
            self._wave_general(frames)
        else:
            self._flush_wave([], [mb_id for _, _, mb_id in frames])
        return n

    def _wave_svm(self, frames):
        """Stage -> (retire previous) -> dispatch: the wave's packed
        conflict tables are balance-independent, so their device
        transfer launches FIRST and overlaps the previous wave's
        compute; that wave then commits (and its completions publish)
        before this wave's balances are read — the rotating-stage
        pipeline, with serial fiction intact."""
        import hashlib
        recs, txns = [], []
        for frame, txn_cnt, mb_id in frames:
            if txn_cnt:
                t, mixin = self._parse_transfers(frame, txn_cnt)
            else:
                t, mixin = [], hashlib.sha256(b"").digest()
            recs.append((frame, txn_cnt, mb_id, mixin))
            txns.extend(t)
        staged = self._wx.stage(txns) if txns else None
        if self._pending is not None:
            self._finalize_wave()
        disp = None
        if staged is not None:
            new_xid = self._next_xid
            self._next_xid += 1
            try:
                disp = self._wx.dispatch(self.funk, self.xid, new_xid,
                                         staged)
            except Exception:
                self.funk.txn_cancel(new_xid)
                raise
        self._pending = (disp, recs)

    def _finalize_wave(self):
        """Force the pending wave's verdict futures, commit its funk
        fork, then flush its poh mixin frames + completion frags as
        one publish_batch per link."""
        from ..svm.executor import STATUS_OK
        disp, recs = self._pending
        self._pending = None
        if disp is not None:
            try:
                st = self._wx.finalize(self.funk, disp)
                self.funk.txn_publish(disp.xid)
                self.xid = None   # published into root
            except Exception:
                self.funk.txn_cancel(disp.xid)
                raise
            ok = sum(1 for s in st if s == STATUS_OK)
            self.m["transfers"] += ok
            self.m["exec_fail"] += len(st) - ok
            # ws notifications OUTSIDE the funk guard (a notification
            # error must not cancel a published txn); unique touched
            # keys, once per wave, zero cost with no subscribers
            if self.ws is not None and self.ws.has_clients:
                touched = {key for t, s in zip(disp.staged.txns, st)
                           if s == STATUS_OK
                           for key in (t.src, t.dst)}
                for key in touched:
                    self.ws.publish_account(
                        key, self.funk.rec_query(None, key),
                        self.slot)
        poh_frames = []
        if self.poh_out is not None:
            for frame, txn_cnt, mb_id, mixin in recs:
                if not txn_cnt:
                    continue
                # forward_payloads: carry the microblock's txn section
                # so poh entries feed the shred tile with real block
                # content (the reference's bank->poh hand-off keeps
                # the txns attached)
                blob = frame[20:] if self.fwd_payloads else b""
                poh_frames.append(
                    (mb_id, struct.pack("<QH", mb_id, txn_cnt)
                     + mixin + blob))
        self._flush_wave(poh_frames, [r[2] for r in recs])

    def _poll_exec_family(self) -> int:
        """Exec fan-out scheduler loop: drain completion frags, then —
        only with NO wave outstanding — gather the next wave and
        dispatch it (ExecFanout keeps waves serial, so cross-wave
        conflicts need no tracking at all)."""
        work = self.fanout.poll()
        if self.fanout.busy:
            return work
        n, self.seq, buf, sizes, sigs, ovr = self.ring.gather(
            self.seq, self.wave, self.mtu)
        self.m["overruns"] += ovr
        if not n:
            return work
        import hashlib
        recs, txns, slots_seen = [], [], []
        for i in range(n):
            frame = bytes(buf[i, :sizes[i]])
            _bank, txn_cnt, mb_id, slot = struct.unpack_from(
                "<HHQQ", frame, 0)
            self.slot = max(self.slot, slot)
            slots_seen.append(slot)
            self.m["txns"] += txn_cnt
            self.m["microblocks"] += 1
            if txn_cnt:
                t, mixin = self._parse_transfers(frame, txn_cnt)
            else:
                t, mixin = [], hashlib.sha256(b"").digest()
            recs.append((frame, txn_cnt, mb_id, mixin))
            txns.extend(t)
        if self.ws is not None:
            for s in sorted({s for s in slots_seen
                             if s > self._ws_last_slot}):
                self._ws_last_slot = s
                self.ws.publish_slot(s)
        self.fanout.dispatch(txns, tag=recs)
        return work + n

    def _ef_commit(self, recs, xid, ok, fail):
        """Fan-out wave complete: publish the fork, then flush the poh
        mixin frames + completion frags in the original microblock
        order (commit ordering stays with the bank, exactly the
        in-process paths' contract)."""
        if xid is not None:
            self.funk.txn_publish(xid)
            self.m["transfers"] += ok
            self.m["exec_fail"] += fail
        poh_frames = []
        if self.poh_out is not None:
            for frame, txn_cnt, mb_id, mixin in recs:
                if not txn_cnt:
                    continue
                blob = frame[20:] if self.fwd_payloads else b""
                poh_frames.append(
                    (mb_id, struct.pack("<QH", mb_id, txn_cnt)
                     + mixin + blob))
        self._flush_wave(poh_frames, [r[2] for r in recs])

    def _wave_general(self, frames):
        """The FULL host SVM per microblock (inherently host-serial
        per txn), with the wave's poh frames + completions flushed as
        batch publishes after the execution loop."""
        poh_frames = []
        for frame, txn_cnt, mb_id in frames:
            if txn_cnt:
                payloads, parsed, mixin = self._parse_payloads(
                    frame, txn_cnt)
                touched = set()
                if payloads:
                    new_xid = self._next_xid
                    self._next_xid += 1
                    self.funk.txn_prepare(None, new_xid)
                    # the Clock view executes at the microblock's slot;
                    # sysvar accounts materialize into this fork
                    self.executor.begin_slot(
                        new_xid, self.slot,
                        slots_per_epoch=self.slots_per_epoch)
                    # epoch boundary: pay EVERY epoch crossed since the
                    # persisted paid-through marker — covers quiet
                    # epochs with no microblocks, and a restart from
                    # snapshot resumes from the marker instead of
                    # re-paying (flamenco/rewards.py)
                    ep = self.slot // self.slots_per_epoch
                    if ep > 0:
                        from ..flamenco import rewards as _rw
                        from ..flamenco import stakes as _stakes
                        start = self._rewards_epoch
                        if start is None:
                            start = _rw.paid_through(self.funk, new_xid)
                        if ep > start:
                            import hashlib as _h
                            for e in range(start, ep):
                                # epoch-boundary duty BEFORE rewards:
                                # append epoch e's cluster totals to the
                                # StakeHistory sysvar so rate-limited
                                # warmup/cooldown engages from the
                                # bank's own state, no external seeding
                                # (ref: fd_sysvar_stake_history.c
                                # update at the boundary)
                                _stakes.update_stake_history(
                                    self.funk, new_xid, e)
                                s = _rw.distribute_epoch_rewards(
                                    self.funk, new_xid, e, None,
                                    self.slots_per_epoch,
                                    _h.sha256(b"epoch-%d" % (e + 1))
                                    .digest())
                                self.m["rewards_paid"] += s["paid"]
                            _rw.mark_paid_through(self.funk, new_xid,
                                                  ep)
                        self._rewards_epoch = ep
                    ok = fail = 0
                    try:
                        for p, t in self._wave_order(payloads, parsed,
                                                     new_xid):
                            res = self.executor.execute(new_xid, p)
                            if res.status == "ok":
                                ok += 1
                                touched.update(
                                    t.account_keys(p)[i]
                                    for i in range(t.acct_cnt)
                                    if t.is_writable(i))
                            else:
                                fail += 1
                        self.funk.txn_publish(new_xid)
                    except Exception:
                        self.funk.txn_cancel(new_xid)
                        raise
                    self.m["transfers"] += ok
                    self.m["exec_fail"] += fail
                if self.ws is not None and self.ws.has_clients:
                    for key in touched:
                        self.ws.publish_account(
                            key, self.funk.rec_query(None, key),
                            self.slot)
                if self.poh_out is not None:
                    blob = frame[20:] if self.fwd_payloads else b""
                    poh_frames.append(
                        (mb_id, struct.pack("<QH", mb_id, txn_cnt)
                         + mixin + blob))
        self._flush_wave(poh_frames, [mb_id for _, _, mb_id in frames])

    def _flush_wave(self, poh_frames, done_ids):
        cnc = getattr(self.ctx, "cnc", None)
        if poh_frames and self.poh_out is not None:
            publish_wave(self.poh_out, self.poh_fseqs, poh_frames,
                         cnc=cnc)
        if done_ids:
            publish_wave(
                self.out, self.out_fseqs,
                [(mb, struct.pack("<Q", mb)) for mb in done_ids],
                cnc=cnc)

    def on_halt(self):
        # a wave already dispatched must still commit and publish its
        # completions (the verify tile's flush contract)
        if self._pending is not None:
            self._finalize_wave()
        if self.fanout is not None and self.fanout.busy:
            # bounded drain then cancel (ExecFanout.halt): exec tiles
            # are halting too, so after the window the fork is dropped
            # rather than wedging the halt — no poh frame for a wave
            # that never completed, no partial commits in the store
            self.fanout.halt()

    def in_seqs(self):
        s = {self.in_link: self.seq}
        if self.fanout is not None:
            s.update(self.fanout.done_seq)
        return s

    def metrics_items(self):
        return dict(self.m)


@register("exec")
class ExecAdapter:
    """Exec tile (r16, ref: src/discof/exec/fd_exec_tile.c): one shard
    of the bank's execution fan-out. Consumes the bank's
    conflict-group dispatch frames, executes them through the
    WaveExecutor against the shm funk store AT THE FORK THE BANK
    PREPARED — dispatch reads balances at the frame's xid itself, so
    a split group's later frames see the earlier frames' commits
    (WireFunk's txn_prepare is idempotent, which is what lets the
    WaveExecutor's stage->dispatch->finalize seam run here unchanged)
    — and publishes one completion frag per frame. A frame whose fork
    the bank already cancelled (timeout redispatch) is abandoned with
    NO completion: the retry under the fresh fork supersedes it.

    args: batch (dispatch frames gathered per poll)."""

    METRICS = ["frames", "txns", "ok", "fail", "stale_xid",
               "overruns", "backpressure"]

    def __init__(self, ctx, args):
        self.ctx = ctx
        if len(ctx.in_rings) != 1:
            raise ValueError(f"exec tile {ctx.tile_name}: one in link")
        self.in_link = next(iter(ctx.in_rings))
        self.ring = ctx.in_rings[self.in_link]
        self.out = _single(ctx.out_rings, "out link", ctx.tile_name)
        self.out_fseqs = _single(ctx.out_fseqs, "out link",
                                 ctx.tile_name)
        self.batch = max(1, int(args.get("batch", 8)))
        fk = ctx.plan.get("funk") or {}
        if fk.get("backend") != "shm" or "off" not in fk:
            raise ValueError(
                f"exec {ctx.tile_name}: needs [funk] backend=\"shm\"")
        from ..funk.shmfunk import WireFunk
        from ..svm.executor import WaveExecutor
        self.funk = WireFunk.from_plan(ctx.wksp, fk)
        self._wx = WaveExecutor()
        self.m = {k: 0 for k in self.METRICS}
        self.seq = ctx.in_seq0.get(self.in_link, 0)
        self.mtu = ctx.plan["links"][self.in_link]["mtu"]

    def poll_once(self) -> int:
        n, self.seq, buf, sizes, sigs, ovr = self.ring.gather(
            self.seq, self.batch, self.mtu)
        self.m["overruns"] += ovr
        if not n:
            return 0
        from ..funk import FunkTxnError
        from ..svm.executor import STATUS_OK, SystemTxn
        comps = []
        for i in range(n):
            frame = bytes(buf[i, :sizes[i]])
            wave_seq, xid, cnt = _EXEC_HDR.unpack_from(frame, 0)
            off = _EXEC_HDR.size
            txns = []
            for _ in range(cnt):
                amt, fee = _EXEC_TXN.unpack_from(frame, off + 64)
                txns.append(SystemTxn(
                    src=frame[off:off + 32],
                    dst=frame[off + 32:off + 64],
                    amount=amt, fee=fee))
                off += _EXEC_TXN_SZ
            self.m["frames"] += 1
            self.m["txns"] += cnt
            try:
                staged = self._wx.stage(txns)
                disp = self._wx.dispatch(self.funk, xid, xid, staged)
                st = self._wx.finalize(self.funk, disp)
            except (FunkTxnError, KeyError, MemoryError):
                self.m["stale_xid"] += 1
                continue
            ok = sum(1 for s in st if s == STATUS_OK)
            self.m["ok"] += ok
            self.m["fail"] += len(st) - ok
            comps.append((wave_seq,
                          _EXEC_DONE.pack(wave_seq, ok, len(st) - ok)))
        if comps:
            publish_wave(self.out, self.out_fseqs, comps,
                         cnc=getattr(self.ctx, "cnc", None))
        return n

    def in_seqs(self):
        return {self.in_link: self.seq}

    def metrics_items(self):
        return dict(self.m)


@register("resolv")
class ResolvAdapter:
    """Resolution stage ahead of pack (r16, ref: src/discof/resolv/
    fd_resolv_tile.c): parse each txn once, resolve v0 address-table
    loads against the shm account store, drop txns whose fee payer
    can't cover the signature fee, and ship RESOLVED frames so pack
    never re-parses and never needs account-db access (pack side:
    resolved_in + pack/scheduler.py meta_from_resolved).

    Without a shm [funk] section the tile still runs — legacy txns
    resolve statically from their own account keys; v0 txns with
    table loads are refused (alut_fail), exactly meta_from_payload's
    rule — and the fee-payer gate is off (no store to read).

    args: batch, fee_payer_check (default on when the store is
    present)."""

    METRICS = ["rx", "resolved", "parse_fail", "alut_fail",
               "fee_fail", "oversz", "overruns", "backpressure"]

    def __init__(self, ctx, args):
        self.ctx = ctx
        if len(ctx.in_rings) != 1:
            raise ValueError(
                f"resolv tile {ctx.tile_name}: one in link")
        self.in_link = next(iter(ctx.in_rings))
        self.ring = ctx.in_rings[self.in_link]
        out = [ln for ln in ctx.out_rings]
        if len(out) != 1:
            raise ValueError(
                f"resolv tile {ctx.tile_name}: one out link")
        self.out_link = out[0]
        self.out = ctx.out_rings[self.out_link]
        self.out_fseqs = ctx.out_fseqs[self.out_link]
        self.batch = max(1, int(args.get("batch", 64)))
        self.db = None
        fk = ctx.plan.get("funk") or {}
        if fk.get("backend") == "shm" and "off" in fk:
            from ..funk.shmfunk import WireFunk
            from ..svm.accdb import AccDb
            self.db = AccDb(WireFunk.from_plan(ctx.wksp, fk))
        self.fee_check = bool(args.get("fee_payer_check",
                                       self.db is not None))
        if self.fee_check and self.db is None:
            raise ValueError(
                f"resolv {ctx.tile_name}: fee_payer_check needs "
                f"[funk] backend=\"shm\"")
        self.m = {k: 0 for k in self.METRICS}
        self.seq = ctx.in_seq0.get(self.in_link, 0)
        self.mtu = ctx.plan["links"][self.in_link]["mtu"]
        self.out_mtu = ctx.plan["links"][self.out_link]["mtu"]

    def _resolve(self, payload):
        """payload -> RESOLVED frame bytes, or None (counted drop).
        The meta_from_payload cost/reward model with the v0 refusal
        replaced by REAL table resolution against the store."""
        from ..pack.cost import CostError
        from ..pack.scheduler import (FEE_PER_SIGNATURE, TxnMeta,
                                      serialize_resolved,
                                      txn_cost_and_reward)
        from ..protocol.txn import parse_txn
        from ..svm.alut import AlutResolveError, resolve_loaded_keys
        try:
            t = parse_txn(payload)
        except Exception:
            self.m["parse_fail"] += 1
            return None
        keys = t.account_keys(payload)
        flags = [t.is_writable(i) for i in range(t.acct_cnt)]
        if t.version == 0 and t.aluts:
            if self.db is None:
                self.m["alut_fail"] += 1
                return None
            try:
                lk, lw = resolve_loaded_keys(self.db, None, t,
                                             slot=0)
            except AlutResolveError:
                self.m["alut_fail"] += 1
                return None
            keys, flags = keys + lk, flags + list(lw)
        try:
            cost, reward, vote = txn_cost_and_reward(t, payload)
        except CostError:
            self.m["parse_fail"] += 1
            return None
        if self.fee_check:
            payer = self.db.peek(None, keys[0])
            fee = FEE_PER_SIGNATURE * t.sig_cnt
            if payer is None or payer.lamports < fee:
                self.m["fee_fail"] += 1
                return None
        meta = TxnMeta(
            payload, t, reward, cost,
            tuple(k for k, w in zip(keys, flags) if w),
            tuple(k for k, w in zip(keys, flags) if not w),
            is_vote=vote)
        return serialize_resolved(meta)

    def poll_once(self) -> int:
        n, self.seq, buf, sizes, sigs, ovr = self.ring.gather(
            self.seq, self.batch, self.mtu)
        self.m["overruns"] += ovr
        if not n:
            return 0
        frames = []
        for i in range(n):
            self.m["rx"] += 1
            out = self._resolve(bytes(buf[i, :sizes[i]]))
            if out is None:
                continue
            if len(out) > self.out_mtu:
                self.m["oversz"] += 1
                continue
            self.m["resolved"] += 1
            frames.append((int(sigs[i]), out))
        if frames:
            publish_wave(self.out, self.out_fseqs, frames,
                         cnc=getattr(self.ctx, "cnc", None))
        return n

    def in_seqs(self):
        return {self.in_link: self.seq}

    def metrics_items(self):
        return dict(self.m)


def _shed_for(ctx, args):
    """Resolve one ingest tile's effective shed table from the plan's
    [shed] section + the tile's own `shed` override (disco/shed.py).
    None = no gate object, zero per-packet cost."""
    from .shed import effective_shed
    return effective_shed(ctx.plan.get("shed"), args.get("shed"))


def _shed_slo_poll(ctx, gate):
    """Cross-tile overload coupling, polled at housekeeping cadence:
    an [slo] breach anywhere (the metric tile's slo_breach gauge, read
    through PressureProbe) trips this tile's door into stake-weighted
    shedding for the hold window."""
    if gate is None:
        return
    probe = getattr(ctx, "_pressure_probe", None)
    if probe is None:
        from .slo import PressureProbe
        probe = ctx._pressure_probe = PressureProbe(ctx.plan, ctx.wksp)
    if probe.overloaded():
        gate.trip_overload()


@register("sock")
class SockAdapter:
    """UDP socket ingest (ref: src/disco/net/sock/fd_sock_tile.c).
    args: port (0 = ephemeral; bound port published in metrics),
    bind_addr, batch, mtu, shed (per-tile policing override —
    disco/shed.py; merged over the topology [shed] section)."""

    METRICS = ["rx", "bytes", "oversz", "backpressure", "shed",
               "shed_unstaked", "shed_overflow", "peers", "overload",
               "port"]
    GAUGES = ["peers", "overload", "port"]

    def __init__(self, ctx, args):
        from ..tiles.sock import SockTile
        self.ctx = ctx
        out = _single(ctx.out_rings, "out link", ctx.tile_name)
        fseqs = _single(ctx.out_fseqs, "out link", ctx.tile_name)
        self.tile = SockTile(
            out, fseqs, port=int(args.get("port", 0)),
            bind_addr=args.get("bind_addr", "127.0.0.1"),
            batch=int(args.get("batch", 64)),
            mtu=int(args.get("mtu", 1500)),
            shed=_shed_for(ctx, args))

    def poll_once(self) -> int:
        return self.tile.poll_once()

    def housekeeping(self):
        _shed_slo_poll(self.ctx, self.tile.shed)

    def on_halt(self):
        self.tile.close()

    def metrics_items(self):
        return dict(self.tile.metrics)


@register("quic")
class QuicAdapter:
    """QUIC TPU ingest tile (ref: src/disco/quic/fd_quic_tile.c): the
    production txn ingest transport; each completed unidirectional
    stream publishes one txn frag. args: port (0 = ephemeral, bound
    port in metrics), bind_addr, batch, mtu."""

    METRICS = ["rx", "txns", "conns", "bad_pkts", "oversz",
               "backpressure", "dropped", "replayed", "shed",
               "shed_unstaked", "peers", "overload", "port"]
    GAUGES = ["peers", "overload", "port"]

    def __init__(self, ctx, args):
        from ..tiles.quic import QuicTile
        self.ctx = ctx
        out_ln = next(iter(ctx.out_rings))
        # never exceed the out link's mtu: an oversize txn must be
        # DROPPED (oversz), not crash Ring.publish on hostile input
        link_mtu = ctx.plan["links"][out_ln]["mtu"]
        self.tile = QuicTile(
            _single(ctx.out_rings, "out link", ctx.tile_name),
            _single(ctx.out_fseqs, "out link", ctx.tile_name),
            port=int(args.get("port", 0)),
            bind_addr=args.get("bind_addr", "127.0.0.1"),
            batch=int(args.get("batch", 64)),
            mtu=min(int(args.get("mtu", 1500)), link_mtu),
            shed=_shed_for(ctx, args))
        self._attack_peer = 0

    def poll_once(self) -> int:
        return self.tile.poll_once()

    def housekeeping(self):
        _shed_slo_poll(self.ctx, self.tile.shed)

    def on_chaos(self, ev: dict):
        """flood_malformed_quic traffic plan: push the hostile
        datagrams straight through the policed rx path, each from a
        fresh fake source address (TEST-NET-3) — they must die in the
        QUIC parser as bad_pkts, never crash, never publish a txn."""
        from ..utils.chaos import attack_frames
        if ev["action"] != "flood_malformed_quic":
            return
        for f in attack_frames(ev["action"], ev["frames"],
                               seed=ev["seed"], pool=ev["pool"]):
            self._attack_peer += 1
            self.tile.inject(
                f, (f"203.0.113.{self._attack_peer % 254 + 1}",
                    1024 + self._attack_peer % 60000))

    def on_halt(self):
        self.tile.close()

    def metrics_items(self):
        return dict(self.tile.metrics)


@register("poh")
class PohAdapter:
    """Proof-of-History tile (ref: src/discof/poh/fd_poh.h:4-31): owns
    the hash chain; mixes executed microblocks (from bank tiles) into
    it as record entries, emits tick entries on schedule, and declares
    slot boundaries by TICK COUNT — the pack tile ends its block on the
    slot frag, not a wall clock.

    Chain generation is host-side (inherently sequential); entry
    verification is the batched device kernel (ops/poh.py) run by
    consumers/tests.

    Batched mixin (r13): the gathered wave of bank microblocks mixes
    into the chain as hash-chain RUNS (one host_poh_mixin_chain call
    per run between tick boundaries — byte-identical to the
    sequential fold, pinned by the conformance suite), and every
    entry/slot frag the wave produced flushes as ONE credit-gated
    publish_batch per link — the recurrence stays ordered, only the
    per-record Python call/publish overhead is batched away.

    Entry frag wire: u64 slot | u32 tick | u32 num_hashes |
    u8 has_mixin | prev 32 | hash 32 | mixin 32 | u8 flags
    (bit0 = slot_complete, set on the slot's final tick entry) |
    u16 txn_cnt | txn section (u16 len | payload)* — the txn section
    is whatever the bank forwarded (forward_payloads), so the shred
    tile downstream shreds real block content.
    Slot frag wire (slot_link): u64 completed_slot.

    args: hashes_per_tick, ticks_per_slot, seed (hex, 32B),
    slot_link (optional out link to pack), entry link = remaining out.
    """

    METRICS = ["mixins", "ticks", "slots", "entries", "overruns",
               "backpressure"]

    def __init__(self, ctx, args):
        from ..ops.poh import (host_poh_append, host_poh_mixin,
                               host_poh_mixin_chain)
        self._append = host_poh_append
        self._mixin = host_poh_mixin
        self._mixin_chain = host_poh_mixin_chain
        self.ctx = ctx
        self.hashes_per_tick = int(args.get("hashes_per_tick", 64))
        self.ticks_per_slot = int(args.get("ticks_per_slot", 8))
        self.state = bytes.fromhex(args["seed"]) if "seed" in args \
            else bytes(32)
        self.slot_link = args.get("slot_link")
        if self.slot_link:
            self.slot_out = ctx.out_rings[self.slot_link]
            self.slot_fseqs = ctx.out_fseqs[self.slot_link]
            ent = [ln for ln in ctx.out_rings if ln != self.slot_link]
            assert len(ent) == 1, ent
            self.entry_out = ctx.out_rings[ent[0]]
            self.entry_fseqs = ctx.out_fseqs[ent[0]]
        else:
            self.slot_out = None
            self.entry_out = _single(ctx.out_rings, "out link",
                                     ctx.tile_name)
            self.entry_fseqs = _single(ctx.out_fseqs, "out link",
                                       ctx.tile_name)
        self.seqs = ctx.in_seqs0()
        self.mtu = max((ctx.plan["links"][ln]["mtu"]
                        for ln in ctx.in_rings), default=64)
        # entry frames re-wrap the bank frame's txn section (bank hdr
        # 42 -> entry hdr 116); catch an undersized entry link at boot
        ent_ln = next(ln for ln, r in ctx.out_rings.items()
                      if r is self.entry_out)
        ent_mtu = ctx.plan["links"][ent_ln]["mtu"]
        if ctx.in_rings and ent_mtu < self.mtu - 42 + 116:
            raise ValueError(
                f"poh {ctx.tile_name}: entry link mtu {ent_mtu} < "
                f"worst-case entry frame {self.mtu - 42 + 116}")
        self.slot = 0
        self.tick_in_slot = 0
        self.hashes_in_tick = 0
        self.entry_idx = 0
        # wave staging: entry/slot frames built while walking a
        # gathered wave, flushed as one publish_batch per link
        self._pend_entries: list[tuple[int, bytes]] = []
        self._pend_slots: list[int] = []
        self.m = {k: 0 for k in self.METRICS}

    def _emit_entry(self, num_hashes: int, prev: bytes,
                    mixin: bytes | None, txn_blob: bytes = b"",
                    txn_cnt: int = 0, slot_done: bool = False):
        frame = struct.pack("<QII B", self.slot, self.tick_in_slot,
                            num_hashes, 1 if mixin else 0)
        frame += prev + self.state + (mixin or bytes(32))
        frame += bytes([1 if slot_done else 0]) \
            + struct.pack("<H", txn_cnt) + txn_blob
        self._pend_entries.append((self.entry_idx, frame))
        self.entry_idx += 1
        self.m["entries"] += 1

    def _flush_pending(self):
        cnc = getattr(self.ctx, "cnc", None)
        if self._pend_entries:
            frames, self._pend_entries = self._pend_entries, []

            def bp():
                self.m["backpressure"] += 1
            publish_wave(self.entry_out, self.entry_fseqs, frames,
                         cnc=cnc, on_stall=bp)
        if self._pend_slots and self.slot_out is not None:
            slots, self._pend_slots = self._pend_slots, []
            publish_wave(
                self.slot_out, self.slot_fseqs,
                [(s, struct.pack("<Q", s)) for s in slots], cnc=cnc)

    def poll_once(self) -> int:
        total = 0
        # 1) mix in executed microblocks (one hash consumed per record;
        # fd_poh mixin semantics, src/ballet/poh/fd_poh.c). The chain
        # is inherently sequential, but the wave batches everything
        # around the recurrence: maximal runs between tick boundaries
        # hash as ONE chain call, and every frame the wave produced
        # ships as one publish_batch per link after the walk.
        for ln, ring in self.ctx.in_rings.items():
            n, self.seqs[ln], buf, sizes, sigs, ovr = ring.gather(
                self.seqs[ln], 16, self.mtu)
            self.m["overruns"] += ovr
            if not n:
                continue
            mixins = [bytes(buf[i, 10:42]) for i in range(n)]
            cnts = [struct.unpack_from("<H", buf[i], 8)[0]
                    for i in range(n)]
            blobs = [bytes(buf[i, 42:sizes[i]]) for i in range(n)]
            i = 0
            while i < n:
                # a record must fit before the tick boundary
                # (identical boundary walk to the sequential path:
                # at most one tick fires per record position)
                if self.hashes_in_tick + 1 >= self.hashes_per_tick:
                    self._tick()
                take = min(max(1, self.hashes_per_tick
                               - self.hashes_in_tick - 1), n - i)
                states = self._mixin_chain(self.state,
                                           mixins[i:i + take])
                for j in range(take):
                    prev = self.state
                    self.state = states[j]
                    self.hashes_in_tick += 1
                    blob = blobs[i + j]
                    self._emit_entry(1, prev, mixins[i + j],
                                     txn_blob=blob,
                                     txn_cnt=cnts[i + j] if blob else 0)
                    self.m["mixins"] += 1
                i += take
            total += n
        if self._pend_entries or self._pend_slots:
            self._flush_pending()
        return total

    def _tick(self):
        remaining = self.hashes_per_tick - self.hashes_in_tick
        prev = self.state
        self.state = self._append(prev, remaining)
        self._emit_entry(
            remaining, prev, None,
            slot_done=self.tick_in_slot + 1 >= self.ticks_per_slot)
        self.hashes_in_tick = 0
        self.tick_in_slot += 1
        self.m["ticks"] += 1
        if self.tick_in_slot >= self.ticks_per_slot:
            if self.slot_out is not None:
                self._pend_slots.append(self.slot)
            self.slot += 1
            self.tick_in_slot = 0
            self.m["slots"] += 1

    def housekeeping(self):
        # tick cadence: one tick per housekeeping interval (the jittered
        # stem timer stands in for the tick clock; production would pace
        # against tempo ticks-per-ns calibration)
        self._tick()
        self._flush_pending()

    def on_halt(self):
        self._flush_pending()    # staged frames must not die with us

    def in_seqs(self):
        return dict(self.seqs)

    def metrics_items(self):
        return dict(self.m)


@register("shred")
class ShredAdapter:
    """Turbine shred tile (ref: src/disco/shred/fd_shred_tile.c:6-60 —
    one tile serves both directions).

    mode="leader": in link = poh entries; shreds entry batches into
    signed merkle FEC sets (keyguard LEADER role via req/resp links)
    and transmits each shred to its stake-weighted turbine first hop
    over UDP. args: cluster = [{pubkey_hex, stake, addr "host:port"}],
    identity_hex, req/resp (keyguard links), optional out link
    "shreds" mirror + "batches" witness link, flush_bytes, fanout,
    shred_version.

    mode="recover": in link = raw shred wires (net/sock tile);
    FEC-resolves, stores, reassembles ordered slices on the out link.
    args: leader_pubkey_hex."""

    METRICS = ["entries", "batches", "fec_sets", "data_shreds",
               "parity_shreds", "sent", "no_dest", "sign_fail",
               "slots", "dropped", "shreds", "fecs", "slices",
               "slots_done", "parse_fail", "retransmitted",
               "overruns"]

    def __init__(self, ctx, args):
        import socket

        from ..shred.shred_dest import ClusterNode
        from ..tiles import shred as shredmod
        self.ctx = ctx
        self.mode = args.get("mode", "leader")
        self._ovr = 0
        if self.mode == "leader":
            from ..keyguard import KeyguardClient
            ins = [ln for ln in ctx.in_rings if ln != args["resp"]]
            assert len(ins) == 1, ins
            self.in_link = ins[0]
            kg = KeyguardClient(ctx.out_rings[args["req"]],
                                ctx.in_rings[args["resp"]],
                                req_fseqs=ctx.out_fseqs[args["req"]])

            def sign_fn(root):
                sig = kg.sign(root)
                if sig is None:
                    self.core.metrics["sign_fail"] += 1
                    raise RuntimeError("keyguard refused shred root")
                return sig

            self._kg = kg
            cluster = [ClusterNode(bytes.fromhex(n["pubkey_hex"]),
                                   int(n["stake"]),
                                   (n["addr"].rsplit(":", 1)[0],
                                    int(n["addr"].rsplit(":", 1)[1])))
                       for n in args.get("cluster", [])]
            aux = [ln for ln in ctx.out_rings if ln != args["req"]]
            shreds_ln = args.get("shreds_link")
            batch_ln = args.get("batches_link")
            assert set(aux) == {ln for ln in (shreds_ln, batch_ln)
                                if ln}, (aux, shreds_ln, batch_ln)
            self.core = shredmod.ShredLeaderCore(
                sign_fn, bytes.fromhex(args["identity_hex"]), cluster,
                socket.socket(socket.AF_INET, socket.SOCK_DGRAM),
                out_ring=ctx.out_rings.get(shreds_ln),
                out_fseqs=ctx.out_fseqs.get(shreds_ln),
                batch_out=ctx.out_rings.get(batch_ln),
                batch_fseqs=ctx.out_fseqs.get(batch_ln),
                shred_version=int(args.get("shred_version", 0)),
                fanout=int(args.get("fanout", 200)),
                flush_bytes=int(args.get("flush_bytes", 31840)),
                drop_slot_every=int(args.get("drop_slot_every", 0)),
                cnc=getattr(ctx, "cnc", None))
            self._handle = self.core.on_entry
            self.in_links = [self.in_link]
        else:
            # recover mode fans in every in link (turbine ingest +
            # repair responses feed the same resolver); with a cluster
            # + identity it also RETRANSMITS to its turbine children
            self.in_links = list(ctx.in_rings)
            dest = identity = rt_sock = None
            if args.get("cluster") and args.get("identity_hex"):
                identity = bytes.fromhex(args["identity_hex"])
                cluster = [ClusterNode(
                    bytes.fromhex(n["pubkey_hex"]), int(n["stake"]),
                    (n["addr"].rsplit(":", 1)[0],
                     int(n["addr"].rsplit(":", 1)[1])))
                    for n in args["cluster"]]
                dest = shredmod.ShredDest(
                    cluster, identity,
                    fanout=int(args.get("fanout", 200)))
                rt_sock = socket.socket(socket.AF_INET,
                                        socket.SOCK_DGRAM)
            self.core = shredmod.ShredRecoverCore(
                bytes.fromhex(args["leader_pubkey_hex"]),
                _single(ctx.out_rings, "out link", ctx.tile_name),
                _single(ctx.out_fseqs, "out link", ctx.tile_name),
                dest=dest, identity=identity, sock=rt_sock)
            # repair responses must NOT re-enter turbine: only the
            # turbine ingest link (default: the first in link)
            # retransmits
            turbine_in = args.get("turbine_in", self.in_links[0])

            def handle_factory(ln):
                rt = ln == turbine_in
                return lambda w: self.core.on_shred(w, retransmit=rt)
            self._handlers = {ln: handle_factory(ln)
                              for ln in self.in_links}
            self._handle = None
        self.seqs = {ln: ctx.in_seq0.get(ln, 0) for ln in self.in_links}
        self.mtus = {ln: ctx.plan["links"][ln]["mtu"]
                     for ln in self.in_links}

    def poll_once(self) -> int:
        m = {"overruns": 0}
        if self._handle is not None:
            n = _gather_all(self.ctx, self.seqs, self.mtus, 16,
                            self._handle, m)
            # the wave's mirror wires ship as one batched publish
            # (leader core buffers per entry, publishes per poll)
            self.core.flush_egress()
        else:
            n = 0
            for ln in self.in_links:
                only = {ln: self.seqs[ln]}
                n += _gather_all(self.ctx, only,
                                 {ln: self.mtus[ln]}, 16,
                                 self._handlers[ln], m)
                self.seqs[ln] = only[ln]
        self._ovr += m["overruns"]
        return n

    def in_seqs(self):
        seqs = dict(self.seqs)
        if self.mode == "leader":
            for ln in self.ctx.in_rings:
                if ln not in seqs:
                    seqs[ln] = self._kg.resp_seq
        return seqs

    def on_halt(self):
        if self.mode == "leader":
            self.core.flush_egress()   # buffered wires must not die

    def metrics_items(self):
        return {k: self.core.metrics.get(k, 0) for k in self.METRICS
                if k != "overruns"} | {"overruns": self._ovr}


@register("sign")
class SignAdapter:
    """Identity-key custody tile (ref: src/disco/sign/fd_sign_tile.c).
    args: seed (hex, 32B private key seed), clients = ordered list of
    {role: "leader"|"gossip"|"repair"|"send", req: in link,
    resp: out link} — the role is bound to the ring pair at topology
    build, so policy is attached to the wire."""

    METRICS = ["signed", "refused", "overruns", "backpressure",
               "keyswitches"]

    def __init__(self, ctx, args):
        from ..keyguard import SignTile
        from ..keyguard.keyguard import ROLE_NAMES
        role_ids = {v: k for k, v in ROLE_NAMES.items()}
        self.ctx = ctx
        clients = []
        for c in args["clients"]:
            clients.append({
                "role": role_ids[c["role"]],
                "in_ring": ctx.in_rings[c["req"]],
                "out_ring": ctx.out_rings[c["resp"]],
                "out_fseqs": ctx.out_fseqs[c["resp"]],
            })
        self._links = [c["req"] for c in args["clients"]]
        self.tile = SignTile(bytes.fromhex(args["seed"]), clients)
        self._ks_off = ctx.spec.get("keyswitch_off")

    def poll_once(self) -> int:
        return self.tile.poll_once()

    def housekeeping(self):
        # live identity hot-swap (ref: fd_keyswitch + set_identity)
        if self._ks_off is None:
            return
        from ..keyguard import keyswitch as ks
        pending = ks.poll_switch(self.ctx.wksp, self._ks_off)
        if pending is not None:
            seed, gen = pending
            self.tile.rekey(seed)
            # compare-and-ack on the generation: a racing newer request
            # stays pending and applies next housekeeping
            ks.ack_switch(self.ctx.wksp, self._ks_off, gen)

    def in_seqs(self):
        return {ln: s for ln, s in
                zip(self._links, self.tile.seqs)}

    def metrics_items(self):
        return dict(self.tile.metrics)


@register("tower")
class TowerAdapter:
    """Consensus tile (ref: src/discof/tower/fd_tower_tile.c): consumes
    block/vote frames, runs ghost + tower checks at housekeeping, emits
    own votes. args: total_stake, in link = replay fan-in, out link =
    votes."""

    METRICS = ["blocks", "votes_in", "votes_out", "lockout_skips",
               "switch_skips", "threshold_skips", "roots", "root_slot",
               "bad_frames", "overruns"]
    GAUGES = ["root_slot"]

    def __init__(self, ctx, args):
        from ..tiles.tower import TowerCore
        self.ctx = ctx
        self.core = TowerCore(int(args["total_stake"]))
        # fan-in: replay blocks + gossip/driver votes arrive on
        # separate links (the reference's tower tile polls several
        # producers the same way)
        self.seqs = ctx.in_seqs0()
        self.out = _single(ctx.out_rings, "out link", ctx.tile_name)
        self.out_fseqs = _single(ctx.out_fseqs, "out link",
                                 ctx.tile_name)
        self._ovr = 0
        self.mtus = {ln: ctx.plan["links"][ln]["mtu"]
                     for ln in ctx.in_rings}

    def poll_once(self) -> int:
        m = {"overruns": 0}
        n = _gather_all(self.ctx, self.seqs, self.mtus, 32,
                        self.core.handle, m)
        self._ovr += m["overruns"]
        return n

    def housekeeping(self):
        decision = self.core.decide()
        if decision is not None:
            slot, block_id = decision
            while self.out_fseqs and \
                    self.out.credits(self.out_fseqs) <= 0:
                time.sleep(20e-6)
            # vote frame carries the FULL tower (lockouts + root) so
            # the send tile can build a real TowerSync instruction
            tw = self.core.tower
            frame = struct.pack("<Q", slot) + block_id
            frame += (bytes([1]) + struct.pack("<Q", tw.root)
                      if tw.root is not None else bytes([0]))
            votes = list(tw.votes)[-31:]   # tower depth cap == 31
            frame += struct.pack("<H", len(votes))
            for v in votes:
                frame += struct.pack("<QI", v.slot, v.conf)
            self.out.publish(frame, sig=slot)

    def in_seqs(self):
        return dict(self.seqs)

    def metrics_items(self):
        return {**self.core.metrics, "overruns": self._ovr}


@register("repair")
class RepairAdapter:
    """Repair tile (ref: src/discof/repair/fd_repair_tile.c:1-15):
    watches the data-shred stream for gaps (forest), sends signed
    repair requests (keyguard REPAIR role) to peers over UDP, serves
    peers' requests from its own shred cache, and forwards repair
    responses onto the out link toward the FEC resolver.

    args: identity_hex, port (0 = ephemeral, published as metric),
    bind_addr, peers = [{pubkey_hex, addr "host:port"}], root_slot,
    req/resp = keyguard links; shred in link = the remaining in link;
    out link toward the shred tile (optional for pure servers); shed
    (per-tile policing override — disco/shed.py, merged over the
    topology [shed] section: the repair port is internet-facing)."""

    METRICS = ["shreds_seen", "reqs_sent", "sign_fail", "reqs_served",
               "reqs_refused", "resps_in", "cache_slots", "incomplete",
               "overruns", "port", "shed", "shed_unstaked", "peers",
               "overload"]
    GAUGES = ["cache_slots", "incomplete", "port", "peers", "overload"]

    def __init__(self, ctx, args):
        import socket

        from ..keyguard import KeyguardClient
        from ..tiles.repair import RepairCore
        self.ctx = ctx
        resp_ln = args.get("resp")
        ins = [ln for ln in ctx.in_rings if ln != resp_ln]
        assert len(ins) == 1, ins
        self.in_link = ins[0]
        self.ring = ctx.in_rings[self.in_link]
        if resp_ln:
            kg = KeyguardClient(ctx.out_rings[args["req"]],
                                ctx.in_rings[resp_ln],
                                req_fseqs=ctx.out_fseqs[args["req"]])
            self._kg = kg
            sign_fn = kg.sign
        else:
            self._kg = None
            sign_fn = lambda payload: None        # serve-only tile
        sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        sock.bind((args.get("bind_addr", "127.0.0.1"),
                   int(args.get("port", 0))))
        sock.setblocking(False)
        self.port = sock.getsockname()[1]
        peers = []
        for p in args.get("peers", []):
            host, port = p["addr"].rsplit(":", 1)
            peers.append((bytes.fromhex(p["pubkey_hex"]),
                          (host, int(port))))
        outs = {ln: r for ln, r in ctx.out_rings.items()
                if ln != args.get("req")}
        if outs:
            out_ring = _single(outs, "shred out link", ctx.tile_name)
            out_ln = next(iter(outs))
            out_fseqs = ctx.out_fseqs[out_ln]
        else:
            out_ring = out_fseqs = None      # serve-only tile
        self.core = RepairCore(
            bytes.fromhex(args["identity_hex"]), sign_fn, sock,
            peers=peers,
            root_slot=(int(args["root_slot"])
                       if "root_slot" in args else None),
            out_ring=out_ring, out_fseqs=out_fseqs,
            shed=_shed_for(ctx, args))
        self.seq = ctx.in_seq0.get(self.in_link, 0)
        self._ovr = 0
        self.mtu = ctx.plan["links"][self.in_link]["mtu"]

    def poll_once(self) -> int:
        n, self.seq, buf, sizes, sigs, ovr = self.ring.gather(
            self.seq, 32, self.mtu)
        self._ovr += ovr
        for i in range(n):
            self.core.on_shred(bytes(buf[i, :sizes[i]]))
        return n + self.core.poll_socket()

    def housekeeping(self):
        if self._kg is not None:
            self.core.plan_and_send()
        _shed_slo_poll(self.ctx, self.core.shed)

    def in_seqs(self):
        seqs = {self.in_link: self.seq}
        if self._kg is not None:
            for ln in self.ctx.in_rings:
                if ln != self.in_link:
                    seqs[ln] = self._kg.resp_seq
        return seqs

    def metrics_items(self):
        gate = (self.core.shed.counters() if self.core.shed is not None
                else {"shed": 0, "shed_unstaked": 0, "peers": 0,
                      "overload": 0})
        return {**self.core.metrics, **gate, "overruns": self._ovr,
                "port": self.port}


@register("replay")
class ReplayAdapter:
    """Replay tile (ref: src/discof/replay/fd_replay_tile.c:77-95):
    consumes reassembled slices, verifies PoH with the batched device
    kernel, stages txns through the conflict DAG, executes via the SVM
    host path, and notifies tower per completed block.

    Follower mode (r17): with `exec_links`/`exec_done` the slot's
    transfers execute over the exec tile family against the shm funk
    store — the SAME ExecFanout engine the leader bank uses, so
    `exec_tile_cnt` shards replay a slot in parallel with exactly-once
    commits across an exec-shard crash. `wait_restore` gates replay on
    snapin's restore marker (cold-start from snapshot, then catch up);
    `expected` pins the leader's per-slot bank hashes — a mismatch is
    a divergence VERDICT (metric + loud tile FAIL), never a silent
    wrong state. [snapshot] every_slots/path make this tile a periodic
    crash-safe snapshot writer. Chaos: diverge_block perturbs the next
    slot's lattice (the verdict must trip); crash_mid_snapshot kills
    the next snapshot write between rows (the previous file must
    survive the atomic-rename discipline).

    args: genesis ({pubkey_hex: lamports}), genesis_synth,
    hashes_per_tick, verify_poh (default true), slots_per_epoch,
    exec_links/exec_done (ordered per-shard dispatch/completion
    links), redispatch_s, expected ({slot: bank_hash_hex}),
    wait_restore, snapshot_path/snapshot_every/snapshot_compress
    (default from the plan's [snapshot] section)."""

    METRICS = ["slices", "slots_replayed", "entries", "txns", "exec_ok",
               "exec_fail", "poh_fail", "buffered", "waves",
               "parse_fail", "exec_skip", "exec_waves",
               "exec_redispatch", "divergent_slot", "snapshots",
               "restore_slot", "behind", "overruns"]
    GAUGES = ["buffered", "behind", "divergent_slot", "restore_slot"]
    # catch-up telemetry promoted to first-class fdtpu_tile_<name>
    # prometheus families (r19): until now only the fdgui catch-up
    # panel read these slots — dashboards and [slo] targets can key on
    # them directly
    DEVICE_SERIES = ["slots_replayed", "divergent_slot", "restore_slot",
                     "behind", "buffered"]

    def __init__(self, ctx, args):
        from ..tiles.replay import ReplayCore
        self.ctx = ctx
        self.exec_links = list(args.get("exec_links") or [])
        self.exec_done = list(args.get("exec_done") or [])
        non_done = [ln for ln in ctx.in_rings
                    if ln not in self.exec_done]
        if len(non_done) != 1:
            raise ValueError(
                f"replay tile {ctx.tile_name}: exactly one slice in "
                f"link, got {non_done}")
        self.in_link = non_done[0]
        self.ring = ctx.in_rings[self.in_link]
        genesis = {bytes.fromhex(k): int(v)
                   for k, v in args.get("genesis", {}).items()}
        if args.get("genesis_synth"):
            genesis.update(_synth_genesis(int(args["genesis_synth"])))
        outs = {ln: r for ln, r in ctx.out_rings.items()
                if ln not in self.exec_links}
        out_fseqs = {ln: f for ln, f in ctx.out_fseqs.items()
                     if ln not in self.exec_links}
        rp = ctx.plan.get("replay") or {}
        sp = ctx.plan.get("snapshot") or {}
        funk = fanout = None
        if self.exec_links:
            fk = ctx.plan.get("funk") or {}
            if fk.get("backend") != "shm" or "off" not in fk:
                raise ValueError(
                    f"replay {ctx.tile_name}: exec_links need "
                    f"[funk] backend=\"shm\"")
            from ..funk.shmfunk import WireFunk
            funk = WireFunk.from_plan(ctx.wksp, fk)
            fanout = ExecFanout(
                ctx, funk, self.exec_links, self.exec_done, m={},
                redispatch_s=float(args.get(
                    "redispatch_s", rp.get("redispatch_s", 2.0))))
        expected = {int(s): bytes.fromhex(h)
                    for s, h in (args.get("expected") or {}).items()}
        self.core = ReplayCore(
            out_ring=_single(outs, "tower out link", ctx.tile_name),
            out_fseqs=_single(out_fseqs, "tower out link",
                              ctx.tile_name),
            genesis=genesis,
            hashes_per_tick=int(args.get(
                "hashes_per_tick", rp.get("hashes_per_tick", 16))),
            verify_poh=bool(args.get(
                "verify_poh", rp.get("verify_poh", True))),
            slots_per_epoch=int(args.get("slots_per_epoch", 432_000)),
            funk=funk, fanout=fanout, expected=expected,
            wait_restore=bool(args.get("wait_restore", False)),
            snapshot_path=str(args.get("snapshot_path",
                                       sp.get("path", ""))),
            snapshot_every=int(args.get("snapshot_every",
                                        sp.get("every_slots", 0))),
            snapshot_compress=bool(args.get(
                "snapshot_compress", sp.get("compress", True))),
            cnc=getattr(ctx, "cnc", None))
        if fanout is not None:
            fanout.m = self.core.metrics   # shared counters, one dict
        self.seq = ctx.in_seq0.get(self.in_link, 0)
        self._ovr = 0
        self.mtu = ctx.plan["links"][self.in_link]["mtu"]

    def poll_once(self) -> int:
        if self.core.waiting:
            # cold-start: keep polling for snapin's restore marker;
            # slices gathered below buffer until it lands
            self.core.check_restore()
        n, self.seq, buf, sizes, sigs, ovr = self.ring.gather(
            self.seq, 8, self.mtu)
        self._ovr += ovr
        for i in range(n):
            self.core.on_slice(bytes(buf[i, :sizes[i]]))
        return n

    def on_chaos(self, ev: dict):
        if ev["action"] == "diverge_block":
            self.core._diverge_seed = int(ev.get("seed", 1))
        elif ev["action"] == "crash_mid_snapshot":
            self.core._crash_snap = True

    def on_halt(self):
        if self.core.fanout is not None and self.core.fanout.busy:
            self.core.fanout.halt()

    def in_seqs(self):
        s = {self.in_link: self.seq}
        if self.core.fanout is not None:
            s.update(self.core.fanout.done_seq)
        return s

    def metrics_items(self):
        m = dict(self.core.metrics)
        m["overruns"] += self._ovr
        return m


@register("send")
class SendAdapter:
    """Vote egress tile (ref: src/discof/send/): consumes vote frames,
    builds+signs the vote txn via the keyguard rings, sends over UDP.
    args: identity_hex (node pubkey; the SEED stays in the sign tile),
    vote_account_hex, dest ("host:port"), req/resp = keyguard links."""

    METRICS = ["votes", "sent", "sign_fail", "overruns"]

    def __init__(self, ctx, args):
        import socket

        from ..keyguard import KeyguardClient
        from ..tiles.tower import SendCore
        self.ctx = ctx
        vote_in = [ln for ln in ctx.in_rings if ln != args["resp"]]
        assert len(vote_in) == 1, vote_in
        self.in_link = vote_in[0]
        self.ring = ctx.in_rings[self.in_link]
        host, port = args["dest"].rsplit(":", 1)
        kg = KeyguardClient(ctx.out_rings[args["req"]],
                            ctx.in_rings[args["resp"]],
                            req_fseqs=ctx.out_fseqs[args["req"]])
        self.core = SendCore(
            bytes.fromhex(args["identity_hex"]),
            bytes.fromhex(args["vote_account_hex"]), kg,
            (host, int(port)),
            socket.socket(socket.AF_INET, socket.SOCK_DGRAM))
        self.seq = ctx.in_seq0.get(self.in_link, 0)
        self.m_extra = {"overruns": 0}
        self.mtu = ctx.plan["links"][self.in_link]["mtu"]

    def poll_once(self) -> int:
        n, self.seq, buf, sizes, sigs, ovr = self.ring.gather(
            self.seq, 8, self.mtu)
        self.m_extra["overruns"] += ovr
        for i in range(n):
            frame = bytes(buf[i, :sizes[i]])
            (slot,) = struct.unpack_from("<Q", frame, 0)
            block_id = frame[8:40]
            lockouts, root = [], None
            if len(frame) > 40:                # tower payload present
                off = 40
                if frame[off]:
                    (root,) = struct.unpack_from("<Q", frame, off + 1)
                    off += 9
                else:
                    off += 1
                (cnt,) = struct.unpack_from("<H", frame, off)
                off += 2
                for _ in range(cnt):
                    s, c = struct.unpack_from("<QI", frame, off)
                    lockouts.append((s, c))
                    off += 12
            self.core.send_vote(slot, block_id, lockouts=lockouts,
                                root=root)
        return n

    def in_seqs(self):
        # the keyguard resp link is consumed inside KeyguardClient
        return {self.in_link: self.seq,
                **{ln: self.core.kg.resp_seq
                   for ln in self.ctx.in_rings if ln != self.in_link}}

    def metrics_items(self):
        return {**self.core.metrics, **self.m_extra}


@register("archiver")
class ArchiverAdapter:
    """Frag-stream recorder (ref: src/disco/archiver/ writer tile).
    args: path. Consumes its in link (unreliable by convention — the
    recorder must never backpressure production, matching the
    reference's observer stance)."""

    METRICS = ["frags", "bytes", "overruns"]

    def __init__(self, ctx, args):
        from ..tiles.archiver import ArchiveWriter
        self.ctx = ctx
        self.in_link = next(iter(ctx.in_rings))
        self.tile = ArchiveWriter(ctx.in_rings[self.in_link],
                                  args["path"])

    def poll_once(self) -> int:
        return self.tile.poll_once()

    def in_seqs(self):
        return {self.in_link: self.tile.seq}

    def on_halt(self):
        self.tile.close()

    def metrics_items(self):
        return dict(self.tile.metrics)


@register("playback")
class PlaybackAdapter:
    """Frag-stream replayer (ref: src/disco/archiver/ playback tile).
    args: path."""

    METRICS = ["frags", "bytes", "done", "backpressure"]
    GAUGES = ["done"]

    def __init__(self, ctx, args):
        from ..tiles.archiver import ArchivePlayback
        self.tile = ArchivePlayback(
            args["path"],
            _single(ctx.out_rings, "out link", ctx.tile_name),
            _single(ctx.out_fseqs, "out link", ctx.tile_name))

    def poll_once(self) -> int:
        return self.tile.poll_once()

    def metrics_items(self):
        return dict(self.tile.metrics)


@register("gossip")
class GossipAdapter:
    """Gossip tile (ref: src/discof/gossip/ + src/flamenco/gossip/):
    CRDS over UDP with signed values. args: seed (hex), port,
    bind_addr, entrypoints (["host:port", ...]), publish (list of
    {kind, index, data_hex} values to originate at boot),
    gossvf_bulk (front the gossvf device batch with the RLC bulk
    kernel — gossip/gossvf.py mode="bulk"), shed (per-tile policing
    override, merged over the topology [shed] section)."""

    METRICS = ["gossvf_bad", "rx", "tx", "values", "contacts",
               "bad_msg", "shed", "shed_unstaked", "peers",
               "overload", "port"]
    GAUGES = ["values", "contacts", "peers", "overload", "port"]

    def __init__(self, ctx, args):
        from ..tiles.gossip import GossipTile
        self.ctx = ctx
        self.tile = GossipTile(
            bytes.fromhex(args["seed"]),
            port=int(args.get("port", 0)),
            bind_addr=args.get("bind_addr", "127.0.0.1"),
            entrypoints=args.get("entrypoints", ()),
            device_verify=bool(args.get("device_verify", False)),
            gossvf_bulk=bool(args.get("gossvf_bulk", False)),
            shed=_shed_for(ctx, args))
        self._attack_peer = 0
        for v in args.get("publish", []):
            self.tile.publish(int(v["kind"]), int(v.get("index", 0)),
                              bytes.fromhex(v["data_hex"]))

    def poll_once(self) -> int:
        return self.tile.poll_once()

    def on_chaos(self, ev: dict):
        """flood_crds_spam traffic plan: validly signed CRDS push spam
        from many throwaway unstaked origins (the Sybil flood),
        injected through the policed rx path from fake TEST-NET-2
        socket addresses — the bounded peer table + stake gate must
        absorb it without growing."""
        from ..utils.chaos import attack_frames
        if ev["action"] != "flood_crds_spam":
            return
        for f in attack_frames(ev["action"], ev["frames"],
                               seed=ev["seed"], pool=ev["pool"]):
            self._attack_peer += 1
            self.tile.inject(
                f, (f"198.51.100.{self._attack_peer % 254 + 1}",
                    1024 + self._attack_peer % 60000))

    def housekeeping(self):
        self.tile.housekeeping()
        _shed_slo_poll(self.ctx, self.tile.shed)

    def on_halt(self):
        self.tile.close()

    def metrics_items(self):
        return dict(self.tile.metrics)


@register("snapld")
class SnapLdAdapter:
    """Snapshot loader tile (ref: src/discof/restore/fd_snapct_tile.c
    download/read orchestration, simplified to local file streaming).

    Chaos seams (r17): corrupt_checkpt_frame flips one seeded byte in
    the next fragment (downstream verify MUST reject the stream);
    crash_mid_snapshot hard-kills this process mid-file (restart
    re-streams from byte 0 — the snapshot protocol is resumable by
    restart, not by offset); stale_snapshot_offer re-offers
    `stale_path`, whose older slot the inserter's min_slot gate must
    refuse. args: path, chunk, stale_path."""

    METRICS = ["bytes", "frags", "done", "total_bytes", "corrupted",
               "offers"]
    GAUGES = ["done", "total_bytes"]

    def __init__(self, ctx, args):
        from ..tiles.snapshot import SnapLoader
        sp = ctx.plan.get("snapshot") or {}
        self.stale_path = args.get("stale_path", "")
        self.tile = SnapLoader(
            args.get("path") or sp.get("path"),
            _single(ctx.out_rings, "out link", ctx.tile_name),
            _single(ctx.out_fseqs, "out link", ctx.tile_name),
            chunk=int(args.get("chunk", sp.get("chunk", 1024))))

    def poll_once(self) -> int:
        return self.tile.poll_once()

    def on_chaos(self, ev: dict):
        if ev["action"] == "corrupt_checkpt_frame":
            self.tile._corrupt_seed = int(ev.get("seed", 1))
        elif ev["action"] == "crash_mid_snapshot":
            # die halfway through the file, between publishes
            self.tile._crash_at = max(1, self.tile.size // 2)
        elif ev["action"] == "stale_snapshot_offer" and self.stale_path:
            self.tile.offer(self.stale_path)

    def metrics_items(self):
        return dict(self.tile.metrics)


@register("snapdc")
class SnapDcAdapter:
    """Snapshot decompress tile (ref: src/discof/restore/ snapdc —
    streaming zstd between two frag links)."""

    METRICS = ["in_bytes", "out_bytes", "frags", "done", "stream_err",
               "backpressure"]
    GAUGES = ["done"]

    def __init__(self, ctx, args):
        from ..tiles.snapshot import SnapDecompress
        self.ctx = ctx
        self.in_link = next(iter(ctx.in_rings))
        self.tile = SnapDecompress(
            ctx.in_rings[self.in_link],
            _single(ctx.out_rings, "out link", ctx.tile_name),
            _single(ctx.out_fseqs, "out link", ctx.tile_name))

    def poll_once(self) -> int:
        return self.tile.poll_once()

    def in_seqs(self):
        return {self.in_link: self.tile.seq}

    def metrics_items(self):
        return dict(self.tile.metrics)


@register("snapin")
class SnapInAdapter:
    """Snapshot inserter tile (ref: src/discof/restore/fd_snapin_tile.c
    — stream -> account DB). format="checkpt" (default): the
    framework's own checkpoint frames (integrity trailer inside the
    reader). format="archive": the real tar+AppendVec layout, fed
    DECOMPRESSED bytes by an upstream snapdc tile, lattice checksum
    verified at EOM.

    Follower mode (r17): when the plan carves a shm funk store
    ([funk] backend="shm"), format="checkpt" restores INTO that
    shared store (install-after-verify: every row validated before
    any lands) and then writes the restore marker the replay tile
    gates on — snapshot boot handoff without a control channel.
    min_slot (arg, or [snapshot] min_slot) refuses stale snapshots
    loudly instead of silently rolling the state back."""

    METRICS = ["frags", "bytes", "accounts", "restored", "fingerprint",
               "slot", "lattice_ok", "stream_err"]
    GAUGES = ["accounts", "fingerprint", "slot", "lattice_ok"]

    def __init__(self, ctx, args):
        from ..tiles.snapshot import ArchiveInserter, SnapInserter
        self.ctx = ctx
        self.in_link = next(iter(ctx.in_rings))
        if args.get("format") == "archive":
            self.tile = ArchiveInserter(ctx.in_rings[self.in_link])
        else:
            sp = ctx.plan.get("snapshot") or {}
            funk = None
            fk = ctx.plan.get("funk") or {}
            if fk.get("backend") == "shm" and "off" in fk:
                from ..funk.shmfunk import WireFunk
                funk = WireFunk.from_plan(ctx.wksp, fk)
            self.tile = SnapInserter(
                ctx.in_rings[self.in_link], funk=funk,
                min_slot=int(args.get("min_slot",
                                      sp.get("min_slot", 0))))

    def poll_once(self) -> int:
        return self.tile.poll_once()

    def in_seqs(self):
        return {self.in_link: self.tile.seq}

    def metrics_items(self):
        return dict(self.tile.metrics)


@register("metric")
class MetricAdapter:
    """The observability tile (ref: src/disco/metrics/fd_metric_tile.c
    + fd_prometheus.c): an HTTP endpoint rendered straight from the
    shared-memory metrics/cnc/link regions — reader-side only, so it
    survives any other tile's death — plus the SLO engine evaluated at
    the housekeeping cadence.

      GET /metrics       prometheus text (tile counters, wait/work/tpu
                         histograms, fdtpu_link_* per-link telemetry)
      GET /summary.json  the monitor snapshot + link table + SLO state
      GET /healthz       CNC + heartbeat-staleness roll-up: 200 when
                         every tile is RUN with a fresh heartbeat,
                         503 (with per-tile detail) otherwise

    args: port (0 = ephemeral; bound port published in the "port"
    metric), bind_addr, healthz_stale_s (heartbeat age that flips a
    tile unhealthy, default 5s)."""

    METRICS = ["port", "scrapes", "requests", "slo_breach",
               "slo_breaches", "slo_evals"]
    GAUGES = ["port", "slo_breach"]

    def __init__(self, ctx, args):
        from .httpd import Counter, TileHttpServer
        from .metrics import render_prometheus
        from .slo import SloEngine
        self.ctx = ctx
        self._scrapes = Counter()
        self.stale_ticks = int(
            float(args.get("healthz_stale_s", 5.0)) * 1e9)
        # SLO objectives ride the plan ([slo] section, validated at
        # build); breaches land in THIS tile's flight-recorder ring
        self.engine = SloEngine(ctx.plan, ctx.wksp,
                                trace=getattr(ctx, "trace", None))

        def metrics_route():
            self._scrapes.bump()
            body = render_prometheus(ctx.plan, ctx.wksp).encode()
            return 200, "text/plain; version=0.0.4", body

        def summary_route():
            # the ONE summary-document shape (monitor --json emits the
            # same), plus the SLO state only this tile can evaluate —
            # including the breach-history ring, so a flapping
            # objective reads straight off /summary.json
            from .monitor import full_snapshot
            body = json.dumps({
                **full_snapshot(ctx.plan, ctx.wksp),
                "slo": self.engine.status(),
                "slo_history": list(self.engine.history),
                "catchup": self._catchup(),
            }).encode()
            return 200, "application/json", body

        def healthz_route():
            doc = self._healthz()
            return (200 if doc["ok"] else 503), "application/json", \
                json.dumps(doc).encode()

        self.server = TileHttpServer(
            {"/metrics": metrics_route, "/": metrics_route,
             "/summary.json": summary_route, "/healthz": healthz_route},
            port=int(args.get("port", 0)),
            bind_addr=args.get("bind_addr", "127.0.0.1"))
        self.port = self.server.port

    def _catchup(self) -> dict | None:
        """r17 replay/snapshot progress as a first-class summary block
        (r19): per-replay-tile catch-up slots, mirroring the fdgui
        panel so dashboards scraping /summary.json need no gui tile.
        None when the topology has no replay tile."""
        from . import topo as topo_mod
        out = {}
        for tn, spec in self.ctx.plan["tiles"].items():
            if spec["kind"] != "replay":
                continue
            names = spec.get("metrics_names", [])
            vals = topo_mod.read_metrics(self.ctx.wksp, self.ctx.plan,
                                         tn)
            m = {nm: int(vals[i]) for i, nm in enumerate(names)}
            out[tn] = {k: m.get(k, 0) for k in
                       ("slots_replayed", "divergent_slot",
                        "restore_slot", "behind", "buffered")}
        return out or None

    def _healthz(self) -> dict:
        from ..runtime import Cnc, CNC_RUN
        from . import topo as topo_mod
        from .monitor import _STATE
        now = topo_mod.now_ticks()
        tiles = {}
        ok = True
        for tn, spec in self.ctx.plan["tiles"].items():
            cnc = Cnc(self.ctx.wksp, off=spec["cnc_off"])
            state = cnc.state
            age = max(0, now - cnc.last_heartbeat)
            stale = age > self.stale_ticks
            healthy = state == CNC_RUN and not stale
            ok = ok and healthy
            tiles[tn] = {
                "state": _STATE.get(state, f"?{state}"),
                "hb_age_ticks": age, "stale": stale,
                "healthy": healthy,
            }
        return {"ok": ok, "tiles": tiles,
                # informational: a burning SLO is a service problem,
                # not a liveness one — it must not flip readiness
                "slo_breached": [n for n, s in
                                 self.engine.status().items()
                                 if s["breached"]]}

    def housekeeping(self):
        for ev in self.engine.sample():
            from ..utils import log
            log.warning(f"slo {ev['kind']}: {ev['target']} "
                        f"({ev['expr']}) value={ev['value']}")
            if ev["kind"] != "breach":
                continue
            # SLO-breach-triggered device capture (fdprof): ring the
            # doorbell on each [prof] breach_capture tile — its own
            # housekeeping runs the bounded jax.profiler window and
            # acks, so the breach ships WITH its device attribution
            for tn in (self.ctx.plan.get("prof") or {}).get(
                    "breach_capture") or ():
                from ..prof.device import request_capture
                request_capture(self.ctx.plan, self.ctx.wksp, tn)

    def poll_once(self) -> int:
        return 0

    def on_halt(self):
        self.server.close()

    def metrics_items(self):
        return {"port": self.port,
                "scrapes": self._scrapes.value,
                "requests": self.server.requests.value,
                "slo_breach": self.engine.breached,
                "slo_breaches": self.engine.total_breaches,
                "slo_evals": self.engine.evals}


@register("flight")
class FlightAdapter:
    """fdflight recorder tile (r19): drains the shm observability
    plane — metric slot deltas, link counters + queue-wait
    quantiles, SLO breach/clear transitions, sampled trace events,
    prof folded-stack digests — into the durable on-disk archive the
    `[flight]` section configures (flight/archive.py segments +
    incident bundles). Reader-side only, the fdmetrics contract: every
    drain pass is a read of regions other tiles already maintain, so
    writer tiles pay nothing. The drain cadence (`[flight].hz`) is
    rate-limited inside the recorder; the stem just calls
    housekeeping. On halt the recorder takes one final drain and seals
    any pending incident, so a clean shutdown archives its own tail.

    args: none — all configuration rides the plan's [flight] section
    (validated at config load + topo.build + fdlint bad-flight)."""

    METRICS = ["frames", "drains", "segments", "incidents", "bytes"]
    GAUGES = ["segments"]

    def __init__(self, ctx, args):
        from ..flight.recorder import FlightRecorder
        self.ctx = ctx
        self.recorder = FlightRecorder(ctx.plan, ctx.wksp,
                                       ctx.plan.get("flight"))

    def housekeeping(self):
        self.recorder.maybe_drain()

    def poll_once(self) -> int:
        return 0

    def on_halt(self):
        self.recorder.close()

    def metrics_items(self):
        return dict(self.recorder.metrics)


@register("bundle")
class BundleAdapter:
    """Block-engine bundle ingest (ref: src/disco/bundle/
    fd_bundle_tile.c — a gRPC client subscribing to the Jito block
    engine and forwarding bundles to pack). Transport is the real
    thing (waltz/h2.py + waltz/grpc.py over TCP); the SCHEMA is this
    framework's own minimal proto (documented divergence: Jito's
    .proto tree is not vendored): a SubscribeBundles response message
    is `repeated bytes packets = 1` — one serialized txn per entry.

    The gRPC stream runs on a daemon thread feeding a local queue; the
    tile loop drains it into pack's bundle_in wire format
    (u8 count | count x (u16 len | payload)). Reconnects with backoff.

    args: engine ("host:port"), path, authority."""

    METRICS = ["bundles", "txns", "reconnects", "errors",
               "backpressure"]

    def __init__(self, ctx, args):
        import queue
        import threading
        self.ctx = ctx
        host, _, port = args["engine"].rpartition(":")
        self.addr = (host or "127.0.0.1", int(port))
        self.path = args.get("path",
                             "/fdtpu.BlockEngine/SubscribeBundles")
        self.authority = args.get("authority", "block-engine")
        self.out = _single(ctx.out_rings, "out link", ctx.tile_name)
        self.out_fseqs = _single(ctx.out_fseqs, "out link",
                                 ctx.tile_name)
        self.mtu = ctx.plan["links"][
            next(iter(ctx.out_rings))]["mtu"]
        self.q: "queue.Queue[list[bytes]]" = queue.Queue(maxsize=256)
        self._head: list[bytes] | None = None   # backpressured bundle
        self.m = {k: 0 for k in self.METRICS}
        self._halt = False
        self.thread = threading.Thread(target=self._stream_loop,
                                       daemon=True)
        self.thread.start()

    def _stream_loop(self):
        import time as _t
        from ..waltz.grpc import GrpcClient, GrpcError, pb_decode
        backoff = 0.2
        while not self._halt:
            try:
                cli = GrpcClient(self.addr, timeout=5.0)
                _, nxt = cli.open_server_stream(self.authority,
                                                self.path, b"")
                backoff = 0.2
                while not self._halt:
                    msg = nxt(timeout=5.0)
                    if msg is None:
                        break
                    txns = [v for v in pb_decode(msg).get(1, [])
                            if isinstance(v, bytes)]
                    if len(txns) > 5:
                        # a bundle is <=5 txns (pack.MAX_BUNDLE_TXNS);
                        # an oversized message is remote garbage, not
                        # a tile crash
                        self.m["errors"] += 1
                        continue
                    if txns:
                        self.q.put(txns, timeout=5.0)
                cli.close()
            except (OSError, GrpcError, Exception):  # noqa: BLE001
                self.m["errors"] += 1
            if not self._halt:
                _t.sleep(backoff)
                backoff = min(backoff * 2, 5.0)
                self.m["reconnects"] += 1

    def poll_once(self) -> int:
        import queue
        n = 0
        while n < 8:
            if self._head is not None:
                txns = self._head       # retry the backpressured head
            else:
                try:
                    txns = self.q.get_nowait()
                except queue.Empty:
                    break
            frame = bytearray([len(txns)])
            for t in txns:
                frame += struct.pack("<H", len(t)) + t
            if len(frame) > self.mtu:
                self.m["errors"] += 1
                self._head = None
                continue
            if self.out_fseqs and self.out.credits(self.out_fseqs) <= 0:
                self.m["backpressure"] += 1
                # hold the HEAD locally — re-putting into the queue
                # would reorder behind later bundles (and a blocking
                # put could deadlock against the stream thread)
                self._head = txns
                break
            self.out.publish(bytes(frame), sig=self.m["bundles"])
            self._head = None
            self.m["bundles"] += 1
            self.m["txns"] += len(txns)
            n += 1
        return n

    def on_halt(self):
        self._halt = True

    def metrics_items(self):
        return dict(self.m)


@register("plugin")
class PluginAdapter:
    """External-consumer event bridge (ref: src/disco/plugin/
    fd_plugin_tile.c — forwards validator data out-of-process for the
    GUI/Agave side; here an NDJSON stream over a unix socket, the
    python-idiomatic out-of-process seam). Every consumed frag becomes
    one event line {link, seq, sig, sz, data(hex, truncated)}; slow or
    dead clients are dropped, never block the tile (the reference's
    non-blocking plugin discipline).

    args: sock_path (unix socket), data_hex_max (payload prefix)."""

    METRICS = ["rx", "events", "clients", "dropped", "overruns"]
    GAUGES = ["clients"]

    def __init__(self, ctx, args):
        import socket as _s
        self.ctx = ctx
        self.path = args["sock_path"]
        self.hex_max = int(args.get("data_hex_max", 64))
        try:
            os.unlink(self.path)
        except OSError:
            pass
        self.srv = _s.socket(_s.AF_UNIX, _s.SOCK_STREAM)
        self.srv.bind(self.path)
        self.srv.listen(8)
        self.srv.setblocking(False)
        self.clients: list = []
        self.seqs = ctx.in_seqs0()
        self.mtus = {ln: ctx.plan["links"][ln]["mtu"]
                     for ln in ctx.in_rings}
        self.m = {k: 0 for k in self.METRICS}

    def _accept(self):
        while True:
            try:
                c, _ = self.srv.accept()
            except OSError:
                return
            c.setblocking(False)
            self.clients.append(c)

    def _emit(self, obj):
        if not self.clients:
            return
        line = (json.dumps(obj) + "\n").encode()
        alive = []
        for c in self.clients:
            try:
                c.sendall(line)
                alive.append(c)
            except BlockingIOError:
                self.m["dropped"] += 1       # slow consumer: drop it
                c.close()
            except OSError:
                c.close()
        self.clients = alive
        self.m["events"] += 1

    def poll_once(self) -> int:
        self._accept()
        total = 0
        for ln, ring in self.ctx.in_rings.items():
            n, self.seqs[ln], buf, sizes, sigs, ovr = ring.gather(
                self.seqs[ln], 16, self.mtus[ln])
            self.m["overruns"] += ovr
            for i in range(n):
                frame = bytes(buf[i, :sizes[i]])
                self.m["rx"] += 1
                self._emit({"link": ln, "sig": int(sigs[i]),
                            "sz": len(frame),
                            "data": frame[:self.hex_max].hex()})
            total += n
        return total

    def housekeeping(self):
        self._accept()
        self.m["clients"] = len(self.clients)

    def in_seqs(self):
        return self.seqs

    def on_halt(self):
        for c in self.clients:
            c.close()
        self.srv.close()
        try:
            os.unlink(self.path)
        except OSError:
            pass

    def metrics_items(self):
        return dict(self.m)


@register("netlnk")
class NetlnkAdapter:
    """Kernel route/neighbor table mirror (ref: src/disco/netlink/
    fd_netlink_tile.c — publishes FIB4 + ARP into shared maps; here
    waltz/nettables.py snapshots procfs at the housekeeping cadence
    and the counts surface as metrics; see the module docstring for
    why the sock-based net path only needs visibility)."""

    METRICS = ["routes", "neighbors", "refreshes", "default_via"]
    GAUGES = ["routes", "neighbors", "default_via"]

    def __init__(self, ctx, args):
        self.ctx = ctx
        self.m = {k: 0 for k in self.METRICS}
        self.fib = None
        self.neigh = None
        self.housekeeping()

    def housekeeping(self):
        from ..waltz.nettables import refresh_from_proc
        self.fib, self.neigh = refresh_from_proc()
        self.m["routes"] = len(self.fib)
        self.m["neighbors"] = len(self.neigh)
        self.m["refreshes"] += 1
        # the DEFAULT route's gateway, not whatever more-specific
        # route happens to cover a probe address
        default = next((r for r in self.fib.routes
                        if r.prefix_len == 0), None)
        self.m["default_via"] = default.gw if default else 0

    def poll_once(self) -> int:
        return 0

    def metrics_items(self):
        return dict(self.m)


@register("vinyl")
class VinylAdapter:
    """vinyl DB service tile (ref: src/vinyl/fd_vinyl.h:13-29 — the
    log-structured disk DB "run as a dedicated tile driven over tango
    rings"; clients speak request/completion queues, rq/ and cq/, tile
    src/discof/vinyl/fd_vinyl_tile.c). Request frame:

        op u8 (1=PUT 2=GET 3=DEL) | req_id u64 | key 32 | val...

    Completion frame:  req_id u64 | status u8 (0=ok 1=miss 2=err) |
    val... (GET hits). The store is the crash-recovering append log in
    vinyl/vinyl.py; durability boundary = the housekeeping fsync
    (args: sync_every_hk) with opportunistic GC compaction.

    args: path (log file), gc (run maybe_compact in housekeeping)."""

    METRICS = ["puts", "gets", "hits", "dels", "errs", "records",
               "backpressure", "overruns"]
    GAUGES = ["records"]

    OP_PUT, OP_GET, OP_DEL = 1, 2, 3
    ST_OK, ST_MISS, ST_ERR = 0, 1, 2

    def __init__(self, ctx, args):
        from ..vinyl.vinyl import Vinyl
        self.ctx = ctx
        self.in_link = _single({k: k for k in ctx.in_rings}, "in link",
                               ctx.tile_name)
        self.ring = ctx.in_rings[self.in_link]
        out_link = _single({k: k for k in ctx.out_rings}, "out link",
                           ctx.tile_name)
        self.out = ctx.out_rings[out_link]
        self.out_fseqs = ctx.out_fseqs[out_link]
        self.mtu = ctx.plan["links"][self.in_link]["mtu"]
        self.out_mtu = ctx.plan["links"][out_link]["mtu"]
        self.db = Vinyl(args["path"])
        self.gc = bool(args.get("gc", True))
        self.seq = ctx.in_seq0.get(self.in_link, 0)
        self.m = {k: 0 for k in self.METRICS}

    def poll_once(self) -> int:
        n, self.seq, buf, sizes, sigs, ovr = self.ring.gather(
            self.seq, 16, self.mtu)
        self.m["overruns"] += ovr
        # request/response server grain: each frame is one db request
        # (get/put/scan) whose parse, db call, and completion publish
        # are a per-request protocol exchange, not batchable ring I/O
        # fdlint: disable=per-frag-loop — req/resp serving grain
        for i in range(n):
            frame = bytes(buf[i, :sizes[i]])
            self._serve(frame)
        self.m["records"] = len(self.db)
        return n

    def _serve(self, frame: bytes):
        if len(frame) < 41:
            self.m["errs"] += 1
            if len(frame) >= 9:
                # req_id parseable: answer ST_ERR so the client fails
                # fast instead of burning its timeout (r4 review) —
                # through the same credit gate as every completion
                rid, = struct.unpack_from("<Q", frame, 1)
                self._publish_completion(
                    struct.pack("<QB", rid, self.ST_ERR), rid)
            return
        op = frame[0]
        req_id, = struct.unpack_from("<Q", frame, 1)
        key = frame[9:41]
        resp = struct.pack("<QB", req_id, self.ST_OK)
        try:
            if op == self.OP_PUT:
                # a value a GET completion could not carry is refused
                # at PUT time (the cq mtu bounds the protocol, not a
                # crash in Ring.publish)
                if 9 + len(frame) - 41 > self.out_mtu:
                    resp = struct.pack("<QB", req_id, self.ST_ERR)
                    self.m["errs"] += 1
                else:
                    self.db.put(key, frame[41:])
                    self.m["puts"] += 1
            elif op == self.OP_GET:
                val = self.db.get(key)
                self.m["gets"] += 1
                if val is None:
                    resp = struct.pack("<QB", req_id, self.ST_MISS)
                elif 9 + len(val) > self.out_mtu:
                    # legacy oversize record (written under a larger
                    # cq mtu): typed error, not a tile crash
                    resp = struct.pack("<QB", req_id, self.ST_ERR)
                    self.m["errs"] += 1
                else:
                    self.m["hits"] += 1
                    resp += val
            elif op == self.OP_DEL:
                self.db.delete(key)
                self.m["dels"] += 1
            else:
                resp = struct.pack("<QB", req_id, self.ST_ERR)
                self.m["errs"] += 1
        except Exception:
            resp = struct.pack("<QB", req_id, self.ST_ERR)
            self.m["errs"] += 1
        self._publish_completion(resp, req_id)

    def _publish_completion(self, resp: bytes, req_id: int):
        # reliable (tile) consumers are credit-gated here; EXTERNAL
        # clients have no fseq, so for them the cq is overrun-lossy
        # like any unreliable link — the client's gather() sees the
        # seq gap and must size cq depth >= its in-flight window
        # (the _Client contract in tests/test_vinyl_tile.py)
        while self.out_fseqs and self.out.credits(self.out_fseqs) <= 0:
            self.m["backpressure"] += 1
            time.sleep(50e-6)
        self.out.publish(resp, sig=req_id)

    def housekeeping(self):
        self.db.sync()
        if self.gc:
            self.db.maybe_compact()

    def in_seqs(self):
        # publish consumer progress: without this a RELIABLE rq
        # producer (an in-topo client tile, unlike the external-link
        # test clients) wedges once the ring fills against a frozen
        # fseq (found by fdlint's silent-consumer rule)
        return {self.in_link: self.seq}

    def on_halt(self):
        self.db.close()

    def metrics_items(self):
        return dict(self.m)


@register("gui")
class GuiAdapter:
    """fdgui v2: the live operator dashboard (ref: src/disco/gui/
    fd_gui.c + fd_gui_tile.c — the reference serves a bundled frontend
    over HTTP+WebSocket with a snapshot+delta protocol,
    book/api/websocket.md, on the shared waltz/http server). Here the
    same shape over the shared plumbing: TileHttpServer (disco/httpd)
    serves the self-contained page (gui/page.py) plus a `/ws` route —
    on connect the client gets one full topology snapshot, then a
    delta per housekeeping pass (gui/schema.py: TPS, per-tile
    state/metrics/latency/occupancy incl. supervisor counters,
    per-link pub/consumed/loss/backpressure + consume quantiles, SLO
    status + breach history). Everything is READ-side over existing
    shm surfaces: zero writer-side cost.

    Slow clients degrade gracefully (WsConn): enqueue never blocks the
    housekeeping loop; a backed-up queue drops oldest frames, and a
    stalled client is shed (ws_shed metric) — the cadence is never
    hostage to a dead TCP peer.

    args (gui/schema.py normalize_gui — validated at config load,
    topo.build, and by fdlint's bad-gui rule): port (0 = ephemeral,
    published as the "port" metric), bind_addr, tps_tile/tps_metric
    (TPS counter source, default sink.rx), ws_max_clients, ws_queue,
    ws_sndbuf, bench_glob (/bench.json trend source), report_on_halt
    (write the static report artifact on clean halt)."""

    METRICS = ["port", "requests", "ws_clients", "ws_sent",
               "ws_dropped", "ws_shed"]
    GAUGES = ["port", "ws_clients"]

    def __init__(self, ctx, args):
        from ..gui import (DeltaSource, normalize_gui, page_html,
                           snapshot_doc)
        from .httpd import TileHttpServer
        a = normalize_gui(args)
        self.ctx = ctx
        # TPS rides the delta source on utils/tempo.monotonic_ns —
        # THE topology clock (heartbeats, watchdog, trace, prof); a
        # perf_counter-derived rate would disagree with the trace/prof
        # timelines on the shared clock domain
        self._delta_src = DeltaSource(ctx.plan, ctx.wksp,
                                      tps_tile=a["tps_tile"],
                                      tps_metric=a["tps_metric"])
        self._report_on_halt = a["report_on_halt"]
        self._bench_glob = a["bench_glob"]

        def page_route():
            return 200, "text/html", page_html().encode()

        def summary_route():
            # handler-thread shm reads can race a halting topology
            # (tiles tearing down mid-snapshot): answer 503 like the
            # monitor tolerates a stale plan, never a traceback-500
            try:
                body = json.dumps({
                    "topology": ctx.plan["topology"],
                    "tps": self._delta_src.tps,
                    **{k: v for k, v in self._summary().items()
                       if k != "topology"},
                }).encode()
            except Exception as e:   # noqa: BLE001 — teardown race
                return 503, "application/json", json.dumps(
                    {"error": f"topology unreadable: {e!r}"}).encode()
            return 200, "application/json", body

        def flame_route():
            from ..prof.export import read_folded
            try:
                body = json.dumps(
                    read_folded(ctx.plan, ctx.wksp)).encode()
            except Exception as e:   # noqa: BLE001 — teardown race
                return 503, "application/json", json.dumps(
                    {"error": f"prof unreadable: {e!r}"}).encode()
            return 200, "application/json", body

        def bench_route():
            import glob as _glob

            from ..gui.report import bench_series
            body = json.dumps(bench_series(
                sorted(_glob.glob(self._bench_glob)))).encode()
            return 200, "application/json", body

        def history_route():
            # archive-backed history panel (r19): sparklines from the
            # [flight] directory on DISK, so the window reaches past
            # whatever the live shm rings still hold
            from ..gui.report import history_series
            flight_dir = (ctx.plan.get("flight") or {}).get("dir")
            if not flight_dir:
                return 404, "application/json", json.dumps(
                    {"error": "topology has no [flight] archive"}
                ).encode()
            try:
                body = json.dumps(history_series(flight_dir)).encode()
            except Exception as e:   # noqa: BLE001 — unreadable dir
                return 503, "application/json", json.dumps(
                    {"error": f"archive unreadable: {e!r}"}).encode()
            return 200, "application/json", body

        def on_ws_connect(conn):
            conn.send_json(snapshot_doc(ctx.plan))

        self.server = TileHttpServer(
            {"/": page_route, "/index.html": page_route,
             "/summary.json": summary_route,
             "/flame.json": flame_route, "/bench.json": bench_route,
             "/history.json": history_route},
            port=a["port"], bind_addr=a["bind_addr"],
            ws_routes={"/ws": on_ws_connect},
            ws_max_clients=a["ws_max_clients"],
            ws_queue=a["ws_queue"], ws_sndbuf=a["ws_sndbuf"])
        self.port = self.server.port

    def _summary(self) -> dict:
        from .monitor import full_snapshot
        return full_snapshot(self.ctx.plan, self.ctx.wksp)

    def housekeeping(self):
        # TPS samples every pass (cheap: one metric-slot read); the
        # full delta document is built only when someone is listening
        self._delta_src.sample_tps()
        if not self.server.has_ws_clients("/ws"):
            return
        try:
            delta = self._delta_src.delta()
        except Exception:   # noqa: BLE001 — mid-teardown read race:
            return          # skip the tick, the stream resumes
        self.server.broadcast("/ws", delta)

    def poll_once(self) -> int:
        return 0

    def on_halt(self):
        if self._report_on_halt:
            import glob as _glob

            from ..gui.report import bench_series, collect, \
                render_html
            try:
                data = collect(self.ctx.plan, self.ctx.wksp,
                               deltas=1)
                data["bench"] = bench_series(
                    sorted(_glob.glob(self._bench_glob)))
                with open(self._report_on_halt, "w") as f:
                    f.write(render_html(data))
            except Exception as e:   # noqa: BLE001 — the artifact is
                from ..utils import log      # best-effort on halt
                log.warning(f"gui: report_on_halt failed: {e!r}")
        self.server.close()

    def metrics_items(self):
        ws = self.server.ws_stats()
        return {"port": self.port,
                "requests": self.server.requests.value,
                "ws_clients": ws["clients"],
                "ws_sent": ws["sent"],
                "ws_dropped": ws["dropped"],
                "ws_shed": ws["shed"]}


@register("cswtch")
class CswtchAdapter:
    """Context-switch sampler (ref: src/disco/cswtch/fd_cswtch_tile.c —
    reads every tile's scheduling counters; a jump in INVOLUNTARY
    switches means a tile lost its core). Tile pids come from the
    per-tile pidfiles the launcher publishes; sampling reads
    /proc/<pid>/status at the housekeeping cadence.

    Metrics: aggregate voluntary/involuntary totals across the
    topology plus the worst single-tile involuntary count."""

    METRICS = ["vol", "invol", "tiles_sampled", "max_invol"]
    GAUGES = ["vol", "invol", "tiles_sampled", "max_invol"]

    def __init__(self, ctx, args):
        self.ctx = ctx
        self.topo = ctx.plan["topology"]
        self.m = {k: 0 for k in self.METRICS}
        self._last: dict[str, int] = {}

    def _sample(self):
        vol = invol = n = worst = 0
        for tn in self.ctx.plan["tiles"]:
            try:
                with open(f"/dev/shm/fdtpu_{self.topo}.pid.{tn}") as f:
                    parts = f.read().split()
                pid = int(parts[0])
                want_start = parts[1] if len(parts) > 1 else None
                with open(f"/proc/{pid}/stat") as f:
                    have_start = f.read().rsplit(")", 1)[1].split()[19]
                if want_start is not None and have_start != want_start:
                    continue            # recycled pid: stale pidfile
                with open(f"/proc/{pid}/status") as f:
                    st = f.read()
            except (OSError, ValueError, IndexError):
                continue
            v = i = 0
            for line in st.splitlines():
                if line.startswith("voluntary_ctxt_switches"):
                    v = int(line.split()[-1])
                elif line.startswith("nonvoluntary_ctxt_switches"):
                    i = int(line.split()[-1])
            vol += v
            invol += i
            worst = max(worst, i)
            n += 1
            prev = self._last.get(tn, i)
            if i - prev > 1000:
                from ..utils import log
                log.warning(f"cswtch: tile {tn} took {i - prev} "
                            f"involuntary switches since last sample")
            self._last[tn] = i
        self.m.update(vol=vol, invol=invol, tiles_sampled=n,
                      max_invol=worst)

    def housekeeping(self):
        self._sample()

    def poll_once(self) -> int:
        return 0

    def metrics_items(self):
        return dict(self.m)


@register("ipecho")
class IpechoAdapter:
    """Shred-version echo service (ref: src/discof/ipecho/ — a booting
    node connects to an entrypoint to learn its OWN public address and
    the cluster's shred version before joining gossip). TCP server on
    a daemon thread; wire format: magic u32 | shred_version u16 |
    observed peer ip 4B | observed peer port u16."""

    METRICS = ["port", "queries"]
    GAUGES = ["port"]
    WIRE_MAGIC = 0xFD19E040

    def __init__(self, ctx, args):
        import socket
        import threading
        self.ctx = ctx
        self.shred_version = int(args.get("shred_version", 0))
        self.queries = 0
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind((args.get("bind_addr", "127.0.0.1"),
                        int(args.get("port", 0))))
        self.sock.listen(8)
        self.port = self.sock.getsockname()[1]
        self._halt = False
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()

    def _serve(self):
        import socket
        import struct as st
        while not self._halt:
            try:
                conn, peer = self.sock.accept()
            except OSError:
                return
            try:
                ip = socket.inet_aton(peer[0])
                conn.sendall(st.pack("<IH4sH", self.WIRE_MAGIC,
                                     self.shred_version, ip, peer[1]))
                self.queries += 1
            except OSError:
                pass
            finally:
                conn.close()

    def poll_once(self) -> int:
        return 0

    def on_halt(self):
        self._halt = True
        try:
            self.sock.close()
        except OSError:
            pass

    def metrics_items(self):
        return {"port": self.port, "queries": self.queries}


def ipecho_query(addr: tuple, timeout: float = 5.0):
    """Client side: -> (shred_version, my_ip_str, my_port)."""
    import socket
    import struct as st
    data = b""
    with socket.create_connection(addr, timeout=timeout) as s:
        while len(data) < 12:            # TCP may split the reply
            chunk = s.recv(12 - len(data))
            if not chunk:
                raise ValueError("short ipecho reply")
            data += chunk
    magic, sv, ip, port = st.unpack("<IH4sH", data)
    if magic != IpechoAdapter.WIRE_MAGIC:
        raise ValueError("bad ipecho magic")
    return sv, socket.inet_ntoa(ip), port


@register("pcap")
class PcapAdapter:
    """pcap replay tile (ref: src/disco/pcap/fd_pcap_replay_tile.c):
    re-drives captured packet payloads into an out link, preserving
    either full pacing (realtime=true scales inter-packet gaps) or
    flat-out replay. args: path, realtime, loop (replay count)."""

    METRICS = ["tx", "loops", "done", "backpressure"]
    GAUGES = ["done"]

    def __init__(self, ctx, args):
        from ..utils.pcap import read_pcap
        self.ctx = ctx
        self.out = _single(ctx.out_rings, "out link", ctx.tile_name)
        self.out_fseqs = _single(ctx.out_fseqs, "out link",
                                 ctx.tile_name)
        self.path = args["path"]
        self.realtime = bool(args.get("realtime", False))
        self.loops_want = int(args.get("loop", 1))
        self.m = {k: 0 for k in self.METRICS}
        self.pkts = []
        with open(self.path, "rb") as f:
            self.pkts = list(read_pcap(f))
        self._idx = 0
        self._t0 = None
        self._ts0 = self.pkts[0][0] if self.pkts else 0

    def poll_once(self) -> int:
        import time as _t
        if self.m["done"]:
            return 0
        if self._idx >= len(self.pkts):
            self.m["loops"] += 1
            if self.m["loops"] >= self.loops_want or not self.pkts:
                self.m["done"] = 1       # empty capture: done, no loop
                return 0
            self._idx = 0
            self._t0 = None
        ts, data = self.pkts[self._idx]
        if self.realtime:
            if self._t0 is None:
                self._t0 = _t.perf_counter()
            due = self._t0 + (ts - self._ts0) / 1e6
            if _t.perf_counter() < due:
                return 0
        if self.out_fseqs and self.out.credits(self.out_fseqs) <= 0:
            self.m["backpressure"] += 1
            return 0
        self.out.publish(data, sig=self.m["tx"])
        self.m["tx"] += 1
        self._idx += 1
        return 1

    def metrics_items(self):
        return dict(self.m)


@register("sink")
class SinkAdapter:
    """Terminal consumer: counts frags (the reference's bencho TPS
    observer, ref: src/app/shared_dev/commands/bench/fd_bencho_tile.c).
    args: batch."""

    METRICS = ["rx", "bytes", "overruns"]

    def __init__(self, ctx, args):
        self.ctx = ctx
        self.batch = int(args.get("batch", 64))
        self.seqs = ctx.in_seqs0()
        self.mtu = max(ctx.plan["links"][ln]["mtu"] for ln in ctx.in_rings)
        self.m = {k: 0 for k in self.METRICS}
        self._tr = getattr(ctx, "trace", None)

    def poll_once(self) -> int:
        # counting consumer: the whole gather tallies vectorized (one
        # sizes-sum per batch) — the bencho TPS observation must never
        # itself be the per-frag-Python bottleneck it measures
        total = 0
        for ln, ring in self.ctx.in_rings.items():
            n, self.seqs[ln], buf, sizes, sigs, ovr = ring.gather(
                self.seqs[ln], self.batch, self.mtu)
            self.m["overruns"] += ovr
            if not n:
                continue
            self.m["rx"] += n
            self.m["bytes"] += int(sizes[:n].sum())
            if self._tr is not None:
                from ..trace.events import EV_CONSUME
                self._tr.frag_batch(EV_CONSUME, sigs[:n],
                                    link=self._tr.link_id(ln))
            total += n
        return total

    def in_seqs(self):
        return dict(self.seqs)

    def metrics_items(self):
        return dict(self.m)
