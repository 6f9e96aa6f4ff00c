"""One cell, one run: sign the corpus, boot the configuration's topology,
inject the mix open loop from the scheduled send times, observe what
the configuration's `observe` link delivers, judge it against the
reference, print one result line.

This process stands in for the upstream quic->verify producer: it
publishes pre-built frames into the ingest ring (credit-gated on the
verify tile's fseq, as the repo's ingest link is reliable) and reads
the observed ring (an external, unreliable observer): dedup's output,
or the PoH entries of a leader. It never imports JAX: the verify
tile's process owns the chip. The device block comes from that
process: its boot line (platform, kind, chips), its `tpu_mem_bytes`
gauge, and, with --trace 1, its profiler capture.

The kind of the tile that produces the observed link names the judge.
Of dedup's output: every offered txn against its signatures
(reference.py). Of a poh tile's entries: the same over the committed
txns, the PoH record itself, the block rules a replaying validator
holds each entry and slot to (blockrules.py, with the config's
block_limits), and the balances the program serves against a plain
serial executor (ledger.py); the payers are funded through the bank
tile's genesis arg, and read back over its JSON-RPC port after the
drain.

Everything cell-specific is found by name: the workload entry of
BENCHMARK.json names its config (configs/<name>.json, a self-contained
topology) and its traffic (traffic/<name>.json, read by traffic.py);
cells/<workload>.json holds the offered rate; each metric is read by
metrics/<metric>.py. Adding a cell, mix or metric adds files only.
"""
from __future__ import annotations

import ctypes as ct
import gc
import glob
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, ".out")          # logs + traces (git-ignored)
PLATFORM = "tpu"      # tests steer this to "cpu" for the CPU rehearsal
BOOT_S = 900.0
DRAIN_S = 60.0        # after the window: wait this long for late txns
WARM_DRAIN_S = 30.0
CAPTURE_MS = 2000.0


class BenchError(RuntimeError):
    """A run that must print no result."""


def say(msg: str):
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# what a cell is, by name
# ---------------------------------------------------------------------------

def load_cell(workload: str, root: str = ROOT) -> dict:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    cfg = load_json(os.path.join(root, conf["file"]))
    mix_path = os.path.join(root, "perfbench", "traffic",
                            w["traffic"] + ".json")
    from .traffic import load_mix
    return {
        "workload": w, "config": cfg, "mix": load_mix(mix_path),
        "cell": load_json(os.path.join(root, "perfbench", "cells",
                                       workload + ".json")),
        "metrics": _metrics_for(bench, workload),
    }


def _metrics_for(bench: dict, workload: str) -> dict:
    """{"end_to_end": [entry...], "per_layer": [entry...]} this cell
    reports: an entry with `workloads` lists its cells; one without is
    reported wherever its `moves` metric (or, end to end, every cell)."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    pl = [m for m in bench["per_layer"]
          if (workload in m["workloads"] if "workloads" in m
              else m["moves"] in names)]
    return {"end_to_end": e2e, "per_layer": pl}


def reader(name: str):
    """metrics/<name>.py's read(run) -> float | None."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the topology, from a self-contained config file
# ---------------------------------------------------------------------------

def build_topology(cfg: dict, name: str, trace: bool):
    from firedancer_tpu.disco import Topology
    prof = None
    if trace:
        # only the device tile is profiled: its capture doorbell opens
        # the jax.profiler window; the host sampler runs at a token rate
        prof = {"enable": True, "tiles": [cfg["device_tile"]], "hz": 1.0,
                "capture_ms": CAPTURE_MS}
    topo = Topology(name, wksp_size=int(cfg["wksp_size"]), prof=prof)
    for ln in cfg["links"]:
        topo.link(ln["name"], depth=int(ln["depth"]), mtu=int(ln["mtu"]),
                  external=bool(ln.get("external", False)))
    for tc in cfg["tcaches"]:
        topo.tcache(tc["name"], depth=int(tc["depth"]))
    for t in cfg["tiles"]:
        topo.tile(t["name"], t["kind"], ins=t.get("ins", ()),
                  outs=t.get("outs", ()), **t.get("args", {}))
    return topo


def _ring(wksp, plan: dict, link: str):
    from firedancer_tpu.runtime import Ring
    li = plan["links"][link]
    return Ring(wksp, li["ring_off"], li["depth"], li["arena_off"],
                li["mtu"])


def peaks(kind: str) -> dict:
    """Published peaks of one chip (peaks.json). No metric divides by
    them yet: the strict kernel is int32 VPU work, and no VPU int32
    peak is published. An unknown device kind is an error."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise BenchError(f"no published peaks for device kind {kind!r}")
    return table[kind]


def check_device(boot: dict, chips: int):
    """The device tile must sit on PLATFORM with the cell's chips."""
    if boot.get("platform") != PLATFORM:
        raise BenchError(f"the device tile runs on "
                         f"{boot.get('platform')!r}, not {PLATFORM!r}")
    if boot["count"] < chips:
        raise BenchError(f"{boot['count']} chip(s), the cell asks for "
                         f"{chips}")
    if PLATFORM == "tpu":
        peaks(boot["kind"])


def boot_line(log_path: str, tile: str) -> dict:
    """platform / kind / chips from the device tile's boot line."""
    line = ""
    if os.path.exists(log_path):
        with open(log_path, errors="replace") as f:
            line = next((ln for ln in f if f":{tile}:" in ln
                         and "jax platform=" in ln), "")
    if not line:
        return {}
    kv = line.split("jax platform=", 1)[1]
    plat = kv.split()[0]
    kind = kv.split("device_kind=", 1)[1].split(" devices=")[0]
    devs = int(kv.split(" devices=", 1)[1].split()[0])
    return {"platform": plat, "kind": kind, "count": devs}


# ---------------------------------------------------------------------------
# the open-loop injector and the observer of the observed link
# ---------------------------------------------------------------------------

class Injector:
    """Publishes due frames into the ingest ring, credit-gated on the
    consumer's fseq, and records when each frame actually went out."""

    def __init__(self, ring, fseqs, corpus, send_txn):
        self.ring, self.fseqs, self.c = ring, fseqs, corpus
        self.send_txn = send_txn
        self.cur = 0                     # next frame to publish
        self.pub_t = np.full(len(send_txn), np.nan)
        self.stalls = 0                  # publishes cut short by credits
        self._buf = np.zeros((1024, 1232), np.uint8)

    def publish_until(self, end: int) -> int:
        """Publish frames [cur, end) as credits allow; returns count."""
        done = 0
        while self.cur < end:
            k = min(end - self.cur, len(self._buf))
            idx = self.send_txn[self.cur:self.cur + k]
            sizes = self.c.size[idx].astype(np.uint32)
            for j, i in enumerate(idx):
                o = int(self.c.off[i])
                self._buf[j, :sizes[j]] = self.c.flat[o:o + sizes[j]]
            stop, _ = self.ring.publish_batch(
                self._buf[:k], sizes, idx.astype(np.uint64),
                np.ones(k, np.uint8), fseqs=self.fseqs)
            self.pub_t[self.cur:self.cur + stop] = time.monotonic()
            self.cur += stop
            done += stop
            if stop < k:
                self.stalls += 1         # out of credits: the ring is full
                break
        return done


class Observer:
    """The consumer of the observed link, in a process of its own (so
    neither it nor the injector starves the other): it gathers what the
    link delivers, matches each txn to the corpus by the first 8 bytes
    of its first signature, checks the payload's checksum, and writes
    the first delivery time and the delivery count of each txn into
    shared memory that this process reads.

    With `entries` a frame is a PoH entry (ENTRY_HDR bytes of header
    ending in a u16 txn count, then u16-framed txns): the observer joins
    the ring at its head (PoH ticks from boot on), walks each entry's
    txns, and also keeps the order of each txn's first delivery (the
    commit order on the one chain), the entry that carried it, and
    every entry's header with the time it came, for the ledger judge."""

    STOP, SEEN, ALTERED, UNKNOWN, OVERRUN, READY, ENTRIES = range(7)

    def __init__(self, plan: dict, link: str, corpus,
                 entries: bool = False):
        import multiprocessing as mp
        from multiprocessing import shared_memory
        n = len(corpus.size)
        self.shm = shared_memory.SharedMemory(create=True,
                                              size=12 * n + 128)
        self.first_t, self.count, self.stats = _views(self.shm.buf, n)
        self.first_t[:] = np.nan
        self.count[:] = 0
        self.stats[:] = 0
        self.ext = None
        if entries:
            self.ext = shared_memory.SharedMemory(
                create=True, size=_entry_views_size(n))
            self.first_seq, self.entry_of, self.hdr, self.hdr_t = \
                _entry_views(self.ext.buf, n)
            self.first_seq[:] = -1
            self.entry_of[:] = -1
        order = np.argsort(corpus.key)
        self.proc = mp.get_context("spawn").Process(
            target=_observe, name="perfbench-observer", daemon=True,
            args=(self.shm.name, n, plan, link, corpus.key[order], order,
                  corpus.size, corpus.check,
                  self.ext.name if entries else None))
        self.proc.start()
        while not self.stats[self.READY]:
            if not self.proc.is_alive():
                raise BenchError("the observer process died at start")
            time.sleep(0.01)

    def stat(self, k: int) -> int:
        return int(self.stats[k])

    def stop(self):
        """Let the observer drain the ring, then keep copies of its
        arrays and free the shared memory."""
        self.stats[self.STOP] = 1
        self.proc.join(30)
        if self.proc.is_alive():
            self.proc.terminate()
            self.proc.join(5)
        self.first_t = self.first_t.copy()
        self.count = self.count.copy()
        self.stats = self.stats.copy()
        self.shm.close()
        self.shm.unlink()
        if self.ext is not None:
            m = self.stat(self.ENTRIES)
            self.first_seq = self.first_seq.copy()
            self.entry_of = self.entry_of.copy()
            self.hdr, self.hdr_t = self.hdr[:m].copy(), self.hdr_t[:m].copy()
            self.ext.close()
            self.ext.unlink()


# PoH entry frag: u64 slot | u32 tick | u32 num_hashes | u8 has_mixin |
# prev 32 | hash 32 | mixin 32 | u8 flags | u16 txn_cnt, then txn_cnt x
# (u16 len | payload) (the program's poh tile, as its docstring states)
ENTRY_HDR = 116
ENTRY_CAP = 1 << 18               # entry headers kept in one run


def _views(buf, n: int):
    first_t = np.ndarray((n,), np.float64, buffer=buf, offset=0)
    count = np.ndarray((n,), np.int32, buffer=buf, offset=8 * n)
    stats = np.ndarray((8,), np.int64, buffer=buf,
                       offset=(12 * n + 7) // 8 * 8)
    return first_t, count, stats


def _entry_views_size(n: int) -> int:
    return 16 * n + 8 * ENTRY_CAP + ENTRY_HDR * ENTRY_CAP


def _entry_views(buf, n: int):
    first_seq = np.ndarray((n,), np.int64, buffer=buf, offset=0)
    entry_of = np.ndarray((n,), np.int64, buffer=buf, offset=8 * n)
    hdr_t = np.ndarray((ENTRY_CAP,), np.float64, buffer=buf,
                       offset=16 * n)
    hdr = np.ndarray((ENTRY_CAP, ENTRY_HDR), np.uint8, buffer=buf,
                     offset=16 * n + 8 * ENTRY_CAP)
    return first_seq, entry_of, hdr, hdr_t


def entry_txns(buf, szs, k: int, mtu: int):
    """The txns inside k gathered PoH entry frames -> (payloads zero
    padded to `mtu`, (m, mtu) uint8; their sizes; the frame of each;
    frames malformed).
    A txn that would run past its frame ends the walk of that frame,
    which then counts as malformed."""
    rows, offs, lens, bad = [], [], [], 0
    for i in range(k):
        sz = int(szs[i])
        if sz < ENTRY_HDR:
            bad += 1
            continue
        f = buf[i]
        off = ENTRY_HDR
        for _ in range(int(f[ENTRY_HDR - 2]) | int(f[ENTRY_HDR - 1]) << 8):
            ln = int(f[off]) | int(f[off + 1]) << 8 if off + 2 <= sz else -1
            if ln < 0 or off + 2 + ln > sz or ln > mtu:
                bad += 1
                break
            rows.append(i)
            offs.append(off + 2)
            lens.append(ln)
            off += 2 + ln
    out = np.zeros((len(rows), mtu), np.uint8)
    for j, (i, o, ln) in enumerate(zip(rows, offs, lens)):
        out[j, :ln] = buf[i, o:o + ln]
    return out, np.array(lens, np.uint32), np.array(rows, np.int64), bad


def _observe(shm_name, n, plan, link, keys, order, sizes, checks,
             ext_name=None):
    """The observer process's loop (see Observer)."""
    from multiprocessing import shared_memory

    from firedancer_tpu.runtime import Workspace
    from firedancer_tpu.runtime.tango import lib

    from .traffic import MTU, WORD_WEIGHTS
    from multiprocessing import resource_tracker
    shm = shared_memory.SharedMemory(name=shm_name)
    # the creating process unlinks it; this attach must not (py < 3.13)
    resource_tracker.unregister(shm._name, "shared_memory")
    first_t, count, stats = _views(shm.buf, n)
    wksp = Workspace(plan["wksp"]["name"], plan["wksp"]["size"],
                     create=False)
    ring = _ring(wksp, plan, link)
    if ext_name is None:
        n_max, width, seq0 = 4096, MTU, 0
    else:
        ext = shared_memory.SharedMemory(name=ext_name)
        first_seq, entry_of, hdr, hdr_t = _entry_views(ext.buf, n)
        n_max, width, seq0 = 256, plan["links"][link]["mtu"], ring.seq
    buf = np.zeros((n_max, width), np.uint8)
    words = buf.view(np.uint64)
    szs = np.zeros(n_max, np.uint32)
    sigs = np.zeros(n_max, np.uint64)
    seq, ovr = ct.c_uint64(seq0), ct.c_uint64(0)
    u8p = ct.POINTER(ct.c_uint8)
    args = (wksp.base, ring.off, ct.byref(seq), n_max,
            buf.ctypes.data_as(u8p), width,
            szs.ctypes.data_as(ct.POINTER(ct.c_uint32)),
            sigs.ctypes.data_as(ct.POINTER(ct.c_uint64)), ct.byref(ovr),
            None)
    stats[Observer.READY] = 1
    idle = 0
    try:
        while True:
            k = lib.fdtpu_ring_gather(*args)
            if not k:
                stats[Observer.OVERRUN] = ovr.value
                if stats[Observer.STOP]:
                    idle += 1
                    if idle > 3:
                        break
                time.sleep(20e-6)
                continue
            idle = 0
            now = time.monotonic()
            if ext_name is not None:
                _observe_entries(buf, szs, k, now, keys, order, sizes,
                                 checks, first_t, count, stats, first_seq,
                                 entry_of, hdr, hdr_t)
                continue
            key = buf[:k, 1:9].copy().view(np.uint64).ravel()
            pos = np.minimum(np.searchsorted(keys, key), len(keys) - 1)
            hit = keys[pos] == key
            idx = order[pos[hit]]
            same = szs[:k][hit] == sizes[idx]
            same &= (words[:k][hit] * WORD_WEIGHTS).sum(
                axis=1, dtype=np.uint64) == checks[idx]
            np.add.at(count, idx, 1)
            fresh = idx[np.isnan(first_t[idx])]
            first_t[fresh] = now
            stats[Observer.SEEN] += k
            stats[Observer.UNKNOWN] += int((~hit).sum())
            stats[Observer.ALTERED] += int((~same).sum())
    finally:
        stats[Observer.OVERRUN] = ovr.value
        del first_t, count, stats
        wksp.close()
        shm.close()
        if ext_name is not None:
            del first_seq, entry_of, hdr, hdr_t
            ext.close()


def _observe_entries(buf, szs, k, now, keys, order, sizes, checks,
                     first_t, count, stats, first_seq, entry_of, hdr,
                     hdr_t):
    """One gather of PoH entries: keep their headers, then match the
    txns inside them as the txn path matches frames, numbering each
    txn's first delivery in the order delivered (numbers rise; they
    need not be consecutive) and keeping the index of the entry (into
    the kept headers) that carried it."""
    from .traffic import MTU, WORD_WEIGHTS
    m = int(stats[Observer.ENTRIES])
    if m + k > ENTRY_CAP:
        stats[Observer.OVERRUN] += k        # a header lost: judge nothing
    else:
        hdr[m:m + k] = buf[:k, :ENTRY_HDR]
        hdr_t[m:m + k] = now
        stats[Observer.ENTRIES] = m + k
    txns, tsz, rows, bad = entry_txns(buf, szs, k, MTU)
    stats[Observer.UNKNOWN] += bad
    if not len(tsz):
        return
    key = txns[:, 1:9].copy().view(np.uint64).ravel()
    pos = np.minimum(np.searchsorted(keys, key), len(keys) - 1)
    hit = keys[pos] == key
    idx = order[pos[hit]]
    same = tsz[hit] == sizes[idx]
    same &= (txns.view(np.uint64)[hit] * WORD_WEIGHTS).sum(
        axis=1, dtype=np.uint64) == checks[idx]
    np.add.at(count, idx, 1)
    new = np.isnan(first_t[idx])
    fresh, rows = idx[new], rows[hit][new]
    _, at = np.unique(fresh, return_index=True)
    at = np.sort(at)                            # in order of delivery
    fresh = fresh[at]
    first_t[fresh] = now
    first_seq[fresh] = stats[Observer.SEEN] + np.arange(len(fresh))
    entry_of[fresh] = m + rows[at]
    stats[Observer.SEEN] += len(tsz)
    stats[Observer.UNKNOWN] += int((~hit).sum())
    stats[Observer.ALTERED] += int((~same).sum())


# ---------------------------------------------------------------------------
# counters, histograms, the device trace
# ---------------------------------------------------------------------------

def snapshot(runner, tiles) -> dict:
    from firedancer_tpu.disco.metrics import read_hists
    out = {}
    for tn in tiles:
        h = read_hists(runner.wksp, runner.plan, tn)
        out[tn] = {"metrics": runner.metrics(tn),
                   "hist": {k: (v["count"], v["sum_ns"])
                            for k, v in h.items()}}
    return out


def delta(a: dict, b: dict) -> dict:
    out = {}
    for tn in b:
        out[tn] = {
            "metrics": {k: v - a[tn]["metrics"].get(k, 0)
                        for k, v in b[tn]["metrics"].items()},
            "hist": {k: (v[0] - a[tn]["hist"][k][0],
                         v[1] - a[tn]["hist"][k][1])
                     for k, v in b[tn]["hist"].items()},
        }
    return out


def read_trace(trace_dir: str, manifest: dict) -> dict | None:
    """Device trace -> reduction (tracereduce.py). The .xplane.pb is read
    by a child pinned to the CPU (jax.profiler.ProfileData): this
    process stays off JAX."""
    from .tracereduce import reduce_trace
    pbs = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True))
    if not pbs:
        say(f"trace: no .xplane.pb under {trace_dir}")
        return None
    dump = os.path.join(OUT, "trace_dump.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-m", "perfbench.xplane", pbs[-1],
                        dump], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=240)
    if r.returncode:
        say(f"trace: reading {pbs[-1]} failed: {r.stderr[-800:]}")
        return None
    window_ns = manifest["t1_ns"] - manifest["t0_ns"]
    return reduce_trace(load_json(dump), window_ns)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

class Run:
    """What the metric readers read (metrics/<name>.py)."""

    def busy_pct(self, tile: str):
        """A tile's stem work time over the window's wall time, in %."""
        d = self.delta.get(tile)
        if d is None or "work" not in d["hist"]:
            return None
        return 100.0 * d["hist"]["work"][1] / ((self.t1 - self.t0) * 1e9)

    def tile(self, kind: str):
        """The name of the config's first tile of this kind, or None."""
        return next((t["name"] for t in self.cfg["tiles"]
                     if t["kind"] == kind), None)

    def idle_pct(self):
        """Device idle share of the capture, in %; None untraced."""
        if self.trace is None:
            return None
        return 100.0 * (1.0 - self.trace["busy_s"] / self.trace["window_s"])


def _set_env(cfg: dict, topo: str, trace: bool):
    # the compile cache lives inside the checkout, at a fixed path
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)   # jax does not make it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["TPU_LOG_DIR"] = "disabled"
    os.environ["FDTPU_LOG_PATH"] = os.path.join(OUT, f"{topo}.log")
    for k, v in cfg.get("env", {}).items():
        os.environ[k] = str(v)
    if trace:
        # tile processes write their profiler window here (run.py's
        # __mp_main__ hook) instead of /tmp
        os.environ["PERFBENCH_PROF_DIR"] = os.path.join(OUT, "prof")
    else:
        os.environ.pop("PERFBENCH_PROF_DIR", None)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, overrides: dict | None = None,
             rate: float | None = None) -> dict:
    """One run of one cell; returns the result object (the last line).
    Raises BenchError where no result may be printed."""
    from . import ledger
    from . import reference as ref
    from . import traffic

    spec = load_cell(workload)
    for k, v in (overrides or {}).items():
        spec[k] = v(spec[k]) if callable(v) else v
    w, cfg, mix, cell = (spec["workload"], spec["config"], spec["mix"],
                         spec["cell"])
    from firedancer_tpu.jaxcfg import plan_platform
    if plan_platform() != PLATFORM:
        raise BenchError(f"the plan would put the device tile on "
                         f"{plan_platform()!r}, not {PLATFORM!r} "
                         f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')})")
    os.makedirs(OUT, exist_ok=True)
    topo_name = f"pb{os.getpid()}"
    _set_env(cfg, topo_name, trace)
    procs = traffic.default_procs()

    # -- the corpus: signed before the tiles boot (they busy-spin) ------
    rate = float(rate or cell["rate"])
    warm_s = float(cell["warmup_s"])
    p = traffic.plan(mix, seed, rate, warm_s + seconds)
    corpus = traffic.sign(mix, seed, p, procs)
    n_txn = len(corpus.size)
    for tc in cfg["tcaches"]:
        if tc["depth"] < n_txn:
            raise BenchError(f"tcache {tc['name']} depth {tc['depth']} < "
                             f"{n_txn} unique txns offered")
    # a leader's record: PoH entries, judged with the bank's state
    producer = next(t for t in cfg["tiles"]
                    if cfg["observe"] in t.get("outs", ()))
    leader = producer["kind"] == "poh"
    genesis = bank = None
    if leader:
        # every payer funded through the bank tile's genesis arg
        genesis = ledger.genesis(traffic.payers(mix, seed), n_txn,
                                 mix["lamports"][1])
        cfg = json.loads(json.dumps(cfg))
        bank = next(t for t in cfg["tiles"] if t["kind"] == "bank")
        bank["args"]["genesis"] = {k.hex(): v for k, v in genesis.items()}
    say(f"corpus: {n_txn} txns, {len(p.send_t)} frames, signed in "
        f"{time.monotonic() - t_start:.1f} s since start")

    from firedancer_tpu.disco import TopologyRunner
    from firedancer_tpu.runtime import Fseq
    dev = cfg["device_tile"]
    plan = build_topology(cfg, topo_name, trace).build()
    runner = TopologyRunner(plan)
    cap_manifest = f"/dev/shm/fdtpu_{topo_name}.prof.{dev}.capture.json"
    log_path = os.environ["FDTPU_LOG_PATH"]
    r = Run()
    obs = None
    man = None
    try:
        runner.start()
        runner.wait_running(timeout_s=BOOT_S)
        boot = boot_line(log_path, dev)
        say(f"boot: {boot} after {time.monotonic() - t_start:.1f} s")
        check_device(boot, int(w["chips"]))
        ingest = _ring(runner.wksp, plan, cfg["ingest"])
        fseqs = [Fseq(runner.wksp, off=off) for key, off in
                 plan["fseqs"].items()
                 if key.split(":", 1)[0] == cfg["ingest"]]
        inj = Injector(ingest, fseqs, corpus, p.send_txn)
        obs = Observer(plan, cfg["observe"], corpus, leader)
        watch = [t["name"] for t in cfg["tiles"]]
        mem_peak = [0]

        def housekeep():
            runner.check_failures()
            m = runner.metrics(dev)
            mem_peak[0] = max(mem_peak[0], int(m.get("tpu_mem_bytes", 0)))
            if m.get("cpu_fallback") or m.get("device_errors"):
                raise BenchError(f"device tile: cpu_fallback="
                                 f"{m.get('cpu_fallback')} device_errors="
                                 f"{m.get('device_errors')}")

        def drive(t0: float, lo: int, hi: int, t_end: float,
                  shift: float, on_tick=None):
            """Publish frames [lo, hi) at t0 + send_t - shift until
            t_end, observing deliveries; returns the backlog (frames
            due but not yet sent) sampled every 0.25 s."""
            sched = p.send_t
            next_hk = 0.0
            backlog = []
            while True:
                now = time.monotonic()
                if now >= t_end:
                    break
                due = lo + int(np.searchsorted(sched[lo:hi],
                                               now - t0 + shift, "right"))
                inj.publish_until(due)
                if inj.cur >= due:
                    time.sleep(50e-6)
                if now >= next_hk:
                    housekeep()
                    backlog.append((now - t0, due - inj.cur))
                    if on_tick is not None:
                        on_tick(now)
                    next_hk = now + 0.25
            return backlog

        # no collector pauses in this process from here to the window's
        # end: the injector's lateness is the validator's, not ours
        gc.collect()
        gc.disable()
        # -- warm-up: the first warm_s of the schedule, then drained ----
        n_warm = int(np.searchsorted(p.send_t, warm_s))
        t_w = time.monotonic()
        drive(t_w, 0, n_warm, t_w + warm_s, 0.0)
        while inj.cur < n_warm:          # the warm-up's last frames
            if not inj.publish_until(n_warm):
                housekeep()
                time.sleep(0.001)
        _drain(obs, inj, corpus, p, 0, n_warm, housekeep, WARM_DRAIN_S)
        say(f"warm-up: {n_warm} frames, "
            f"{obs.stat(Observer.SEEN)} delivered")

        # -- the measured window ----------------------------------------
        t0 = time.monotonic()
        r.setup_s = t0 - t_start
        snap_a = snapshot(runner, watch)
        ring_at = t0 + max(0.0, seconds / 2 - CAPTURE_MS / 2e3)
        rung = [False]

        def tick(now):
            if trace and not rung[0] and now >= ring_at:
                from firedancer_tpu.prof.device import request_capture
                rung[0] = request_capture(runner.plan, runner.wksp, dev)
                r.rung_at = now
        # plan time warm_s is the window's first instant
        backlog = drive(t0, n_warm, len(p.send_t), t0 + seconds, warm_s,
                        tick)
        t1 = time.monotonic()
        gc.enable()
        snap_b = snapshot(runner, watch)
        sent_hi = inj.cur
        _drain(obs, inj, corpus, p, n_warm, sent_hi, housekeep, DRAIN_S)
        if trace:
            # stop_trace serializes the window inside the tile: wait
            deadline = time.monotonic() + 60
            while not os.path.exists(cap_manifest) \
                    and time.monotonic() < deadline:
                time.sleep(0.05)
                housekeep()
            if os.path.exists(cap_manifest):
                time.sleep(0.2)               # the writer's json.dump
                man = load_json(cap_manifest)
        housekeep()
        r.memory_peak_bytes = mem_peak[0]
        served = None
        if leader:
            # the balances the program serves, after the drain
            t_rpc = time.monotonic()
            read = ledger.sample_accounts(
                list(genesis), np.unique(p.send_txn[:sent_hi]),
                corpus.txn, seed)
            port = int(runner.metrics(bank["name"])["rpc_port"])
            served = ledger.read_balances(port, read)
            say(f"state: {len(served)} balances read in "
                f"{time.monotonic() - t_rpc:.1f} s")
    finally:
        gc.enable()
        if obs is not None:
            obs.stop()
        runner.halt()
        runner.close()
        for f in glob.glob(f"/dev/shm/fdtpu_{topo_name}.*"):
            try:            # pid files and prof manifests the tiles left
                os.unlink(f)
            except OSError:
                pass
    if obs.stat(Observer.OVERRUN):
        raise BenchError(f"the observer fell behind {cfg['observe']}: "
                         f"{obs.stat(Observer.OVERRUN)} frames overrun")

    # -- what the metric readers see ----------------------------------
    frames = p.send_txn
    offered = np.zeros(n_txn, bool)
    offered[frames[:sent_hi]] = True
    first_sched = np.full(n_txn, np.inf)
    np.minimum.at(first_sched, frames, p.send_t)
    in_win = (first_sched >= warm_s) & (first_sched < warm_s + seconds)
    r.t0, r.t1 = t0, t1
    r.corpus, r.in_window = corpus, in_win
    r.first_sched = first_sched - warm_s + t0     # monotonic time
    r.first_t = obs.first_t
    sent_win = slice(n_warm, sent_hi)
    r.sched_s = p.send_t[sent_win] - warm_s + t0     # monotonic time
    r.late_s = inj.pub_t[sent_win] - r.sched_s
    r.backlog = backlog
    r.stalls = inj.stalls
    r.delta = delta(snap_a, snap_b)
    r.cfg = cfg
    r.trace = r.capture = None
    if man and man.get("ok"):
        # the capture's span on this clock (tempo.monotonic_ns is
        # CLOCK_MONOTONIC, as time.monotonic is)
        r.capture = (getattr(r, "rung_at", man["t0_ns"] / 1e9),
                     man["t1_ns"] / 1e9)
    if trace:
        if man and man.get("ok") and man.get("trace_dir"):
            r.trace = read_trace(man["trace_dir"], man)
            shutil.rmtree(man["trace_dir"], ignore_errors=True)
        if r.trace is None:
            raise BenchError(f"--trace 1 without a device trace: the "
                             f"capture manifest read {man}")

    # -- the reference, after the window and with the chip released ----
    t_ref = time.monotonic()
    ref_idx = ref.sample(corpus.valid, offered, seed)
    ref_ok = ref.reference_verdicts([corpus.txn(i) for i in ref_idx],
                                    procs)
    checks = ref.judge(corpus.valid, offered, obs.count,
                       obs.stat(Observer.ALTERED),
                       obs.stat(Observer.UNKNOWN), ref_idx, ref_ok)
    say(f"reference: {len(ref_idx)} txns re-verified in "
        f"{time.monotonic() - t_ref:.1f} s")
    if leader:
        t_ref = time.monotonic()
        seen = np.flatnonzero(obs.count > 0)
        committed = seen[np.argsort(obs.first_seq[seen], kind="stable")]
        checks["state_mismatch"] = [ledger.state_mismatch(
            genesis, [corpus.txn(int(i)) for i in committed], served), 0]
        poh = producer["args"]
        checks["poh_mismatch"] = [ledger.poh_mismatch(
            obs.hdr, int(poh["hashes_per_tick"]),
            int(poh["ticks_per_slot"]), seed), 0]
        checks["ticks_early"] = [ledger.ticks_early(
            obs.hdr, obs.hdr_t, int(poh["lazy_ns"]) * 1e-9,
            int(poh["ticks_per_slot"])), 0]
        say(f"ledger: {len(committed)} committed txns executed, "
            f"{len(served)} balances compared, {len(obs.hdr)} PoH entries "
            f"checked in {time.monotonic() - t_ref:.1f} s")
        t_ref = time.monotonic()
        for k, v in ledger.block_checks(obs.hdr, obs.entry_of, corpus.txn,
                                        cfg["block_limits"]).items():
            checks[k] = [v, 0]
        say(f"block rules: {len(committed)} committed txns checked in "
            f"{time.monotonic() - t_ref:.1f} s")

    say("window: " + json.dumps(diagnostics(r, rate)))
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec["metrics"][kind]:
        v = reader(m["name"])(r)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = dict(boot, memory_peak_bytes=int(r.memory_peak_bytes))
    out = {"correct": ref.correct(checks),
           "attempted": int(offered.sum()),
           "failed": int(sum(v for k, (v, _) in checks.items()
                             if k in ("wrong_accept", "missing", "double",
                                      "altered"))),
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = r.trace["busy_s"]
        device["window_s"] = r.trace["window_s"]
        out["breakdown"] = r.trace["breakdown"]
    out["checks"] = checks
    return out


def _drain(obs, inj, corpus, p, lo, hi, housekeep, limit_s):
    """After sending stops: wait until every valid txn sent in [lo, hi)
    has come out of dedup (or limit_s passes), then stay until the
    counters hold still for 0.3 s so a late double or forgery shows."""
    txns = np.unique(p.send_txn[lo:hi])
    want = txns[corpus.valid[txns]]
    deadline = time.monotonic() + limit_s
    quiet_since = None
    next_hk = 0.0
    seen = -1
    while time.monotonic() < deadline:
        n = obs.stat(Observer.SEEN)
        now = time.monotonic()
        done = bool((obs.count[want] > 0).all())
        if n != seen:
            seen, quiet_since = n, None
        elif done:
            quiet_since = quiet_since or now
            if now - quiet_since >= 0.3:
                return
        time.sleep(0.005)
        if now >= next_hk:
            housekeep()
            next_hk = now + 0.25


def diagnostics(r, rate: float) -> dict:
    """Information lines for the knee sweep and for debugging: offered
    and delivered rates, the p99 and p50 latency, the backlog."""
    sel = r.in_window & r.corpus.valid
    lat = r.first_t[sel] - r.first_sched[sel]
    done = ~np.isnan(lat)
    q = (lambda x: float(np.percentile(lat[done], x)) * 1e3) \
        if done.any() else (lambda x: None)
    win = (r.first_t >= r.t0) & (r.first_t <= r.t1) & r.corpus.valid
    return {
        "offered_frames_per_s": rate,
        "delivered_tps": int(win.sum()) / (r.t1 - r.t0),
        "valid_in_window": int(sel.sum()),
        "delivered_share_in_window": float(
            ((r.first_t[sel] <= r.t1)).mean()) if sel.any() else None,
        "delivered_share_1s_after": float(
            ((r.first_t[sel] <= r.t1 + 1.0)).mean()) if sel.any() else None,
        "p50_ms": q(50), "p90_ms": q(90), "p99_ms": q(99),
        "backlog_frames": r.backlog[-8:],
        "injector_credit_stalls": r.stalls,
        "verify_batches": r.delta.get("verify", {}).get("metrics", {})
        .get("batches"),
        "verify_counters": {k: v for k, v in r.delta.get("verify", {})
                            .get("metrics", {}).items()
                            if k in ("deferred_parked",
                                     "deferred_host_verifies",
                                     "verify_fail", "dedup_drop")},
        "late_p99_ms": float(np.nanpercentile(r.late_s, 99)) * 1e3
        if len(r.late_s) and not np.isnan(r.late_s).all() else None,
        "memory_peak_bytes": r.memory_peak_bytes,
        "busy_pct": {t: r.busy_pct(t) for t in r.delta},
        "pending_growth": {t: d["metrics"]["inserted"]
                           - d["metrics"]["scheduled"]
                           for t, d in r.delta.items()
                           if "scheduled" in d["metrics"]},
    }


def report(out: dict):
    """Each number compared, beside its limit, as the last stderr
    lines; then the result as the last stdout line."""
    for k, (v, lim) in out["checks"].items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
