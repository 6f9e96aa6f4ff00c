"""Stem: the tile run loop.

Re-expression of the reference's templated run loop
(ref: src/disco/stem/fd_stem.c:1-168 — housekeeping scheduler, credit
management, frag polling with overrun detection; :240 run1; :385 the
main for(;;)). The reference specializes the loop with ~8 compile-time
callbacks; here a tile object supplies the same seams as methods:

  poll_once() -> int      frags consumed this iteration (0 = idle)
  housekeeping()          optional, called at the lazy interval
  metrics_items() -> dict optional, name -> int, flushed to shm metrics
  on_halt()               optional, called once on exit

The stem owns what every tile shares: cnc lifecycle (BOOT -> RUN ->
HALT/FAIL), heartbeating, the lazy housekeeping interval with jitter
(ref: fd_stem.c housekeeping randomization — avoids thundering herds),
consumer-side fseq publication (so upstream producers can credit-gate),
and flushing tile metrics into the shared-memory metrics region the
monitor reads (ref: src/disco/metrics/fd_metrics.h:6-40).

It also keeps the tile's stall and collector-pause histograms: a poll
or housekeeping pass of STALL_NS or longer is a stall (traced, an
EV_STALL instant naming the stage it spent most time in — the tile's
`stages` StageClock, a collector pause, or the poll/housekeeping pass
itself); a stall in a device capture's own start/stop is left to the
tile's capture stage counter, so traced runs can leave it out. Python
collector pauses (gc.callbacks) land in the gc histogram.
"""
from __future__ import annotations

import gc
import random
import time

from ..runtime import CNC_RUN, CNC_HALT, CNC_FAIL
from ..trace.events import STAGE_IDS
from .metrics import HIST_KINDS, HIST_U64, HistAccum

STALL_NS = 50_000_000     # a poll or housekeeping pass this long stalls
_CAPTURE, _GC, _POLL, _HOUSEKEEP = (
    STAGE_IDS[s] for s in ("capture", "gc", "poll", "housekeep"))


class Stem:
    def __init__(self, ctx, tile, hk_interval_s: float = 0.01,
                 idle_sleep_s: float = 20e-6):
        """ctx: TileCtx (cnc/metrics/fseqs); tile: the callback object."""
        self.ctx, self.tile = ctx, tile
        self.hk_interval_s = hk_interval_s
        # tempo-derived cadence (ref: fd_tempo_lazy_default): a tile
        # may pin lazy_ns explicitly, or ask for depth-derived lazy
        # with lazy_auto (credits must return ~10x faster than the
        # smallest out-link window drains)
        args = ctx.spec.get("args", {})
        if args.get("lazy_ns"):
            self.hk_interval_s = int(args["lazy_ns"]) * 1e-9
        elif args.get("lazy_auto"):
            from ..utils.tempo import lazy_default
            depths = [ctx.plan["links"][ln]["depth"]
                      for ln in getattr(ctx, "out_rings", {})]
            if depths:
                # floor = the python loop's useful granularity (100us)
                # so the depth derivation actually differentiates
                # windows; ceiling keeps heartbeats frequent
                self.hk_interval_s = min(0.05, max(
                    1e-4, lazy_default(min(depths)) * 1e-9))
        self.idle_sleep_s = idle_sleep_s
        # slot-name ABI comes from the plan (explicit, reorder-proof);
        # a tile kind with no registered names falls back to the dict
        # insertion order of its first metrics_items() result
        self._metrics_names: list[str] | None = \
            list(ctx.spec.get("metrics_names", [])) or None
        # wait/work poll latency, stall and gc histograms (flushed at
        # housekeeping); seeded from shm so a supervised restart
        # RESUMES the cumulative series (flush_into writes wholesale —
        # a fresh accumulator would rewind readers to zero), same
        # continuity contract as the link counters. The tile-owned tpu
        # histogram (verify's tpu_hist) gets the same seeding. Only the
        # kinds the plan's region holds are bound (an older plan holds
        # fewer).
        self._hists = {"wait": HistAccum(), "work": HistAccum(),
                       "stall": HistAccum(), "gc": HistAccum()}
        owned = dict(self._hists)
        if getattr(tile, "tpu_hist", None) is not None:
            owned["tpu"] = tile.tpu_hist
        self._hist_views = []
        hv = ctx.hist_view()
        if hv is not None:
            for i, kind in enumerate(HIST_KINDS[:len(hv) // HIST_U64]):
                if kind in owned:
                    view = hv[i * HIST_U64:(i + 1) * HIST_U64]
                    owned[kind].seed_from(view)
                    self._hist_views.append((view, owned[kind]))
        # the tile's stage accounting (trace/recorder.py StageClock), if
        # it keeps one: a stall names the stage it spent most time in
        self._stages = getattr(tile, "stages", None)
        self._gc_t0 = 0
        self._gc_mark = 0              # gc ns at the last housekeeping
        self._gc_spans: list[tuple[int, int]] = []
        self._stall_said = 0           # when a stall was last logged
        # chaos harness: a seeded fault plan injected purely via tile
        # args (utils/chaos.py) — fires deterministically in run()
        self._chaos = None
        self._hb_frozen = False
        self._wedged = False
        self._stalled_links: set | None = None   # None = no stall
        if args.get("chaos"):
            from ..utils.chaos import ChaosPlan
            self._chaos = ChaosPlan(args["chaos"])
        # fdtrace flight recorder: None on untraced tiles — the whole
        # disabled path is this one cached attribute staying None
        # (trace/__init__.py contract; no per-frag cost when off)
        self._trace = getattr(ctx, "trace", None)
        # fdprof continuous profiler: same None-is-disabled contract.
        # The sampler thread starts in run() (it samples THE stem
        # thread); _prof_state is the attribution channel the loop
        # stores wait/work/housekeep + the active in-link into — one
        # attribute store per poll when profiling, one None check when
        # not. Attribution lags the sample by one poll (the state a
        # sample sees was set after the PREVIOUS poll) — statistically
        # exact in steady regimes, which is all a sampling profiler
        # claims.
        self._prof_region = getattr(ctx, "prof", None)
        self._prof_state = None
        self._sampler = None
        # fdprof's active in-link: the in link whose consume counter
        # advanced in the last productive poll (profiled tiles only)
        self._link_seen = {
            ln: r.m_consumed
            for ln, r in getattr(ctx, "in_rings", {}).items()} \
            if self._prof_region is not None else {}
        self._wait_t0: int | None = None      # idle-streak start (ns)
        # WORK attribution accumulators: with sample>1 one EV_WORK
        # record aggregates the last `sample` productive polls
        # (sum-preserving — wait/work attribution stays exact, only
        # the record RATE is thinned)
        self._work_ns = 0
        self._work_frags = 0
        self._work_polls = 0

    def _apply_chaos(self, iters: int, rx: int):
        from ..utils import log
        for ev in self._chaos.poll(iters, rx):
            act = ev["action"]
            log.warning(f"chaos: firing {act} (iter={iters} rx={rx})")
            if self._trace is not None:
                # record the injection BEFORE acting so even a crash
                # leaves its footprint for the black-box dump
                from ..trace import chaos_event
                chaos_event(self._trace, act, at=iters)
            if act == "crash":
                import os
                os._exit(ev["code"])
            elif act == "freeze_hb":
                self._hb_frozen = True
            elif act == "wedge":
                self._hb_frozen = True
                self._wedged = True
            elif act == "stall_fseq":
                if self._stalled_links is None:
                    self._stalled_links = set()
                self._stalled_links.add(ev["link"])   # None = all links
            else:
                # adversarial traffic plans (utils/chaos.py
                # TRAFFIC_ACTIONS): the stem records the injection (the
                # chaos_event above) and hands the event to the tile
                # adapter, which owns rendering + flooding the frames
                hook = getattr(self.tile, "on_chaos", None)
                if hook is not None:
                    hook(ev)

    def _leave(self):
        """On ANY loop exit (halt, fail, external FAIL): stop the fdprof
        sampler — the shm region keeps the aggregate, but a sampler
        outliving run() would keep attributing samples to a loop that
        no longer exists — and stop timing collector pauses."""
        if self._sampler is not None:
            self._sampler.stop()
            self._sampler = None
            self._prof_state = None
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict):
        """gc.callbacks hook: one collector pause into the gc histogram
        (traced: queued for an EV_STAGE span at the next housekeeping
        pass — the collector can run in the middle of a ring append)."""
        if phase == "start":
            self._gc_t0 = time.perf_counter_ns()
        elif self._gc_t0:
            t1 = time.perf_counter_ns()
            self._hists["gc"].add(t1 - self._gc_t0)
            if self._trace is not None and len(self._gc_spans) < 64:
                self._gc_spans.append((t1, t1 - self._gc_t0))
            self._gc_t0 = 0

    def _stall(self, dt: int, t_end: int, where: int):
        """A poll or housekeeping pass of dt >= STALL_NS ns ending at
        t_end: name the stage it spent most time in (the tile's longest
        stage since the pass began, a collector pause since the last
        housekeeping if longer, else `where`), count it unless it is
        a device capture's own start/stop, and trace it."""
        stage, top = where, 0
        st = self._stages
        if st is not None and st.top_ns:
            stage, top = st.top, st.top_ns
        if self._hists["gc"].sum_ns - self._gc_mark > top:
            stage = _GC
        if stage != _CAPTURE:
            self._hists["stall"].add(dt)
        if self._trace is not None:
            from ..trace.events import EV_STALL
            self._trace.ring.append(t_end, EV_STALL, arg=dt, count=stage)
        if t_end - self._stall_said >= 1_000_000_000:
            # at most one line a second: the histogram counts them all
            self._stall_said = t_end
            from ..trace.events import STAGE_NAMES
            from ..utils import log
            log.warning(f"stall: {dt / 1e6:.1f} ms, longest in "
                        f"{STAGE_NAMES[stage]}")

    def _trace_flush(self, tr):
        """Close out pending trace state on any loop exit (halt, fail,
        external FAIL): the aggregated-but-unemitted work window and an
        open wait streak are exactly 'the last thing the tile was
        doing' — the black-box dump must not lose them."""
        from ..trace import events as trace_ev
        if self._work_polls:
            tr.event(trace_ev.EV_WORK, arg=self._work_ns,
                     count=self._work_frags)
            self._work_ns = self._work_frags = self._work_polls = 0
        if self._wait_t0 is not None:
            tr.event(trace_ev.EV_WAIT,
                     arg=time.perf_counter_ns() - self._wait_t0)
            self._wait_t0 = None

    def _flush_metrics(self):
        items = getattr(self.tile, "metrics_items", None)
        if items is not None:
            d = items()
            if self._metrics_names is None:
                self._metrics_names = list(d.keys())
            view = self.ctx.metrics_view()
            for i, k in enumerate(self._metrics_names):
                if i >= len(view):
                    break
                view[i] = d.get(k, 0)
        # device-time attribution: a tile that drives an accelerator
        # exposes a `tpu_hist` HistAccum (verify tile's dispatch +
        # readback time); host-only tiles leave its slot zero and the
        # renderer skips the empty series
        for view, acc in self._hist_views:
            acc.flush_into(view)
        # per-link telemetry blocks: the Ring join's instance-local
        # counters and queue-wait histogram (runtime/tango.py) are THE
        # per-link truth for this tile; flushing them wholesale keeps
        # the hot path free of any shm write (same single-writer
        # cumulative contract as hists: the count word lands last)
        for ln, view in getattr(self.ctx, "link_cons_views",
                                {}).items():
            r = self.ctx.in_rings[ln]
            view[0] = r.m_consumed
            view[1] = r.m_bytes
            view[2] = r.m_overruns
            view[4:3 + HIST_U64] = r.m_wait[1:]
            view[3] = r.m_wait[0]
        for ln, view in getattr(self.ctx, "link_prod_views",
                                {}).items():
            r = self.ctx.out_rings[ln]
            view[0] = r.m_pub
            view[1] = r.m_pub_bytes
            view[2] = r.m_backpressure

    def _update_in_fseqs(self):
        """Publish consumer progress so upstream producers see credits."""
        seqs = getattr(self.tile, "in_seqs", None)
        if seqs is None:
            return
        for ln, fs in self.ctx.in_fseqs.items():
            if self._stalled_links is not None and \
                    (None in self._stalled_links
                     or ln in self._stalled_links):
                continue              # chaos: progress frozen
            if ln in seqs():
                fs.update(seqs()[ln])

    def run(self, max_iters: int | None = None):
        from ..trace import events as trace_ev
        tr = self._trace
        cnc = self.ctx.cnc
        cnc.heartbeat()
        cnc.state = CNC_RUN
        if self._prof_region is not None and self._sampler is None:
            # host sampling profiler over THIS thread (fdprof): the
            # daemon sampler walks our stack at prof_hz and aggregates
            # folded stacks into the shm region
            import threading
            from ..prof import ProfState, Sampler
            spec = self.ctx.spec
            self._prof_state = ProfState()
            self._sampler = Sampler(
                self._prof_region,
                float(spec.get("prof_hz", 97.0)),
                threading.get_ident(), self._prof_state,
                stack_depth=int(spec.get("prof_stack_depth", 16)),
            ).start()
        ps = self._prof_state
        stg = self._stages
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)
        if tr is not None:
            tr.event(trace_ev.EV_BOOT)
        # jittered lazy interval: same reasoning as the reference's
        # randomized housekeeping (fd_stem.c — avoid phase-locking tiles)
        next_hk = 0.0
        iters = 0
        rx_total = 0
        try:
            while True:
                now = time.perf_counter()
                if now >= next_hk:
                    if not self._hb_frozen:
                        cnc.heartbeat()
                    st = cnc.state
                    if st == CNC_HALT:
                        break
                    if st == CNC_FAIL:
                        # externally failed (wedge watchdog): exit NOW,
                        # leaving the FAIL state visible — on_halt and
                        # the HALT transition are for clean shutdowns
                        if tr is not None:
                            self._trace_flush(tr)
                            tr.event(trace_ev.EV_FAIL)
                        self._flush_metrics()
                        self._leave()
                        return
                    hk_t0 = time.perf_counter_ns()
                    if stg is not None:
                        stg.top_ns = 0
                    if ps is not None:
                        ps.state = 2          # fdprof: housekeep
                    self._update_in_fseqs()
                    hk = getattr(self.tile, "housekeeping", None)
                    if hk is not None:
                        hk()
                    self._flush_metrics()
                    hk_t1 = time.perf_counter_ns()
                    if tr is not None:
                        tr.ring.append(hk_t1, trace_ev.EV_HOUSEKEEP,
                                       arg=hk_t1 - hk_t0)
                        for t_end, d in self._gc_spans:
                            tr.ring.append(t_end, trace_ev.EV_STAGE,
                                           arg=d, count=_GC)
                        self._gc_spans.clear()
                    if hk_t1 - hk_t0 >= STALL_NS:
                        self._stall(hk_t1 - hk_t0, hk_t1, _HOUSEKEEP)
                    self._gc_mark = self._hists["gc"].sum_ns
                    next_hk = now + self.hk_interval_s * (
                        0.7 + 0.6 * random.random())
                if self._wedged:
                    # chaos: a hung tile — no polling, no heartbeats,
                    # still killable (and halt-able) by the supervisor
                    time.sleep(0.005)
                    iters += 1
                    continue
                if stg is not None:
                    stg.top_ns = 0
                t0 = time.perf_counter_ns()
                n = self.tile.poll_once()
                t1 = time.perf_counter_ns()
                # wait/work latency attribution: an idle poll is time
                # spent waiting on upstream, a productive one is work
                # (the reference's per-link regime split)
                self._hists["work" if n else "wait"].add(t1 - t0)
                if t1 - t0 >= STALL_NS:
                    self._stall(t1 - t0, t1, _POLL)
                if ps is not None:
                    ps.state = 1 if n else 0  # fdprof: work / wait
                    if n:
                        for ln, c in self._link_seen.items():
                            cur = self.ctx.in_rings[ln].m_consumed
                            if cur != c:
                                self._link_seen[ln] = cur
                                ps.link = ln  # fdprof: active in-link
                if tr is not None:
                    # trace shape: one WAIT span per idle STREAK
                    # (credit-wait begin at the first empty poll, end
                    # at the next productive one) + one WORK span per
                    # `sample` productive polls carrying their SUMMED
                    # duration and frag count
                    if n:
                        if self._wait_t0 is not None:
                            # stamped at t0 — the poll START where the
                            # streak actually ended — so the rendered
                            # span never overlaps the work that ended
                            # it. perf_counter_ns and monotonic_ns are
                            # both CLOCK_MONOTONIC on this platform
                            # (pinned by tests/test_trace.py).
                            tr.ring.append(t0, trace_ev.EV_WAIT,
                                           arg=t0 - self._wait_t0)
                            self._wait_t0 = None
                        self._work_ns += t1 - t0
                        self._work_frags += n
                        self._work_polls += 1
                        if self._work_polls >= tr.sample:
                            tr.event(trace_ev.EV_WORK,
                                     arg=self._work_ns,
                                     count=self._work_frags)
                            self._work_ns = 0
                            self._work_frags = 0
                            self._work_polls = 0
                    elif self._wait_t0 is None:
                        self._wait_t0 = t0
                if not n:
                    time.sleep(self.idle_sleep_s)
                iters += 1
                rx_total += n
                if self._chaos is not None:
                    self._apply_chaos(iters, rx_total)
                if max_iters is not None and iters >= max_iters:
                    break
        except Exception as e:
            cnc.state = CNC_FAIL
            if tr is not None:
                self._trace_flush(tr)
                tr.event(trace_ev.EV_FAIL)
            self._flush_metrics()
            self._leave()
            from ..utils import log
            log.err(f"tile failed: {e!r}")
            raise
        # drain-side bookkeeping before exit
        self._update_in_fseqs()
        self._flush_metrics()
        on_halt = getattr(self.tile, "on_halt", None)
        if on_halt is not None:
            on_halt()
        if tr is not None:
            self._trace_flush(tr)
            tr.event(trace_ev.EV_HALT)
        self._leave()
        cnc.state = CNC_HALT
