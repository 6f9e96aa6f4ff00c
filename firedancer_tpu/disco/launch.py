"""Process launcher + policy-driven supervisor.

The reference launches one sandboxed process per tile and runs a
supervisor that tears the whole validator down if ANY tile dies
(ref: src/disco/topo/fd_topo_run.c:65-190 — per-tile clone + init;
src/app/shared/commands/run/run.c:229-260,925 — pid-namespace
supervisor, "one tile dies => everything dies"). Heartbeat liveness is
observed through each tile's cnc (ref: src/tango/cnc/fd_cnc.h:6-40).

Here tiles are spawned processes (fresh interpreters — the moral
equivalent of clone: no inherited jax/backends state); the plan dict is
the only shared contract. The runner writes the plan JSON next to the
shm segment so an external monitor can attach by topology name.

Supervision policy is per tile (disco/supervise.py): fail_fast keeps
the reference's "one tile dies => everything dies" default; restart
respawns the tile with backoff + circuit breaker and rejoins its ring
cursors at the producers' current seq. The wedge watchdog catches
live-but-stuck tiles by heartbeat staleness and consumer-fseq stall.
"""
from __future__ import annotations

import json
import multiprocessing as mp
import os
import time

from ..runtime import Workspace, Cnc, CNC_RUN, CNC_HALT, CNC_BOOT
from . import topo as topo_mod
from .stem import Stem
from .topo import TileCtx


def tile_main(plan: dict, tile_name: str):
    """Entry point of a tile process (ref: fd_topo_run_tile)."""
    from .tiles import REGISTRY
    # the plan decides where this process's JAX runs (the device for
    # its one owner tile, the host for the rest) before any adapter
    # can import jax; a pinned platform that cannot start raises
    # instead of falling back
    os.environ["JAX_PLATFORMS"] = plan["tiles"][tile_name]["jax_platform"]
    from ..jaxcfg import use_compile_cache
    use_compile_cache()
    # core pinning (ref: src/util/tile/fd_tile.h:6-38 — tiles pin to
    # dedicated cores; here args.cpu_idx pins this tile PROCESS via
    # sched_setaffinity, clamped to the machine's online set)
    cpu_idx = plan["tiles"][tile_name]["args"].get("cpu_idx")
    if cpu_idx is not None:
        avail = sorted(os.sched_getaffinity(0))
        os.sched_setaffinity(0, {avail[int(cpu_idx) % len(avail)]})
    # sandbox hardening (ref: src/util/sandbox/fd_sandbox.h — the
    # python-enforceable subset: no-new-privs + rlimit caps; fd
    # closing stays opt-in because adapters open sockets/files later)
    if plan["tiles"][tile_name]["args"].get("sandbox"):
        from ..utils import sandbox
        sandbox.apply(max_files=int(
            plan["tiles"][tile_name]["args"].get("sandbox_files", 1024)),
            close_high_fds=False)
    # per-tile thread-tagged logging (ref: fd_topo_run.c
    # initialize_logging before tile init)
    from ..utils import log
    log.init(f"{plan['topology']}:{tile_name}")
    log.info("tile booting")
    # publish this tile's pid + /proc starttime (the cswtch sampler
    # validates the starttime so a stale pidfile from a dead run can't
    # attribute a RECYCLED pid's counters to this tile; the reference
    # gets pids from its private pid namespace)
    pidfile = f"/dev/shm/fdtpu_{plan['topology']}.pid.{tile_name}"
    try:
        with open(f"/proc/{os.getpid()}/stat") as sf:
            starttime = sf.read().rsplit(")", 1)[1].split()[19]
        with open(pidfile, "w") as pf:
            pf.write(f"{os.getpid()} {starttime}")
    except OSError:
        pidfile = None
    ctx = TileCtx(plan, tile_name)
    try:
        kind = plan["tiles"][tile_name]["kind"]
        adapter = REGISTRY[kind](ctx, plan["tiles"][tile_name]["args"])
        Stem(ctx, adapter).run()
    finally:
        ctx.close()
        if pidfile:
            try:
                os.unlink(pidfile)
            except OSError:
                pass


def plan_path(topology_name: str) -> str:
    return f"/dev/shm/fdtpu_{topology_name}.plan.json"


class TopologyRunner:
    """Build-products holder + launcher + supervisor."""

    def __init__(self, plan: dict):
        self.plan = plan
        self.wksp = Workspace(plan["wksp"]["name"], plan["wksp"]["size"],
                              create=False)
        self.procs: dict[str, mp.process.BaseProcess] = {}
        self._mp = mp.get_context("spawn")
        self._halted = False
        from .supervise import Supervisor
        self.supervisor = Supervisor(
            plan, self.wksp, procs=lambda: self.procs,
            spawn=self._spawn, halt_all=self._halt_for_supervisor)
        with open(plan_path(plan["topology"]), "w") as f:
            json.dump(plan, f)

    # -- lifecycle ---------------------------------------------------------

    def _spawn(self, tn: str, rejoin: bool = False):
        plan = self.plan
        if rejoin:
            # a deep copy only the child sees: the respawned consumer
            # joins its in rings at the producers' CURRENT seq
            plan = json.loads(json.dumps(self.plan))
            plan["tiles"][tn]["rejoin_at_tail"] = True
            # a chaos drill simulates ONE fault per boot: the
            # replacement process comes up clean, or a crash/wedge
            # event re-arms every incarnation and breaker-loops the
            # tile instead of exercising recovery. Plans that WANT
            # the fault to survive respawn (crash-loop drills that
            # drive the breaker open on purpose) opt in with
            # {"rearm": true}.
            ch = plan["tiles"][tn]["args"].get("chaos")
            if not (isinstance(ch, dict) and ch.get("rearm")):
                plan["tiles"][tn]["args"].pop("chaos", None)
        p = self._mp.Process(target=tile_main, args=(plan, tn),
                             name=f"tile:{tn}", daemon=True)
        p.start()
        self.procs[tn] = p
        return p

    def start(self, tiles=None):
        for tn in (tiles or self.plan["tiles"]):
            self._spawn(tn)
        return self

    def _cnc(self, tn: str) -> Cnc:
        return Cnc(self.wksp, off=self.plan["tiles"][tn]["cnc_off"])

    def wait_running(self, timeout_s: float = 600.0):
        """Block until every launched tile reaches RUN (compile warmup
        for device tiles can dominate; hence the generous default)."""
        t0 = time.time()
        for tn in list(self.procs):
            while self._cnc(tn).state != CNC_RUN:
                self.check_failures()
                if time.time() - t0 > timeout_s:
                    raise TimeoutError(f"tile {tn} never reached RUN")
                time.sleep(0.01)
        return self

    def check_failures(self):
        """One supervision pass: fail-fast tiles raise on abnormal death
        (ref: run.c:925 — pid-namespace teardown, the default policy);
        restart-policy tiles are respawned with backoff, wedged tiles
        are killed by the watchdog, and an exhausted restart budget
        raises CircuitOpen after a clean halt."""
        if not self._halted:
            self.supervisor.poll()

    def supervise(self, duration_s: float, poll_s: float = 0.02):
        """Run supervision passes for duration_s (test/driver aid)."""
        deadline = time.time() + duration_s
        while time.time() < deadline:
            self.check_failures()
            time.sleep(poll_s)
        return self

    def heartbeats(self) -> dict[str, int]:
        """Ticks since each tile's last heartbeat."""
        now = topo_mod.now_ticks()
        return {tn: max(0, now - self._cnc(tn).last_heartbeat)
                for tn in self.procs}

    def metrics(self, tile_name: str):
        vals = topo_mod.read_metrics(self.wksp, self.plan, tile_name)
        # the plan carries the slot-name ABI (reorder-proof; r2 W7)
        names = self.plan["tiles"][tile_name].get("metrics_names", [])
        out = {nm: int(vals[i]) for i, nm in enumerate(names)}
        # supervisor counters ride in the region's top slots
        from .supervise import sup_counters
        out.update(sup_counters(vals))
        # the queue wait on each in link (the tile's consumer blocks):
        # frags timed and their summed publish -> consume wait
        from .metrics import read_queue_waits
        for ln, (cnt, ns) in read_queue_waits(
                self.wksp, self.plan, tile_name).items():
            out[f"{ln}.wait_count"] = cnt
            out[f"{ln}.wait_ns"] = ns
        return out

    def _halt_for_supervisor(self):
        self.halt(join_timeout_s=10.0)

    def halt(self, join_timeout_s: float = 30.0):
        self._halted = True
        for tn in self.procs:
            self._cnc(tn).state = CNC_HALT
        deadline = time.time() + join_timeout_s
        for tn, p in self.procs.items():
            p.join(max(0.1, deadline - time.time()))
            if p.is_alive():
                p.terminate()
                p.join(5.0)
        return self

    def close(self, unlink: bool = True):
        name = self.plan["wksp"]["name"]
        self.wksp.close()
        if unlink:
            try:
                os.unlink(plan_path(self.plan["topology"]))
            except OSError:
                pass
            Workspace.unlink_name(name)

    # -- convenience -------------------------------------------------------

    def wait_idle(self, tile_name: str, metric: str, target: int,
                  timeout_s: float = 600.0, poll_s: float = 0.05):
        """Poll one tile's metric until it reaches target (test/bench
        aid — the bencho pattern)."""
        t0 = time.time()
        while time.time() - t0 < timeout_s:
            self.check_failures()
            if self.metrics(tile_name).get(metric, 0) >= target:
                return self
            time.sleep(poll_s)
        raise TimeoutError(
            f"{tile_name}.{metric} never reached {target}: "
            f"{self.metrics(tile_name)}")
