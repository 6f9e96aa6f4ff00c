"""Tests for the native tango-semantics layer (rings/fseq/fctl/cnc/tcache).

Mirrors the reference's tango test tiers (ref: src/tango/test_ipc_full,
test_ipc_meta; src/util/tmpl unit tests): single-process semantic checks
plus a true multi-process producer/consumer shell test over shared memory.
"""
import multiprocessing as mp
import os

import numpy as np
import pytest

from firedancer_tpu.runtime import (Workspace, Ring, Fseq, Cnc, Tcache,
                                    CNC_RUN)

MTU = 256


@pytest.fixture
def wksp():
    name = f"/fdtpu_test_{os.getpid()}"
    w = Workspace(name, 1 << 22)
    yield w
    w.close()
    w.unlink()


def test_ring_publish_consume(wksp):
    ring = Ring.create(wksp, depth=8, mtu=MTU)
    msgs = [bytes([i]) * (i + 1) for i in range(5)]
    for i, m in enumerate(msgs):
        ring.publish(m, sig=100 + i)
    for i, m in enumerate(msgs):
        rc, frag = ring.consume(i)
        assert rc == 0
        assert frag.sig == 100 + i
        assert bytes(ring.payload(frag)) == m
    rc, _ = ring.consume(5)
    assert rc == 1  # not yet published


def test_ring_overrun_detection(wksp):
    ring = Ring.create(wksp, depth=4, mtu=MTU)
    for i in range(10):  # laps the depth-4 ring twice
        ring.publish(b"x%d" % i, sig=i)
    rc, _ = ring.consume(2)   # slot 2 now holds seq 6
    assert rc == -1
    rc, frag = ring.consume(7)
    assert rc == 0 and frag.sig == 7


def test_ring_gather_batch(wksp):
    ring = Ring.create(wksp, depth=64, mtu=MTU)
    for i in range(20):
        ring.publish(bytes([i]) * (10 + i), sig=i)
    n, seq, buf, sizes, sigs, ovr = ring.gather(0, 16, MTU)
    assert n == 16 and seq == 16 and ovr == 0
    assert sizes[:16].tolist() == [10 + i for i in range(16)]
    assert sigs[:16].tolist() == list(range(16))
    assert buf[3, :13].tolist() == [3] * 13
    assert buf[3, 13:].sum() == 0  # zero-padded
    n, seq, *_ = ring.gather(seq, 16, MTU)
    assert n == 4 and seq == 20


def test_fseq_fctl_credits(wksp):
    ring = Ring.create(wksp, depth=8, mtu=MTU)
    f1, f2 = Fseq(wksp), Fseq(wksp)
    assert ring.credits([f1, f2]) == 8
    for i in range(6):
        ring.publish(b"m", sig=i)
    assert ring.credits([f1, f2]) == 2   # slowest consumer at 0
    f1.update(6)
    assert ring.credits([f1, f2]) == 2
    f2.update(4)
    assert ring.credits([f1, f2]) == 6
    f2.update(6)
    assert ring.credits([f1, f2]) == 8


def test_cnc(wksp):
    cnc = Cnc(wksp)
    assert cnc.state == 0  # BOOT
    cnc.state = CNC_RUN
    assert cnc.state == CNC_RUN
    assert cnc.last_heartbeat == 0
    cnc.heartbeat()
    assert cnc.last_heartbeat > 0


def test_tcache_dedup(wksp):
    tc = Tcache(wksp, depth=4)
    assert not tc.insert(10)
    assert not tc.insert(11)
    assert tc.insert(10)        # dup
    assert not tc.insert(12)
    assert not tc.insert(13)
    assert not tc.insert(14)    # evicts 10
    assert not tc.insert(10)    # 10 was evicted -> fresh again
    assert tc.insert(13)        # still resident


def test_tcache_query_no_mutation(wksp):
    tc = Tcache(wksp, depth=4)
    assert not tc.query(42)     # absent
    assert not tc.query(42)     # query never inserts
    assert not tc.insert(42)
    assert tc.query(42)
    assert tc.insert(42)        # still a dup after queries


def test_wksp_create_replaces_stale_segment():
    """create=True over a leftover segment must produce fresh zeroed
    memory, not silently reuse stale contents (advisor finding r1)."""
    name = f"/fdtpu_stale_{os.getpid()}"
    w1 = Workspace(name, 1 << 20)
    w1.view(0, 8)[:] = np.arange(1, 9, dtype=np.uint8)
    w1.close()                  # crash simulation: no unlink
    w2 = Workspace(name, 1 << 20)   # re-create
    assert w2.view(0, 8).sum() == 0
    w2.close()
    w2.unlink()


def test_wksp_exclusive_create_fails_on_existing():
    """replace=False is a strict O_EXCL create: safe under racing
    creators (never destroys a live segment)."""
    name = f"/fdtpu_excl_{os.getpid()}"
    w1 = Workspace(name, 1 << 16, replace=False)
    try:
        with pytest.raises(OSError):
            Workspace(name, 1 << 16, replace=False)
    finally:
        w1.close()
        w1.unlink()


def test_wksp_join_missing_or_small_fails():
    name = f"/fdtpu_missing_{os.getpid()}"
    with pytest.raises(OSError):
        Workspace(name, 1 << 20, create=False)
    w = Workspace(name, 1 << 16)
    try:
        with pytest.raises(OSError):
            Workspace(name, 1 << 20, create=False)  # larger than segment
    finally:
        w.close()
        w.unlink()


def test_tcache_eviction_map_consistency(wksp):
    tc = Tcache(wksp, depth=16)
    rng = np.random.default_rng(3)
    tags = rng.integers(1, 1 << 62, size=500, dtype=np.uint64)
    window = []
    for t in tags.tolist():
        dup = tc.insert(t)
        assert dup == (t in window)
        if not dup:
            window.append(t)
            if len(window) > 16:
                window.pop(0)


def _producer(name, ring_off, arena_off, depth, fseq_off, n_msgs):
    w = Workspace(name, 1 << 22, create=False)
    ring = Ring(w, ring_off, depth, arena_off, MTU)
    fseq = Fseq(w, off=fseq_off)
    rng = np.random.default_rng(1)
    for i in range(n_msgs):
        while ring.credits([fseq]) <= 0:   # reliable consumer: backpressure
            pass
        body = rng.integers(0, 256, size=32, dtype=np.uint8)
        body[:8] = np.frombuffer(np.uint64(i).tobytes(), np.uint8)
        ring.publish(body, sig=int(body[8:16].view(np.uint64)[0]))
    w.close()


def test_ipc_producer_consumer(wksp):
    """True multi-process: child publishes (credit-gated on the parent's
    fseq), parent consumes every frag in order with zero gaps."""
    depth, n_msgs = 256, 2000
    ring = Ring.create(wksp, depth=depth, mtu=MTU)
    fseq = Fseq(wksp)
    ctx = mp.get_context("fork")
    p = ctx.Process(target=_producer,
                    args=(wksp.name, ring.off, ring.arena_off, depth,
                          fseq.off, n_msgs), daemon=True)
    p.start()
    got, seq, spins = 0, 0, 0
    rng = np.random.default_rng(1)
    while got < n_msgs and spins < 100_000_000:
        rc, frag = ring.consume(seq)
        if rc == 1:
            spins += 1
            continue
        assert rc == 0, "consumer overrun despite flow control"
        body = ring.payload(frag).copy()
        want = rng.integers(0, 256, size=32, dtype=np.uint8)
        idx = int(body[:8].view(np.uint64)[0])
        assert idx == got                       # in-order, gap-free
        assert body[8:].tolist() == want[8:].tolist()
        assert frag.sig == int(want[8:16].view(np.uint64)[0])
        got += 1
        seq += 1
        fseq.update(seq)
    p.join(timeout=60)
    if p.is_alive():
        p.terminate()
    assert got == n_msgs


def test_wksp_alternate_backing_dir(tmp_path):
    """FDTPU_HUGETLBFS redirects workspace backing files to a
    hugetlbfs mount (ref: src/util/shmem/fd_shmem.h hugepage
    workspaces). No hugetlbfs exists in this container, so the test
    proves the selection + cross-process-visibility logic against a
    plain directory — on a real mount the identical path yields
    kernel-enforced huge pages."""
    import os
    import subprocess
    import sys

    repo_root = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    env = dict(os.environ, FDTPU_HUGETLBFS=str(tmp_path),
               PYTHONPATH=repo_root)
    code = """
import os
from firedancer_tpu.runtime import Workspace
w = Workspace("hugetest", 1 << 20, create=True)
import numpy as np
v = w.view(0, 8)
v[:] = np.frombuffer(b"hugedata", np.uint8)
print("created")
"""
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=60)
    assert "created" in r.stdout, r.stderr
    # the backing landed in the alternate dir, not /dev/shm
    assert (tmp_path / "hugetest").exists()
    # second process joins and sees the data
    code2 = """
from firedancer_tpu.runtime import Workspace
w = Workspace("hugetest", 1 << 20, create=False)
print(bytes(bytearray(w.view(0, 8))))
"""
    r2 = subprocess.run([sys.executable, "-c", code2], env=env,
                        capture_output=True, text=True, timeout=60)
    assert "hugedata" in r2.stdout, r2.stderr


def test_ring_publish_batch_masked_and_credit_gated(wksp):
    ring = Ring.create(wksp, depth=8, mtu=MTU)
    f = Fseq(wksp)
    n = 12
    buf = np.zeros((n, MTU), np.uint8)
    for i in range(n):
        buf[i, :4] = i
    sizes = np.full(n, 4, np.uint32)
    sigs = np.arange(n, dtype=np.uint64)
    mask = np.ones(n, np.uint8)
    mask[5] = 0                       # hole: row 5 must not publish
    stop, pub = ring.publish_batch(buf, sizes, sigs, mask, fseqs=[f])
    assert pub == 8                   # depth-limited by the consumer
    assert stop < n
    f.update(8)                       # consumer catches up
    stop, pub2 = ring.publish_batch(buf, sizes, sigs, mask, fseqs=[f],
                                    start=stop)
    assert stop == n and pub + pub2 == n - 1
    # 11 publishes on a depth-8 ring: the first 3 slots were lapped;
    # the live window holds the last 8 published sigs
    published = [i for i in range(n) if i != 5]
    got = []
    seq = 3
    while True:
        rc, frag = ring.consume(seq)
        if rc != 0:
            break
        got.append(int(frag.sig))
        seq += 1
    assert got == published[3:]


# -- queue wait per hop (native gather, Ring.m_wait) -------------------------

def _hist_of(waits):
    from firedancer_tpu.disco.metrics import HistAccum
    h = HistAccum()
    for w in waits:
        h.add(w)
    return [h.count, h.sum_ns] + h.buckets


def test_hist_waits_exact_with_32bit_wrap():
    """fdtpu_hist_waits adds (uint32)(now - tspub) per stamp in the
    disco/metrics.py layout: a stamp past `now` numerically is one that
    the low 32 bits of the clock wrapped under, and reads as its true
    wait, not a negative or a 4.29 s one."""
    import ctypes as ct

    from firedancer_tpu.runtime.tango import WAIT_HIST_U64, lib
    now = 5
    stamps = np.array([2**32 - 10, 5, 0, 3, 2**32 - 1, 5 - 3_000_000 + 2**32],
                      np.uint32)
    waits = [15, 0, 5, 2, 6, 3_000_000]
    hist = np.zeros(WAIT_HIST_U64, np.uint64)
    lib.fdtpu_hist_waits(hist.ctypes.data_as(ct.POINTER(ct.c_uint64)), now,
                         stamps.ctypes.data_as(ct.POINTER(ct.c_uint32)),
                         len(stamps))
    assert hist.tolist() == _hist_of(waits)


def test_gather_records_each_frags_queue_wait(wksp):
    """The native gather times every frag it gathers from its slot's
    publish stamp, with one clock read per block of frags: a stamp set
    a known distance back from the clock read before the gather waits
    that distance plus at most the gather's own span (`slack`), so
    each frag lands in a bucket between those of its two ends (its
    exact bucket when the span crosses no bucket edge), and the summed
    wait is the distances plus at most `slack` per frag. Ring.consume
    times its frag the same way."""
    from firedancer_tpu.runtime.tango import lib
    ring = Ring.create(wksp, depth=512, mtu=MTU)
    back = [3_000_000, 100_000_000, 1_200_000] * 100    # 300 frags
    for i in range(len(back) + 1):
        ring.publish(bytes([i % 251]) * 8, sig=i)
    # rewrite the first 300 slots' 32-bit publish stamps
    tspub = wksp.view(ring.off + 64, 512 * 32).view(np.uint32) \
        .reshape(512, 8)[:, 7]
    t_before = lib.fdtpu_ticks()
    for i, d in enumerate(back):
        tspub[i] = (t_before - d) & 0xFFFFFFFF
    n, seq, *_ = ring.gather(0, len(back), MTU)
    slack = lib.fdtpu_ticks() - t_before
    assert n == len(back) and seq == len(back)
    want = _hist_of(back)
    late = _hist_of([d + slack for d in back])
    got = ring.m_wait.tolist()
    assert got[0] == want[0]
    # a later clock read only moves a frag up: per bucket edge, the
    # frags below it are at most those whose stamp is, and at least
    # those whose stamp plus the span is
    below = np.cumsum(got[2:])
    assert (np.cumsum(late[2:]) <= below).all()
    assert (below <= np.cumsum(want[2:])).all()
    assert want[1] <= got[1] <= want[1] + n * slack
    rc, frag = ring.consume(seq)          # the one frag left
    assert rc == 0 and int(ring.m_wait[0]) == n + 1


def test_concurrent_imports_all_load_the_native_library(tmp_path):
    """Processes that import the bindings at once on a tree with no
    built library (test workers at collection, tile spawns) each load
    a whole one: the builds are serialized, so none loads a library
    another's compiler is still writing."""
    import shutil
    import subprocess
    import sys
    import firedancer_tpu.runtime.tango as tango
    pkg = tmp_path / "fdpkg"
    (pkg / "runtime").mkdir(parents=True)
    (pkg / "native").mkdir()
    (pkg / "__init__.py").write_text("")
    shutil.copy(tango.__file__, pkg / "runtime" / "tango.py")
    (pkg / "runtime" / "__init__.py").write_text(
        "from .tango import lib  # noqa: F401\n")
    native = os.path.dirname(tango._LIB_PATH)
    for f in ("Makefile", "fdtpu.cc", "fdtpu.h"):
        shutil.copy(os.path.join(native, f), pkg / "native" / f)
    procs = [subprocess.Popen(
        [sys.executable, "-c", "import fdpkg.runtime.tango as t; "
         "assert t.lib.fdtpu_ticks() > 0"],
        cwd=tmp_path, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)
        for _ in range((os.cpu_count() or 4) + 2)]
    errs = [p.communicate(timeout=240)[1].decode()[-300:] for p in procs]
    assert [p.returncode for p in procs] == [0] * len(procs), errs
