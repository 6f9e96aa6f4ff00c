"""Aux tiles: pcap codec + replay tile, ipecho service, cswtch
sampler (ref: src/disco/pcap/fd_pcap_replay_tile.c,
src/discof/ipecho/, src/disco/cswtch/fd_cswtch_tile.c)."""
import io
import os
import struct
import time

import pytest

from firedancer_tpu.utils.pcap import (LINKTYPE_USER0, read_pcap,
                                       write_pcap)


def test_pcap_roundtrip_and_endian_tolerance():
    pkts = [(1_000_000 + i * 137, os.urandom(20 + i)) for i in range(9)]
    buf = io.BytesIO()
    write_pcap(buf, pkts)
    buf.seek(0)
    assert list(read_pcap(buf)) == pkts
    # torn tail: truncated final packet is dropped, not an error
    raw = buf.getvalue()
    buf2 = io.BytesIO(raw[:-5])
    assert list(read_pcap(buf2)) == pkts[:-1]
    with pytest.raises(ValueError):
        list(read_pcap(io.BytesIO(b"\x00" * 40)))


def test_ipecho_service_roundtrip():
    from firedancer_tpu.disco.tiles import IpechoAdapter, ipecho_query

    class Ctx:
        plan = {"topology": "t", "tiles": {}}
        tile_name = "ipecho"
        in_rings = {}
        out_rings = {}
        out_fseqs = {}

    a = IpechoAdapter(Ctx(), {"shred_version": 5122})
    try:
        sv, ip, port = ipecho_query(("127.0.0.1", a.port))
        assert sv == 5122
        assert ip == "127.0.0.1" and port > 0
        # the server counts a query after its reply is sent, so the
        # reply can reach the client first: wait for the count
        deadline = time.monotonic() + 10.0
        while a.queries < 1 and time.monotonic() < deadline:
            time.sleep(0.001)
        assert a.queries == 1
    finally:
        a.on_halt()


def test_cswtch_samples_own_process(tmp_path):
    from firedancer_tpu.disco.tiles import CswtchAdapter

    class Ctx:
        plan = {"topology": f"cs{os.getpid()}", "tiles": {"me": {}}}
        tile_name = "cswtch"
        in_rings = {}
        out_rings = {}
        out_fseqs = {}

    topo = Ctx.plan["topology"]
    with open(f"/dev/shm/fdtpu_{topo}.pid.me", "w") as f:
        f.write(str(os.getpid()))
    try:
        a = CswtchAdapter(Ctx(), {})
        a.housekeeping()
        m = a.metrics_items()
        assert m["tiles_sampled"] == 1
        assert m["vol"] > 0              # this process has switched
        assert m["max_invol"] == m["invol"]
    finally:
        os.unlink(f"/dev/shm/fdtpu_{topo}.pid.me")


def test_pcap_tile_replays_into_topology(tmp_path):
    """pcap tile -> sink across real processes; payloads byte-exact
    and in order."""
    from firedancer_tpu.disco import Topology, TopologyRunner

    pkts = [(i * 1000, bytes([i]) * (60 + i)) for i in range(1, 33)]
    path = str(tmp_path / "cap.pcap")
    with open(path, "wb") as f:
        write_pcap(f, pkts)

    topo = (
        Topology(f"pc{os.getpid()}", wksp_size=1 << 22)
        .link("replayed", depth=64, mtu=256)
        .tile("pcap", "pcap", outs=["replayed"], path=path, loop=2)
        .tile("sink", "sink", ins=["replayed"])
    )
    runner = TopologyRunner(topo.build()).start()
    try:
        runner.wait_running(timeout_s=60)
        # sink rx lands a housekeeping flush BEFORE the pcap tile's
        # own tx/done counters do — poll both sides of the link to
        # the same deadline, assert once, after
        deadline = time.time() + 30
        while time.time() < deadline:
            p = runner.metrics("pcap")
            if (runner.metrics("sink")["rx"] >= 2 * len(pkts)
                    and p["tx"] >= 2 * len(pkts) and p["done"]):
                break
            time.sleep(0.05)
        assert runner.metrics("sink")["rx"] == 2 * len(pkts)
        p = runner.metrics("pcap")
        assert p["tx"] == 2 * len(pkts) and p["done"] == 1
    finally:
        runner.halt()
        runner.close()


def test_pcap_tile_empty_capture_is_done_not_crash(tmp_path):
    from firedancer_tpu.disco.tiles import PcapAdapter

    path = str(tmp_path / "empty.pcap")
    with open(path, "wb") as f:
        write_pcap(f, [])

    class Ring:
        def credits(self, fseqs):
            return 1

        def publish(self, *a, **kw):
            raise AssertionError("nothing to publish")

    class Ctx:
        plan = {"topology": "t", "tiles": {},
                "links": {"out": {"mtu": 256, "depth": 8}}}
        tile_name = "pcap"
        in_rings = {}
        out_rings = {"out": Ring()}
        out_fseqs = {"out": []}

    a = PcapAdapter(Ctx(), {"path": path, "loop": 3})
    for _ in range(5):
        assert a.poll_once() == 0
    assert a.metrics_items()["done"] == 1


def test_cswtch_ignores_recycled_pid():
    from firedancer_tpu.disco.tiles import CswtchAdapter

    class Ctx:
        plan = {"topology": f"cr{os.getpid()}", "tiles": {"ghost": {}}}
        tile_name = "cswtch"
        in_rings = {}
        out_rings = {}
        out_fseqs = {}

    topo = Ctx.plan["topology"]
    # stale pidfile: right pid, WRONG starttime
    with open(f"/dev/shm/fdtpu_{topo}.pid.ghost", "w") as f:
        f.write(f"{os.getpid()} 12345")
    try:
        a = CswtchAdapter(Ctx(), {})
        a.housekeeping()
        assert a.metrics_items()["tiles_sampled"] == 0
    finally:
        os.unlink(f"/dev/shm/fdtpu_{topo}.pid.ghost")


def test_gui_tile_serves_dashboard_and_summary():
    """gui tile in a live topology: the page serves, summary.json
    reflects real tile metrics, TPS turns nonzero under load."""
    import json as _json
    import urllib.request

    from firedancer_tpu.disco import Topology, TopologyRunner

    pkts = [(i * 10, bytes([i % 250 + 1]) * 80) for i in range(400)]
    import tempfile
    cap = tempfile.NamedTemporaryFile(suffix=".pcap", delete=False)
    with open(cap.name, "wb") as f:
        write_pcap(f, pkts)

    topo = (
        Topology(f"gt{os.getpid()}", wksp_size=1 << 22)
        .link("feed", depth=256, mtu=256)
        .tile("pcap", "pcap", outs=["feed"], path=cap.name, loop=50)
        .tile("sink", "sink", ins=["feed"])
        .tile("gui", "gui", port=0)
    )
    runner = TopologyRunner(topo.build()).start()
    try:
        runner.wait_running(timeout_s=60)
        deadline = time.time() + 30
        port = 0
        while time.time() < deadline:
            port = int(runner.metrics("gui").get("port", 0))
            if port:
                break
            time.sleep(0.05)
        assert port
        page = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/", timeout=10).read()
        assert b"firedancer-tpu" in page
        deadline = time.time() + 30
        tps = 0.0
        while time.time() < deadline:
            s = _json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/summary.json",
                timeout=10).read())
            assert set(s["tiles"]) == {"pcap", "sink", "gui"}
            if s["tps"] > 0 and s["tiles"]["sink"]["metrics"]["rx"] > 0:
                tps = s["tps"]
                break
            time.sleep(0.3)
        assert tps > 0
    finally:
        runner.halt()
        runner.close()
        os.unlink(cap.name)


def test_plugin_tile_streams_events_over_unix_socket(tmp_path):
    """plugin tile: frag stream -> NDJSON events to an external unix-
    socket client (ref: src/disco/plugin/fd_plugin_tile.c role)."""
    import json as _json
    import socket as _s

    from firedancer_tpu.disco import Topology, TopologyRunner

    # 300 ms between packets: realtime pacing holds the stream open
    # long enough for the client to attach (events emitted before any
    # client connects are dropped by design)
    pkts = [(i * 300_000, bytes([i]) * 50) for i in range(1, 17)]
    cap = str(tmp_path / "c.pcap")
    with open(cap, "wb") as f:
        write_pcap(f, pkts)
    sock_path = str(tmp_path / "plugin.sock")
    topo = (
        Topology(f"pl{os.getpid()}", wksp_size=1 << 22)
        .link("feed", depth=64, mtu=256)
        .tile("pcap", "pcap", outs=["feed"], path=cap, loop=3,
              realtime=True)                     # paced: client attaches
        .tile("plugin", "plugin", ins=[("feed", False)],
              sock_path=sock_path)
    )
    runner = TopologyRunner(topo.build()).start()
    try:
        runner.wait_running(timeout_s=60)
        deadline = time.time() + 10
        cli = None
        while time.time() < deadline and cli is None:
            try:
                cli = _s.socket(_s.AF_UNIX, _s.SOCK_STREAM)
                cli.connect(sock_path)
            except OSError:
                cli = None
                time.sleep(0.05)
        assert cli is not None
        cli.settimeout(20)
        buf = b""
        events = []
        while len(events) < 10:
            chunk = cli.recv(4096)
            if not chunk:
                break
            buf += chunk
            while b"\n" in buf:
                line, buf = buf.split(b"\n", 1)
                events.append(_json.loads(line))
        assert len(events) >= 10
        assert events[0]["link"] == "feed"
        assert all(e["sz"] == 50 for e in events[:10])
        # payload prefix round-trips
        tag = int(events[0]["data"][:2], 16)
        assert events[0]["data"] == bytes([tag]).hex() * 50
        cli.close()
    finally:
        runner.halt()
        runner.close()
