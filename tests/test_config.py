"""Layered TOML config tests: merge semantics + an e2e topology launched
from config files (the fdctl config-stack analog,
ref: src/app/fdctl/config/default.toml, src/app/shared/fd_config.h)."""
import os
import textwrap

import pytest

from firedancer_tpu.app.config import build_topology, load_config
from firedancer_tpu.disco.launch import TopologyRunner

BASE = """
[topology]
wksp_size = 16777216

[[link]]
name = "synth_verify"
depth = 64
mtu = 1280

[[link]]
name = "verify_sink"
depth = 64
mtu = 1280

[[tcache]]
name = "verify_tc"
depth = 4096

[[tile]]
name = "synth"
kind = "synth"
outs = ["synth_verify"]
count = 16
unique = 16
seed = 9

[[tile]]
name = "verify"
kind = "verify"
ins = ["synth_verify"]
outs = ["verify_sink"]
batch = 16
tcache = "verify_tc"

[[tile]]
name = "sink"
kind = "sink"
ins = ["verify_sink"]
"""

OVERRIDE = """
[[link]]
name = "synth_verify"
depth = 128

[[tile]]
name = "synth"
count = 24
unique = 24
"""


@pytest.fixture()
def cfgdir(tmp_path):
    (tmp_path / "base.toml").write_text(textwrap.dedent(BASE))
    (tmp_path / "override.toml").write_text(textwrap.dedent(OVERRIDE))
    return tmp_path


def test_layer_merge_semantics(cfgdir):
    cfg = load_config(cfgdir / "base.toml", cfgdir / "override.toml")
    links = {e["name"]: e for e in cfg["link"]}
    assert links["synth_verify"]["depth"] == 128      # overridden
    assert links["synth_verify"]["mtu"] == 1280       # inherited
    assert links["verify_sink"]["depth"] == 64        # untouched
    tiles = {e["name"]: e for e in cfg["tile"]}
    assert tiles["synth"]["count"] == 24
    assert tiles["synth"]["unique"] == 24
    assert tiles["synth"]["seed"] == 9


def test_unknown_section_rejected(tmp_path):
    p = tmp_path / "bad.toml"
    p.write_text("[nonsense]\nx = 1\n")
    with pytest.raises(ValueError, match="nonsense"):
        load_config(p)


# a retired [tune] section, in the shapes configs carried it: the
# disabled default, an enabled controller policy, a knob override
STALE_TUNE = {
    "disabled": "[tune]\nenable = false\n",
    "enabled": "[tune]\nenable = true\ninterval_s = 0.25\n"
               "cooldown_s = 2.0\n",
    "knob_override": "[tune.knob.coalesce_us]\nmin = 0\nmax = 2000\n"
                     "step = 50\n",
}


@pytest.mark.parametrize("body", list(STALE_TUNE.values()),
                         ids=list(STALE_TUNE))
def test_stale_tune_section_rejected(cfgdir, body):
    """[tune] is not a config section: a layer that still carries one
    is refused at load, never silently ignored."""
    p = cfgdir / "stale.toml"
    p.write_text(body)
    with pytest.raises(ValueError,
                       match=r"stale.toml: unknown config sections "
                             r"\['tune'\]"):
        load_config(cfgdir / "base.toml", p)


def test_stale_controller_tile_rejected_as_unknown_kind(cfgdir):
    """`controller` is not a tile kind: the config's tile gate refuses
    it by name before anything is built."""
    p = cfgdir / "stale.toml"
    p.write_text('[[tile]]\nname = "ctl"\nkind = "controller"\n')
    cfg = load_config(cfgdir / "base.toml", p)
    with pytest.raises(ValueError,
                       match=r"'ctl': unknown kind 'controller'"):
        build_topology(cfg)


def test_unknown_tile_key_rejected_with_hint():
    """A typo'd tile arg used to pass through silently as an arg the
    adapter never reads; the schema gate (key registry shared with
    fdlint) rejects it with a did-you-mean."""
    with pytest.raises(ValueError, match=r"bacth.*did you mean 'batch'"):
        build_topology({"tile": [{"name": "v", "kind": "verify",
                                  "bacth": 32}]})


def test_unknown_tile_kind_rejected_with_hint():
    with pytest.raises(ValueError, match=r"verfy.*did you mean 'verify'"):
        build_topology({"tile": [{"name": "v", "kind": "verfy"}]})


def test_common_tile_keys_accepted():
    # supervise/chaos/cpu_idx etc. are stem/launcher keys valid on any kind
    topo = build_topology({
        "link": [{"name": "a_b"}],
        "tile": [{"name": "s", "kind": "synth", "outs": ["a_b"],
                  "supervise": {"policy": "restart"},
                  "chaos": {"events": []}, "cpu_idx": 0,
                  "lazy_auto": True},
                 {"name": "d", "kind": "sink", "ins": ["a_b"]}]})
    assert topo.tiles["s"].args["supervise"]["policy"] == "restart"


def test_overrides_dict(cfgdir):
    cfg = load_config(cfgdir / "base.toml",
                      overrides={"topology": {"wksp_size": 1 << 25}})
    assert cfg["topology"]["wksp_size"] == 1 << 25


def test_topology_launched_from_toml(cfgdir):
    """The e2e pipeline declared purely in TOML runs to completion."""
    cfg = load_config(cfgdir / "base.toml", cfgdir / "override.toml")
    topo = build_topology(cfg, name=f"cfg{os.getpid()}")
    runner = TopologyRunner(topo.build()).start()
    try:
        runner.wait_running(timeout_s=540)
        import time
        deadline = time.monotonic() + 120
        while runner.metrics("sink")["rx"] < 24 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert runner.metrics("synth")["tx"] == 24    # override applied
        assert runner.metrics("sink")["rx"] == 24
        assert runner.metrics("verify")["verify_fail"] == 0
    finally:
        runner.halt()
        runner.close()
