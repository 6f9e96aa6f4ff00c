"""The reader of the strict kernel's grid-program counter
(`kernel_programs.lat`) on a hand-built run."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

NAME = "kernel_programs.lat"


def _run(verify_metrics: dict) -> harness.Run:
    r = harness.Run()
    r.cfg = harness.load_json(os.path.join(
        ROOT, "perfbench", "configs", "frontdoor-1chip.json"))
    r.t0, r.t1 = 100.0, 120.0
    r.delta = {"verify": {"metrics": verify_metrics, "hist": {}},
               "dedup": {"metrics": {"rx": 1}, "hist": {}}}
    return r


@pytest.mark.parametrize("metrics,want", [
    ({"batches": 100, "kernel_programs": 600}, 6.0),
    ({"batches": 3, "kernel_programs": 96}, 32.0),   # full width
    ({"batches": 0, "kernel_programs": 0}, None),    # nothing dispatched
    ({"batches": 100, "lanes": 131_072}, None),      # no such counter
])
def test_reader_is_programs_per_batch(metrics, want):
    got = harness.reader(NAME)(_run(metrics))
    assert got == (pytest.approx(want) if want is not None else None)


def test_declared_for_the_latency_cell():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    m = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert m["source"] == "program_counter"
    assert m["moves"] == "verdict_p50_ms"
    assert m["workloads"] == ["frontdoor.mainnet.60k"]
