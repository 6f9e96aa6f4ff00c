"""The reader of the verify tile's dispatch-width counter
(`dispatch_width.lat`) on a hand-built run."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

NAME = "dispatch_width.lat"


def _run(verify_metrics: dict) -> harness.Run:
    r = harness.Run()
    r.cfg = harness.load_json(os.path.join(
        ROOT, "perfbench", "configs", "frontdoor-1chip.json"))
    r.t0, r.t1 = 100.0, 120.0
    r.delta = {"verify": {"metrics": verify_metrics, "hist": {}},
               "dedup": {"metrics": {"rx": 1}, "hist": {}}}
    return r


@pytest.mark.parametrize("metrics,want", [
    ({"batches": 100, "staged_lanes": 100 * 1024}, 1024.0),
    ({"batches": 4, "staged_lanes": 3 * 1024 + 8192}, 2816.0),
    ({"batches": 3, "staged_lanes": 3 * 8192}, 8192.0),   # whole batch
    ({"batches": 0, "staged_lanes": 0}, None),    # nothing dispatched
    ({"batches": 100, "kernel_programs": 200}, None),   # no such counter
])
def test_reader_is_staged_lanes_per_batch(metrics, want):
    got = harness.reader(NAME)(_run(metrics))
    assert got == (pytest.approx(want) if want is not None else None)


def test_declared_for_the_latency_cell():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    m = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert (m["unit"], m["better"], m["source"]) == (
        "lanes", "lower", "program_counter")
    assert m["layer"] == "verify tile host (tiles/verify.py)"
    assert m["moves"] == "verdict_p50_ms"
    assert m["workloads"] == ["frontdoor.mainnet.60k"]
