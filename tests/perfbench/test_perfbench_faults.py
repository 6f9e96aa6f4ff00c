"""The timed path broken underneath, on the CPU at a small size: each
fault a frontdoor cell can have makes `correct` come out false, through
the number meant to catch it (fault_driver.py plants the fault in the
tile processes; the device check is steered to the CPU)."""
import pytest

from test_perfbench_harness import drive


@pytest.mark.parametrize("workload,fault,caught_by", [
    ("frontdoor.mainnet.60k", "altered_verdict", ("missing",
                                                   "wrong_accept")),
    ("frontdoor.mainnet.60k", "half_unverified", ("wrong_accept",)),
    ("frontdoor.dupflood.over", "state_unchanged", ("double",)),
])
def test_fault_is_not_correct(workload, fault, caught_by):
    out, _ = drive(workload, fault)
    assert out["correct"] is False
    assert sum(out["checks"][k][0] for k in caught_by) > 0, out["checks"]
