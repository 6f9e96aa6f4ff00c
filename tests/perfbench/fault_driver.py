"""Drives one perfbench run on the CPU, at a small size, with the device
check steered to the CPU and, where asked, the timed path broken
underneath (tests/perfbench/test_perfbench_faults.py):

    PERFBENCH_TEST_FAULT=<fault> python tests/perfbench/fault_driver.py WORKLOAD

The topology's tile processes import this file as __mp_main__, so the
fault is planted in the program inside them, where it runs:

  none             no fault (the CPU rehearsal)
  altered_verdict  the verify tile's readback flips the verdict of the
                   first lane of every device batch (an answer altered
                   where it is produced)
  half_unverified  every odd lane of a batch passes without its verdict
                   (half of the batch left out of the check)
  state_unchanged  the ha-dedup and dedup tcaches never record a tag (a
                   step that returns its state unchanged)

Prints the run's result line last, as run.py does.
"""
import json
import os
import sys
import time

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
FAULT = os.environ.get("PERFBENCH_TEST_FAULT", "none")


def plant():
    import numpy as np
    if FAULT in ("altered_verdict", "half_unverified"):
        from firedancer_tpu.tiles.verify import VerifyTile
        real = VerifyTile._read_verdicts

        def broken(self, fut):
            v = np.array(real(self, fut), bool)
            if FAULT == "altered_verdict":
                v[0] = ~v[0]
            else:
                v[1::2] = True
            return v
        VerifyTile._read_verdicts = broken
    elif FAULT == "state_unchanged":
        from firedancer_tpu.runtime import Tcache

        def no_insert(self, tags, mask=None):
            return np.zeros(len(tags), np.uint8)
        Tcache.insert_batch = no_insert
    elif FAULT != "none":
        raise ValueError(FAULT)


def small(cfg):
    cfg = json.loads(json.dumps(cfg))
    cfg["wksp_size"] = 1 << 26
    for ln in cfg["links"]:
        ln["depth"] = 1024
    for tc in cfg["tcaches"]:
        tc["depth"] = 1 << 14
    for t in cfg["tiles"]:
        if t["name"] == cfg["device_tile"]:
            t["args"]["batch"] = 64
    return cfg


def main(workload: str) -> int:
    from perfbench import harness
    harness.PLATFORM = "cpu"
    harness.DRAIN_S = harness.WARM_DRAIN_S = 3.0  # a fault's txns never come
    mix = {"none": {}, "altered_verdict": {},
           "half_unverified": {"forged_share": 0.3},
           "state_unchanged": {}}[FAULT]
    out = harness.run_cell(
        workload, 2 ** 40 + 11, 2.0, False, T_START,
        overrides={"config": small,
                   "mix": lambda m: dict(m, **mix),
                   "cell": lambda c: dict(c, rate=120, warmup_s=0.5)})
    harness.report(out)
    return 0


if __name__ == "__mp_main__":
    plant()

if __name__ == "__main__":
    plant()
    sys.exit(main(sys.argv[1]))
