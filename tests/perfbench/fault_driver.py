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
  bank_unchanged   the bank tile cancels each executed fork instead of
                   publishing it (its state returned unchanged)
  bank_half        the bank tile executes every other transfer of a wave
                   and records them all (half of the batch left out)
  bank_amount      the bank tile moves one lamport more than each
                   transfer says (an answer altered where it is made)
  poh_short        the poh tile's ticks hash half their count and record
                   the whole (an answer altered where it is made)
  poh_fast         the poh tile makes four ticks in each housekeeping
                   pass, of 256 hashes each (set in the config for this
                   fault alone, so they can outrun the wall clock)
  pack_no_conflict pack's conflict test passes every txn, so a
                   microblock can lock one account twice (run on a mix
                   of two payers, so that one does)
  pack_no_limits   pack's block-limit test passes every txn (run on
                   txns asking for 1.4M compute units, so that a slot
                   of them passes the 48M-unit block limit)

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
    elif FAULT == "bank_unchanged":
        from firedancer_tpu.funk.funk import Funk
        Funk.txn_publish = Funk.txn_cancel
    elif FAULT in ("bank_half", "bank_amount"):
        import dataclasses

        from firedancer_tpu.disco.tiles import BankAdapter
        real_parse = BankAdapter._parse_transfers
        seen = [0]                 # transfers parsed so far, tile-wide

        def parse(self, frame, txn_cnt):
            txns, mixin = real_parse(self, frame, txn_cnt)
            if FAULT == "bank_half":
                keep = [t for j, t in enumerate(txns, seen[0]) if j % 2]
                seen[0] += len(txns)
                return keep, mixin
            return [dataclasses.replace(t, amount=t.amount + 1)
                    for t in txns], mixin
        BankAdapter._parse_transfers = parse
    elif FAULT == "poh_short":
        from firedancer_tpu.ops import poh
        real_append = poh.host_poh_append
        poh.host_poh_append = lambda state, n: real_append(state, n // 2)
    elif FAULT == "poh_fast":
        from firedancer_tpu.disco.tiles import PohAdapter
        real_hk = PohAdapter.housekeeping

        def housekeeping(self):
            for _ in range(3):
                self._tick()
            real_hk(self)
        PohAdapter.housekeeping = housekeeping
    elif FAULT in ("pack_no_conflict", "pack_no_limits"):
        from firedancer_tpu.pack.scheduler import PackScheduler
        name = "_conflicts" if FAULT == "pack_no_conflict" \
            else "_block_allows"
        setattr(PackScheduler, name, lambda self, *a: name != "_conflicts")
    elif FAULT != "none":
        raise ValueError(FAULT)


def small(cfg):
    cfg = json.loads(json.dumps(cfg))
    cfg["wksp_size"] = 1 << 26
    for ln in cfg["links"]:
        ln["depth"] = 1024 if ln["mtu"] <= 1280 else min(ln["depth"], 256)
    for tc in cfg["tcaches"]:
        tc["depth"] = 1 << 14
    for t in cfg["tiles"]:
        if t["name"] == cfg["device_tile"]:
            t["args"]["batch"] = 64
        if t["kind"] == "poh" and FAULT == "poh_fast":
            t["args"]["hashes_per_tick"] = 256
    return cfg


def main(workload: str) -> int:
    from perfbench import harness
    harness.PLATFORM = "cpu"
    # a drain ends once every valid txn came; only a fault that loses
    # valid txns waits its whole limit, so only that one keeps it short.
    # The warm-up's drain outlasts the CPU compiles, so the window sees
    # the pipeline running.
    harness.DRAIN_S = 3.0 if FAULT == "altered_verdict" else 60.0
    harness.WARM_DRAIN_S = harness.DRAIN_S
    mix = {"none": {}, "altered_verdict": {},
           "half_unverified": {"forged_share": 0.3},
           "state_unchanged": {},
           "pack_no_conflict": {"signer_pool": 2},
           "pack_no_limits": {"compute_budget": {
               "cu_limit": 1_400_000, "loaded_data_bytes": 32768}},
           }.get(FAULT, {})
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
