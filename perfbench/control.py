#!/usr/bin/env python3
"""The control: the reference put in the program's place with one
guarantee of the configuration broken, judged by the same comparison
as a run. It has to come out not correct.

    python3 perfbench/control.py --workload NAME --seed N --seconds S \\
        --control first_sig_only|no_dedup|no_fee|no_conflict_check|\
                  no_block_limit

  first_sig_only  verifies only each txn's first signature (the lane a
                  later PR could be tempted to drop the rest of): breaks
                  "no transaction with a failing signature is delivered"
  no_dedup        verifies every signature but delivers every frame,
                  resends included: breaks "each transaction is
                  delivered at most once" (dupflood only)
  no_fee          commits every valid txn once, in the order of its
                  first send, and executes them serially without
                  charging the signature fee: breaks "balances after
                  the run equal a serial execution of the committed
                  txns" (a leader cell, which observes PoH entries)
  no_conflict_check  commits every valid txn once, in the order of its
                  first send, in entries of the pack tile's
                  max_txn_per_microblock txns with no conflict test:
                  breaks "no PoH entry holds two transactions that lock
                  one account where one lock is a write" (a leader cell)
  no_block_limit  commits every valid txn once, in the order of its
                  first send, into one slot: breaks "no slot's committed
                  transactions cost more than block_limits.block" (a
                  leader cell)

A leader control charges the fee (but for no_fee) and builds entries as
a sound pack would (but for no_conflict_check): a txn opens a new entry
where it conflicts with the entry, the entry is full, or its first send
falls in another slot (ticks_per_slot x lazy_ns of the config's poh
tile; no_block_limit: all in slot 0). No PoH chain is made, so
poh_mismatch and ticks_early are not judged.

The corpus and schedule are the cell's own (its rate, its window), as a
run builds them. No topology boots and no chip is touched; the driver's
runs never run this. Prints each number compared beside its limit, then
{"control", "correct", "checks"} as the last line.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
LEADER = ("no_fee", "no_conflict_check", "no_block_limit")
CONTROLS = ("first_sig_only", "no_dedup") + LEADER


def _first_sig_ok(payloads):
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import \
        Ed25519PublicKey

    from perfbench.reference import parse
    out = []
    for p in payloads:
        sigs, pubs, msg = parse(p)
        try:
            Ed25519PublicKey.from_public_bytes(pubs[0]).verify(sigs[0], msg)
            out.append(True)
        except InvalidSignature:
            out.append(False)
    return out


def _entries(control: str, cfg: dict, payloads, first_t) -> list:
    """[(slot, [payload, ...]), ...]: the committed txns (in order) put
    into entries, as the module docstring says."""
    from perfbench.blockrules import locks_and_cost
    pack = next(t for t in cfg["tiles"] if t["kind"] == "pack")
    cap = int(pack["args"]["max_txn_per_microblock"])
    poh = next(t for t in cfg["tiles"] if t["kind"] == "poh")["args"]
    slot_s = int(poh["ticks_per_slot"]) * int(poh["lazy_ns"]) * 1e-9
    out = []
    wrote = read = None
    for p, t in zip(payloads, first_t):
        slot = 0 if control == "no_block_limit" else int(t // slot_s)
        writes, reads, _ = locks_and_cost(p)
        if not out or out[-1][0] != slot or len(out[-1][1]) == cap or (
                control != "no_conflict_check"
                and not (wrote.isdisjoint(writes) and wrote.isdisjoint(reads)
                         and read.isdisjoint(writes))):
            out.append((slot, []))
            wrote, read = set(), set()
        out[-1][1].append(p)
        wrote.update(writes)
        read.update(reads)
    return out


def control_run(workload: str, seed: int, seconds: float, control: str,
                overrides: dict | None = None) -> dict:
    import multiprocessing as mp

    import numpy as np

    from perfbench import blockrules, harness, ledger, reference, traffic
    spec = harness.load_cell(workload)
    for k, v in (overrides or {}).items():
        spec[k] = v(spec[k])
    mix, cell = spec["mix"], spec["cell"]
    warm_s = float(cell["warmup_s"])
    p = traffic.plan(mix, seed, float(cell["rate"]), warm_s + seconds)
    procs = traffic.default_procs()
    corpus = traffic.sign(mix, seed, p, procs)
    n = len(corpus.size)
    offered = np.zeros(n, bool)
    offered[p.send_txn] = True
    payloads = [corpus.txn(i) for i in range(n)]
    if control == "first_sig_only":
        step = -(-n // (procs * 4))
        chunks = [payloads[i:i + step] for i in range(0, n, step)]
        with mp.get_context("spawn").Pool(procs) as pool:
            ok = np.array([v for c in pool.map(_first_sig_ok, chunks)
                           for v in c], bool)
        deliveries = ok.astype(np.int32)
    elif control == "no_dedup" or control in LEADER:
        ok = reference.reference_verdicts(payloads, procs)
        frames = np.bincount(p.send_txn, minlength=n).astype(np.int32)
        deliveries = np.where(ok, frames if control == "no_dedup" else 1,
                              0)
    else:
        raise ValueError(control)
    ref_idx = reference.sample(corpus.valid, offered, seed)
    ref_ok = reference.reference_verdicts([payloads[i] for i in ref_idx],
                                          procs)
    checks = reference.judge(corpus.valid, offered, deliveries, 0, 0,
                             ref_idx, ref_ok)
    if control in LEADER:
        genesis = ledger.genesis(traffic.payers(mix, seed), n,
                                 mix["lamports"][1])
        first = np.full(n, np.inf)
        np.minimum.at(first, p.send_txn, p.send_t)
        order = [i for i in np.argsort(first, kind="stable") if ok[i]]
        committed = [payloads[i] for i in order]
        bal = dict(genesis)
        ledger.execute(bal, committed, fee_per_signature=0
                       if control == "no_fee" else ledger.FEE_PER_SIGNATURE)
        read = ledger.sample_accounts(list(genesis),
                                      np.flatnonzero(offered), corpus.txn,
                                      seed)
        served = {k: bal.get(k, 0) for k in read}
        checks["state_mismatch"] = [
            ledger.state_mismatch(genesis, committed, served), 0]
        entries = _entries(control, spec["config"], committed, first[order])
        for k, v in blockrules.block_rules(
                entries, spec["config"]["block_limits"]).items():
            checks[k] = [v, 0]
    return {"control": control, "correct": reference.correct(checks),
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", required=True, choices=CONTROLS)
    args = ap.parse_args(argv)
    out = control_run(args.workload, args.seed, args.seconds, args.control)
    for k, (v, lim) in out["checks"].items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
