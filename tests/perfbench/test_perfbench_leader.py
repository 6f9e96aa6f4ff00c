"""The leader cell off the chip: a CPU rehearsal of leader.transfers.over
end to end, each bank and PoH fault it can have coming out not correct,
the PoH entry decoder on the program's own entry frames, the plain
serial executor on each branch of the fee model, the ledger judge
counting each planted fault, the PoH checks on a chain built by the
program's entry format, the control, the transfer mix, and the leader's
metric readers on a hand-built run."""
import hashlib
import json
import os
import shutil
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import (blockrules, harness, ledger,  # noqa: E402
                       reference, traffic)
from test_perfbench_harness import KEYS, drive  # noqa: E402

CELL = "leader.transfers.over"
SEED = 2 ** 35 + 23
LEDGER_CHECKS = ["wrong_accept", "missing", "double", "altered", "unknown",
                 "label_disagree", "state_mismatch", "poh_mismatch",
                 "ticks_early", "entry_conflict", "block_cost_over",
                 "acct_cost_over"]


def test_cpu_rehearsal_is_correct_and_reports_committed_tps():
    out, err = drive(CELL)
    assert list(out) == KEYS
    assert out["correct"] is True, out["checks"]
    assert list(out["checks"]) == LEDGER_CHECKS
    assert out["attempted"] > 100 and out["failed"] == 0
    assert set(out["metrics"]) == {"committed_tps", "setup_s"}
    assert out["metrics"]["committed_tps"]["value"] > 0
    assert out["metrics"]["committed_tps"]["unit"] == "txns/s"
    assert out["device"]["platform"] == "cpu"
    assert "perfbench: state: " in err
    assert "perfbench: block rules: " in err
    tail = err.strip().splitlines()[-len(out["checks"]):]
    assert tail[-1] == "check acct_cost_over: 0 (limit 0)"


@pytest.mark.parametrize("fault", ["bank_unchanged", "bank_half",
                                   "bank_amount"])
def test_bank_fault_is_not_correct(fault):
    """Each is caught by the balances: every txn is still recorded in
    PoH, once and unaltered."""
    out, _ = drive(CELL, fault)
    assert out["correct"] is False
    assert out["checks"]["state_mismatch"][0] > 0, out["checks"]
    assert out["checks"]["missing"][0] == 0


@pytest.mark.parametrize("fault,caught", [("poh_short", "poh_mismatch"),
                                          ("poh_fast", "ticks_early")])
def test_poh_fault_is_not_correct(fault, caught):
    """Each is caught by the PoH record alone: every txn is still
    recorded once, unaltered, and the balances agree."""
    out, _ = drive(CELL, fault)
    assert out["correct"] is False
    assert out["checks"][caught][0] > 0, out["checks"]
    assert out["checks"]["missing"][0] == 0
    assert out["checks"]["state_mismatch"][0] == 0


# -- the entry decoder, on the poh tile's own frames ----------------------

def _entries(txn_groups):
    """Entry frames as PohAdapter._emit_entry builds them: a tick with no
    txns, then one record entry per group (u16-framed payloads)."""
    from firedancer_tpu.disco.tiles import PohAdapter
    poh = SimpleNamespace(slot=7, tick_in_slot=3, state=bytes(range(32)),
                          _pend_entries=[], entry_idx=0, m={"entries": 0})
    PohAdapter._emit_entry(poh, 2048, bytes(32), None, slot_done=True)
    for g in txn_groups:
        blob = b"".join(len(t).to_bytes(2, "little") + t for t in g)
        PohAdapter._emit_entry(poh, 1, bytes(32), b"\x01" * 32,
                               txn_blob=blob, txn_cnt=len(g))
    return [f for _, f in poh._pend_entries]


def _chain(n_ticks=3, hashes_per_tick=40, ticks_per_slot=2,
           mixins_per_tick=2):
    """Entry headers of a sound chain in the poh tile's wire format
    (PohAdapter._emit_entry): each tick's remainder after its mixins."""
    from firedancer_tpu.disco.tiles import PohAdapter
    poh = SimpleNamespace(slot=5, tick_in_slot=1, state=bytes(32),
                          _pend_entries=[], entry_idx=0, m={"entries": 0})
    for _ in range(n_ticks):
        for j in range(mixins_per_tick):
            prev, mixin = poh.state, bytes([j + 1]) * 32
            poh.state = hashlib.sha256(prev + mixin).digest()
            PohAdapter._emit_entry(poh, 1, prev, mixin)
        prev, state = poh.state, poh.state
        for _ in range(hashes_per_tick - mixins_per_tick):
            state = hashlib.sha256(state).digest()
        poh.state = state
        PohAdapter._emit_entry(poh, hashes_per_tick - mixins_per_tick, prev,
                               None)
        poh.tick_in_slot += 1
        if poh.tick_in_slot == ticks_per_slot:
            poh.slot, poh.tick_in_slot = poh.slot + 1, 0
    return np.array([np.frombuffer(f[:harness.ENTRY_HDR], np.uint8)
                     for _, f in poh._pend_entries])


def test_poh_mismatch_passes_a_sound_chain():
    assert ledger.poh_mismatch(_chain(), 40, 2, SEED) == 0


def _put(hdrs, i, off, b):
    hdrs[i, off:off + len(b)] = np.frombuffer(b, np.uint8)


@pytest.mark.parametrize("fault", ["prev", "mixin", "tick_hash",
                                   "tick_count", "tick_order", "dropped"])
def test_poh_mismatch_counts_each_fault(fault):
    hdrs = _chain()        # (mixin, mixin, tick) x 3; ticks 5:1, 6:0, 6:1
    if fault == "prev":                 # a record entry off the chain
        _put(hdrs, 1, 17, b"\x07" * 32)
    elif fault == "mixin":              # its mixin altered
        _put(hdrs, 3, 81, b"\x09" * 32)
    elif fault == "tick_hash":          # a tick's hash not its hashes
        _put(hdrs, 5, 49, b"\x05" * 32)
    elif fault == "tick_count":         # a tick closing too few hashes
        _put(hdrs, 5, 12, (37).to_bytes(4, "little"))
    elif fault == "tick_order":         # a tick out of (slot, tick) order
        _put(hdrs, 8, 8, (0).to_bytes(4, "little"))
    else:                               # an entry never delivered
        hdrs = np.delete(hdrs, 4, axis=0)
    assert ledger.poh_mismatch(hdrs, 40, 2, SEED) > 0


def test_ticks_early_counts_ticks_ahead_of_the_clock():
    hdrs = _chain(n_ticks=8, mixins_per_tick=0)
    tick_s = 0.00625
    on_time = np.arange(8) * tick_s * 1.01
    assert ledger.ticks_early(hdrs, on_time, tick_s, 2) == 0
    fast = np.arange(8) * tick_s / 4               # four times too fast
    assert ledger.ticks_early(hdrs, fast, tick_s, 2) == 5
    assert ledger.ticks_early(hdrs[:0], on_time[:0], tick_s, 2) == 0


def _gathered(frames, width=16640):
    buf = np.zeros((len(frames), width), np.uint8)
    for i, f in enumerate(frames):
        buf[i, :len(f)] = np.frombuffer(f, np.uint8)
    return buf, np.array([len(f) for f in frames], np.uint32)


def _corpus(name="transfers", seed=SEED, rate=400, seconds=1.0):
    mix = traffic.load_mix(os.path.join(ROOT, "perfbench", "traffic",
                                        name + ".json"))
    p = traffic.plan(mix, seed, rate, seconds)
    return mix, p, traffic.sign(mix, seed, p, procs=1)


def test_entry_decoder_reads_the_poh_wire_format():
    _, _, c = _corpus()
    groups = [[c.txn(0), c.txn(1), c.txn(2)], [c.txn(3)], [c.txn(4)] * 2]
    frames = _entries(groups)
    assert len(frames[0]) == harness.ENTRY_HDR      # the tick: no txns
    buf, szs = _gathered(frames)
    txns, sizes, rows, bad = harness.entry_txns(buf, szs, len(frames),
                                                traffic.MTU)
    assert bad == 0
    assert rows.tolist() == [1, 1, 1, 2, 3, 3]      # the frame of each
    want = [t for g in groups for t in g]
    assert [txns[j, :sizes[j]].tobytes() for j in range(len(want))] == want
    # zero padded to the MTU: the corpus's checksums apply as they are
    assert np.array_equal((txns.view(np.uint64) * traffic.WORD_WEIGHTS).sum(
        axis=1, dtype=np.uint64), c.check[[0, 1, 2, 3, 4, 4]])


def test_entry_decoder_counts_a_truncated_frame():
    _, _, c = _corpus()
    frames = _entries([[c.txn(0), c.txn(1)]])
    frames[1] = frames[1][:-10]                       # cut the last txn
    frames.append(b"\x00" * 20)                       # shorter than a header
    buf, szs = _gathered(frames)
    txns, sizes, rows, bad = harness.entry_txns(buf, szs, len(frames),
                                                traffic.MTU)
    assert bad == 2 and rows.tolist() == [1]
    assert len(sizes) == 1 and txns[0, :sizes[0]].tobytes() == c.txn(0)


# -- the plain serial executor ---------------------------------------------

A, B, C = (bytes([i]) * 32 for i in (1, 2, 3))
FEE = ledger.FEE_PER_SIGNATURE


def _txn(payer, transfers, sigs=1, other=False):
    """A legacy txn: `sigs` signers (payer first), the transfers as
    system-program instructions [(dst, lamports)], and, with `other`, a
    first instruction of another program."""
    prog_other = b"\x09" * 32
    keys = [payer] + [bytes([0x40 + i]) * 32 for i in range(1, sigs)]
    for d, _ in transfers:
        if d not in keys:
            keys.append(d)
    keys += [ledger.SYSTEM_PROGRAM_ID, prog_other]
    sysi, othi = len(keys) - 2, len(keys) - 1
    ixs = [bytes((othi, 1, 0, 1, 7))] if other else []
    ixs += [bytes((sysi, 2, 0, keys.index(d), 12)) + b"\x02\x00\x00\x00"
            + amt.to_bytes(8, "little") for d, amt in transfers]
    msg = (bytes((sigs, 0, 2, len(keys))) + b"".join(keys) + bytes(32)
           + bytes((len(ixs),)) + b"".join(ixs))
    return bytes((sigs,)) + b"\x00" * 64 * sigs + msg


def test_parse_transfers_of_the_mix_txn():
    _, _, c = _corpus()
    sigs, transfers = ledger.parse_transfers(c.txn(0))
    assert sigs == 1 and len(transfers) == 1
    src, dst, amt = transfers[0]
    assert src == c.txn(0)[69:101] and dst == c.txn(0)[101:133]
    assert 1_000_000 <= amt <= 9_999_999


@pytest.mark.parametrize("start,want,status", [
    (FEE + 100, {A: 0, B: 100}, ledger.OK),                 # exactly enough
    (FEE + 99, {A: 99}, ledger.INSUFFICIENT),               # fee charged
    (FEE - 1, {A: FEE - 1}, ledger.FEE_FAIL),               # nothing moves
])
def test_executor_fee_model_branches(start, want, status):
    bal = {A: start}
    assert ledger.execute(bal, [_txn(A, [(B, 100)])]) == [status]
    assert {k: v for k, v in bal.items() if v or k == A} == want


def test_executor_charges_the_fee_once_per_txn_by_signatures():
    bal = {A: 10 ** 9}
    st = ledger.execute(bal, [_txn(A, [(B, 5), (C, 7)], sigs=2)])
    assert st == [ledger.OK, ledger.OK]
    assert bal == {A: 10 ** 9 - 2 * FEE - 12, B: 5, C: 7}


def test_executor_skips_a_txn_without_a_transfer():
    bal = {A: 10 ** 9}
    assert ledger.execute(bal, [_txn(A, [], other=True)]) == []
    assert bal == {A: 10 ** 9}
    # another program's instruction first: the transfer still executes
    assert ledger.execute(bal, [_txn(A, [(B, 1)], other=True)]) == [
        ledger.OK]
    assert bal == {A: 10 ** 9 - FEE - 1, B: 1}


def test_executor_applies_commit_order():
    """A payer short of funds: which transfer fails depends on order."""
    t1, t2 = _txn(A, [(B, 100)]), _txn(A, [(C, 200)])
    one, two = {A: FEE + 200}, {A: FEE + 200}
    assert ledger.execute(one, [t1, t2]) == [ledger.OK, ledger.FEE_FAIL]
    assert ledger.execute(two, [t2, t1]) == [ledger.OK, ledger.FEE_FAIL]
    assert one != two


def test_b58encode_agrees_with_the_program():
    from firedancer_tpu.utils.base58 import b58_encode_32
    for key in (bytes(32), b"\x00\x01" + bytes(30), A, bytes(range(32))):
        assert ledger.b58encode(key) == b58_encode_32(key)


# -- the ledger judge, each planted fault -----------------------------------

def _judged(deliveries, committed, served_delta=None):
    """Judge a hand-made outcome of four offered transfers (txn 3
    forged) the way run_cell does."""
    _, _, c = _corpus(rate=4)
    valid = np.array([True, True, True, False])
    offered = np.ones(4, bool)
    idx = np.arange(4)
    checks = reference.judge(valid, offered, np.array(deliveries), 0, 0,
                             idx, valid.copy())
    payers = [c.txn(i)[69:101] for i in range(4)]
    genesis = ledger.genesis(payers, 4, 9_999_999)
    bal = dict(genesis)
    ledger.execute(bal, [c.txn(i) for i in committed])
    read = ledger.sample_accounts(list(genesis), np.arange(4), c.txn, SEED)
    served = {k: bal.get(k, 0) for k in read}
    for k, d in (served_delta or {}).items():
        served[read[k]] += d
    checks["state_mismatch"] = [ledger.state_mismatch(
        genesis, [c.txn(i) for i in committed], served), 0]
    return {k: v for k, (v, _) in checks.items()}, reference.correct(checks)


def test_ledger_judge_passes_a_sound_run():
    got, ok = _judged([1, 1, 1, 0], [0, 1, 2])
    assert ok and not any(got.values())


@pytest.mark.parametrize("deliveries,committed,delta,caught", [
    ([1, 1, 1, 1], [0, 1, 2, 3], None, "wrong_accept"),   # forged committed
    ([1, 2, 1, 0], [0, 1, 2], None, "double"),            # committed twice
    ([1, 0, 1, 0], [0, 2], None, "missing"),              # never committed
    ([1, 1, 1, 0], [0, 1, 2], {0: -1}, "state_mismatch"),  # wrong balance
])
def test_ledger_judge_counts_each_fault(deliveries, committed, delta, caught):
    got, ok = _judged(deliveries, committed, delta)
    assert not ok
    assert got[caught] > 0, got


def test_no_fee_control_comes_out_not_correct():
    from perfbench import control
    out = control.control_run(
        CELL, 2 ** 36 + 5, 1.0, "no_fee",
        overrides={"cell": lambda c: dict(c, rate=1500, warmup_s=0.0)})
    assert out["correct"] is False
    assert out["checks"]["state_mismatch"][0] > 0
    assert out["checks"]["missing"][0] == 0
    for k in ("entry_conflict", "block_cost_over", "acct_cost_over"):
        assert out["checks"][k] == [0, 0], k      # packed soundly


@pytest.mark.parametrize("control,caught,cu_limit", [
    ("no_conflict_check", "entry_conflict", 300),
    ("no_block_limit", "block_cost_over", 1_400_000),
])
def test_block_rule_control_comes_out_not_correct(control, caught,
                                                   cu_limit):
    """A second of transfers (for no_block_limit each asking for 1.4M
    compute units, so that the second's txns pass the block limit); only
    the rule the control breaks reads above 0."""
    from perfbench import control as ctl
    out = ctl.control_run(
        CELL, 2 ** 36 + 7, 1.0, control,
        overrides={"cell": lambda c: dict(c, rate=1500, warmup_s=0.0),
                   "mix": lambda m: dict(m, compute_budget=dict(
                       m["compute_budget"], cu_limit=cu_limit))})
    got = {k: v for k, (v, _) in out["checks"].items()}
    assert out["correct"] is False
    assert got[caught] > 0, got
    assert {k for k, v in got.items() if v} == {caught}


# -- the transfer mix --------------------------------------------------------

def test_transfer_mix_shape():
    mix, p, c = _corpus(rate=2000)
    n = len(c.size)
    assert n == 2000 and (c.size == traffic.TRANSFER_SIZE).all()
    assert (c.sig_cnt == 1).all() and (~c.valid).sum() == 40   # 2% forged
    assert len(np.unique(c.key)) == n
    dst = {ledger.parse_transfers(c.txn(i))[1][0][1] for i in range(n)}
    assert len(dst) == n                            # fresh: one each
    pool = set(traffic.payers(mix, SEED))
    assert {c.txn(i)[69:101] for i in range(n)} <= pool
    assert reference.verify_txn(c.txn(int(np.flatnonzero(c.valid)[0])))


def test_transfer_mix_same_seed_same_corpus():
    _, p1, c1 = _corpus()
    _, p2, c2 = _corpus()
    assert np.array_equal(c1.flat, c2.flat)
    assert np.array_equal(p1.send_t, p2.send_t)


def test_zipf_destinations_follow_the_rule():
    mix, _, _ = _corpus()
    mix = dict(mix, destinations={"rule": "zipf", "count": 50,
                                  "exponent": 1.2})
    p = traffic.plan(mix, SEED, 3000, 1.0)
    c = traffic.sign(mix, SEED, p, procs=1)
    dst = [ledger.parse_transfers(c.txn(i))[1][0][1]
           for i in range(len(c.size))]
    rank = {traffic.account_key(SEED, b"acct", r): r for r in range(50)}
    got = np.bincount([rank[d] for d in dst], minlength=50) / len(dst)
    want = np.arange(1, 51) ** -1.2
    want /= want.sum()
    assert np.abs(got - want).max() < 0.03
    assert len(np.unique(c.key)) == len(c.size)


def test_mix_refuses_an_unknown_rule(tmp_path):
    path = tmp_path / "m.json"
    mix = json.load(open(os.path.join(ROOT, "perfbench", "traffic",
                                      "transfers.json")))
    mix["destinations"] = {"rule": "round_robin"}
    path.write_text(json.dumps(mix))
    with pytest.raises(ValueError):
        traffic.load_mix(str(path))


# -- the leader's metric readers ---------------------------------------------

def _run(poh_metrics=None, **busy):
    r = harness.Run()
    r.cfg = harness.load_json(os.path.join(
        ROOT, "perfbench", "configs", "leader-1chip.json"))
    r.t0, r.t1 = 100.0, 120.0
    r.delta = {t["name"]: {"metrics": {}, "hist": {
        "work": (10, int(busy.get(t["name"], 0) * 1e9))}}
        for t in r.cfg["tiles"]}
    r.delta["poh"]["metrics"] = poh_metrics or {}
    return r


@pytest.mark.parametrize("name,want", [
    ("pack_busy.leader", 25.0),           # 5 s of work in a 20 s window
    ("tick_ms.leader", 12.5),             # 1,600 ticks in 20 s
])
def test_leader_reader_on_a_hand_built_run(name, want):
    r = _run({"ticks": 1_600}, pack=5.0, bank0=10.0, poh=1.0)
    assert harness.reader(name)(r) == pytest.approx(want)


def test_tick_ms_is_silent_without_ticks():
    assert harness.reader("tick_ms.leader")(_run({})) is None


# -- leader cells as data: what holds for any number of them -------------

ZIPF = {"rule": "zipf", "count": 1024, "exponent": 0.99}


def add_zipf_cell(root):
    """Add leader.zipf.over to a copy of the benchmark at `root` as new
    files and new BENCHMARK.json entries only: a config that is a copy
    of leader-1chip's, the transfers mix with Zipf destinations, a cell
    file, and the cell's name appended to committed_tps's workloads."""
    pb = os.path.join(root, "perfbench")
    shutil.copy(os.path.join(pb, "configs", "leader-1chip.json"),
                os.path.join(pb, "configs", "leader-zipf-1chip.json"))
    mix = harness.load_json(os.path.join(pb, "traffic", "transfers.json"))
    with open(os.path.join(pb, "traffic", "zipf.json"), "w") as f:
        json.dump(dict(mix, name="zipf", destinations=ZIPF), f)
    shutil.copy(os.path.join(pb, "cells", CELL + ".json"),
                os.path.join(pb, "cells", "leader.zipf.over.json"))
    path = os.path.join(root, "BENCHMARK.json")
    bench = harness.load_json(path)
    conf = next(c for c in bench["configs"] if c["name"] == "leader-1chip")
    bench["configs"].append(dict(conf, name="leader-zipf-1chip",
                                 file="perfbench/configs/"
                                 "leader-zipf-1chip.json"))
    bench["workloads"].append({
        "name": "leader.zipf.over", "config": "leader-zipf-1chip",
        "traffic": "zipf", "chips": 1,
        "why": "transfers to 1,024 Zipf-0.99 accounts: pack's conflicts"})
    next(m for m in bench["end_to_end"]
         if m["name"] == "committed_tps")["workloads"].append(
        "leader.zipf.over")
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)


@pytest.fixture(scope="module", params=["repo", "zipf_added"])
def root(request, tmp_path_factory):
    """The repo's benchmark, and a copy of it with a second leader cell
    added as data."""
    if request.param == "repo":
        return ROOT
    dst = tmp_path_factory.mktemp("room")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "perfbench"), dst / "perfbench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    add_zipf_cell(str(dst))
    return str(dst)


def _leader_cells(root) -> list:
    """Cells whose config's observed link a poh tile produces."""
    bench = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    out = []
    for w in bench["workloads"]:
        cfg = harness.load_cell(w["name"], root)["config"]
        producer = next(t for t in cfg["tiles"]
                        if cfg["observe"] in t.get("outs", ()))
        if producer["kind"] == "poh":
            out.append(w["name"])
    return out


def _e2e(root) -> dict:
    bench = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    return {m["name"]: m for m in bench["end_to_end"]}


def test_committed_tps_cells_are_leader_cells(root):
    cells = _e2e(root)["committed_tps"]["workloads"]
    assert CELL in cells
    assert set(cells) <= set(_leader_cells(root))


def test_leader_cells_report_committed_tps_and_setup_s(root):
    for cell in _leader_cells(root):
        spec = harness.load_cell(cell, root)
        names = [m["name"] for m in spec["metrics"]["end_to_end"]]
        assert {"committed_tps", "setup_s"} <= set(names), cell
        assert spec["config"]["block_limits"]["block"] > 0
        assert spec["config"]["block_limits"]["writable_account"] > 0
    assert [m["name"] for m in harness.load_cell(
        CELL, root)["metrics"]["end_to_end"]] == ["committed_tps", "setup_s"]


def test_leader_metrics_are_declared_for_the_leader_cell(root):
    bench = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    cells = set(_e2e(root)["committed_tps"]["workloads"])
    pl = [m for m in bench["per_layer"] if m["name"].endswith(".leader")]
    for m in pl:
        assert m["moves"] == "committed_tps"
        assert set(m.get("workloads", cells)) <= cells, m["name"]
    spec = harness.load_cell(CELL, root)
    assert {"pack_busy.leader", "tick_ms.leader"} <= {
        m["name"] for m in spec["metrics"]["per_layer"]}


def test_a_second_leader_cell_is_added_as_data(tmp_path):
    """The copy holds every file of the repo's benchmark as it was, and
    BENCHMARK.json only gains entries; load_cell reads all four files
    of the new cell from the copy."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    add_zipf_cell(str(tmp_path))
    new = {"perfbench/configs/leader-zipf-1chip.json",
           "perfbench/traffic/zipf.json",
           "perfbench/cells/leader.zipf.over.json"}
    for dirpath, _, files in os.walk(tmp_path / "perfbench"):
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), tmp_path)
            if rel not in new:
                with open(os.path.join(ROOT, rel), "rb") as a, \
                        open(os.path.join(tmp_path, rel), "rb") as b:
                    assert a.read() == b.read(), rel
    old = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    got = harness.load_json(os.path.join(tmp_path, "BENCHMARK.json"))
    assert got["configs"][:-1] == old["configs"]
    assert got["workloads"][:-1] == old["workloads"]
    assert got["per_layer"] == old["per_layer"]
    for a, b in zip(old["end_to_end"], got["end_to_end"]):
        if a["name"] == "committed_tps":
            assert b["workloads"] == a["workloads"] + ["leader.zipf.over"]
            b = dict(b, workloads=a["workloads"])
        assert a == b
    spec = harness.load_cell("leader.zipf.over", str(tmp_path))
    assert spec["mix"]["destinations"] == ZIPF
    assert spec["cell"]["rate"] > 0
    assert spec["config"]["observe"] == "poh_entries"
    assert "leader.zipf.over" in _leader_cells(str(tmp_path))
    p = traffic.plan(spec["mix"], SEED, 50, 1.0)
    c = traffic.sign(spec["mix"], SEED, p, procs=1)
    dst = {ledger.parse_transfers(c.txn(i))[1][0][1]
           for i in range(len(c.size))}
    ranks = {traffic.account_key(SEED, b"acct", r) for r in range(1024)}
    assert dst <= ranks


@pytest.mark.parametrize("budget,cost", [
    ({"cu_limit": 300, "loaded_data_bytes": 32768}, 1_633),   # the mix's
    ({"cu_limit": 150, "loaded_data_bytes": 65536}, 1_491),
])
def test_transfer_cost_as_pack_prices_it(budget, cost):
    """The program's pack charges the mix's txn what its why says; the
    compute-budget program id is the program's."""
    from firedancer_tpu.pack.cost import (COMPUTE_BUDGET_PROGRAM_ID,
                                          compute_cost)
    from firedancer_tpu.protocol.txn import parse_txn
    assert traffic.COMPUTE_BUDGET_PROGRAM_ID == COMPUTE_BUDGET_PROGRAM_ID
    mix, _, _ = _corpus()
    mix = dict(mix, compute_budget=budget)
    c = traffic.sign(mix, SEED, traffic.plan(mix, SEED, 10, 1.0), procs=1)
    t = c.txn(0)
    assert len(t) == traffic.TRANSFER_SIZE
    assert compute_cost(parse_txn(t), t).total == cost
    assert blockrules.locks_and_cost(t)[2] == cost
    assert ledger.parse_transfers(t)[1][0][0] == t[69:101]
