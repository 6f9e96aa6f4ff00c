"""The block rules off the chip: the plain cost model against the
program's pack on every leader mix and on the other txn kinds it
prices, and each rule counting a planted breach exactly once in entries
built by hand in the observer's format."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import blockrules, harness, ledger, traffic  # noqa: E402

SEED = 2 ** 37 + 29
LIMITS = harness.load_json(os.path.join(
    ROOT, "perfbench", "configs", "leader-1chip.json"))["block_limits"]
ZIPF = {"rule": "zipf", "count": 1024, "exponent": 0.99}


def _leader_mixes() -> dict:
    """name -> mix of every cell observed at a poh tile, and the
    transfers mix with Zipf destinations (the next leader cell's)."""
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    out = {}
    for w in bench["workloads"]:
        spec = harness.load_cell(w["name"])
        cfg = spec["config"]
        if next(t for t in cfg["tiles"] if cfg["observe"] in
                t.get("outs", ()))["kind"] == "poh":
            out[w["traffic"]] = spec["mix"]
    out["transfers.zipf"] = dict(out["transfers"], destinations=ZIPF)
    return out


def _program_cost(payload: bytes):
    from firedancer_tpu.pack.cost import compute_cost
    from firedancer_tpu.protocol.txn import parse_txn
    t = parse_txn(payload)
    keys = t.account_keys(payload)
    writes = [k for i, k in enumerate(keys) if t.is_writable(i)]
    return writes, compute_cost(t, payload).total


def _sign(mix: dict, n: int, seed: int = SEED):
    return traffic.sign(mix, seed, traffic.plan(mix, seed, n, 1.0),
                        procs=1)


@pytest.mark.parametrize("name", sorted(_leader_mixes()))
def test_cost_model_agrees_with_pack_on_every_leader_mix(name):
    """10,000 seeded txns of the mix: each costs what the program's pack
    charges for it, and locks for writing the keys pack's parser marks
    writable."""
    c = _sign(_leader_mixes()[name], 10_000)
    assert len(c.size) == 10_000
    for i in range(len(c.size)):
        t = c.txn(i)
        writes, _, cost = blockrules.locks_and_cost(t)
        assert (writes, cost) == _program_cost(t), i


def _legacy(sigs: int, keys: list, ro_unsigned: int, ixs: list) -> bytes:
    """A legacy txn with zeroed signatures: `sigs` writable signers
    first among `keys`, the last `ro_unsigned` read, each instruction
    (program index, account indices, data)."""
    body = b"".join(bytes((p, len(a))) + bytes(a) + bytes((len(d),)) + d
                    for p, a, d in ixs)
    msg = (bytes((sigs, 0, ro_unsigned, len(keys))) + b"".join(keys)
           + bytes(32) + bytes((len(ixs),)) + body)
    return bytes((sigs,)) + bytes(64 * sigs) + msg


def _unpriced() -> dict:
    """Txns upstream prices by terms no leader mix needs yet."""
    from firedancer_tpu.pack import cost
    a, b, c = b"\x11" * 32, b"\x22" * 32, b"\x33" * 32
    transfer = b"\x02\x00\x00\x00" + (7).to_bytes(8, "little")
    limit = b"\x02" + (300).to_bytes(4, "little")
    return {
        "no_unit_limit": _legacy(1, [a, b, cost.SYSTEM_PROGRAM_ID], 1,
                                 [(2, [0, 1], transfer)]),
        "simple_vote": _legacy(1, [a, b, cost.VOTE_PROGRAM_ID], 1,
                               [(2, [1, 0], b"\x02\x00\x00\x00")]),
        "precompile": _legacy(
            1, [a, cost.COMPUTE_BUDGET_PROGRAM_ID,
                cost.ED25519_SV_PROGRAM_ID], 2,
            [(1, [], limit), (2, [], bytes((1, 0)) + bytes(14))]),
        "other_program": _legacy(
            1, [a, b, cost.COMPUTE_BUDGET_PROGRAM_ID, c], 2,
            [(2, [], limit), (3, [1], b"memo")]),
        "versioned": b"\x01" + bytes(64) + b"\x80" + bytes(40),
    }


@pytest.mark.parametrize("kind", sorted(_unpriced()))
def test_cost_model_refuses_what_it_does_not_price(kind):
    """Each is a txn the program's pack prices (but the versioned one,
    which it cannot parse here): the model refuses it rather than give
    it another price."""
    t = _unpriced()[kind]
    if kind != "versioned":
        assert _program_cost(t)[1] > 0
    with pytest.raises(ValueError):
        blockrules.locks_and_cost(t)


@pytest.mark.parametrize("data", [
    b"\x02\x2c\x01\x00",                      # too short
    b"\x00\x2c\x01\x00\x00",                  # deprecated request
    b"\x04\x00\x00\x00\x00",                  # 0 bytes of loaded data
    b"\x01\x01\x04\x00\x00",                  # heap frame not KiB
])
def test_cost_model_refuses_a_malformed_budget(data):
    with pytest.raises(ValueError):
        blockrules._budget({}, data)
    with pytest.raises(ValueError):             # each request once
        blockrules._budget({2: 300}, b"\x02\x2c\x01\x00\x00")


# -- each rule on entries built by hand ------------------------------------

def _judge(groups):
    """groups: [(slot, [payload, ...]), ...], one record entry each, in
    order; each entry followed by a tick with no txns. -> the three
    counts, read by ledger.block_checks from entry headers and the
    entry of each txn, as the observer keeps them."""
    hdrs = np.zeros((2 * len(groups), harness.ENTRY_HDR), np.uint8)
    payloads, entry_of = [], []
    for e, (slot, txns) in enumerate(groups):
        for row in (2 * e, 2 * e + 1):
            hdrs[row, :8] = np.frombuffer(int(slot).to_bytes(8, "little"),
                                          np.uint8)
        payloads += txns
        entry_of += [2 * e] * len(txns)
    entry_of.append(-1)                         # a txn never committed
    payloads.append(b"")
    return ledger.block_checks(hdrs, np.array(entry_of), payloads.__getitem__,
                               LIMITS)


def _transfers(n: int, **mix):
    c = _sign(dict(_leader_mixes()["transfers"], **mix), n)
    return [c.txn(i) for i in range(len(c.size))]


def _reading(key: bytes) -> bytes:
    """A transfer between two fresh accounts that also reads `key` (a
    readonly unsigned account), with a compute-unit limit."""
    from firedancer_tpu.pack import cost
    return _legacy(1, [b"\x44" * 32, b"\x55" * 32, key,
                       cost.SYSTEM_PROGRAM_ID, cost.COMPUTE_BUDGET_PROGRAM_ID],
                   3, [(4, [], b"\x02" + (300).to_bytes(4, "little")),
                       (3, [0, 1, 2], b"\x02\x00\x00\x00" + bytes(8))])


def _distinct(txns):
    """Txns whose locks overlap none of another's."""
    seen, out = set(), []
    for t in txns:
        keys = set(blockrules.locks_and_cost(t)[0])
        if seen.isdisjoint(keys):
            seen |= keys
            out.append(t)
    return out


def test_block_rules_pass_a_sound_chain():
    t = _distinct(_transfers(400))
    groups = [(9, t[i:i + 31]) for i in range(0, 310, 31)]
    groups += [(10, t[310:341]), (10, [t[0], _reading(t[5][69:101])])]
    assert _judge(groups) == {"entry_conflict": 0, "block_cost_over": 0,
                              "acct_cost_over": 0}


@pytest.mark.parametrize("fault", ["same_payer", "same_destination",
                                   "read_and_write"])
def test_entry_conflict_counts_the_entry(fault):
    """One entry of six holds two txns that lock one account, one of
    them for writing; the same txns in two entries are sound."""
    t = _distinct(_transfers(300))
    zipf = _transfers(60, destinations={"rule": "zipf", "count": 1,
                                        "exponent": 1.0})
    a, b = {"same_payer": tuple(_transfers(2, signer_pool=1)),
            "same_destination": (zipf[0], zipf[1]),
            "read_and_write": (t[0], _reading(t[0][101:133]))}[fault]
    assert a != b
    groups = [(3, t[1 + 5 * i:6 + 5 * i]) for i in range(5)]
    assert _judge(groups + [(3, [a, b])])["entry_conflict"] == 1
    assert _judge(groups + [(3, [a]), (3, [b])]) == {
        "entry_conflict": 0, "block_cost_over": 0, "acct_cost_over": 0}


def test_block_cost_over_counts_the_slot():
    """35 txns of 1,401,333 units each (49.05M) in slot 4, one to an
    entry; 34 of them (47.6M) in slot 5."""
    t = _transfers(69, compute_budget={"cu_limit": 1_400_000,
                                       "loaded_data_bytes": 32768})
    assert blockrules.locks_and_cost(t[0])[2] == 1_401_333
    groups = [(4, [x]) for x in t[:35]] + [(5, [x]) for x in t[35:]]
    assert _judge(groups) == {"entry_conflict": 0, "block_cost_over": 1,
                              "acct_cost_over": 0}
    assert _judge(groups[1:])["block_cost_over"] == 0


def test_acct_cost_over_counts_the_account():
    """7,349 transfers of one payer at 1,633 units (12,000,917) in slot
    2, one to an entry; 7,348 (11,999,284) of them in slot 3."""
    t = _transfers(7_349, signer_pool=1)
    assert {x[69:101] for x in t} == {t[0][69:101]}
    groups = [(2, [x]) for x in t]
    assert _judge(groups) == {"entry_conflict": 0, "block_cost_over": 0,
                              "acct_cost_over": 1}
    assert _judge([(3, [x]) for x in t[1:]])["acct_cost_over"] == 0


def test_block_checks_group_by_the_entry_not_the_order():
    """Txns of one entry need not sit together in the corpus: the entry
    of each, not its index, makes the groups."""
    a, b = _transfers(2, signer_pool=1)
    hdrs = np.zeros((2, harness.ENTRY_HDR), np.uint8)
    assert ledger.block_checks(hdrs, np.array([0, 1]), [a, b].__getitem__,
                               LIMITS)["entry_conflict"] == 0
    assert ledger.block_checks(hdrs, np.array([1, 1]), [a, b].__getitem__,
                               LIMITS)["entry_conflict"] == 1


def test_config_states_the_limits_as_data():
    cfg = json.load(open(os.path.join(ROOT, "perfbench", "configs",
                                      "leader-1chip.json")))
    assert cfg["block_limits"]["block"] == 48_000_000
    assert cfg["block_limits"]["writable_account"] == 12_000_000
    assert "block_limits" not in cfg["assumed"]


@pytest.mark.parametrize("fault,caught", [
    ("pack_no_conflict", "entry_conflict"),
    ("pack_no_limits", "block_cost_over"),
])
def test_pack_fault_is_not_correct(fault, caught):
    """The program's pack with one block rule's test switched off, in a
    CPU rehearsal of the leader cell: the balances and the PoH record
    still agree, and only the rule the fault breaks reads above 0."""
    from test_perfbench_harness import drive
    out, _ = drive("leader.transfers.over", fault)
    got = {k: v for k, (v, _) in out["checks"].items()}
    assert out["correct"] is False
    assert got[caught] > 0, got
    assert {k for k, v in got.items() if v} == {caught}, got
