"""The leader's guarantees: balances after a run equal a plain serial
execution of the committed txns, in commit order; and the PoH record is
one unbroken chain that keeps pace with the wall clock.

Nothing here imports the program. The executor parses each committed
txn with its own legacy-txn parser and applies it under the fee model
the program documents (svm/executor.py, pack/scheduler.py):

  fee = FEE_PER_SIGNATURE x signatures, charged once per txn, with its
  first system-program transfer instruction; a txn with none is skipped
  and charged nothing. Each transfer instruction, in order:
    balance < fee                  fee_fail: nothing changes
    fee <= balance < fee + amount  insufficient: the fee is charged
    otherwise                      ok: fee + amount leave the payer,
                                   amount reaches the destination
  An account never written reads 0 lamports.

`state_mismatch` counts the accounts, of every payer and a seeded
sample of destinations, whose balance as the program serves it
(JSON-RPC getBalance) differs from this executor's. Limit 0.

The PoH record is read from the entry headers the observer kept, in
the order they came (u64 slot | u32 tick | u32 num_hashes |
u8 has_mixin | prev 32 | hash 32 | mixin 32 | u8 flags | u16 txn_cnt;
a hash is SHA-256, a record entry's one hash is of prev | mixin, a
tick's num_hashes hashes are of prev alone). `poh_mismatch` counts the
entries that break the chain (see poh_mismatch); `ticks_early` the
ticks that run more than one slot ahead of the wall clock. Limit 0.

The block rules (blockrules.py) are judged over the committed txns,
each in the entry that first carried it, each entry in the slot its
header names (block_checks). Limit 0.
"""
from __future__ import annotations

import hashlib
import http.client
import json
import struct
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .blockrules import block_rules
from .reference import _cu16

FEE_PER_SIGNATURE = 5000          # lamports per signature (fd_pack.h)
SYSTEM_PROGRAM_ID = bytes(32)
OK, INSUFFICIENT, FEE_FAIL = 0, 1, 2
SAMPLE_DESTINATIONS = 4096
SAMPLE_TICKS = 32                 # ticks whose hashes are recomputed
RPC_THREADS = 8
B58 = "123456789ABCDEFGHJKLMNPQRSTUVWXYZabcdefghijkmnopqrstuvwxyz"


def b58encode(data: bytes) -> str:
    n = int.from_bytes(data, "big")
    out = ""
    while n:
        n, r = divmod(n, 58)
        out = B58[r] + out
    return "1" * (len(data) - len(data.lstrip(b"\0"))) + out


def parse_transfers(payload: bytes):
    """-> (signature count, [(src, dst, lamports)] of its system-program
    transfer instructions, in order), of a legacy txn."""
    k, off = _cu16(payload, 0)
    msg = payload[off + 64 * k:]
    n_acct, a = _cu16(msg, 3)
    keys = [msg[a + 32 * i:a + 32 * (i + 1)] for i in range(n_acct)]
    off = a + 32 * n_acct + 32              # past the blockhash
    n_ix, off = _cu16(msg, off)
    out = []
    for _ in range(n_ix):
        prog = msg[off]
        n_ia, off = _cu16(msg, off + 1)
        accts = msg[off:off + n_ia]
        d_len, off = _cu16(msg, off + n_ia)
        data = msg[off:off + d_len]
        off += d_len
        if keys[prog] == SYSTEM_PROGRAM_ID and len(data) == 12 \
                and data[:4] == b"\x02\x00\x00\x00" and n_ia >= 2:
            out.append((keys[accts[0]], keys[accts[1]],
                        int.from_bytes(data[4:], "little")))
    return k, out


def execute(balances: dict, payloads,
            fee_per_signature: int = FEE_PER_SIGNATURE) -> list:
    """Apply txns in order to `balances` (pubkey -> lamports), serially;
    returns each transfer instruction's outcome. (The control alone
    passes another fee.)"""
    out = []
    for p in payloads:
        sigs, transfers = parse_transfers(p)
        fee = fee_per_signature * sigs
        for src, dst, amount in transfers:
            bal = balances.get(src, 0)
            if bal < fee:
                out.append(FEE_FAIL)
            elif bal < fee + amount:
                balances[src] = bal - fee
                out.append(INSUFFICIENT)
            else:
                balances[src] = bal - fee - amount
                balances[dst] = balances.get(dst, 0) + amount
                out.append(OK)
            fee = 0
    return out


def genesis(payers, n_txn: int, lamports_hi: int) -> dict:
    """Every payer funded with what n_txn transfers at the largest
    amount would cost it, so none can fail for funds."""
    fund = n_txn * (FEE_PER_SIGNATURE + int(lamports_hi))
    return {bytes(p): fund for p in payers}


def sample_accounts(payers, offered, txn, seed: int) -> list:
    """The accounts read back after a run: every payer, then the
    destinations of a seeded sample of SAMPLE_DESTINATIONS offered txns
    (indices `offered`, payload `txn(i)`), each account once."""
    rng = np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, 0x6c6467])
    pick = np.sort(rng.permutation(len(offered))[:SAMPLE_DESTINATIONS])
    out = dict.fromkeys(payers)
    for i in offered[pick]:
        for _, dst, _ in parse_transfers(txn(int(i)))[1]:
            out.setdefault(dst)
    return list(out)


def read_balances(port: int, keys) -> dict:
    """getBalance of each key from the JSON-RPC server on localhost."""
    def one(key):
        c = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        try:
            c.request("POST", "/", json.dumps({
                "jsonrpc": "2.0", "id": 1, "method": "getBalance",
                "params": [b58encode(key)]}),
                {"Content-Type": "application/json"})
            resp = json.loads(c.getresponse().read())
        finally:
            c.close()
        return int(resp["result"]["value"])
    with ThreadPoolExecutor(RPC_THREADS) as ex:
        return dict(zip(keys, ex.map(one, keys)))


def state_mismatch(genesis_balances: dict, committed, served: dict) -> int:
    """Accounts in `served` whose balance differs from a serial
    execution of `committed` (payloads in commit order) on genesis."""
    bal = dict(genesis_balances)
    execute(bal, committed)
    return sum(1 for k, v in served.items() if bal.get(k, 0) != v)


def poh_mismatch(hdrs, hashes_per_tick: int, ticks_per_slot: int,
                 seed: int) -> int:
    """Entries (headers in the order observed) that break the record: a
    prev that is not the hash of the entry before; a record entry that
    is not one hash of prev | mixin; a tick that does not close
    hashes_per_tick hashes since the tick before it, or does not follow
    it in (slot, tick) order; and, of a seeded sample of SAMPLE_TICKS
    ticks, one whose hash is not num_hashes hashes of its prev. The
    first entry observed has no entry before it, the first tick none
    to count from."""
    bad = set()
    ticks = []
    last = since = at = None
    for i, h in enumerate(hdrs):
        h = bytes(h)
        slot, tick, nh, mixed = struct.unpack_from("<QIIB", h)
        prev, hsh, mixin = h[17:49], h[49:81], h[81:113]
        if last is not None and prev != last:
            bad.add(i)
        last = hsh
        if since is not None:
            since += nh
        if mixed:
            if nh != 1 or hashlib.sha256(prev + mixin).digest() != hsh:
                bad.add(i)
            continue
        ticks.append(i)
        if at is not None:
            nxt = (at[0] + (at[1] + 1) // ticks_per_slot,
                   (at[1] + 1) % ticks_per_slot)
            if since != hashes_per_tick or (slot, tick) != nxt:
                bad.add(i)
        since, at = 0, (slot, tick)
    rng = np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, 0x706f68])
    for i in rng.permutation(ticks)[:SAMPLE_TICKS]:
        h = bytes(hdrs[i])
        state = h[17:49]
        for _ in range(struct.unpack_from("<I", h, 12)[0]):
            state = hashlib.sha256(state).digest()
        if state != h[49:81]:
            bad.add(int(i))
    return len(bad)


def ticks_early(hdrs, times, tick_s: float, ticks_per_slot: int) -> int:
    """Ticks seen more than one slot ahead of the wall clock: the j-th
    tick after the first one observed, seen at times[j], is early where
    j - (times[j] - times[0]) / tick_s > ticks_per_slot."""
    if not len(hdrs):
        return 0
    t = np.asarray(times)[np.asarray(hdrs)[:, 16] == 0]     # the ticks
    ahead = np.arange(len(t)) - (t - t[:1]) / tick_s
    return int((ahead > ticks_per_slot).sum())


def block_checks(hdrs, entry_of, txn, limits: dict) -> dict:
    """blockrules.block_rules over the committed txns: entry_of[i] is
    the index into hdrs of the entry that first carried txn i (-1: never
    committed), txn(i) its payload, and an entry's slot the u64 its
    header starts with."""
    entry_of = np.asarray(entry_of)
    idx = np.flatnonzero(entry_of >= 0)
    idx = idx[np.argsort(entry_of[idx], kind="stable")]
    ent = entry_of[idx]
    slots = np.ascontiguousarray(np.asarray(hdrs)[:, :8]).view(
        "<u8").ravel()
    groups = np.split(idx, np.flatnonzero(np.diff(ent)) + 1) \
        if len(idx) else []
    return block_rules(((int(slots[entry_of[g[0]]]),
                         [txn(int(i)) for i in g]) for g in groups), limits)
