"""The block rules a validator replaying a leader's block holds it to,
and the plain cost model they are priced by. Nothing here imports the
program.

A txn's account locks come from its legacy message header
(num_required_signatures s, num_readonly_signed rs,
num_readonly_unsigned ru over n account keys): key i is written where
i < s - rs, or s <= i < n - ru; every other key is read.

Its cost, in cost units, is upstream's fd_pack_compute_cost
(src/disco/pack/fd_pack_cost.h) for the txns the leader mixes build:
system-program instructions after compute-budget requests that name
the compute-unit limit. Term by term:

  signatures   FD_PACK_COST_PER_SIGNATURE (720) a signature
  write locks  FD_PACK_COST_PER_WRITABLE_ACCT (300) a written key
  data         all instruction data bytes over
               FD_PACK_INV_COST_PER_INSTR_DATA_BYTE (4), rounded down
  execution    the SetComputeUnitLimit request, at most
               FD_COMPUTE_BUDGET_MAX_CU_LIMIT (1.4M)
  loaded data  FD_COMPUTE_BUDGET_HEAP_COST (8) a
               FD_COMPUTE_BUDGET_ACCOUNT_DATA_COST_PAGE_SIZE (32 KiB)
               page, rounded up, of the SetLoadedAccountsDataSizeLimit
               request, else of FD_COMPUTE_BUDGET_MAX_LOADED_DATA_SZ
               (64 MiB), the most a request takes

Compute-budget requests are fd_compute_budget_program.h's: the first
data byte names the request (1 heap frame, 2 unit limit, 3 unit price,
4 loaded data size), each at most once. A txn that repeats one, asks
for 0 bytes of loaded data or for a heap frame that is not a whole
number of KiB, or makes any other malformed request, is one the model
refuses (ValueError), as pack drops it. It also refuses a versioned
txn, a txn that names no compute-unit limit, and an instruction of any
other program: upstream prices those by terms no leader mix needs yet
(address tables, a default limit per instruction, a fixed cost for a
simple vote, precompile signatures), which a mix that sends them adds
here.

The rules (limit 0 each, counted by block_rules):
  entry_conflict   PoH entries holding two txns that lock one account
                   where at least one lock is a write (Agave's replay
                   fails such an entry, and with it the block)
  block_cost_over  slots whose committed txns cost more than the
                   config's block_limits.block
  acct_cost_over   (slot, written account) pairs whose txns cost more
                   than block_limits.writable_account
"""
from __future__ import annotations

from collections import defaultdict

from .reference import _cu16
from .traffic import COMPUTE_BUDGET_PROGRAM_ID, SYSTEM_PROGRAM_ID

COST_PER_SIGNATURE = 720
COST_PER_WRITABLE_ACCT = 300
INV_COST_PER_INSTR_DATA_BYTE = 4
MAX_CU_LIMIT = 1_400_000
HEAP_COST = 8
ACCOUNT_DATA_COST_PAGE_SIZE = 32 * 1024
MAX_LOADED_DATA_SZ = 64 * 1024 * 1024
PRICED = (SYSTEM_PROGRAM_ID, COMPUTE_BUDGET_PROGRAM_ID)


def _u16(data: bytes, off: int):
    """A compact-u16, read without a call where it is one byte."""
    return (data[off], off + 1) if data[off] < 0x80 else _cu16(data, off)


def locks_and_cost(payload: bytes):
    """-> (written keys, read keys, cost units) of a legacy txn."""
    k, off = _cu16(payload, 0)
    msg = payload[off + 64 * k:]
    if msg[0] & 0x80:
        raise ValueError("a versioned txn: the model reads legacy ones")
    s, rs, ru = msg[0], msg[1], msg[2]
    n, a = _cu16(msg, 3)
    keys = [msg[a + 32 * i:a + 32 * (i + 1)] for i in range(n)]
    writes = keys[:s - rs] + keys[s:n - ru]
    reads = keys[s - rs:s] + keys[max(s, n - ru):]
    off = a + 32 * n + 32                   # past the blockhash
    n_ix, off = _cu16(msg, off)
    data_sz, budget = 0, {}
    for _ in range(n_ix):
        prog = keys[msg[off]]
        n_ia, off = _u16(msg, off + 1)
        d_len, off = _u16(msg, off + n_ia)
        data_sz += d_len
        if prog == COMPUTE_BUDGET_PROGRAM_ID:
            _budget(budget, msg[off:off + d_len])
        elif prog not in PRICED:
            raise ValueError("an instruction of a program the model does "
                             "not price")
        off += d_len
    if 2 not in budget:
        raise ValueError("a txn that names no compute-unit limit")
    pages = -(-budget.get(4, MAX_LOADED_DATA_SZ)
              // ACCOUNT_DATA_COST_PAGE_SIZE)
    cost = (COST_PER_SIGNATURE * k + COST_PER_WRITABLE_ACCT * len(writes)
            + data_sz // INV_COST_PER_INSTR_DATA_BYTE + budget[2]
            + HEAP_COST * pages)
    return writes, reads, cost


def _budget(got: dict, data: bytes):
    """One compute-budget request into `got` (request kind -> value)."""
    kind = data[0] if data else 0
    if len(data) < (9 if kind == 3 else 5) or kind not in (1, 2, 3, 4) \
            or kind in got:
        raise ValueError(f"a malformed compute-budget request {data!r}")
    v = int.from_bytes(data[1:5], "little")
    if kind == 1 and v % 1024:
        raise ValueError("a heap frame not a whole number of KiB")
    if kind == 2:
        v = min(v, MAX_CU_LIMIT)
    elif kind == 4:
        if not v:
            raise ValueError("a request for 0 bytes of loaded data")
        v = min(v, MAX_LOADED_DATA_SZ)
    got[kind] = v


def block_rules(entries, limits: dict) -> dict:
    """entries: [(slot, [payload, ...]), ...], the PoH record entries
    that carry committed txns; limits: the config's block_limits.
    -> {"entry_conflict", "block_cost_over", "acct_cost_over"}."""
    conflicts = 0
    slot_cost = defaultdict(int)
    acct_cost = defaultdict(int)
    for slot, payloads in entries:
        wrote, read, clash = set(), set(), False
        for p in payloads:
            writes, reads, cost = locks_and_cost(p)
            clash = clash or not wrote.isdisjoint(writes)
            wrote.update(writes)
            read.update(reads)
            slot_cost[slot] += cost
            for key in writes:
                acct_cost[slot, key] += cost
        # a txn never locks one key twice, so a key read and written in
        # one entry is locked by two of its txns
        if clash or not read.isdisjoint(wrote):
            conflicts += 1
    return {
        "entry_conflict": conflicts,
        "block_cost_over": sum(c > limits["block"]
                               for c in slot_cost.values()),
        "acct_cost_over": sum(c > limits["writable_account"]
                              for c in acct_cost.values()),
    }
