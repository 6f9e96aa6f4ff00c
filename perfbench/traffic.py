"""The one traffic generator: a mix file of parameters -> a signed corpus
and an open-loop send schedule, the same for the same seed.

Every seed gets the same work in another order: the count of txns, the
multiset of signature counts and sizes, the forged share and the
inter-arrival gaps are fixed by the mix, the rate and the window; the
seed permutes them and draws the keys, blockhashes, filler and which
signature of a forged txn is bad. So two seeds differ in order, not in
load. In a mix that resends, a txn's copies race its first verdict, and
the racing copies of a forged txn are re-verified one signature at a
time: there each txn's (signature count, size, forged, bad signature,
resend delays) is one tuple fixed for every seed, and the seed permutes
the tuples.

Wire format (Solana legacy txn, built here and not by the program):
compact-u16 sig count | sigs | header (k, 0, 1) | compact-u16 (k+1) |
k signer keys + program id | blockhash | one instruction
(prog_idx k, accounts [0], data filler). Size = 74 + 96k + cu16(d) + d.

A mix file (traffic/<name>.json) holds:
  kind          "memo" (the default: the txns above) or "transfer"
  sig_shares    [[k, share], [[lo, hi], share], ...] (a range is uniform;
                memo only: a transfer has one signer)
  size          {median, sigma, min, max} log-normal target bytes (memo
                only: a transfer is TRANSFER_SIZE bytes)
  forged_share  share of txns with one signature bit-flipped
  resend        {copies, delay_s: [lo, hi]} extra sends of each txn
  signer_pool   distinct signing keys (a transfer's payer is one of
                them, drawn uniformly)
  destinations  transfer only: {"rule": "fresh"} (a new account per
                txn) or {"rule": "zipf", "count": N, "exponent": s}
                (account of rank r drawn with weight r**-s, r = 1..N)
  lamports      transfer only: [lo, hi], the amount drawn uniformly
  compute_budget  transfer only: {"cu_limit": c, "loaded_data_bytes": b},
                the txn's SetComputeUnitLimit and
                SetLoadedAccountsDataSizeLimit requests

A transfer is a single-signer legacy system-program transfer: accounts
(payer, destination, System program, ComputeBudget program), header
(1, 0, 2), one of RECENT_BLOCKHASHES seeded blockhashes, then three
instructions: the two compute-budget requests (prog_idx 3, no accounts,
data u8 2 | u32 c and u8 4 | u32 b) and the transfer (prog_idx 2,
accounts [0, 1], data u32 2 | u64 lamports). TRANSFER_SIZE bytes.
"""
from __future__ import annotations

import hashlib
import json
import os
from dataclasses import dataclass

import numpy as np

MTU = 1232
SIG_MAX = 12
CHUNK = 4096                      # txns per signing task (seeded per chunk)
PROGRAM_ID = hashlib.sha256(b"perfbench-memo-program").digest()
SYSTEM_PROGRAM_ID = bytes(32)
COMPUTE_BUDGET_PROGRAM_ID = bytes.fromhex(
    "0306466fe5211732ffecadba72c39be7bc8ce5bbc5f7126b2c439b3a40000000")
TRANSFER_SIZE = 263
RECENT_BLOCKHASHES = 150          # a blockhash is valid for 150 slots
MIX_KEYS = {
    "memo": ("sig_shares", "size", "forged_share", "resend",
             "signer_pool"),
    "transfer": ("forged_share", "resend", "signer_pool", "destinations",
                 "lamports", "compute_budget"),
}


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    kind = mix.get("kind", "memo")
    if kind not in MIX_KEYS:
        raise ValueError(f"{path}: no txn kind {kind!r}")
    for k in MIX_KEYS[kind]:
        if k not in mix:
            raise ValueError(f"{path}: mix lacks {k!r}")
    if kind == "transfer":
        rule = mix["destinations"].get("rule")
        if rule not in ("fresh", "zipf"):
            raise ValueError(f"{path}: no destination rule {rule!r}")
        lo, hi = mix["lamports"]
        if not 0 < lo <= hi < 1 << 63:
            raise ValueError(f"{path}: lamports {mix['lamports']}")
        cb = mix["compute_budget"]
        if not (0 < cb["cu_limit"] < 1 << 32 and
                0 < cb["loaded_data_bytes"] < 1 << 32):
            raise ValueError(f"{path}: compute_budget {cb}")
    return mix


def _cu16(v: int) -> bytes:
    out = bytearray()
    while True:
        b, v = v & 0x7F, v >> 7
        out.append(b | 0x80 if v else b)
        if not v:
            return bytes(out)


def base_size(k: int) -> int:
    """Wire size of a k-signer txn with no instruction data."""
    return 74 + 96 * k + 1


def _fixed_multiset(shares, n: int) -> np.ndarray:
    """n signature counts with exactly the mix's shares (largest
    remainder rounding), ranges filled uniformly; not yet permuted."""
    want = np.array([s for _, s in shares], float) * n
    cnt = np.floor(want).astype(int)
    for i in np.argsort(-(want - cnt), kind="stable")[:n - cnt.sum()]:
        cnt[i] += 1
    out = []
    for (k, _), c in zip(shares, cnt):
        if isinstance(k, list):
            lo, hi = k
            out.append(lo + np.arange(c) % (hi - lo + 1))
        else:
            out.append(np.full(c, k))
    return np.concatenate(out).astype(np.int8)


def _quantiles_lognormal(n: int, median: float, sigma: float):
    from statistics import NormalDist
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)]) \
        if n <= 4096 else np.interp(
            (np.arange(n) + 0.5) / n,
            (np.arange(4096) + 0.5) / 4096,
            [nd.inv_cdf((i + 0.5) / 4096) for i in range(4096)])
    return median * np.exp(sigma * z)


@dataclass
class Plan:
    """What to build (per txn) and when to send (per frame)."""
    sig_cnt: np.ndarray           # (n,) int8
    size: np.ndarray              # (n,) int32 target wire size
    forged: np.ndarray            # (n,) bool
    send_t: np.ndarray            # (frames,) float64 s from window start
    send_txn: np.ndarray          # (frames,) int64 txn index
    bad_sig: np.ndarray | None = None   # (n,) int8 forged sig index, -1
    #                               valid; None: drawn at signing


def plan(mix: dict, seed: int, rate: float, seconds: float) -> Plan:
    """The send plan: `rate` frames/s over `seconds`. Resent copies are
    frames too, so a mix with 3 resends carries rate/4 unique txns/s."""
    rng = np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, 0x7261])
    copies = 1 + int(mix["resend"].get("copies", 0))
    frames = int(round(rate * seconds))
    n = max(1, frames // copies)
    if mix.get("kind", "memo") == "transfer":
        k = np.ones(n, np.int8)
        size = np.full(n, TRANSFER_SIZE, np.int32)
    else:
        # (sig count, size) pairs are one fixed multiset for every
        # seed, paired by a seed-independent shuffle; the seed permutes
        # the pairs
        k = _fixed_multiset(mix["sig_shares"], n)
        sz = mix["size"]
        size = np.random.default_rng(0x73697a65).permutation(
            _quantiles_lognormal(n, sz["median"], sz["sigma"]))
        size = np.clip(np.rint(size), sz["min"], sz["max"])
        base = np.array([base_size(int(x)) for x in range(SIG_MAX + 1)])
        size = np.minimum(np.maximum(size, base[k]), MTU).astype(np.int32)
    order = rng.permutation(n)
    n_forged = int(round(mix["forged_share"] * n))
    forged = np.zeros(n, bool)
    bad = delays = None
    if copies > 1:
        fixed = np.random.default_rng(0x72657365)
        forged[fixed.permutation(n)[:n_forged]] = True
        bad = np.where(forged, fixed.integers(0, SIG_MAX, n)
                       % np.maximum(k, 1), -1).astype(np.int8)[order]
        lo, hi = mix["resend"]["delay_s"]
        delays = fixed.uniform(lo, hi, (copies - 1, n))[:, order]
        forged = forged[order]
    else:
        forged[rng.permutation(n)[:n_forged]] = True
    k, size = k[order], size[order]
    # open loop: exponential gaps of a fixed multiset (quantiles),
    # permuted; scaled so the n-th first send lands inside the window
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps)
    first = np.cumsum(gaps) / gaps.sum() * seconds * (n - 0.5) / n
    t = [first]
    txn = [np.arange(n)]
    for d in delays if delays is not None else ():
        t.append(first + d)
        txn.append(np.arange(n))
    t, txn = np.concatenate(t), np.concatenate(txn)
    order = np.argsort(t, kind="stable")
    return Plan(k, size, forged, t[order], txn[order], bad)


def _keys(seed: int, pool: int):
    from cryptography.hazmat.primitives.asymmetric.ed25519 import \
        Ed25519PrivateKey
    keys = [Ed25519PrivateKey.from_private_bytes(
        hashlib.sha256(b"perfbench-key-%d-%d" % (seed, i)).digest())
        for i in range(pool)]
    return keys, [k.public_key().public_bytes_raw() for k in keys]


_KEYS: dict = {}


def _sign_chunk(args):
    """Build + sign one chunk of a plan's txns (runs in a pool worker);
    every draw is made for the whole chunk at once."""
    seed, pool, lo, k, size, forged, bad_sig, transfer = args
    if (seed, pool) not in _KEYS:
        _KEYS.clear()
        _KEYS[(seed, pool)] = _keys(seed, pool)
    keys, pubs = _KEYS[(seed, pool)]
    if transfer is not None:
        return _sign_transfers(seed, keys, pubs, lo, forged, transfer)
    rng = np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, 0x6368,
                                 lo // CHUNK])
    n = len(k)
    signers = rng.integers(0, pool, (n, SIG_MAX))
    d = np.maximum(0, size.astype(np.int64) - [base_size(int(x)) for x in k])
    d -= d >= 128                          # the 2-byte compact-u16
    hashes = rng.bytes(32 * n)
    fill = rng.bytes(int(d.sum()))
    fo = np.concatenate([[0], np.cumsum(d)])
    fj = rng.integers(0, SIG_MAX, n) % np.maximum(k, 1)
    if bad_sig is not None:
        fj = bad_sig
    fbit = rng.integers(0, 512, n)
    out, bad = [], []
    for i in range(n):
        ki, di = int(k[i]), int(d[i])
        who = signers[i, :ki]
        if ki > 1 and len(set(who.tolist())) < ki:     # distinct signers
            who = rng.choice(pool, ki, replace=False)
        msg = b"".join((bytes((ki, 0, 1)), _cu16(ki + 1),
                        *(pubs[s] for s in who), PROGRAM_ID,
                        hashes[32 * i:32 * i + 32], b"\x01",
                        bytes((ki,)), b"\x01\x00", _cu16(di),
                        fill[fo[i]:fo[i] + di]))
        sigs = [keys[s].sign(msg) for s in who]
        j = -1
        if forged[i]:
            j, bit = int(fj[i]), int(fbit[i])
            sg = bytearray(sigs[j])
            sg[bit // 8] ^= 1 << (bit % 8)
            sigs[j] = bytes(sg)
        out.append(b"".join((_cu16(ki), *sigs, msg)))
        bad.append(j)
    return out, bad, checksums(out)


def _zipf_cdf(count: int, exponent: float) -> np.ndarray:
    w = np.arange(1, count + 1, dtype=np.float64) ** -float(exponent)
    return np.cumsum(w) / w.sum()


def account_key(seed: int, label: bytes, i: int) -> bytes:
    """A seeded 32-byte account address (no private key behind it)."""
    return hashlib.sha256(b"perfbench-%s-%d-%d" % (label, seed, i)).digest()


def _sign_transfers(seed, keys, pubs, lo, forged, spec):
    """Build + sign one chunk of transfers: payer uniform over the
    signer pool, destination by the mix's rule, amount uniform in the
    mix's lamports, one of RECENT_BLOCKHASHES seeded blockhashes, after
    the mix's compute-budget requests."""
    dests, (lam_lo, lam_hi), budget = spec
    n = len(forged)
    rng = np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, 0x7478,
                                 lo // CHUNK])
    payer = rng.integers(0, len(keys), n)
    amount = rng.integers(lam_lo, lam_hi + 1, n, dtype=np.uint64)
    bh = rng.integers(0, RECENT_BLOCKHASHES, n)
    fbit = rng.integers(0, 512, n)
    if dests["rule"] == "zipf":
        cdf = _zipf_cdf(int(dests["count"]), dests["exponent"])
        dst = [account_key(seed, b"acct", int(r)) for r in np.minimum(
            np.searchsorted(cdf, rng.random(n), "right"), len(cdf) - 1)]
    else:
        dst = [account_key(seed, b"dst", lo + i) for i in range(n)]
    hashes = [account_key(seed, b"blockhash", j)
              for j in range(RECENT_BLOCKHASHES)]
    cu = int(budget["cu_limit"]).to_bytes(4, "little")
    ld = int(budget["loaded_data_bytes"]).to_bytes(4, "little")
    ixs = b"\x03\x03\x00\x05\x02" + cu + b"\x03\x00\x05\x04" + ld
    out, bad = [], []
    for i in range(n):
        s = int(payer[i])
        msg = b"".join((b"\x01\x00\x02\x04", pubs[s], dst[i],
                        SYSTEM_PROGRAM_ID, COMPUTE_BUDGET_PROGRAM_ID,
                        hashes[bh[i]], ixs,
                        b"\x02\x02\x00\x01\x0c\x02\x00\x00\x00",
                        int(amount[i]).to_bytes(8, "little")))
        sig = keys[s].sign(msg)
        j = -1
        if forged[i]:
            j, bit = 0, int(fbit[i])
            sg = bytearray(sig)
            sg[bit // 8] ^= 1 << (bit % 8)
            sig = bytes(sg)
        out.append(b"\x01" + sig + msg)
        bad.append(j)
    return out, bad, checksums(out)


def payers(mix: dict, seed: int) -> list:
    """The signer pool's public keys, in pool order: the payers a
    transfer mix draws from (genesis funds each of them)."""
    return _keys(seed, int(mix["signer_pool"]))[1]


WORD_WEIGHTS = np.random.default_rng(0x5EED).integers(
    1, 1 << 62, MTU // 8, dtype=np.uint64)


def checksums(payloads) -> np.ndarray:
    """A weighted sum (mod 2**64) of each payload's 64-bit words, zero
    padded to the MTU: what the observer recomputes on every frame
    dedup delivers, to see a payload altered in flight."""
    pad = np.zeros((len(payloads), MTU), np.uint8)
    for i, t in enumerate(payloads):
        pad[i, :len(t)] = np.frombuffer(t, np.uint8)
    return (pad.view(np.uint64) * WORD_WEIGHTS).sum(axis=1, dtype=np.uint64)


@dataclass
class Corpus:
    """Signed txns, flat: txn i is flat[off[i]:off[i]+size[i]]."""
    flat: np.ndarray              # uint8
    off: np.ndarray               # int64
    size: np.ndarray              # int32
    sig_cnt: np.ndarray           # int8
    valid: np.ndarray             # bool: the label by construction
    key: np.ndarray               # uint64: first 8 bytes of sig 0
    check: np.ndarray             # uint64: checksums() of each txn

    def txn(self, i: int) -> bytes:
        o = int(self.off[i])
        return self.flat[o:o + int(self.size[i])].tobytes()


def sign(mix: dict, seed: int, p: Plan, procs: int) -> Corpus:
    """Sign every txn of a plan in a spawn pool of `procs` workers (the
    workers import only this module and OpenSSL)."""
    n = len(p.sig_cnt)
    transfer = (mix["destinations"], tuple(mix["lamports"]),
                mix["compute_budget"]) \
        if mix.get("kind", "memo") == "transfer" else None
    tasks = [(seed, int(mix["signer_pool"]), lo, p.sig_cnt[lo:lo + CHUNK],
              p.size[lo:lo + CHUNK], p.forged[lo:lo + CHUNK],
              None if p.bad_sig is None else p.bad_sig[lo:lo + CHUNK],
              transfer)
             for lo in range(0, n, CHUNK)]
    if procs > 1 and len(tasks) > 1:
        import multiprocessing as mp
        with mp.get_context("spawn").Pool(min(procs, len(tasks))) as pool:
            parts = pool.map(_sign_chunk, tasks, chunksize=1)
    else:
        parts = [_sign_chunk(t) for t in tasks]
    txns = [t for out, _, _ in parts for t in out]
    bad = np.array([b for _, bb, _ in parts for b in bb], np.int8)
    check = np.concatenate([c for _, _, c in parts])
    size = np.array([len(t) for t in txns], np.int32)
    off = np.zeros(n, np.int64)
    off[1:] = np.cumsum(size[:-1])
    flat = np.frombuffer(b"".join(txns), np.uint8)
    key = flat[off[:, None] + 1 + np.arange(8)].copy().view(
        np.uint64).ravel()
    return Corpus(flat, off, size, p.sig_cnt.copy(), bad < 0, key, check)


def default_procs() -> int:
    return max(1, min(12, (os.cpu_count() or 2) - 1))
