"""The one traffic generator: a mix file of parameters -> a signed corpus
and an open-loop send schedule, the same for the same seed.

Every seed gets the same work in another order: the count of txns, the
multiset of signature counts and sizes, the forged share and the
inter-arrival gaps are fixed by the mix, the rate and the window; the
seed permutes them and draws the keys, blockhashes, filler and which
signature of a forged txn is bad. So two seeds differ in order, not in
load.

Wire format (Solana legacy txn, built here and not by the program):
compact-u16 sig count | sigs | header (k, 0, 1) | compact-u16 (k+1) |
k signer keys + program id | blockhash | one instruction
(prog_idx k, accounts [0], data filler). Size = 74 + 96k + cu16(d) + d.

A mix file (traffic/<name>.json) holds:
  sig_shares    [[k, share], [[lo, hi], share], ...] (a range is uniform)
  size          {median, sigma, min, max} log-normal target bytes
  forged_share  share of txns with one signature bit-flipped
  resend        {copies, delay_s: [lo, hi]} extra sends of each txn
  signer_pool   distinct signing keys
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


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    for k in ("sig_shares", "size", "forged_share", "resend",
              "signer_pool"):
        if k not in mix:
            raise ValueError(f"{path}: mix lacks {k!r}")
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


def plan(mix: dict, seed: int, rate: float, seconds: float) -> Plan:
    """The send plan: `rate` frames/s over `seconds`. Resent copies are
    frames too, so a mix with 3 resends carries rate/4 unique txns/s."""
    rng = np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, 0x7261])
    copies = 1 + int(mix["resend"].get("copies", 0))
    frames = int(round(rate * seconds))
    n = max(1, frames // copies)
    # (sig count, size) pairs are one fixed multiset for every seed,
    # paired by a seed-independent shuffle; the seed permutes the pairs
    k = _fixed_multiset(mix["sig_shares"], n)
    sz = mix["size"]
    size = np.random.default_rng(0x73697a65).permutation(
        _quantiles_lognormal(n, sz["median"], sz["sigma"]))
    size = np.clip(np.rint(size), sz["min"], sz["max"])
    base = np.array([base_size(int(x)) for x in range(SIG_MAX + 1)])
    size = np.minimum(np.maximum(size, base[k]), MTU).astype(np.int32)
    order = rng.permutation(n)
    k, size = k[order], size[order]
    forged = np.zeros(n, bool)
    forged[rng.permutation(n)[:int(round(mix["forged_share"] * n))]] = \
        True
    # open loop: exponential gaps of a fixed multiset (quantiles),
    # permuted; scaled so the n-th first send lands inside the window
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps)
    first = np.cumsum(gaps) / gaps.sum() * seconds * (n - 0.5) / n
    t = [first]
    txn = [np.arange(n)]
    if copies > 1:
        lo, hi = mix["resend"]["delay_s"]
        for _ in range(copies - 1):
            t.append(first + rng.uniform(lo, hi, n))
            txn.append(np.arange(n))
    t, txn = np.concatenate(t), np.concatenate(txn)
    order = np.argsort(t, kind="stable")
    return Plan(k, size, forged, t[order], txn[order])


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
    seed, pool, lo, k, size, forged = args
    if (seed, pool) not in _KEYS:
        _KEYS.clear()
        _KEYS[(seed, pool)] = _keys(seed, pool)
    keys, pubs = _KEYS[(seed, pool)]
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
    tasks = [(seed, int(mix["signer_pool"]), lo, p.sig_cnt[lo:lo + CHUNK],
              p.size[lo:lo + CHUNK], p.forged[lo:lo + CHUNK])
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
