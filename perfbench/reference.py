"""The plain reference and the comparison that decides `correct`.

Nothing here imports the program. The reference parses each txn with
its own minimal Solana legacy-txn parser and verifies every signature
over the message with OpenSSL's Ed25519 (the `cryptography` package).
OpenSSL agrees with the strict RFC 8032 verifier that chip_smoke.py
uses as its oracle (utils/ed25519_ref.py, copied to ed25519_strict.py
here) on every input the mixes make: valid signatures and single bit
flips of them, with no small-order key, small-order R or non-canonical
S by construction. tests/perfbench pins that agreement on chip_smoke's
own mixed vector classes, and shows where the two part (small order).

The comparison is exact. For every txn the harness offered:
  wrong_accept   delivered, but a signature fails (by its label, and by
                 the reference on the txns it re-verified)
  missing        valid, offered, and never delivered (the harness waits
                 up to a minute after the window for it)
  double         delivered more than once (dedup's exactly-once)
  altered        delivered bytes differ from the bytes offered
  unknown        delivered frames that match no offered txn
  label_disagree the reference disagrees with the corpus label, on the
                 txns it re-verified (every forged txn offered plus a
                 seeded sample of the valid ones)
Each has the limit 0.
"""
from __future__ import annotations

import numpy as np

LIMITS = {"wrong_accept": 0, "missing": 0, "double": 0, "altered": 0,
          "unknown": 0, "label_disagree": 0}
SAMPLE_VALID = 40_000       # valid txns OpenSSL re-verifies per run
SAMPLE_STRICT = 96          # of those, re-verified by the strict copy too


def _cu16(data: bytes, off: int):
    v = 0
    for i in range(3):
        b = data[off]
        off += 1
        v |= (b & 0x7F) << (7 * i)
        if not b & 0x80:
            return v, off
    raise ValueError("compact-u16 too long")


def parse(payload: bytes):
    """-> (signatures, signer pubkeys, message) of a legacy txn; raises
    ValueError on a shape the mixes never make."""
    k, off = _cu16(payload, 0)
    if not 1 <= k <= 12 or len(payload) > 1232:
        raise ValueError("bad signature count or size")
    sigs = [payload[off + 64 * i:off + 64 * (i + 1)] for i in range(k)]
    msg = payload[off + 64 * k:]
    if not msg or msg[0] != k:
        raise ValueError("not a legacy message signed by its k keys")
    n_acct, a = _cu16(msg, 3)
    if n_acct < k or a + 32 * n_acct + 32 > len(msg):
        raise ValueError("truncated accounts")
    pubs = [msg[a + 32 * i:a + 32 * (i + 1)] for i in range(k)]
    return sigs, pubs, msg


def verify_txn_strict(payload: bytes) -> bool:
    """The same verdict by the pure-python strict verifier (~10 ms a
    signature: a few dozen txns per run)."""
    from .ed25519_strict import verify
    try:
        sigs, pubs, msg = parse(payload)
    except (ValueError, IndexError):
        return False
    return all(verify(s, p, msg) for s, p in zip(sigs, pubs))


def verify_txn(payload: bytes) -> bool:
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import \
        Ed25519PublicKey
    try:
        sigs, pubs, msg = parse(payload)
    except (ValueError, IndexError):
        return False
    for s, p in zip(sigs, pubs):
        try:
            Ed25519PublicKey.from_public_bytes(p).verify(s, msg)
        except (InvalidSignature, ValueError):
            return False
    return True


def _verify_chunk(payloads):
    return [verify_txn(p) for p in payloads]


def reference_verdicts(payloads: list, procs: int) -> np.ndarray:
    """OpenSSL verdicts over a spawn pool (the workers import only this
    module and OpenSSL); the first SAMPLE_STRICT payloads also by the
    strict copy, and a disagreement there reads as a failing verdict
    against the label (label_disagree)."""
    strict = [verify_txn_strict(p) for p in payloads[:SAMPLE_STRICT]]
    if procs <= 1 or len(payloads) < 4096:
        ok = np.array(_verify_chunk(payloads), bool)
    else:
        import multiprocessing as mp
        step = -(-len(payloads) // (procs * 4))
        chunks = [payloads[i:i + step]
                  for i in range(0, len(payloads), step)]
        with mp.get_context("spawn").Pool(procs) as pool:
            out = pool.map(_verify_chunk, chunks)
        ok = np.array([v for c in out for v in c], bool)
    k = len(strict)
    ok[:k] &= np.array(strict, bool) == ok[:k]
    return ok


def sample(valid: np.ndarray, offered: np.ndarray, seed: int) -> np.ndarray:
    """Indices the reference re-verifies, in a seeded order (the first
    SAMPLE_STRICT go to the strict copy too): every offered forged txn,
    and a seeded sample of the offered valid ones."""
    rng = np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, 0x726566])
    idx = np.flatnonzero(offered)
    bad, good = idx[~valid[idx]], idx[valid[idx]]
    if len(good) > SAMPLE_VALID:
        good = rng.choice(good, SAMPLE_VALID, replace=False)
    return rng.permutation(np.concatenate([bad, good]))


def judge(valid: np.ndarray, offered: np.ndarray, deliveries: np.ndarray,
          altered: int, unknown: int, ref_idx: np.ndarray,
          ref_ok: np.ndarray) -> dict:
    """The numbers compared, each beside its limit: {name: [n, limit]}.
    `valid` is the corpus label, `offered` marks txns published into
    the ingest ring, `deliveries` counts how often each came out."""
    got = deliveries > 0
    nums = {
        "wrong_accept": int((got & ~valid).sum()
                            + (got[ref_idx] & ~ref_ok
                               & valid[ref_idx]).sum()),
        "missing": int((offered & valid & ~got).sum()),
        "double": int((deliveries > 1).sum()),
        "altered": int(altered),
        "unknown": int(unknown),
        "label_disagree": int((ref_ok != valid[ref_idx]).sum()),
    }
    return {k: [v, LIMITS[k]] for k, v in nums.items()}


def correct(checks: dict) -> bool:
    return all(v <= lim for v, lim in checks.values())
