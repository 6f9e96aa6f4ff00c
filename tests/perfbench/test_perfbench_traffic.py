"""perfbench's traffic generator: the same corpus for the same seed, the
mix's shares exactly, the same work for every seed in another order,
and the reference agreeing with the corpus labels."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import reference, traffic  # noqa: E402

MIXES = os.path.join(ROOT, "perfbench", "traffic")
SEED = 2 ** 35 + 17          # above 32 bits, as the driver's are


def _mix(name):
    return traffic.load_mix(os.path.join(MIXES, name + ".json"))


def _corpus(name, seed, rate=2000, seconds=1.0):
    mix = _mix(name)
    p = traffic.plan(mix, seed, rate, seconds)
    return p, traffic.sign(mix, seed, p, procs=1)


@pytest.mark.parametrize("name", ["mainnet", "dupflood"])
def test_same_seed_same_corpus(name):
    p1, c1 = _corpus(name, SEED)
    p2, c2 = _corpus(name, SEED)
    assert np.array_equal(c1.flat, c2.flat)
    assert np.array_equal(p1.send_t, p2.send_t)
    assert np.array_equal(p1.send_txn, p2.send_txn)


@pytest.mark.parametrize("name", ["mainnet", "dupflood"])
def test_other_seed_same_work_other_order(name):
    p1, c1 = _corpus(name, SEED)
    p2, c2 = _corpus(name, SEED + 1)
    assert not np.array_equal(c1.flat[:4096], c2.flat[:4096])
    assert np.array_equal(np.sort(c1.sig_cnt), np.sort(c2.sig_cnt))
    assert np.array_equal(np.sort(c1.size), np.sort(c2.size))
    assert c1.valid.sum() == c2.valid.sum()
    assert len(p1.send_t) == len(p2.send_t)


def test_mainnet_shares():
    p, c = _corpus("mainnet", SEED, rate=5000)
    n = len(c.size)
    assert n == 5000 and len(p.send_t) == n       # no resends
    k = np.bincount(c.sig_cnt, minlength=13)
    assert k[1] == 4500 and k[2] == 400 and k[3:].sum() == 100
    assert set(np.flatnonzero(k[3:]) + 3) == set(range(3, 13))
    assert (~c.valid).sum() == 100                 # 2% forged
    assert c.size.min() >= 150 and c.size.max() <= 1232
    assert 270 <= np.median(c.size) <= 330
    assert len(np.unique(c.key)) == n              # all distinct
    assert (np.diff(p.send_t) >= 0).all() and p.send_t[-1] < 1.0


def test_dupflood_resends():
    p, c = _corpus("dupflood", SEED, rate=4000)
    n = len(c.size)
    assert len(p.send_t) == 4 * n
    assert (np.bincount(p.send_txn, minlength=n) == 4).all()
    first = np.full(n, np.inf)
    np.minimum.at(first, p.send_txn, p.send_t)
    last = np.zeros(n)
    np.maximum.at(last, p.send_txn, p.send_t)
    assert (last - first <= 2.0).all() and first.max() < 1.0


def test_reference_agrees_with_labels():
    p, c = _corpus("mainnet", SEED, rate=600)
    got = reference.reference_verdicts([c.txn(i) for i in range(len(c.size))],
                                       procs=1)
    assert np.array_equal(got, c.valid)
    assert (~got).sum() == 12


@pytest.mark.parametrize("name,digest", [
    ("mainnet",
     "6ead0b343d3ca48b311abef34b8a7ab697c4981e529c0ce2b6502e3d455b382d"),
    ("dupflood",
     "fdbdbefaf259766ed0f63c15fa00e2cf85ed702a377a7c25eb5ab7181079518b"),
])
def test_frontdoor_corpus_is_pinned(name, digest):
    """The frontdoor mixes build the same bytes, labels, schedule and
    checksums for a seed as they did before the generator learned
    transfers (mainnet's digest taken from that generator; dupflood's
    from the one that fixed each resent txn's tuple for every seed)."""
    import hashlib
    p, c = _corpus(name, SEED, rate=3000)
    h = hashlib.sha256()
    for a in (c.flat, c.valid, p.send_t, p.send_txn, c.check):
        h.update(a.tobytes())
    assert h.hexdigest() == digest


def test_resent_txn_tuples_are_the_same_for_every_seed():
    """In a mix that resends, each txn's signature count, size, forged
    flag, bad signature and resend delays form one tuple that every seed
    shares (in another order): so the copies that race a forged txn's
    verdict, and the signatures their re-verify walks, are the same work
    for every seed."""
    mix = _mix("dupflood")

    def tuples(seed):
        p = traffic.plan(mix, seed, 4000, 1.0)
        n = len(p.sig_cnt)
        first = np.full(n, np.inf)
        np.minimum.at(first, p.send_txn, p.send_t)
        sends = [[] for _ in range(n)]
        for t, i in zip(p.send_t, p.send_txn):
            sends[i].append(round(float(t - first[i]), 9))
        return sorted((int(p.sig_cnt[i]), int(p.size[i]), bool(p.forged[i]),
                       int(p.bad_sig[i]), tuple(sorted(sends[i])))
                      for i in range(n))
    a, b = tuples(SEED), tuples(SEED + 1)
    assert a == b
    assert sum(f for _, _, f, _, _ in a) == 20          # 2% of 1,000
    assert all((j >= 0) == f and j < k for k, _, f, j, _ in a)
