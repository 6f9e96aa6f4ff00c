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
