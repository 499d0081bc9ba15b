"""The traffic generators are fixed by their seed, and every seed gets the
same work in another order."""
from __future__ import annotations

import itertools

import numpy as np

from bench import loadgen

MIX = ([1, 4, 16, 51], [0.4, 0.3, 0.2, 0.1])


def _draw(seed):
    rng = np.random.default_rng(seed)
    order = list(itertools.islice(loadgen.member_order(8, rng), 20))
    gaps = loadgen.poisson_gaps(50, 30.0, rng)
    lengths = loadgen.exact_mix(50, *MIX, rng)
    return order, gaps, lengths


def test_same_seed_same_traffic():
    a, b = _draw(2 ** 31 + 5), _draw(2 ** 31 + 5)
    assert a[0] == b[0]
    np.testing.assert_array_equal(a[1], b[1])
    np.testing.assert_array_equal(a[2], b[2])
    c = _draw(2 ** 31 + 6)
    assert a[0] != c[0] or not np.array_equal(a[1], c[1])


def test_every_seed_gets_the_same_work():
    a, c = _draw(2 ** 33 + 1), _draw(7)
    np.testing.assert_array_equal(np.sort(a[1]), np.sort(c[1]))
    np.testing.assert_array_equal(np.sort(a[2]), np.sort(c[2]))
    assert not np.array_equal(a[2], c[2])


def test_member_order_cycles_through_the_pool():
    order = list(itertools.islice(
        loadgen.member_order(8, np.random.default_rng(1)), 24))
    for k in range(3):
        assert sorted(order[8 * k:8 * k + 8]) == list(range(8))


def test_mix_and_gaps_keep_to_their_parameters():
    lengths = loadgen.exact_mix(4000, *MIX, np.random.default_rng(0))
    assert [int(np.sum(lengths == v)) for v in MIX[0]] == [1600, 1200, 800,
                                                           400]
    odd = loadgen.exact_mix(7, *MIX, np.random.default_rng(0))
    assert len(odd) == 7 and set(odd) <= set(MIX[0])
    gaps = loadgen.poisson_gaps(4000, 20.0, np.random.default_rng(0))
    assert np.all(gaps > 0)
    assert abs(gaps.mean() - 1 / 20.0) < 0.001
    assert abs(np.median(gaps) - np.log(2) / 20.0) < 0.001
