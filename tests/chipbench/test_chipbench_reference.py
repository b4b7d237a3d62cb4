"""The plain reference against brute force over all pairs, on small dense
worlds where most pairs share a key and many are similar."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import data, reference  # noqa: E402

WORLD = dict(num_places=60, num_types=6, classes_per_type=2, n_levels=3,
             min_len=5, max_len=10, repeat_prob=0.15)
ENGINE = dict(k=3, rho=2.0)


def brute_levels(world):
    n = world.ids.size
    a, b = np.triu_indices(n, 1)
    return a, b, reference.level_lcs(world.codes, a, world.codes, b)


def lcs(x, y):
    dp = np.zeros((len(x) + 1, len(y) + 1), int)
    for i, u in enumerate(x):
        for j, v in enumerate(y):
            dp[i + 1, j + 1] = dp[i, j] + 1 if u == v else max(dp[i, j + 1],
                                                               dp[i + 1, j])
    return dp[-1, -1]


@pytest.fixture(scope="module")
def small():
    f = data.forest(WORLD, 3)
    tables = data.level_tables(f)
    places, lengths = data.batch(WORLD, 160, 3, data.BATCH, 0)
    ids = np.arange(1000, 1160)
    return tables, places, lengths, reference.World(tables, ENGINE, places,
                                                    lengths, ids)


def test_level_lcs_is_the_textbook_recurrence(small):
    tables, places, lengths, world = small
    a, b, lv = brute_levels(world)
    for p in range(0, a.size, 997):
        for h in range(3):
            x = world.codes[a[p], h, :lengths[a[p]]]
            y = world.codes[b[p], h, :lengths[b[p]]]
            assert lv[p, h] == lcs(x, y)


def test_similar_pairs_and_communities_equal_brute_force(small):
    tables, places, lengths, world = small
    a, b, lv = brute_levels(world)
    keep = lv.sum(axis=1) > 6
    want = {(int(world.ids[i]), int(world.ids[j]))
            for i, j in zip(a[keep], b[keep])}
    assert len(want) > 100
    assert world.similar_pairs() == want
    comms = reference.communities(want, world.ids)
    assert set().union(*comms) == {x for p in want for x in p}
    assert sum(len(c) for c in comms) == len(set().union(*comms))


def test_top_k_equals_brute_force(small):
    tables, places, lengths, world = small
    qp, ql = data.batch(WORLD, 24, 3, data.QUERY)
    qc = reference.codes(tables, qp)
    qk = reference.subsequence_keys(qc[:, 0, :], ql, 3, 6)
    got = world.top_k(qc, qk, 10)
    n = world.ids.size
    for q in range(24):
        lv = reference.level_lcs(qc, np.full(n, q), world.codes,
                                 np.arange(n)).sum(axis=1)
        order = sorted((-s, int(world.ids[r])) for r, s in enumerate(lv)
                       if s > 6)[:10]
        assert got[q] == [(i, -s) for s, i in order]


def test_the_key_filter_is_exact_only_for_rho_at_least_k_minus_one():
    assert reference.filter_is_exact({"k": 3, "rho": 2.0})
    assert not reference.filter_is_exact({"k": 3, "rho": 1.5})
    with pytest.raises(ValueError):
        reference.World(np.zeros((3, 4), np.int32), {"k": 3, "rho": 1.0},
                        np.zeros((1, 5), np.int32), np.array([5]), [0])
