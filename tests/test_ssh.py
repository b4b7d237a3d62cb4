import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ssh import dedup_pairs, exact_pair_count, pairs_from_rows, ssh_candidates
from repro.core.types import PAD_ID, PAD_KEY


def brute_force_join(keys_2d):
    """Oracle: all unordered trajectory pairs sharing >=1 key."""
    n = keys_2d.shape[0]
    sets = [set(r[r != PAD_KEY].tolist()) for r in keys_2d]
    out = set()
    for i, j in itertools.combinations(range(n), 2):
        if sets[i] & sets[j]:
            out.add((i, j))
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_join_matches_bruteforce(seed):
    rng = np.random.default_rng(seed)
    n, s = 60, 12
    keys = rng.integers(0, 40, size=(n, s)).astype(np.int32)
    # dedup per row + pad like the shingler does
    for i in range(n):
        row = np.unique(keys[i])
        keys[i] = PAD_KEY
        keys[i, : len(row)] = row
    cand = ssh_candidates(jnp.asarray(keys), pair_capacity=1 << 14)
    got = {
        (int(a), int(b))
        for a, b in zip(np.asarray(cand.left), np.asarray(cand.right))
        if a != PAD_ID
    }
    assert int(cand.overflow) == 0
    assert got == brute_force_join(keys)
    assert int(cand.count) == len(got)


def test_exact_pair_count():
    keys = np.array([[1, 2], [1, 3], [1, 4], [5, PAD_KEY]], np.int32)
    # key 1 shared by rows 0,1,2 -> C(3,2)=3 raw pairs
    assert exact_pair_count(jnp.asarray(keys)) == 3


def test_overflow_reported_not_silent():
    keys = np.full((40, 1), 7, np.int32)  # one run of 40 -> 780 pairs
    cand = ssh_candidates(jnp.asarray(keys), pair_capacity=128)
    assert int(cand.overflow) == 780 - 128


def test_pair_dedup_scores_once():
    """Two trajectories sharing MANY shingles must appear exactly once
    (paper section IV.3: 'calculated only once')."""
    keys = np.array([[10, 11, 12, 13], [10, 11, 12, 13]], np.int32)
    cand = ssh_candidates(jnp.asarray(keys), pair_capacity=64)
    valid = np.asarray(cand.left) != PAD_ID
    assert valid.sum() == 1
    assert int(cand.count) == 1


@pytest.mark.parametrize("seed", range(50))
def test_join_property(seed):
    """Property test (seeded generator): the sort-merge join equals the
    brute-force oracle on random small key sets."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 25))
    data = [
        rng.integers(0, 9, size=rng.integers(1, 6)).tolist() for _ in range(n)
    ]
    s = 5
    keys = np.full((n, s), PAD_KEY, np.int32)
    for i, row in enumerate(data):
        u = sorted(set(row))
        keys[i, : len(u)] = u
    cand = ssh_candidates(jnp.asarray(keys), pair_capacity=1 << 12)
    got = {
        (int(a), int(b))
        for a, b in zip(np.asarray(cand.left), np.asarray(cand.right))
        if a != PAD_ID
    }
    assert got == brute_force_join(keys)


def test_dedup_pairs_idempotent_and_canonical():
    lo = jnp.asarray([5, 1, 5, PAD_ID, 2], jnp.int32)
    hi = jnp.asarray([3, 2, 3, PAD_ID, 2], jnp.int32)  # (2,2) self-pair dropped
    out = dedup_pairs(jnp.minimum(lo, hi), jnp.maximum(lo, hi))
    pairs = {
        (int(a), int(b))
        for a, b in zip(np.asarray(out.left), np.asarray(out.right))
        if a != PAD_ID
    }
    assert pairs == {(1, 2), (3, 5)}
    assert int(out.count) == 2


def row_major_pairs(keys, ids, cap):
    """Numpy model of the join's slot order: rows sorted stably by key; row
    r with in-run rank k fills the next k slots with its pairs with the
    run's members 0..k-1; the first ``cap`` slots are kept."""
    order = np.argsort(keys, kind="stable")
    keys, ids = keys[order], ids[order]
    pairs, start = [], 0
    for r in range(keys.shape[0]):
        if r and keys[r] != keys[r - 1]:
            start = r
        if keys[r] != PAD_KEY:
            pairs += [(ids[r], ids[j]) for j in range(start, r)]
    lo = np.full(cap, PAD_ID, np.int32)
    hi = np.full(cap, PAD_ID, np.int32)
    kept = np.asarray(pairs[:cap], np.int32).reshape(-1, 2)
    lo[: len(kept)] = kept.min(axis=1)
    hi[: len(kept)] = kept.max(axis=1)
    return lo, hi, max(len(pairs) - cap, 0)


def _random_rows(seed, n, s, q):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, q, size=(n, s)).astype(np.int32)
    keys[rng.random((n, s)) < 0.3] = PAD_KEY
    return keys.reshape(-1), np.repeat(np.arange(n, dtype=np.int32), s)


def _case(name):
    """(keys, ids, pair_capacity) of one named enumeration case."""
    if name == "no_valid_key":
        return np.full(24, PAD_KEY, np.int32), np.arange(24, dtype=np.int32), 16
    if name == "all_singletons":
        return np.arange(24, dtype=np.int32)[::-1].copy(), np.arange(24, dtype=np.int32), 16
    if name == "one_run":  # C(24, 2) = 276 pairs, all in one run
        return np.full(24, 5, np.int32), np.arange(24, dtype=np.int32) * 3, 512
    if name == "total_eq_capacity":
        return np.full(24, 5, np.int32), np.arange(24, dtype=np.int32), 276
    if name == "total_over_capacity":
        keys, ids = _random_rows(7, 40, 4, 6)
        return keys, ids, 64
    if name == "one_run_over_capacity":
        return np.full(24, 5, np.int32), np.arange(24, dtype=np.int32), 100
    if name == "id_offset":  # the sharded local join: global ids, PAD rows
        keys, ids = _random_rows(3, 40, 4, 12)
        ids = ids + np.int32(2**31 - 1000)
        ids[keys == PAD_KEY] = PAD_ID
        return keys, ids, 1 << 10
    kind, seed = name.split("_")
    keys, ids = _random_rows(int(seed), 40, 4, 10)
    rng = np.random.default_rng(int(seed))
    if kind == "shuffled":  # ids in no order, negative ones too
        ids = rng.permutation(ids.shape[0]).astype(np.int32) - 50
    cap = int(rng.integers(1, 1 << 9)) if kind == "cut" else 1 << 10
    return keys, ids, cap


@functools.partial(jax.jit, static_argnames="pair_capacity")
def _pairs(keys, ids, pair_capacity):
    return pairs_from_rows(keys, ids, pair_capacity=pair_capacity)


@pytest.mark.parametrize(
    "name",
    ["no_valid_key", "all_singletons", "one_run", "total_eq_capacity",
     "total_over_capacity", "one_run_over_capacity", "id_offset"]
    + [f"{kind}_{seed}" for kind in ("random", "shuffled", "cut")
       for seed in range(4)],
)
def test_pairs_from_rows_slot_for_slot(name):
    """Every slot holds the pair the row-major enumeration puts there, the
    tail past the capacity is cut, and the overflow counts it exactly."""
    keys, ids, cap = _case(name)
    lo, hi, overflow = _pairs(jnp.asarray(keys), jnp.asarray(ids), cap)
    want_lo, want_hi, want_overflow = row_major_pairs(keys, ids, cap)
    np.testing.assert_array_equal(np.asarray(lo), want_lo)
    np.testing.assert_array_equal(np.asarray(hi), want_hi)
    assert int(overflow) == want_overflow


@pytest.mark.parametrize("n", [1, 7, 1023, 1024, 1025, 5 * 1024 + 3])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("reverse", [False, True])
def test_running_scan_equals_flat_scan(n, op, reverse):
    """The two-level running scan equals jnp's flat one entry for entry,
    int32 wraparound and uint32 keys included, forwards and backwards."""
    from repro.core.ssh import running

    rng = np.random.default_rng(n)
    flat = {"sum": jnp.cumsum, "min": jax.lax.cummin,
            "max": jax.lax.cummax}[op]
    for dtype in (np.int32, np.uint32):
        info = np.iinfo(dtype)
        x = jnp.asarray(rng.integers(info.min, info.max, n, dtype=dtype))
        want = (jnp.flip(flat(jnp.flip(x))) if reverse else flat(x))
        got = running(op, x, reverse=reverse)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
