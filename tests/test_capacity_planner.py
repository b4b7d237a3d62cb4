"""CapacityPlanner policy + exact sharded capacity planning under skew.

* seeded property tests for ``run_with_retry``: the no-overflow fast path,
  overflow-triggered doubling, and the doubling bound after max_retries;
* ``plan_capacities`` regression with deliberately skewed key
  distributions: the old uniform-hash bound undersized the pair-dedup
  shuffle and the shuffle-mode owner hops; the plan must now cover the
  exact per-bucket loads (computed here by brute force with the device's
  own hash functions);
* an end-to-end engine run on a skewed world in ``score_mode="shuffle"``
  that must succeed on the FIRST capacity attempt (no retry doubling).
"""
import dataclasses
import itertools

import numpy as np
import pytest

from conftest import run_subprocess
from repro.api.capacity import CapacityPlanner
from repro.api.sharded import (
    _pair_hash_np, _positive_hash_np, plan_capacities,
)
from repro.core.types import CandidatePairs, PAD_KEY


def _fake_build(true_total, calls):
    """A candidate builder whose overflow mirrors ssh_candidates': the join
    has ``true_total`` pairs; capacity below that overflows by the rest."""

    def build(capacity):
        calls.append(capacity)
        return CandidatePairs(
            left=None, right=None,
            count=min(capacity, true_total),
            overflow=max(true_total - capacity, 0),
        )

    return build


class TestRunWithRetry:
    def test_no_overflow_fast_path(self):
        rng = np.random.default_rng(0)
        planner = CapacityPlanner(max_retries=3)
        for _ in range(50):
            total = int(rng.integers(0, 1 << 16))
            cap = total + int(rng.integers(1, 1 << 10))
            calls = []
            cand, final = planner.run_with_retry(_fake_build(total, calls), cap)
            assert calls == [cap]          # exactly one build, no retries
            assert final == cap
            assert int(cand.overflow) == 0

    def test_overflow_doubles_until_it_fits(self):
        rng = np.random.default_rng(1)
        planner = CapacityPlanner(max_retries=6)
        for _ in range(100):
            total = int(rng.integers(1, 1 << 20))
            cap = int(rng.integers(1, total + 1))
            calls = []
            cand, final = planner.run_with_retry(_fake_build(total, calls), cap)
            # doublings: smallest k with cap * 2**k >= total (capped below)
            k = 0
            c = cap
            while c < total and k < planner.max_retries:
                c *= 2
                k += 1
            assert calls == [cap * 2**i for i in range(k + 1)]
            assert final == cap * 2**k
            if final >= total:
                assert int(cand.overflow) == 0
            else:   # persistent overflow is surfaced, never dropped
                assert int(cand.overflow) == total - final

    def test_doubling_bound_after_max_retries(self):
        rng = np.random.default_rng(2)
        for _ in range(50):
            retries = int(rng.integers(0, 5))
            planner = CapacityPlanner(max_retries=retries)
            cap = int(rng.integers(1, 64))
            calls = []
            cand, final = planner.run_with_retry(
                _fake_build(1 << 30, calls), cap
            )
            assert final == cap * 2**retries      # hard doubling bound
            assert len(calls) == retries + 1
            assert int(cand.overflow) > 0

    def test_initial_capacity_power_of_two_floor(self):
        planner = CapacityPlanner(slack=1.1, floor_pow2=10)
        assert planner.initial_capacity(0) == 1 << 10
        cap = planner.initial_capacity(3000)
        assert cap >= 3300 and cap & (cap - 1) == 0


def _brute_force_loads(keys, n_shards):
    """Reference per-bucket loads from first principles (itertools), using
    the device hash functions to place rows/pairs on shards."""
    n, _ = keys.shape
    local_n = -(-n // n_shards)
    by_key = {}
    for i in range(n):
        for key in keys[i]:
            if key != PAD_KEY:
                by_key.setdefault(int(key), []).append(i)
    pre = []        # (lo, hi, join_shard) incl. duplicates across keys
    for key, members in by_key.items():
        shard = int(_positive_hash_np(np.int32(key)) % n_shards)
        for a, b in itertools.combinations(members, 2):
            pre.append((min(a, b), max(a, b), shard))
    load2 = np.zeros((n_shards, n_shards), np.int64)
    for lo, hi, src in pre:
        dst = int(_pair_hash_np(np.int32(lo), np.int32(hi)) % n_shards)
        load2[src, dst] += 1
    uniq = sorted({(lo, hi) for lo, hi, _ in pre if lo != hi})
    per_dedup = np.zeros(n_shards, np.int64)
    h1 = np.zeros((n_shards, n_shards), np.int64)
    h2 = np.zeros((n_shards, n_shards), np.int64)
    per_owner_hi = np.zeros(n_shards, np.int64)
    for lo, hi in uniq:
        ded = int(_pair_hash_np(np.int32(lo), np.int32(hi)) % n_shards)
        per_dedup[ded] += 1
        h1[ded, lo // local_n] += 1
        h2[lo // local_n, hi // local_n] += 1
        per_owner_hi[hi // local_n] += 1
    return {
        "pair_route": int(load2.max()),
        "scored": int(per_dedup.max()),
        "owner_hop": int(max(h1.max(), h2.max())),
        "owner_hi": int(per_owner_hi.max()),
        "total_pre": len(pre),
    }


def _skewed_keys(n=64, s=8, hot_fraction=0.75):
    """Most rows share one hot key (a celebrity shingle); every other key
    is globally unique — the uniform-hash bound undersizes every pair stage
    here because all pre-dedup pairs come from ONE join shard."""
    keys = np.full((n, s), PAD_KEY, np.int32)
    n_hot = int(n * hot_fraction)
    keys[:n_hot, 0] = 12345
    uniq = np.arange(n * (s - 1), dtype=np.int32) * 7919 + 65537
    keys[:, 1:] = uniq.reshape(n, s - 1)
    return keys


class TestSkewedPlanning:
    N_SHARDS = 4

    def test_pair_shuffle_caps_cover_skewed_loads(self):
        keys = _skewed_keys()
        truth = _brute_force_loads(keys, self.N_SHARDS)
        plan = plan_capacities(keys, self.N_SHARDS, slack=1.1)
        assert plan.pair_route_cap >= truth["pair_route"]
        assert plan.scored_cap >= truth["scored"]
        # the old uniform-hash bound demonstrably undersized the dedup
        # shuffle for this distribution (all pairs from one join shard)
        uniform_cap3 = int(np.ceil(
            truth["total_pre"] / self.N_SHARDS**2 * 1.1 * 2)) + 64
        assert truth["pair_route"] > uniform_cap3

    def test_shuffle_mode_plans_per_owner_loads(self):
        # star skew: row 0 shares a distinct key with every other row, so
        # every deduped pair has owner(left) == shard 0
        n, n_shards = 64, self.N_SHARDS
        keys = np.full((n, n), PAD_KEY, np.int32)
        for i in range(1, n):
            keys[0, i] = i
            keys[i, 0] = i
        truth = _brute_force_loads(keys, n_shards)
        plan = plan_capacities(keys, n_shards, slack=1.1,
                               score_mode="shuffle")
        assert plan.owner_route_cap >= truth["owner_hop"]
        assert plan.scored_cap >= max(truth["scored"], truth["owner_hi"])
        # replicate-mode plans don't pay for the hops
        rep = plan_capacities(keys, n_shards, slack=1.1)
        assert rep.owner_route_cap == 0

    def test_exact_pair_limit_falls_back_to_uniform_bound(self):
        keys = _skewed_keys()
        plan = plan_capacities(keys, self.N_SHARDS, slack=1.1,
                               exact_pair_limit=1)
        assert plan.owner_route_cap == 0
        assert plan.pair_route_cap > 0 and plan.scored_cap > 0


SKEWED_ENGINE_CODE = r"""
import dataclasses
import numpy as np, jax.numpy as jnp
from repro.api import AnotherMeEngine, EngineConfig, ExecutionPlan
from repro.api.sharded import plan_capacities
from repro.core import encode_types, forest_tables, make_random_forest
from repro.core.shingling import shingles_from_types
from repro.core.types import TrajectoryBatch

rng = np.random.default_rng(11)
forest = make_random_forest(6, 3, 60, seed=5)
n, L = 48, 8
places = rng.integers(0, 60, size=(n, L)).astype(np.int32)
places[: n // 2] = places[0]     # half the world walks the same route
lengths = np.full((n,), L, np.int32)
batch = TrajectoryBatch(jnp.asarray(places), jnp.asarray(lengths),
                        jnp.arange(n, dtype=jnp.int32))

cfg = EngineConfig(rho=2.0)
single = AnotherMeEngine(forest, cfg).run(batch)
for mode in ("replicate", "shuffle"):
    eng = AnotherMeEngine(forest, cfg,
                          ExecutionPlan(n_shards=4, score_mode=mode))
    res = eng.run(batch)
    assert res.similar_pairs == single.similar_pairs, mode
    assert res.communities == single.communities, mode
    assert res.stats["join_overflow"] == 0, mode
    # first-attempt success: no stage retried, and every capacity of the
    # plan counted in the mesh is a power of two covering the exact need
    # (the host oracle's plan without slack, less its constant pad)
    assert res.stats["retries"] == 0, mode
    tables = forest_tables(forest)
    keys_np = np.asarray(shingles_from_types(
        encode_types(batch.places, tables), batch.lengths, k=3,
        num_types=forest.num_types))
    need = plan_capacities(keys_np, 4, slack=1.0, score_mode=mode)
    got = res.stats["shard_plan"]
    pads = dict(shingle_route_cap=8, local_pair_cap=64, pair_route_cap=64,
                scored_cap=64)
    if mode == "shuffle":
        assert got["n_chunks"] == 1, got
        pads["owner_route_cap"] = 64
    for key, pad in pads.items():
        cap = got[key]
        assert cap & (cap - 1) == 0, (mode, key, cap)
        assert cap >= getattr(need, key) - pad, (mode, key, cap, need)
print("OK")
"""


def test_skewed_world_shuffle_mode_first_attempt():
    out = run_subprocess(SKEWED_ENGINE_CODE, devices=4)
    assert "OK" in out
