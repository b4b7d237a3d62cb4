"""The one-shot job on the mesh, planned from counts made in the mesh.

* back-to-back shuffle-mode jobs of one size equal the single-device
  engine and the benchmark's plain reference, and the second compiles
  nothing (its communities program runs over the sticky similar buffer
  the first compiled for);
* the counts the staged programs make (key loads of shuffle 1, each
  shard's pre-dedup join size, the dedup shuffle's loads, the deduped
  pairs and the owner-hop loads) equal the host-numpy oracle's, on uniform
  keys and on one hot key;
* capacities planned too small retry to exact results.

Each runs in a subprocess over four virtual CPU devices.
"""
from pathlib import Path

import pytest

from conftest import run_subprocess

ROOT = Path(__file__).resolve().parents[1]

JOBS_CODE = r"""
import sys
import numpy as np
sys.path.insert(0, %(root)r)
from chipbench import data, reference
from repro.api import AnotherMeEngine, EngineConfig, ExecutionPlan
from repro.core.encoding import SemanticForest
from repro.core.types import TrajectoryBatch
import jax.numpy as jnp

WORLD = dict(num_places=400, num_types=12, classes_per_type=3, n_levels=3,
             min_len=5, max_len=10, repeat_prob=0.15)
ENGINE = dict(k=3, rho=2.0, backend="ssh", lcs_impl="fused",
              community_mode="components")
f = data.forest(WORLD, 21)
forest = SemanticForest(parents=tuple(f["parents"]), sizes=tuple(f["sizes"]))
tables = data.level_tables(f)
single = AnotherMeEngine(forest, EngineConfig(**ENGINE))
mesh = AnotherMeEngine(forest, EngineConfig(**ENGINE),
                       ExecutionPlan(n_shards=4, score_mode="shuffle"))
for j in range(2):
    places, lengths = data.batch(WORLD, 1000, 21, data.BATCH, j)
    batch = TrajectoryBatch(jnp.asarray(places), jnp.asarray(lengths),
                            jnp.arange(1000, dtype=jnp.int32))
    got, want = mesh.run(batch), single.run(batch)
    ref = reference.World(tables, ENGINE, places, lengths, np.arange(1000))
    ref_pairs = ref.similar_pairs()
    assert len(ref_pairs) > 1000, len(ref_pairs)
    assert got.similar_pairs == want.similar_pairs == ref_pairs, j
    assert got.communities == want.communities, j
    assert got.communities == reference.communities(ref_pairs, ref.ids), j
    assert got.stats["retries"] == 0 and got.stats["join_overflow"] == 0
    phases = set(got.stats["compiles_by_phase"])
    if j == 0:
        assert {"plan", "join", "score"} <= phases, phases
    else:
        assert got.stats["compiles_by_phase"] == {}, phases
    assert got.stats["communities_edges"] == len(got.similar_pairs)
    assert got.stats["communities_edge_cap"] == \
        4 * got.stats["shard_plan"]["similar_cap"]
    scored = got.stats["scored_per_chip"]
    assert sum(scored) == got.stats["num_candidates"], scored
    assert got.stats["n_shards"] == 4
    ex = got.stats["exchange"]
    assert set(ex) == {"key_route", "dedup_route", "owner_hop1",
                       "owner_hop2"}, ex
    assert all(0 < rows <= slots for rows, slots in ex.values()), ex
print("OK")
"""

COUNTS_CODE = r"""
import numpy as np
import jax, jax.numpy as jnp
from repro.api import AnotherMeEngine, EngineConfig, ExecutionPlan
from repro.api.backends import BackendContext, get_backend
from repro.api.sharded import (
    MeshStages, _pair_hash_np, _positive_hash_np, pad_to_shards,
    plan_capacities, unpack_stats,
)
from repro.core import compat, default_betas, encode_types, forest_tables
from repro.core import make_random_forest
from repro.core.shingling import shingles_from_types
from repro.core.types import PAD_KEY, TrajectoryBatch

n, N, L = 4, 400, 8
forest = make_random_forest(8, 3, 80, seed=5)
tables = forest_tables(forest)
ctx = BackendContext(k=3, num_types=forest.num_types)
mesh = compat.make_mesh((n,), ("ex",))


def world(hot):
    rng = np.random.default_rng(7)
    places = rng.integers(0, 80, size=(N, L)).astype(np.int32)
    if hot:
        places[: N // 2] = places[0]   # one route walked by half the world
    lengths = rng.integers(5, L + 1, size=N).astype(np.int32)
    places[np.arange(L)[None, :] >= lengths[:, None]] = -1
    return places, lengths


def truth(keys):
    # per-(source, destination) key loads, pre-dedup pairs per join
    # shard, dedup-shuffle loads, deduped pairs and owner-hop loads
    local_n = N // n
    rows, cols = np.nonzero(keys != PAD_KEY)
    k, ids = keys[rows, cols], rows
    dst = _positive_hash_np(k) % n
    load1 = np.zeros((n, n), np.int64)
    np.add.at(load1, (ids // local_n, dst), 1)
    pre = np.zeros(n, np.int64)
    load2 = np.zeros((n, n), np.int64)
    pairs = set()
    for key in np.unique(k):
        members = ids[k == key]
        d = _positive_hash_np(np.int32(key)) % n
        m = len(members)
        pre[d] += m * (m - 1) // 2
        for i in range(m):
            for j in range(i):
                lo, hi = sorted((members[i], members[j]))
                load2[d, _pair_hash_np(np.int32(lo), np.int32(hi)) % n] += 1
                if lo != hi:
                    pairs.add((lo, hi))
    count = np.zeros(n, np.int64)
    owner = np.zeros((n, n, n), np.int64)
    for lo, hi in pairs:
        h = _pair_hash_np(np.int32(lo), np.int32(hi))
        x, y = (hi, lo) if (h >> 16) & 1 else (lo, hi)
        count[h % n] += 1
        owner[h % n, x // local_n, y // local_n] += 1
    return load1, pre, load2, count, owner


for hot in (False, True):
    places, lengths = world(hot)
    keys = np.asarray(shingles_from_types(
        encode_types(jnp.asarray(places), tables), jnp.asarray(lengths),
        k=3, num_types=forest.num_types))
    load1, pre, load2, count, owner = truth(keys)
    stages = MeshStages(
        mesh, n_shards=n, local_n=N // n, betas=default_betas(3),
        key_fn=get_backend("ssh").shard_key_fn(ctx), axis_name="ex",
        score_mode="shuffle", lcs_impl="wavefront", score_prune=False,
        prune_tau=2.0, subtraj=None)
    args = (jnp.asarray(places), jnp.asarray(places), jnp.asarray(lengths),
            tables)
    got1 = np.asarray(stages.loads()(*args)[0]).reshape(n, n)
    pow2 = lambda x: 1 << int(x).bit_length()
    rk, rid, st = stages.route(pow2(load1.max()))(*args)
    route = unpack_stats(st, stages.layout("route"))
    _, _, st = stages.join(pow2(pre.max()), pow2(load2.max()))(
        rk, rid, jnp.asarray(lengths))
    join = unpack_stats(st, stages.layout("join"))
    assert np.array_equal(got1, load1), hot
    assert np.array_equal(route["loads"], load1), hot
    assert np.array_equal(route["pair_total"][:, 0], pre), hot
    assert route["overflow"].sum() == 0 and join["overflow"].sum() == 0
    assert np.array_equal(join["route_loads"], load2), hot
    assert np.array_equal(join["count"][:, 0], count), hot
    assert np.array_equal(join["owner_loads"].reshape(n, n, n), owner), hot
    # the same maxima as the host oracle's plan without slack
    oracle = plan_capacities(keys, n, slack=1.0, score_mode="shuffle")
    assert oracle.shingle_route_cap - 8 == load1.max(), hot
    assert oracle.local_pair_cap - 64 == pre.max(), hot
    assert oracle.pair_route_cap - 64 == load2.max(), hot
    hop = max(owner.sum(axis=2).max(), owner.sum(axis=0).max())
    assert oracle.owner_route_cap - 64 == hop, hot
    rest = owner.sum(axis=(0, 1)).max()
    assert oracle.scored_cap - 64 == max(count.max(), rest), hot

    # capacities planned at half the need: every stage retries, exact
    batch = TrajectoryBatch(jnp.asarray(places), jnp.asarray(lengths),
                            jnp.arange(N, dtype=jnp.int32))
    cfg = EngineConfig(rho=2.0, community_mode="components")
    want = AnotherMeEngine(forest, cfg).run(batch)
    tight = AnotherMeEngine(forest, cfg, ExecutionPlan(
        n_shards=n, score_mode="shuffle", shard_slack=0.5)).run(batch)
    assert tight.stats["retries"] > 0, hot
    assert tight.stats["join_overflow"] == 0, hot
    assert tight.similar_pairs == want.similar_pairs, hot
    assert tight.communities == want.communities, hot
print("OK")
"""


def test_back_to_back_mesh_jobs_are_exact_and_compile_once():
    out = run_subprocess(JOBS_CODE % {"root": str(ROOT)}, devices=4)
    assert "OK" in out


def test_in_mesh_counts_equal_the_host_oracle_and_retry_to_exact():
    out = run_subprocess(COUNTS_CODE, devices=4)
    assert "OK" in out


@pytest.mark.parametrize("mode", ["span", "positions", "stable"])
def test_bucket_keeps_each_destinations_rows(mode, monkeypatch):
    """``_bucket`` in each of its sort forms: bucket ``d`` holds the first
    ``capacity`` rows bound for ``d`` (in their order, or by their first
    value with ``span``), then pads; counts and overflow are exact; with
    ``positions`` each row carries its input index."""
    import jax.numpy as jnp
    import numpy as np

    from repro.api import sharded

    if mode == "stable":  # the fallback when no packed key fits an int32
        monkeypatch.setattr(sharded, "_KEY_LIMIT", 0)
    n, cap, r = 3, 6, 40
    rng = np.random.default_rng(5)
    first = rng.permutation(100)[:r].astype(np.int32)
    second = rng.integers(0, 1000, r).astype(np.int32)
    dest = rng.integers(0, n, r).astype(np.int32)
    valid = rng.random(r) < 0.8
    kw = dict(n_shards=n, capacity=cap, pads=(-7, -8))
    if mode == "span":
        kw["span"] = 100
    else:
        kw["positions"] = True
    out, overflow, counts, kept = sharded._bucket(
        (jnp.asarray(first), jnp.asarray(second)), jnp.asarray(dest),
        jnp.asarray(valid), **kw)
    out = [np.asarray(x).reshape(n, cap) for x in out]
    for d in range(n):
        rows = np.nonzero(valid & (dest == d))[0]
        if mode == "span":
            rows = rows[np.argsort(first[rows], kind="stable")]
        rows = rows[:cap]
        m = rows.size
        np.testing.assert_array_equal(out[0][d, :m], first[rows])
        np.testing.assert_array_equal(out[1][d, :m], second[rows])
        assert (out[0][d, m:] == -7).all() and (out[1][d, m:] == -8).all()
        if mode != "span":
            np.testing.assert_array_equal(out[2][d, :m], rows)
            assert (out[2][d, m:] == -1).all()
    want = np.bincount(dest[valid], minlength=n)
    np.testing.assert_array_equal(np.asarray(counts), want)
    assert int(overflow) == int(np.maximum(want - cap, 0).sum())
    assert int(kept) == int(np.minimum(want, cap).sum())
