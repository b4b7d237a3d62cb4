"""Streaming-vs-oneshot equivalence suite (ISSUE 4 acceptance).

For random worlds split into 1..k micro-batches — including singleton and
empty updates — the final scored edge set and community partition from
``StreamingEngine.update`` must be identical (as sets, and bit-identical
MSS per surviving pair) to a single ``engine.run`` over the concatenated
batch, across {ssh, minhash, brp, udf} x {score_prune on/off}.  Also pins
the delta-only contract: per-update pair generation examines strictly
fewer pairs than the full-world join would, and the per-update examined
counts sum exactly to the full-world pre-dedup join size.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import AnotherMeEngine, EngineConfig, StreamingEngine
from repro.api.capacity import CapacityPlanner
from repro.core.stream_index import BucketIndex
from repro.core.types import PAD_ID, PAD_KEY, PAD_PLACE, TrajectoryBatch
from repro.data import synthetic_setup

BACKENDS = ("ssh", "minhash", "brp", "udf")


def make_batch(places: np.ndarray, lengths: np.ndarray) -> TrajectoryBatch:
    return TrajectoryBatch(
        places=jnp.asarray(places.astype(np.int32)),
        lengths=jnp.asarray(lengths.astype(np.int32)),
        user_id=jnp.arange(places.shape[0], dtype=jnp.int32),
    )


def split_batch(batch: TrajectoryBatch, cuts) -> list[TrajectoryBatch]:
    """Split rows at ``cuts``; each piece is re-padded to its OWN max
    length so the streaming world's width has to grow across updates."""
    places = np.asarray(batch.places)
    lengths = np.asarray(batch.lengths)
    bounds = [0] + sorted(cuts) + [places.shape[0]]
    out = []
    for a, b in zip(bounds[:-1], bounds[1:]):
        p, ln = places[a:b], lengths[a:b]
        w = max(int(ln.max()), 1) if ln.size else 1
        out.append(make_batch(p[:, :w], ln))
    return out


def score_map(res):
    left = np.asarray(res.scored.left)
    right = np.asarray(res.scored.right)
    mss = np.asarray(res.scored.mss)
    lvl = np.asarray(res.scored.level_lcs)
    keep = left != PAD_ID
    return {
        (int(a), int(b)): (float(m), tuple(int(x) for x in lv))
        for a, b, m, lv in zip(left[keep], right[keep], mss[keep], lvl[keep])
    }


def random_world(seed, n=18):
    rng = np.random.default_rng(seed)
    return synthetic_setup(
        n, num_types=int(rng.integers(4, 8)), classes_per_type=3,
        num_places=int(rng.integers(20, 60)), min_len=2, max_len=8,
        seed=seed,
    )


def random_cuts(seed, n, k):
    rng = np.random.default_rng(1000 + seed)
    cuts = sorted(rng.choice(np.arange(0, n + 1), size=k - 1).tolist())
    return cuts  # duplicates / 0 / n produce EMPTY micro-batches


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("prune", (False, True))
def test_streaming_matches_oneshot(backend, prune):
    """The acceptance property, across backends x prune x random splits."""
    for seed in (0, 1, 2):
        batch, forest = random_world(seed)
        cfg = EngineConfig(
            backend=backend, rho=2.0, score_prune=prune,
            community_mode="components",
        )
        want = AnotherMeEngine(forest, cfg).run(batch)
        k = 2 + seed  # 2..4 micro-batches
        pieces = split_batch(batch, random_cuts(seed, batch.num_trajectories, k))
        stream = StreamingEngine(forest, cfg)
        examined = []
        for piece in pieces:
            res = stream.update(piece)
            examined.append(res.stats["pairs_examined"])
        cell = (backend, prune, seed)
        assert res.similar_pairs == want.similar_pairs, cell
        assert res.communities == want.communities, cell
        assert score_map(res) == score_map(want), cell
        # delta-only accounting: the per-update collisions partition the
        # full-world pre-dedup join exactly — each pair is examined in the
        # one update where its later member arrives, and never again
        full = res.stats["full_world_pairs"]
        assert sum(examined) == full, cell
        if full and sum(1 for e in examined if e) > 1:
            assert max(examined) < full, cell


def test_streaming_every_prefix_matches_oneshot():
    """Equivalence holds at EVERY update, not just the last: the result
    after update i equals one-shot over the concatenation of batches
    0..i."""
    batch, forest = random_world(7)
    cfg = EngineConfig(rho=2.0, community_mode="components")
    places = np.asarray(batch.places)
    lengths = np.asarray(batch.lengths)
    cuts = [4, 9, 9, 14]
    stream = StreamingEngine(forest, cfg)
    for piece, end in zip(split_batch(batch, cuts),
                          sorted(cuts) + [batch.num_trajectories]):
        res = stream.update(piece)
        want = AnotherMeEngine(forest, cfg).run(
            make_batch(places[:end], lengths[:end])
        )
        assert res.similar_pairs == want.similar_pairs, end
        assert res.communities == want.communities, end
        assert score_map(res) == score_map(want), end


def test_singleton_and_empty_updates():
    """Explicit degenerate splits: empty first update, singletons, empty
    mid-stream update, trailing empty update."""
    batch, forest = random_world(3, n=8)
    cfg = EngineConfig(rho=2.0)
    want = AnotherMeEngine(forest, cfg).run(batch)
    # cuts at 0 and n make empty pieces; adjacent cuts make singletons
    pieces = split_batch(batch, [0, 1, 4, 4, 7, 8])
    assert min(p.num_trajectories for p in pieces) == 0
    assert 1 in {p.num_trajectories for p in pieces}
    stream = StreamingEngine(forest, cfg)
    res = stream.update_many(pieces)
    assert res.similar_pairs == want.similar_pairs
    assert res.communities == want.communities
    assert score_map(res) == score_map(want)
    assert stream.world_size == batch.num_trajectories


def test_streaming_components_jit_matches_unionfind():
    """The two incremental community paths agree with each other and with
    the one-shot partition after every update."""
    batch, forest = random_world(11)
    cfg = EngineConfig(rho=1.5, community_mode="components")
    pieces = split_batch(batch, [5, 11])
    uf = StreamingEngine(forest, cfg, components_impl="unionfind")
    jit = StreamingEngine(forest, cfg, components_impl="jit")
    for piece in pieces:
        r_uf = uf.update(piece)
        r_jit = jit.update(piece)
        assert r_uf.communities == r_jit.communities
        # the maintained labels are interchangeable fixpoints
        np.testing.assert_array_equal(uf._labels, jit._labels)
    want = AnotherMeEngine(forest, cfg).run(batch)
    assert r_uf.communities == want.communities


def test_streaming_lcs_impls_and_cliques_bit_identical():
    """lcs_impl routes the same dispatch as the one-shot stage; cliques
    mode re-runs the Bron-Kerbosch oracle over the accumulated edges."""
    batch, forest = random_world(5)
    for impl in ("wavefront", "fused-interpret"):
        cfg = EngineConfig(rho=2.0, lcs_impl=impl)  # cliques mode default
        want = AnotherMeEngine(forest, cfg).run(batch)
        res = StreamingEngine(forest, cfg).update_many(
            split_batch(batch, [6, 12])
        )
        assert score_map(res) == score_map(want), impl
        assert res.communities == want.communities, impl


def test_streaming_validates_inputs():
    _, forest = random_world(0, n=4)
    with pytest.raises(ValueError, match="components_impl"):
        StreamingEngine(forest, components_impl="nope")
    with pytest.raises(ValueError, match="micro-batch"):
        StreamingEngine(forest).update_many([])


# ---------------------------------------------------------------------------
# the incremental pieces in isolation
# ---------------------------------------------------------------------------
def test_bucket_index_partitions_oneshot_join():
    """Union over updates == one-shot pairs; each pair exactly once; the
    examined counts sum to the full-world pre-dedup join size."""
    rng = np.random.default_rng(0)
    n, s = 30, 4
    keys = rng.integers(0, 9, size=(n, s)).astype(np.int32)
    keys[rng.random(size=(n, s)) < 0.3] = PAD_KEY
    row_keys = [set(keys[i][keys[i] != PAD_KEY].tolist()) for i in range(n)]
    want = set()
    for i in range(n):
        for j in range(i + 1, n):
            if row_keys[i] & row_keys[j]:
                want.add((i, j))
    # independent oracle for the pre-dedup join size: sum_k C(|rows(k)|, 2)
    from collections import Counter

    per_key = Counter(k for ks in row_keys for k in ks)
    oracle_full = sum(c * (c - 1) // 2 for c in per_key.values())
    for cuts in ([n], [7, 19], [1, 2, 3, 29], list(range(n + 1))):
        index = BucketIndex()
        got: set = set()
        examined_total = 0
        prev = 0
        for c in sorted(set(cuts + [n])):
            lo, hi, examined = index.insert(keys[prev:c], first_id=prev)
            examined_total += examined
            delta = set(zip(lo.tolist(), hi.tolist()))
            assert not (got & delta), "pair emitted twice"
            got |= delta
            prev = c
        assert got == want, cuts
        assert examined_total == oracle_full, cuts
        assert index.full_join_size() == oracle_full, cuts


def test_bucket_index_rejects_out_of_order_rows():
    index = BucketIndex()
    index.insert(np.full((3, 1), PAD_KEY, np.int32))
    with pytest.raises(ValueError, match="in order"):
        index.insert(np.full((2, 1), PAD_KEY, np.int32), first_id=99)


def test_capacity_planner_growth_policy():
    p = CapacityPlanner()
    # amortized doubling: unchanged while covered, then the smallest
    # power-of-two multiple of current that covers
    assert p.grow_capacity(64, 10) == 64
    assert p.grow_capacity(64, 65) == 128
    assert p.grow_capacity(64, 400) == 512
    assert p.grow_capacity(0, 1) == 1
    # update caps quantize to pow2 with a small floor
    assert p.update_capacity(0) == 16
    assert p.update_capacity(100) == 128
    caps = {p.update_capacity(k) for k in range(40, 58)}
    assert caps == {64}, "similar update sizes must share one jit cache"


def test_streaming_world_growth_and_preallocation():
    """Amortized doubling: ingesting N rows in k updates reallocates
    O(log N) times; a world_capacity hint pre-sizes the buffers."""
    batch, forest = synthetic_setup(64, num_types=6, classes_per_type=3,
                                    num_places=50, seed=0)
    pieces = split_batch(batch, list(range(4, 64, 4)))
    st = StreamingEngine(forest, EngineConfig(rho=2.0))
    caps = []
    for piece in pieces:
        st.update(piece)
        caps.append(st._cap)
    assert len(set(caps)) <= 1 + int(np.ceil(np.log2(64 / 16))) + 1
    assert caps[-1] >= 64
    pre = StreamingEngine(forest, EngineConfig(rho=2.0), world_capacity=64)
    for piece in pieces:
        pre.update(piece)
    assert pre._cap == pre._cap_floor  # never reallocated


def test_bucket_index_hot_key_warns_but_stays_exact():
    """ISSUE 5 fix: hot buckets grow unboundedly on the driver — crossing
    the per-bucket cap must WARN (once per key), never truncate: a
    pathological single-key world still completes with exact
    pairs_examined accounting."""
    n = 30
    keys = np.zeros((n, 1), np.int32)  # every row shares ONE key
    index = BucketIndex(hot_bucket_warn=8)
    examined_total = 0
    pairs: set = set()
    with pytest.warns(RuntimeWarning, match="bucket for key 0"):
        for start in range(0, n, 5):
            lo, hi, examined = index.insert(keys[start : start + 5],
                                            first_id=start)
            examined_total += examined
            pairs |= set(zip(lo.tolist(), hi.tolist()))
    assert examined_total == n * (n - 1) // 2       # exact partition
    assert index.full_join_size() == examined_total
    assert pairs == {(i, j) for i in range(n) for j in range(i + 1, n)}
    # warned exactly once for the one hot key
    assert index._warned_keys == {0}
    # default cap is high enough that ordinary worlds never warn
    import warnings as _warnings

    quiet = BucketIndex()
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        quiet.insert(keys[:20])


def test_streaming_hot_key_world_completes_exactly():
    """Engine-level regression: an all-colliding-key world with a tiny
    warn cap completes, warns, and the examined counts still partition
    the C(n, 2) full join."""
    _, forest = random_world(0, n=4)
    n, L = 12, 4
    places = np.full((n, L), 3, np.int32)
    lengths = np.full((n,), L, np.int32)
    batch = make_batch(places, lengths)
    want = AnotherMeEngine(forest, EngineConfig(rho=2.0)).run(batch)
    stream = StreamingEngine(forest, EngineConfig(rho=2.0))
    stream._index = BucketIndex(hot_bucket_warn=4)
    examined = []
    with pytest.warns(RuntimeWarning, match="delta_join"):
        for piece in split_batch(batch, [5, 9]):
            res = stream.update(piece)
            examined.append(res.stats["pairs_examined"])
    assert res.similar_pairs == want.similar_pairs
    assert res.communities == want.communities
    assert score_map(res) == score_map(want)
    assert sum(examined) == res.stats["full_world_pairs"]
