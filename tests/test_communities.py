import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.communities import (
    UnionFind, components_after_deletion, components_as_sets,
    connected_components, maximal_cliques, pairs_to_set, qa1, qa2,
)
from repro.core.types import PAD_ID


def union_find_components(n, edges):
    parent = list(range(n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for a, b in edges:
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[ra] = rb
    groups = {}
    for i in range(n):
        groups.setdefault(find(i), set()).add(i)
    return {frozenset(g) for g in groups.values() if len(g) >= 2}


@pytest.mark.parametrize("seed", range(60))
def test_cc_matches_union_find(seed):
    """Property test (seeded generator): connected_components on random
    edge lists must match a host union-find oracle."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 41))
    m = int(rng.integers(0, 81))
    raw = rng.integers(0, 40, size=(m, 2))
    edges = [(int(a) % n, int(b) % n) for a, b in raw if a % n != b % n]
    cap = max(len(edges), 1)
    left = np.full(cap, PAD_ID, np.int32)
    right = np.full(cap, PAD_ID, np.int32)
    for i, (a, b) in enumerate(edges):
        left[i], right[i] = a, b
    labels = connected_components(
        jnp.asarray(left), jnp.asarray(right), num_nodes=n
    )
    got = components_as_sets(np.asarray(labels))
    assert got == union_find_components(n, edges)


def _edges_to_arrays(edges, cap=None):
    cap = cap or max(len(edges), 1)
    left = np.full(cap, PAD_ID, np.int32)
    right = np.full(cap, PAD_ID, np.int32)
    for i, (a, b) in enumerate(edges):
        left[i], right[i] = a, b
    return jnp.asarray(left), jnp.asarray(right)


@pytest.mark.parametrize("seed", range(20))
def test_cc_warm_start_converges_to_cold_fixpoint(seed):
    """Incremental warm start (ISSUE 4): seeding min-label propagation with
    the stale fixpoint of any edge-prefix must converge to the exact same
    labels as a cold start over the full edge list."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(4, 30))
    m = int(rng.integers(1, 50))
    edges = [(int(a), int(b)) for a, b in rng.integers(0, n, size=(m, 2))
             if a != b]
    cut = int(rng.integers(0, len(edges) + 1))
    l1, r1 = _edges_to_arrays(edges[:cut])
    stale = connected_components(l1, r1, num_nodes=n)
    l2, r2 = _edges_to_arrays(edges)
    cold = connected_components(l2, r2, num_nodes=n)
    warm = connected_components(l2, r2, num_nodes=n, init_labels=stale)
    np.testing.assert_array_equal(np.asarray(warm), np.asarray(cold))


def test_cc_warm_start_pad_only_and_zero_edge_update():
    """PAD_ID-only edge lists and a zero-edge update: the stale labels ARE
    the fixpoint and must come back unchanged."""
    n = 7
    l0, r0 = _edges_to_arrays([(0, 3), (4, 5)])
    stale = connected_components(l0, r0, num_nodes=n)
    pad_l, pad_r = _edges_to_arrays([], cap=4)  # all PAD_ID
    again = connected_components(pad_l, pad_r, num_nodes=n,
                                 init_labels=stale)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(stale))
    # cold PAD-only with a warm seed of arange stays identity
    iden = connected_components(pad_l, pad_r, num_nodes=n,
                                init_labels=jnp.arange(n, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(iden), np.arange(n))


def test_cc_warm_start_bridge_merges_components():
    """A bridge edge arriving later must merge two previously disjoint
    components under the warm start, via the star edges of the stale
    labels (the streaming engine always feeds (label[v], v) stars)."""
    n = 6
    l0, r0 = _edges_to_arrays([(0, 1), (1, 2), (3, 4), (4, 5)])
    stale = np.asarray(connected_components(l0, r0, num_nodes=n))
    assert components_as_sets(stale) == {frozenset({0, 1, 2}),
                                         frozenset({3, 4, 5})}
    # streaming-style update: stars of the stale fixpoint + the bridge
    stars = [(int(stale[v]), v) for v in range(n)]
    l1, r1 = _edges_to_arrays(stars + [(2, 3)])
    warm = connected_components(l1, r1, num_nodes=n,
                                init_labels=jnp.asarray(stale))
    assert components_as_sets(np.asarray(warm)) == {
        frozenset(range(6))
    }
    np.testing.assert_array_equal(np.asarray(warm), np.zeros(n, np.int32))


@pytest.mark.parametrize("seed", range(20))
def test_union_find_matches_connected_components(seed):
    """The host union-find oracle (path compression + union by size) must
    produce the identical canonical labeling as the jit min-label
    propagation, for edges arriving in any micro-batch order."""
    rng = np.random.default_rng(100 + seed)
    n = int(rng.integers(2, 40))
    m = int(rng.integers(0, 70))
    edges = [(int(a), int(b)) for a, b in rng.integers(0, n, size=(m, 2))
             if a != b]
    # nodes arrive in random increments (streaming-style); an edge is
    # unioned as soon as both endpoints exist
    uf = UnionFind()
    pending = [edges[i] for i in rng.permutation(len(edges))]
    while uf.num_nodes < n:
        uf.add(int(rng.integers(1, n - uf.num_nodes + 1)))
        ready = [e for e in pending if max(e) < uf.num_nodes]
        pending = [e for e in pending if max(e) >= uf.num_nodes]
        for a, b in ready:
            uf.union(a, b)
    assert not pending
    l, r = _edges_to_arrays(edges, cap=max(len(edges), 1))
    want = np.asarray(connected_components(l, r, num_nodes=n))
    np.testing.assert_array_equal(uf.labels(), want)
    assert uf.components() == components_as_sets(want)


def test_union_find_matches_bron_kerbosch_pair_membership():
    """QA2 unchanged: every Bron-Kerbosch-side similar pair keeps both
    endpoints in one union-find component (the components are exactly the
    unions of overlapping cliques), so the recovered pair set is 100%."""
    rng = np.random.default_rng(0)
    n = 24
    edges = {(int(a), int(b)) if a < b else (int(b), int(a))
             for a, b in rng.integers(0, n, size=(60, 2)) if a != b}
    uf = UnionFind(n)
    for a, b in edges:
        uf.union(a, b)
    labels = uf.labels()
    cliques = maximal_cliques(edges)
    # each maximal clique sits inside exactly one component
    for clique in cliques:
        assert len({int(labels[v]) for v in clique}) == 1
    # pair membership via the component labeling recovers every similar
    # pair: QA2 == 1.0 exactly
    pairs_in_components = {(a, b) for a, b in edges
                           if labels[a] == labels[b]}
    assert qa2(pairs_in_components, edges) == 1.0
    # and the clique vertex set partitions into the components
    covered = {v for c in cliques for v in c}
    comp_members = {v for c in uf.components() for v in c}
    assert covered == comp_members


def test_maximal_cliques_triangle_plus_edge():
    edges = [(0, 1), (1, 2), (0, 2), (2, 3)]
    cliques = maximal_cliques(edges)
    assert cliques == {frozenset({0, 1, 2}), frozenset({2, 3})}


def test_maximal_cliques_k4():
    import itertools

    edges = list(itertools.combinations(range(4), 2))
    assert maximal_cliques(edges) == {frozenset({0, 1, 2, 3})}


def test_qa_metrics():
    cen = {frozenset({1, 2}), frozenset({3, 4, 5})}
    dis_perfect = set(cen)
    dis_half = {frozenset({1, 2})}
    assert qa1(dis_perfect, cen) == 1.0
    assert qa1(dis_half, cen) == 0.5
    p_cen = {(1, 2), (3, 4)}
    assert qa2({(1, 2)}, p_cen) == 0.5
    assert qa2(p_cen, p_cen) == 1.0
    assert qa1(set(), set()) == 1.0


def test_pairs_to_set_ignores_padding():
    left = jnp.asarray([2, PAD_ID, 5], jnp.int32)
    right = jnp.asarray([1, PAD_ID, 7], jnp.int32)
    assert pairs_to_set(left, right) == {(1, 2), (5, 7)}


# ---------------------------------------------------------------------------
# edge expiry / deletion (ISSUE 8): communities must UN-merge
# ---------------------------------------------------------------------------
def test_bridge_deletion_splits_component():
    """Deleting the bridge node of a path splits the component — the case
    no incremental label update can discover (labels only merge downward
    under edge addition)."""
    l, r = _edges_to_arrays([(0, 1), (1, 2), (2, 3), (3, 4)])
    labels = np.asarray(connected_components(l, r, num_nodes=5))
    assert components_as_sets(labels) == {frozenset(range(5))}
    got = components_after_deletion(labels, [2], [(0, 1), (3, 4)])
    assert components_as_sets(got) == {frozenset({0, 1}), frozenset({3, 4})}
    np.testing.assert_array_equal(got, [0, 0, 2, 3, 3])


@pytest.mark.parametrize("seed", range(20))
def test_components_after_deletion_matches_cold_fixpoint(seed):
    """Property test: the warm re-solve (only touched components recompute)
    must be bit-identical to a cold fixpoint over the surviving edges, and
    ``reset_from_labels`` must re-enter the incremental path losslessly."""
    rng = np.random.default_rng(300 + seed)
    n = int(rng.integers(4, 32))
    m = int(rng.integers(0, 60))
    edges = [(int(a), int(b)) for a, b in rng.integers(0, n, size=(m, 2))
             if a != b]
    dead = sorted({int(x) for x in
                   rng.integers(0, n, size=int(rng.integers(1, n // 2 + 1)))})
    surviving = [e for e in edges if e[0] not in dead and e[1] not in dead]
    l, r = _edges_to_arrays(edges, cap=max(len(edges), 1))
    labels = np.asarray(connected_components(l, r, num_nodes=n))
    got = components_after_deletion(labels, dead, surviving)
    ls, rs = _edges_to_arrays(surviving, cap=max(len(surviving), 1))
    cold = np.asarray(connected_components(ls, rs, num_nodes=n))
    np.testing.assert_array_equal(got, cold)
    # warm-start under deletion: the union-find restored from the warm
    # labels stays in lockstep with a cold union-find on future unions
    uf_warm = UnionFind()
    uf_warm.reset_from_labels(got)
    uf_cold = UnionFind(n)
    for a, b in surviving:
        uf_cold.union(a, b)
    np.testing.assert_array_equal(uf_warm.labels(), uf_cold.labels())
    extra = [(int(a), int(b)) for a, b in rng.integers(0, n, size=(8, 2))
             if a != b]
    for a, b in extra:
        uf_warm.union(a, b)
        uf_cold.union(a, b)
    np.testing.assert_array_equal(uf_warm.labels(), uf_cold.labels())


def _bridge_world():
    """Five trajectories over an 8-place alphabet: two 'A' rows, a bridge
    'B', two 'C' rows.  A and C share NO places; B overlaps both — so at a
    rho between the cross-group MSS and the bridge MSS, the similarity
    graph is exactly A1-A2, A*-B, B-C*, C1-C2: one component held together
    by B alone."""
    from repro.data import synthetic_setup

    A = [1, 2, 3, 4]
    B = [3, 4, 5, 6]
    C = [5, 6, 7, 8]
    places = np.asarray([A, A, B, C, C], np.int32)
    lengths = np.full((5,), 4, np.int32)
    _, forest = synthetic_setup(
        5, num_types=3, classes_per_type=3, num_places=12,
        min_len=4, max_len=4, seed=2,
    )
    return places, lengths, forest


def _pick_bridge_rho(places, lengths, forest):
    """Compute every pair's MSS at rho ~ 0 and place rho strictly between
    the worst cross-group pair and the weakest edge we must keep."""
    from repro.api import AnotherMeEngine, EngineConfig
    from tests.test_streaming import make_batch, score_map

    # shingle order 2: the A/B and B/C overlaps are 2-place runs
    probe = AnotherMeEngine(
        forest, EngineConfig(rho=1e-6, k=2, community_mode="components")
    ).run(make_batch(places, lengths))
    mss = {pair: v[0] for pair, v in score_map(probe).items()}
    keep = [(0, 1), (0, 2), (1, 2), (2, 3), (2, 4), (3, 4)]
    cross = [(0, 3), (0, 4), (1, 3), (1, 4)]
    lo = max((mss.get(p, 0.0) for p in cross), default=0.0)
    hi = min(mss[p] for p in keep)
    assert lo < hi, f"bridge premise violated: cross {lo} >= keep {hi}"
    return (lo + hi) / 2.0


@pytest.mark.parametrize("components_impl", ("unionfind", "jit"))
def test_engine_bridge_expiry_splits_then_reforms(components_impl):
    """Engine-level bridge property: retiring the bridge trajectory splits
    the community; re-ingesting an identical trajectory re-forms it, and
    the rebuilt world matches a fresh engine over the live rows."""
    from repro.api import EngineConfig, StreamingEngine
    from tests.test_streaming import make_batch, score_map

    places, lengths, forest = _bridge_world()
    rho = _pick_bridge_rho(places, lengths, forest)
    cfg = EngineConfig(rho=rho, k=2, community_mode="components")
    stream = StreamingEngine(forest, cfg, components_impl=components_impl)
    res = stream.update(make_batch(places, lengths))
    assert res.similar_pairs == {(0, 1), (0, 2), (1, 2), (2, 3), (2, 4),
                                 (3, 4)}
    assert res.communities == {frozenset(range(5))}
    # expire the bridge: one community must SPLIT into two
    assert stream.retire([2]) == 1
    res = stream.update(make_batch(np.zeros((0, 1), np.int32),
                                   np.zeros((0,), np.int32)))
    assert res.communities == {frozenset({0, 1}), frozenset({3, 4})}
    assert res.similar_pairs == {(0, 1), (3, 4)}
    # re-ingest an identical bridge (fresh id 5): the community re-forms
    res = stream.update(make_batch(places[2:3], lengths[2:3]))
    assert res.communities == {frozenset({0, 1, 3, 4, 5})}
    # expire-then-reinsert == fresh: identical to an engine that only ever
    # saw the surviving rows (ids translated 3->2, 4->3, 5->4)
    fresh_places = np.concatenate([places[:2], places[3:], places[2:3]])
    fresh_lengths = np.concatenate([lengths[:2], lengths[3:], lengths[2:3]])
    fresh = StreamingEngine(
        forest, cfg, components_impl=components_impl
    ).update(make_batch(fresh_places, fresh_lengths))
    trans = {0: 0, 1: 1, 3: 2, 4: 3, 5: 4}
    got_pairs = {(trans[a], trans[b]): v
                 for (a, b), v in score_map(res).items()}
    assert got_pairs == score_map(fresh)
    assert {frozenset(trans[v] for v in c) for c in res.communities} \
        == fresh.communities


def _uf_labels(n, edges):
    uf = UnionFind(n)
    for a, b in edges:
        uf.union(int(a), int(b))
    return uf.labels()


@pytest.mark.parametrize("fill", ["empty", "partial", "full"])
@pytest.mark.parametrize("seed", range(8))
def test_cc_padded_unsorted_buffer_matches_sorted_edges(seed, fill):
    """The communities edge buffer: pairs in any order, duplicates among
    them, at any slots of a fixed capacity with PAD_ID in the rest, label
    as the bare sorted edges do and as a union-find does."""
    rng = np.random.default_rng(seed)
    n, cap = int(rng.integers(2, 60)), 64
    m = {"empty": 0, "partial": int(rng.integers(1, cap)), "full": cap}[fill]
    base = rng.integers(0, n, size=(m - m // 4, 2), dtype=np.int32)
    dups = base[rng.integers(0, max(len(base), 1), size=m // 4)] \
        if len(base) else base
    edges = rng.permutation(np.concatenate([base, dups]))
    slots = rng.choice(cap, size=m, replace=False)
    left = np.full(cap, PAD_ID, np.int32)
    right = np.full(cap, PAD_ID, np.int32)
    left[slots], right[slots] = edges[:, 0], edges[:, 1]
    padded = connected_components(
        jnp.asarray(left), jnp.asarray(right), num_nodes=n)

    bare = np.unique(edges, axis=0)  # sorted rows, no duplicate
    sorted_ = connected_components(
        jnp.asarray(bare[:, 0]), jnp.asarray(bare[:, 1]), num_nodes=n)
    np.testing.assert_array_equal(np.asarray(padded), np.asarray(sorted_))
    np.testing.assert_array_equal(np.asarray(padded), _uf_labels(n, edges))


def _set_based_communities(pairs, n):
    """Components from the similar-pair set: the pairs as sorted arrays,
    as the communities stage once built them."""
    flat = np.asarray(sorted(pairs), np.int32).reshape(-1, 2)
    labels = connected_components(
        jnp.asarray(flat[:, 0]), jnp.asarray(flat[:, 1]), num_nodes=n)
    return components_as_sets(np.asarray(labels))


@pytest.mark.parametrize("path", ["whole", "subtraj"])
def test_engine_communities_from_edge_buffer_match_set_path(path):
    """CommunitiesStage over the similar pairs' edge buffer gives the
    communities the set-based path gives, with the result's types kept;
    the stats count the buffer's real edges and its slots."""
    from repro.api import AnotherMeEngine, EngineConfig
    from repro.data import synthetic_setup

    batch, forest = synthetic_setup(
        120, num_types=8, classes_per_type=4, num_places=60, seed=5)
    extra = dict(subtraj_window=5, subtraj_stride=1) \
        if path == "subtraj" else {}
    res = AnotherMeEngine(forest, EngineConfig(
        k=2, rho=1.5, community_mode="components", **extra)).run(batch)
    n = batch.num_trajectories
    assert len(res.similar_pairs) > 20
    assert all(type(a) is int and type(b) is int
               for a, b in res.similar_pairs)
    assert all(isinstance(c, frozenset) for c in res.communities)
    assert res.communities == _set_based_communities(res.similar_pairs, n)
    assert res.communities == components_as_sets(
        _uf_labels(n, res.similar_pairs))
    s = res.stats
    assert s["communities_edges"] == len(res.similar_pairs)
    cap = s["communities_edge_cap"]
    assert cap >= s["communities_edges"] and cap & (cap - 1) == 0
