"""Subtrajectory "another me" (ISSUE 10 tentpole): windowed candidates
with (traj, offset) coordinates, pinned bit-identical to a numpy
brute-force windowed oracle.

* ``EngineConfig(subtraj_window=W, subtraj_stride=s)`` turns every
  backend's join into a join over sliding windows; the engine's scored
  output (max-over-windows per trajectory pair, deterministic tie-break)
  must EQUAL the oracle restricted to that backend's candidate window
  pairs — bit-identical level_lcs AND mss — for all of
  {ssh, minhash, brp, udf}.
* For the lossless backends (ssh/udf) with ``rho >= (k-1) * sum(betas)``
  the similar set must equal the TRUE oracle's (any window pair above rho
  has type-LCS >= k, hence shares a shingle, hence is a candidate).
* ``W >= L`` degenerates to the whole-trajectory engine bit-exactly;
  ``stride > 1`` restricts the oracle's offsets and still matches.
* Windowed scoring (``score_indexed(window=...)``, ``fused_windowed_score``)
  matches the numpy DP / the jnp reference exactly for every impl family.
* The capacity planners accept window-id coordinates
  (``windows_per_row``) with per-TRAJECTORY shard ownership.
* ``StreamingEngine`` rejects subtrajectory mode loudly (a growing world
  max-length would re-number resident window ids).

The sharded {2, 4, 8} x {replicate, shuffle} x backend sweep lives in
``test_api_parity_matrix.py::test_subtraj_parity_matrix`` (slow).
"""
import numpy as np
import pytest

from repro.api import AnotherMeEngine, EngineConfig, StreamingEngine
from repro.api.backends import BackendContext, get_backend
from repro.core.encoding import encode_codes
from repro.core.subtraj import (
    aggregate_window_pairs, num_windows, window_lengths,
)
from repro.core.types import PAD_ID
from repro.data import synthetic_setup

BACKENDS = ("ssh", "minhash", "brp", "udf")
W, STRIDE, K = 5, 1, 2


# ---------------------------------------------------------------------------
# numpy oracle
# ---------------------------------------------------------------------------

def lcs_np(a, b):
    la, lb = len(a), len(b)
    dp = np.zeros((la + 1, lb + 1), np.int32)
    for i in range(la):
        for j in range(lb):
            dp[i + 1, j + 1] = (
                dp[i, j] + 1 if a[i] == b[j] else max(dp[i, j + 1], dp[i + 1, j])
            )
    return int(dp[la, lb])


@pytest.fixture(scope="module")
def world():
    batch, forest = synthetic_setup(
        28, num_types=8, classes_per_type=4, num_places=60, seed=3
    )
    eng = AnotherMeEngine(forest, EngineConfig(k=K))
    codes = np.asarray(encode_codes(batch.places, eng.tables))
    lengths = np.asarray(batch.lengths)
    betas = np.asarray(eng.betas, np.float32)
    return batch, forest, codes, lengths, betas


@pytest.fixture(scope="module")
def oracle_table(world):
    """Every window pair's exact (level_lcs, mss): the brute-force oracle.

    Keyed (a, b, ja, jb) over trajectories a < b and window indices; the
    per-backend tests restrict it to candidate window pairs, the
    completeness test maxes it over everything.
    """
    _, _, codes, lengths, betas = world
    N, H, L = codes.shape
    Weff = min(W, L)
    nw = num_windows(L, W, STRIDE)
    table = {}
    for a in range(N):
        for b in range(a + 1, N):
            for ja in range(nw):
                oa = ja * STRIDE
                wla = max(0, min(int(lengths[a]) - oa, Weff))
                for jb in range(nw):
                    ob = jb * STRIDE
                    wlb = max(0, min(int(lengths[b]) - ob, Weff))
                    lvl = tuple(
                        lcs_np(codes[a, h, oa:oa + wla], codes[b, h, ob:ob + wlb])
                        for h in range(H)
                    )
                    mss = np.float32(np.sum(
                        betas * np.asarray(lvl, np.float32), dtype=np.float32
                    ))
                    table[(a, b, ja, jb)] = (lvl, mss)
    return table, nw


def oracle_max(table, nw, candidate=None):
    """Max-over-windows per trajectory pair with the engine's tie-break:
    highest mss, then smallest (window_lo_id, window_hi_id)."""
    best = {}
    for (a, b, ja, jb), (lvl, mss) in table.items():
        if candidate is not None and not candidate(a, b, ja, jb):
            continue
        key = (a * nw + ja, b * nw + jb)
        cur = best.get((a, b))
        if cur is None or mss > cur[1] or (mss == cur[1] and key < cur[2]):
            best[(a, b)] = (lvl, mss, key)
    return {p: (lvl, mss) for p, (lvl, mss, _) in best.items()}


def score_map(res):
    sc = res.scored
    cnt = int(sc.count)
    left = np.asarray(sc.left)[:cnt]
    right = np.asarray(sc.right)[:cnt]
    mss = np.asarray(sc.mss)[:cnt]
    lvl = np.asarray(sc.level_lcs)[:cnt]
    return {
        (int(a), int(b)): (tuple(int(x) for x in lv), np.float32(m))
        for a, b, m, lv in zip(left, right, mss, lvl)
    }


def backend_candidate_fn(backend, codes, lengths, forest, nw):
    """Candidate predicate from the backend's OWN windowed join keys:
    window pair (a*nw+ja, b*nw+jb) is a candidate iff the key rows share
    any non-PAD key — exactly the engine's sort-merge join."""
    import jax.numpy as jnp

    from repro.core.types import PAD_KEY

    ctx = BackendContext(
        k=K, num_types=forest.num_types, window=W, stride=STRIDE,
    )
    from types import SimpleNamespace

    enc = SimpleNamespace(
        codes=jnp.asarray(codes), lengths=jnp.asarray(lengths)
    )
    keys = np.asarray(
        get_backend(backend).join_keys(enc, None, ctx)
    )  # [N*nw, S]
    key_sets = [set(row[row != PAD_KEY].tolist()) for row in keys]

    def candidate(a, b, ja, jb):
        return bool(key_sets[a * nw + ja] & key_sets[b * nw + jb])

    return candidate


# ---------------------------------------------------------------------------
# engine vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_engine_matches_windowed_oracle(world, oracle_table, backend):
    """Scored output == the oracle restricted to the backend's candidate
    window pairs: same pair set, bit-identical level_lcs and mss."""
    batch, forest, codes, lengths, betas = world
    table, nw = oracle_table
    rho = float((K - 1) * betas.sum()) + 0.05
    res = AnotherMeEngine(forest, EngineConfig(
        backend=backend, k=K, rho=rho,
        subtraj_window=W, subtraj_stride=STRIDE,
    )).run(batch)
    cand = backend_candidate_fn(backend, codes, lengths, forest, nw)
    want = oracle_max(table, nw, candidate=cand)
    assert score_map(res) == want, backend
    want_sim = {p for p, (_, m) in want.items() if m > np.float32(rho)}
    assert res.similar_pairs == want_sim, backend


@pytest.mark.parametrize("backend", ("ssh", "udf"))
def test_lossless_backends_complete_vs_true_oracle(world, oracle_table,
                                                   backend):
    """rho >= (k-1)*sum(betas) makes the shingle join COMPLETE on the
    similar set: any window pair above rho has type-level LCS >= k, so it
    shares a k-shingle and must be a candidate — the engine's similar set
    equals the UNRESTRICTED oracle's."""
    batch, forest, _, _, betas = world
    table, nw = oracle_table
    rho = float((K - 1) * betas.sum()) + 0.05
    res = AnotherMeEngine(forest, EngineConfig(
        backend=backend, k=K, rho=rho,
        subtraj_window=W, subtraj_stride=STRIDE,
    )).run(batch)
    true_max = oracle_max(table, nw)
    want_sim = {p for p, (_, m) in true_max.items() if m > np.float32(rho)}
    assert res.similar_pairs == want_sim, backend
    # and every similar pair's reported score IS the true maximum
    got = score_map(res)
    for p in want_sim:
        assert got[p] == true_max[p], (backend, p)


def test_w_ge_l_degenerates_to_whole_trajectory(world):
    """subtraj_window >= L is the whole-trajectory engine bit-exactly
    (nw == 1, offset 0, window length == trajectory length)."""
    batch, forest, codes, _, _ = world
    L = codes.shape[2]
    whole = AnotherMeEngine(forest, EngineConfig(k=K, rho=1.05)).run(batch)
    win = AnotherMeEngine(forest, EngineConfig(
        k=K, rho=1.05, subtraj_window=L + 7,
    )).run(batch)
    assert score_map(win) == score_map(whole)
    assert win.similar_pairs == whole.similar_pairs
    assert win.communities == whole.communities


def test_stride_gt_one_matches_strided_oracle(world):
    """stride=2 restricts both the key windows and the oracle's offsets."""
    batch, forest, codes, lengths, betas = world
    N, H, L = codes.shape
    stride = 2
    nw = num_windows(L, W, stride)
    Weff = min(W, L)
    rho = float((K - 1) * betas.sum()) + 0.05
    res = AnotherMeEngine(forest, EngineConfig(
        k=K, rho=rho, subtraj_window=W, subtraj_stride=stride,
    )).run(batch)
    table = {}
    for a in range(N):
        for b in range(a + 1, N):
            for ja in range(nw):
                oa = ja * stride
                wla = max(0, min(int(lengths[a]) - oa, Weff))
                for jb in range(nw):
                    ob = jb * stride
                    wlb = max(0, min(int(lengths[b]) - ob, Weff))
                    lvl = tuple(
                        lcs_np(codes[a, h, oa:oa + wla],
                               codes[b, h, ob:ob + wlb])
                        for h in range(H)
                    )
                    table[(a, b, ja, jb)] = (lvl, np.float32(np.sum(
                        betas * np.asarray(lvl, np.float32), dtype=np.float32
                    )))
    want_sim = {
        p for p, (_, m) in oracle_max(table, nw).items()
        if m > np.float32(rho)
    }
    assert res.similar_pairs == want_sim


# ---------------------------------------------------------------------------
# windowed kernels vs numpy
# ---------------------------------------------------------------------------

def test_lcs_windowed_matches_numpy_dp():
    import jax.numpy as jnp

    from repro.core.similarity import lcs_impl, score_indexed

    rng = np.random.default_rng(0)
    B, L, window = 33, 12, 5
    a = rng.integers(0, 4, size=(B, L)).astype(np.int32)
    b = rng.integers(0, 4, size=(B, L)).astype(np.int32)
    len_a = rng.integers(0, L + 1, size=B).astype(np.int32)
    len_b = rng.integers(0, L + 1, size=B).astype(np.int32)
    off_a = rng.integers(0, L, size=B).astype(np.int32)
    off_b = rng.integers(0, L, size=B).astype(np.int32)
    want = np.array([
        lcs_np(
            a[i, off_a[i]:off_a[i] + max(0, min(len_a[i] - off_a[i], window))],
            b[i, off_b[i]:off_b[i] + max(0, min(len_b[i] - off_b[i], window))],
        )
        for i in range(B)
    ], np.int32)
    iota = jnp.arange(B, dtype=jnp.int32)
    for impl in ("wavefront", "fused-interpret"):
        lvl, _ = score_indexed(
            jnp.asarray(a)[:, None, :], jnp.asarray(len_a),
            jnp.asarray(b)[:, None, :], jnp.asarray(len_b), iota, iota,
            jnp.ones((1,), jnp.float32), impl=lcs_impl(impl), window=window,
            off_a=jnp.asarray(off_a), off_b=jnp.asarray(off_b),
        )
        np.testing.assert_array_equal(np.asarray(lvl)[:, 0], want,
                                      err_msg=impl)


def test_fused_windowed_kernel_matches_ref():
    """The in-register window masking of the fused kernel (sentinels
    outside [off, off+wlen)) equals the gather-then-score reference —
    bit-identical integer level_lcs, identical exact-mss epilogue."""
    import jax.numpy as jnp

    from repro.kernels.lcs.fused import (
        fused_windowed_score, fused_windowed_score_ref,
    )

    rng = np.random.default_rng(1)
    N, H, L, P, window = 10, 3, 11, 65, 4
    codes = rng.integers(0, 5, size=(N, H, L)).astype(np.int32)
    lengths = rng.integers(1, L + 1, size=N).astype(np.int32)
    for i in range(N):  # table padding: sentinel past each row's length
        codes[i, :, lengths[i]:] = -1
    left = rng.integers(0, N, size=P).astype(np.int32)
    right = rng.integers(0, N, size=P).astype(np.int32)
    off_a = rng.integers(0, L, size=P).astype(np.int32)
    off_b = rng.integers(0, L, size=P).astype(np.int32)
    betas = jnp.asarray([1.0, 0.5, 0.25], jnp.float32)
    args = (jnp.asarray(codes), jnp.asarray(lengths),
            jnp.asarray(codes), jnp.asarray(lengths),
            jnp.asarray(left), jnp.asarray(right),
            jnp.asarray(off_a), jnp.asarray(off_b), betas)
    lvl_ref, mss_ref = fused_windowed_score_ref(*args, window=window)
    lvl_k, mss_k = fused_windowed_score(*args, window=window,
                                        mode="interpret")
    np.testing.assert_array_equal(np.asarray(lvl_k), np.asarray(lvl_ref))
    np.testing.assert_array_equal(np.asarray(mss_k), np.asarray(mss_ref))


# ---------------------------------------------------------------------------
# coordinate plumbing units
# ---------------------------------------------------------------------------

def test_num_windows_edges():
    assert num_windows(10, 4, 1) == 7
    assert num_windows(10, 4, 2) == 4
    assert num_windows(10, 4, 3) == 3
    assert num_windows(3, 8, 1) == 1    # W >= L degenerates to one window
    assert num_windows(4, 4, 1) == 1
    with pytest.raises(ValueError):
        num_windows(10, 0, 1)
    with pytest.raises(ValueError):
        num_windows(10, 4, 0)


def test_window_lengths_matches_loop():
    lengths = np.array([0, 3, 7, 10], np.int32)
    got = window_lengths(lengths, max_len=10, window=4, stride=2)
    nw = num_windows(10, 4, 2)
    want = np.array([
        max(0, min(int(l) - j * 2, 4))
        for l in lengths for j in range(nw)
    ], np.int32)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_aggregate_window_pairs_tie_break_and_filtering():
    nw = 3
    # window ids: traj = id // 3.  Rows: a PAD row, a same-traj pair
    # (dropped), and three window pairs of trajectories (1, 2) with a tie
    # at mss=2.0 — the SMALLEST (window_lo, window_hi) must win.
    left = np.array([PAD_ID, 3, 5, 4, 3], np.int32)
    right = np.array([0, 4, 6, 7, 8], np.int32)
    lvl = np.array([[9], [5], [4], [2], [1]], np.int32)
    mss = np.array([9.0, 1.0, 2.0, 2.0, 1.5], np.float32)
    tl, tr, tlvl, tmss = aggregate_window_pairs(
        left, right, lvl, mss, nw=nw
    )
    np.testing.assert_array_equal(tl, [1])
    np.testing.assert_array_equal(tr, [2])
    # tied mss=2.0 between window pairs (5, 6) lvl [4] and (4, 7) lvl [2]:
    # the smaller window_lo (4) wins, so the reported lvl row is [2]
    np.testing.assert_array_equal(tlvl, [[2]])
    np.testing.assert_array_equal(tmss, np.float32(2.0))


def test_plan_capacities_windowed_ownership_is_per_trajectory():
    from repro.api.sharded import plan_capacities

    nw, n_shards = 2, 2
    # 4 trajectories x 2 windows; every window of trajectory t keys on t,
    # so all joins are within-trajectory windows
    keys = np.repeat(np.arange(4, dtype=np.int32), nw)[:, None]
    plan = plan_capacities(keys, n_shards, windows_per_row=nw)
    assert plan.local_n == 2  # TRAJECTORY units: ceil(4 / 2)
    plain = plan_capacities(keys[::nw], n_shards)
    assert plain.local_n == 2

    # shuffle-mode owner loads must also be in trajectory units: identical
    # plans for window ids g = t * nw and plain trajectory ids t
    lengths_w = np.full(4 * nw, 6, np.int32)
    pw = plan_capacities(
        keys, n_shards, score_mode="shuffle", windows_per_row=nw,
        lengths_np=lengths_w, prune_tau=0.5, betas_sum=1.0,
    )
    assert pw.owner_route_cap > 0 and pw.local_n == 2


def test_plan_stream_capacities_windows_per_row():
    from repro.api.sharded import plan_stream_capacities

    rng = np.random.default_rng(7)
    nw = 4
    lo_t = rng.integers(0, 16, size=40).astype(np.int64)
    hi_t = rng.integers(0, 16, size=40).astype(np.int64)
    # window ids of the SAME trajectories must plan identically to the
    # plain trajectory ids: ownership is (id // nw) % n_shards
    jw = rng.integers(0, nw, size=40)
    plain = plan_stream_capacities(lo_t, hi_t, 4, 64, score_mode="shuffle")
    windowed = plan_stream_capacities(
        lo_t * nw + jw, hi_t * nw + jw, 4, 64, score_mode="shuffle",
        windows_per_row=nw,
    )
    assert windowed == plain


def test_streaming_engine_rejects_subtraj(world):
    _, forest, _, _, _ = world
    with pytest.raises(NotImplementedError, match="subtraj"):
        StreamingEngine(forest, EngineConfig(subtraj_window=4))


def test_keyless_backend_rejects_subtraj(world):
    _, forest, _, _, _ = world
    from repro.api.backends import CallableBackend, register_backend

    register_backend("test-callable", lambda: CallableBackend(lambda e, b: None))
    try:
        with pytest.raises(ValueError, match="subtraj"):
            AnotherMeEngine(forest, EngineConfig(
                backend="test-callable", subtraj_window=4,
            ))
    finally:
        from repro.api.backends import _REGISTRY

        _REGISTRY.pop("test-callable", None)


def test_shingle_budget_guard_suggests_windowed_mode():
    from repro.core.shingling import MAX_SHINGLE_COMBOS, shingle_indices

    with pytest.raises(ValueError, match="subtraj_window"):
        shingle_indices(200, 5)  # C(200, 5) >> MAX_SHINGLE_COMBOS
    assert MAX_SHINGLE_COMBOS >= 2_000_000
