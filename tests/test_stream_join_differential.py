"""Differential streaming-join harness (ISSUE 5 acceptance).

Pins the in-mesh incremental delta join (``delta_join="device"``,
core/device_index.py + the shard_map join program) bit-identical to the
host ``BucketIndex`` oracle (``delta_join="host"``) and to one-shot
``engine.run`` over the concatenation, across every backend, shard count,
and adversarial update schedule:

* seeded randomized splits plus the degenerate schedules — empty updates,
  singleton updates, a skewed all-colliding-key world (every trajectory
  shares one bucket), and duplicate-trajectory batches;
* per-UPDATE equivalence, not just final: after every update the two
  engines' accumulated scored sets (bit-identical MSS + level LCS per
  pair), similar sets and community partitions match;
* exact work accounting: the per-update ``pairs_examined`` counts of the
  device join partition the full-world pre-dedup join size, verified
  against an independent per-key C(n, 2) oracle built from the backend's
  own keys;
* driver-transfer accounting: the device path ships NO pair list
  (``driver_pair_rows == 0``), holds NO bucket-table state on the driver
  (``host_index_entries == 0``; its only residual driver state is the
  per-distinct-key COUNT mirror surfaced as ``driver_mirror_keys``),
  and its per-update host->device bytes stay delta-sized while the
  world grows;
* zero steady-state recompiles: the join program's trace counter
  plateaus under constant-shape updates (compiles happen only at pow2
  capacity crossings, like the world buffer's amortized doubling).

Shard counts {2, 4} (and the shuffle score mode) bind the device count at
jax init, so those cells run in subprocesses; the {1 shard} axis runs
in-process across the full backend x schedule grid.  The deletion
differentials (TTL, retire, sliding window) live in
``test_stream_deletion_differential.py``.
"""
import numpy as np
import pytest

from conftest import make_batch, run_subprocess, score_map

from repro.api import (
    AnotherMeEngine, EngineConfig, ExecutionPlan, StreamingEngine,
    get_backend,
)
from repro.api.backends import BackendContext
from repro.core.encoding import encode_types, forest_tables
from repro.core.types import PAD_KEY
from repro.data import synthetic_setup

BACKENDS = ("ssh", "minhash", "brp", "udf")


def split_batch(batch, cuts):
    places = np.asarray(batch.places)
    lengths = np.asarray(batch.lengths)
    bounds = [0] + sorted(cuts) + [places.shape[0]]
    out = []
    for a, b in zip(bounds[:-1], bounds[1:]):
        p, ln = places[a:b], lengths[a:b]
        w = max(int(ln.max()), 1) if ln.size else 1
        out.append(make_batch(p[:, :w], ln))
    return out


def oracle_full_join(batch, forest, backend_name):
    """Independent pre-dedup join-size oracle: sum_key C(|rows(key)|, 2)
    over the backend's own per-row-deduped keys."""
    from collections import Counter

    backend = get_backend(backend_name)
    ctx = BackendContext(k=3, num_types=forest.num_types)
    tables = forest_tables(forest)
    types = encode_types(batch.places, tables)
    from repro.core.types import EncodedBatch

    view = EncodedBatch(codes=types[:, None, :], lengths=batch.lengths)
    keys = np.asarray(backend.join_keys(view, batch, ctx))
    per_key = Counter()
    for row in keys:
        for k in set(row[row != PAD_KEY].tolist()):
            per_key[k] += 1
    return sum(c * (c - 1) // 2 for c in per_key.values())


# ---------------------------------------------------------------------------
# adversarial update schedules
# ---------------------------------------------------------------------------
def schedule_random(seed):
    batch, forest = synthetic_setup(
        16, num_types=6, classes_per_type=3, num_places=40, min_len=2,
        max_len=8, seed=seed,
    )
    rng = np.random.default_rng(100 + seed)
    cuts = sorted(rng.choice(np.arange(0, 17), size=3).tolist())
    return split_batch(batch, cuts), batch, forest


def schedule_empty_and_singleton(seed):
    """Empty first / mid / trailing updates plus singleton updates."""
    batch, forest = synthetic_setup(
        8, num_types=5, classes_per_type=3, num_places=30, min_len=2,
        max_len=6, seed=seed,
    )
    pieces = split_batch(batch, [0, 1, 4, 4, 7, 8])
    assert min(p.num_trajectories for p in pieces) == 0
    assert 1 in {p.num_trajectories for p in pieces}
    return pieces, batch, forest


def schedule_hotkey(seed):
    """Skewed all-colliding-key world: every trajectory is the same place
    repeated, so every backend maps the whole world into ONE bucket —
    maximal per-owner skew for the key-sharded slab."""
    _, forest = synthetic_setup(
        4, num_types=5, classes_per_type=3, num_places=30, seed=seed,
    )
    n, L = 12, 5
    places = np.full((n, L), 7, np.int32)
    lengths = np.full((n,), L, np.int32)
    batch = make_batch(places, lengths)
    return split_batch(batch, [3, 7, 9]), batch, forest


def schedule_duplicates(seed):
    """Duplicate-trajectory batches: the same rows recur within one update
    and across updates (distinct ids, identical keys)."""
    base, forest = synthetic_setup(
        5, num_types=5, classes_per_type=3, num_places=30, min_len=3,
        max_len=6, seed=seed,
    )
    p = np.asarray(base.places)
    ln = np.asarray(base.lengths)
    places = np.concatenate([p, p[:2], p, p[4:]])
    lengths = np.concatenate([ln, ln[:2], ln, ln[4:]])
    batch = make_batch(places, lengths)
    return split_batch(batch, [4, 7, 12]), batch, forest


SCHEDULES = {
    "random": schedule_random,
    "empty_singleton": schedule_empty_and_singleton,
    "hotkey": schedule_hotkey,
    "duplicates": schedule_duplicates,
}


# ---------------------------------------------------------------------------
# the differential property, 1-shard axis (full backend x schedule grid)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("schedule", sorted(SCHEDULES))
def test_device_join_differential(backend, schedule):
    pieces, batch, forest = SCHEDULES[schedule](seed=0)
    cfg = EngineConfig(backend=backend, rho=2.0,
                       community_mode="components")
    host = StreamingEngine(forest, cfg)
    dev = StreamingEngine(forest, cfg, ExecutionPlan(delta_join="device"))
    examined = []
    prev_pairs: set = set()
    for i, piece in enumerate(pieces):
        rh = host.update(piece)
        rd = dev.update(piece)
        cell = (backend, schedule, i)
        # per-update equivalence of the whole accumulated state
        assert score_map(rd) == score_map(rh), cell
        assert rd.similar_pairs == rh.similar_pairs, cell
        assert rd.communities == rh.communities, cell
        # the device join emits exactly the host oracle's delta pairs:
        # same accumulated pair set, disjoint per-update increments
        pairs_now = set(score_map(rd))
        delta = pairs_now - prev_pairs
        assert len(prev_pairs) + len(delta) == len(pairs_now), cell
        assert rd.stats["num_delta_pairs"] == rh.stats["num_delta_pairs"], cell
        prev_pairs = pairs_now
        # exact work accounting, update by update
        assert rd.stats["pairs_examined"] == rh.stats["pairs_examined"], cell
        examined.append(rd.stats["pairs_examined"])
        # transfer accounting: no pair list through the driver, no
        # bucket-table state on the driver (only the count mirror, which
        # is surfaced — not hidden — by its own stat)
        assert rd.stats["driver_pair_rows"] == 0, cell
        assert rd.stats["host_index_entries"] == 0, cell
        assert rd.stats["driver_mirror_keys"] <= rh.stats["host_index_entries"], cell
        assert rh.stats["driver_key_rows"] == 0, cell
        assert rh.stats["driver_mirror_keys"] == 0, cell
    # final state == one-shot over the concatenation
    one = AnotherMeEngine(forest, cfg).run(batch)
    assert score_map(rd) == score_map(one), (backend, schedule)
    assert rd.similar_pairs == one.similar_pairs
    assert rd.communities == one.communities
    # the per-update examined counts partition the full-world pre-dedup
    # join size — pinned against an independent per-key C(n, 2) oracle
    full = oracle_full_join(batch, forest, backend)
    assert sum(examined) == full, (backend, schedule)
    assert rd.stats["full_world_pairs"] == full
    assert rh.stats["full_world_pairs"] == full


SCORE_CAP_CODE = r"""
import numpy as np
import jax.numpy as jnp
from repro.api import EngineConfig, ExecutionPlan, StreamingEngine
from repro.core.types import TrajectoryBatch
from repro.data import synthetic_setup

base, forest = synthetic_setup(5, num_types=5, classes_per_type=3,
                               num_places=30, min_len=3, max_len=6, seed=0)
p = np.asarray(base.places); ln = np.asarray(base.lengths)
places = np.concatenate([p, p[:2], p, p[4:]])
lengths = np.concatenate([ln, ln[:2], ln, ln[4:]])

def mk(lo, hi):
    return TrajectoryBatch(
        places=jnp.asarray(places[lo:hi].astype(np.int32)),
        lengths=jnp.asarray(lengths[lo:hi].astype(np.int32)),
        user_id=jnp.arange(hi - lo, dtype=jnp.int32),
    )

cuts = [0, 4, 7, 12, places.shape[0]]
cfg = EngineConfig(rho=2.0, community_mode="components")
for n_shards in (1, 2):
    st = StreamingEngine(
        forest, cfg, ExecutionPlan(n_shards=n_shards, delta_join="device"))
    caps = []
    for lo, hi in zip(cuts, cuts[1:]):
        res = st.update(mk(lo, hi))
        caps.append((res.stats["score_pair_cap"],
                     res.stats["join_pair_cap"]))
    # the score cap never exceeds the join emission cap, both are
    # pow2-sticky (monotone), ...
    for sc, jc in caps:
        assert sc <= jc, caps
    assert [c[0] for c in caps] == sorted(c[0] for c in caps), caps
    assert [c[1] for c in caps] == sorted(c[1] for c in caps), caps
    # ...and on this schedule (identical rows sharing MANY keys, so each
    # pair is emitted once per shared key pre-dedup) the final score cap
    # is strictly tighter
    assert caps[-1][0] < caps[-1][1], (n_shards, caps)
print("OK score cap")
"""


def test_device_join_score_cap_is_post_dedup():
    """The score stage's pair buffer is sized from the POST-dedup
    candidate count (the in-mesh pmax of per-shard dedup survivors), not
    the join stage's pre-dedup emission bound — on duplicate-heavy
    streams the two diverge and the score program must compile against
    the tighter cap."""
    out = run_subprocess(SCORE_CAP_CODE, devices=2)
    assert "OK score cap" in out


def test_device_join_prune_differential():
    """score_prune runs IN-MESH on the device path (the pairs never visit
    the host to be pruned there) and must keep the surviving scored set
    bit-identical to host-side pruning and to the unpruned similar set."""
    pieces, batch, forest = schedule_random(seed=2)
    cfg = EngineConfig(rho=2.0, score_prune=True,
                       community_mode="components")
    host = StreamingEngine(forest, cfg).update_many(pieces)
    dev = StreamingEngine(
        forest, cfg, ExecutionPlan(delta_join="device")
    ).update_many(pieces)
    one = AnotherMeEngine(forest, cfg).run(batch)
    assert score_map(dev) == score_map(host) == score_map(one)
    assert dev.similar_pairs == host.similar_pairs == one.similar_pairs
    assert dev.communities == host.communities
    assert dev.stats["num_pruned"] == host.stats["num_pruned"]


def test_device_join_transfer_stays_delta_sized():
    """Constant-shape updates into a growing world: per-update
    host->device bytes and key rows must stay bounded by the DELTA (the
    world's keys and the pair list never transit the driver)."""
    from repro.core.encoding import SemanticForest

    T = 128
    forest = SemanticForest(parents=(np.arange(T, dtype=np.int32),),
                            sizes=(T, T))
    B, L, K = 6, 5, 8

    def block_batch(u):
        rng = np.random.default_rng(9)
        places = (u * 8 + rng.integers(0, 8, size=(B, L))).astype(np.int32)
        return make_batch(places, np.full((B,), L, np.int32))

    st = StreamingEngine(
        forest, EngineConfig(rho=2.0), ExecutionPlan(delta_join="device"),
        world_capacity=B * K, join_slab_capacity=B * K * 8,
    )
    bytes_in, key_rows, traces = [], [], []
    for u in range(K):
        res = st.update(block_batch(u))
        bytes_in.append(res.stats["driver_bytes_in"])
        key_rows.append(res.stats["driver_key_rows"])
        traces.append(res.stats["join_traces"])
        assert res.stats["driver_pair_rows"] == 0
        assert res.stats["host_index_entries"] == 0
    # steady state (after the first compile/allocation): constant
    # per-update transfer while the world grows 8x
    assert len(set(bytes_in[1:])) == 1, bytes_in
    assert len(set(key_rows[1:])) == 1, key_rows
    # ...and the compiled join program is reused: the trace counter
    # plateaus (recompiles happen only at pow2 capacity crossings)
    assert traces[-1] == traces[-2] == traces[-3], traces
    assert traces[-1] <= 3, traces


def test_device_join_rejects_bad_plan():
    _, forest = synthetic_setup(4, num_types=5, classes_per_type=3,
                                num_places=30, seed=0)
    with pytest.raises(ValueError, match="delta_join"):
        StreamingEngine(forest, EngineConfig(),
                        ExecutionPlan(delta_join="nope"))


# ---------------------------------------------------------------------------
# sharded axis: {2, 4 shards} x {replicate, shuffle} in a subprocess
# ---------------------------------------------------------------------------
SHARDED_DIFFERENTIAL_CODE = r"""
import numpy as np
import jax.numpy as jnp
from repro.api import AnotherMeEngine, EngineConfig, ExecutionPlan, StreamingEngine
from repro.core.types import PAD_ID, TrajectoryBatch
from repro.data import synthetic_setup

def split(places, lengths, cuts):
    bounds = [0] + sorted(cuts) + [places.shape[0]]
    out = []
    for a, b in zip(bounds[:-1], bounds[1:]):
        p, ln = places[a:b], lengths[a:b]
        w = max(int(ln.max()), 1) if ln.size else 1
        out.append(TrajectoryBatch(places=jnp.asarray(p[:, :w]),
                                   lengths=jnp.asarray(ln),
                                   user_id=jnp.arange(b - a, dtype=jnp.int32)))
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

backends = ("ssh", "minhash", "brp", "udf")
for seed, backend in enumerate(backends):
    batch, forest = synthetic_setup(16, num_types=6, classes_per_type=3,
                                    num_places=40, min_len=2, max_len=8,
                                    seed=seed)
    places = np.asarray(batch.places); lengths = np.asarray(batch.lengths)
    rng = np.random.default_rng(50 + seed)
    cuts = sorted(rng.choice(np.arange(0, 17), size=3).tolist())
    pieces = split(places, lengths, cuts)
    cfg = EngineConfig(backend=backend, rho=2.0, community_mode="components")
    # the host-join streaming engine is the oracle; itself pinned to
    # one-shot engine.run by tests/test_streaming.py
    want = StreamingEngine(forest, cfg).update_many(pieces)
    one = AnotherMeEngine(forest, cfg).run(batch)
    assert score_map(want) == score_map(one), backend
    for n_shards in (2, 4):
        for mode in ("replicate", "shuffle"):
            st = StreamingEngine(
                forest, cfg,
                ExecutionPlan(n_shards=n_shards, score_mode=mode,
                              delta_join="device"),
            )
            ex_total = 0
            for piece in pieces:
                res = st.update(piece)
                ex_total += res.stats["pairs_examined"]
                assert res.stats["driver_pair_rows"] == 0
                assert res.stats["host_index_entries"] == 0
            cell = (backend, n_shards, mode)
            assert score_map(res) == score_map(want), cell
            assert res.similar_pairs == want.similar_pairs, cell
            assert res.communities == want.communities, cell
            assert ex_total == want.stats["full_world_pairs"], cell
print("OK sharded differential")
"""


def test_device_join_differential_sharded():
    out = run_subprocess(SHARDED_DIFFERENTIAL_CODE, devices=4)
    assert "OK sharded differential" in out


def test_device_join_refuses_lossy_commit(monkeypatch):
    """If the join still overflows after the retry budget (only reachable
    when the exact-planning invariant is broken — forced here with a
    deliberately undersized plan), the engine must RAISE rather than
    commit a slab whose merge dropped entries: a lossy bucket state would
    silently miss pairs forever."""
    from repro.api.capacity import CapacityPlanner
    from repro.api.sharded import StreamJoinPlan

    pieces, _, forest = schedule_hotkey(seed=0)

    def tiny(self, keys_flat, n_shards, stats, *, floor_pow2=4):
        return StreamJoinPlan(
            n_shards=n_shards, slab_cap=4, key_in_cap=256,
            key_route_cap=4, nn_cap=4, no_cap=4,
            pair_route_cap=4, pair_cap=4,
        )

    monkeypatch.setattr(CapacityPlanner, "plan_stream_join", tiny)
    st = StreamingEngine(forest, EngineConfig(rho=2.0, max_retries=0),
                         ExecutionPlan(delta_join="device"))
    with pytest.raises(RuntimeError, match="refusing to commit"):
        for piece in pieces:
            st.update(piece)
