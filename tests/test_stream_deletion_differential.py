"""Differential harness for deletion in streaming (windowed worlds).

Pins windowed ``StreamingEngine`` runs — TTL expiry, explicit
retirement, sliding windows, tombstone compaction and base rebases — to
the host ``BucketIndex`` oracle (``delta_join="host"``) at every update,
and the final state to a one-shot ``engine.run`` over the surviving
window, across every backend and adversarial schedule:

* a hot key that dominates the world and then expires;
* inserts interleaved with explicit retires under a per-batch TTL;
* retiring the whole world and streaming on into the empty shell.

Each cell also runs under ``REPRO_FAULT_INJECT=1``, which derates every
fresh plan so the overflow -> compact -> retry recovery path runs on
every update.  Shard counts {2, 4} bind the device count at jax init, so
that axis runs in a subprocess.  The insert-only differentials live in
``test_stream_join_differential.py``.
"""
import numpy as np
import pytest

from conftest import make_batch, run_subprocess, score_map

from repro.api import (
    AnotherMeEngine, EngineConfig, ExecutionPlan, StreamingEngine,
)
from repro.data import synthetic_setup

BACKENDS = ("ssh", "minhash", "brp", "udf")


def wsched_hotkey_expire(seed):
    """Hot key dominates then expires: the first updates are all-colliding
    rows riding a window=2, later updates are diverse rows — the skewed
    owner's slab fills with tombstones and must compact back down."""
    batch, forest = synthetic_setup(
        12, num_types=6, classes_per_type=3, num_places=30, min_len=2,
        max_len=6, seed=seed,
    )
    div_p = np.asarray(batch.places)
    div_l = np.asarray(batch.lengths)
    hot_p = np.full((8, div_p.shape[1]), 7, np.int32)
    hot_l = np.full((8,), min(5, div_p.shape[1]), np.int32)
    places = np.concatenate([hot_p, div_p])
    lengths = np.concatenate([hot_l, div_l])
    actions = [("update", 0, 4, None), ("update", 4, 8, None),
               ("update", 8, 14, None), ("update", 14, 20, None)]
    return dict(window=2, compact_watermark=0.5), actions, places, lengths, forest


def wsched_interleaved(seed):
    """Interleaved insert/retire: explicit retires between updates, a
    per-batch TTL riding on top, no engine window."""
    batch, forest = synthetic_setup(
        20, num_types=6, classes_per_type=3, num_places=40, min_len=2,
        max_len=8, seed=seed,
    )
    places = np.asarray(batch.places)
    lengths = np.asarray(batch.lengths)
    actions = [
        ("update", 0, 6, None),
        ("retire", [0, 2, 4]),
        ("update", 6, 12, 2),       # TTL: gone at the start of update 4
        ("retire", [7, 5]),
        ("update", 12, 16, None),
        ("update", 16, 20, None),   # the TTL batch expires here
        ("update", 20, 20, None),   # empty trailing update
    ]
    return dict(compact_watermark=0.4), actions, places, lengths, forest


def wsched_retire_everything(seed):
    """Retire the whole world, then keep streaming into the empty shell."""
    batch, forest = synthetic_setup(
        16, num_types=5, classes_per_type=3, num_places=30, min_len=2,
        max_len=6, seed=seed,
    )
    places = np.asarray(batch.places)
    lengths = np.asarray(batch.lengths)
    actions = [
        ("update", 0, 8, None),
        ("retire", list(range(8))),
        ("update", 8, 12, None),
        ("update", 12, 16, None),
    ]
    return dict(), actions, places, lengths, forest


WINDOWED_SCHEDULES = {
    "hotkey_expire": wsched_hotkey_expire,
    "interleaved": wsched_interleaved,
    "retire_everything": wsched_retire_everything,
}


def run_actions(stream, actions, places, lengths):
    """Drive one engine through a schedule; returns per-update results."""
    results = []
    for act in actions:
        if act[0] == "update":
            _, lo, hi, ttl = act
            p, ln = places[lo:hi], lengths[lo:hi]
            w = max(int(ln.max()), 1) if ln.size else 1
            results.append(stream.update(make_batch(p[:, :w], ln), ttl=ttl))
        else:
            stream.retire(act[1])
    return results


def live_reference(stream, cfg, forest, places, lengths):
    """One-shot run over the SURVIVING window, translated to global ids
    (order-preserving: survivor i of the fresh run is global id
    ``alive[i]``) — the equivalence target for windowed streaming."""
    span = stream.n - stream._base
    alive = np.nonzero(stream._alive_np[:span])[0] + stream._base
    if alive.size == 0:
        return {}, set(), set()
    p, ln = places[alive], lengths[alive]
    w = max(int(ln.max()), 1) if ln.size else 1
    ref = AnotherMeEngine(forest, cfg).run(make_batch(p[:, :w], ln))
    g = {i: int(x) for i, x in enumerate(alive.tolist())}
    smap = {(g[a], g[b]): v for (a, b), v in score_map(ref).items()}
    sim = {(g[a], g[b]) for (a, b) in ref.similar_pairs}
    comms = {frozenset(g[i] for i in s) for s in ref.communities}
    return smap, sim, comms


@pytest.mark.parametrize("fault_inject", ("off", "on"))
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("schedule", sorted(WINDOWED_SCHEDULES))
def test_windowed_deletion_differential(backend, schedule, fault_inject,
                                        monkeypatch):
    """Windowed streaming == one-shot over the surviving window, device
    join bit-identical to the host oracle at every update, through TTL
    expiry, explicit retirement, tombstone compaction and base rebases.
    With ``fault_inject="on"`` (``REPRO_FAULT_INJECT=1``) every fresh
    plan is derated, so each update also runs the overflow -> compact ->
    replan -> retry path, and the results must not change.

    NOTE: ``pairs_examined`` parity is deliberately NOT asserted here —
    the host oracle evicts buckets eagerly while the device slab defers
    reclamation behind tombstones (tombstoned slots still count as
    examined until a compaction), so under deletion the two paths agree
    on RESULTS, not on probe work.
    """
    if fault_inject == "on":
        monkeypatch.setenv("REPRO_FAULT_INJECT", "1")
    kwargs, actions, places, lengths, forest = \
        WINDOWED_SCHEDULES[schedule](seed=0)
    cfg = EngineConfig(backend=backend, rho=2.0,
                       community_mode="components")
    host = StreamingEngine(forest, cfg, **kwargs)
    dev = StreamingEngine(forest, cfg, ExecutionPlan(delta_join="device"),
                          **kwargs)
    rh_all = run_actions(host, actions, places, lengths)
    rd_all = run_actions(dev, actions, places, lengths)
    for i, (rh, rd) in enumerate(zip(rh_all, rd_all)):
        cell = (backend, schedule, i)
        assert score_map(rd) == score_map(rh), cell
        assert rd.similar_pairs == rh.similar_pairs, cell
        assert rd.communities == rh.communities, cell
        # deletion must not reintroduce driver-resident pair/bucket state
        assert rd.stats["driver_pair_rows"] == 0, cell
        assert rd.stats["host_index_entries"] == 0, cell
        # the bounded-memory counters ride every update
        for k in ("world_live", "num_expired", "retired_total",
                  "resident_bytes", "dead_fraction", "compactions",
                  "compact_ms_total"):
            assert k in rd.stats, (cell, k)
    assert host.live_size == dev.live_size, (backend, schedule)
    assert host.retired_total == dev.retired_total, (backend, schedule)
    # final state == one-shot over the survivors (global-id translated)
    smap, sim, comms = live_reference(dev, cfg, forest, places, lengths)
    assert score_map(rd_all[-1]) == smap, (backend, schedule)
    assert rd_all[-1].similar_pairs == sim, (backend, schedule)
    assert rd_all[-1].communities == comms, (backend, schedule)
    if schedule == "hotkey_expire":
        # the expiring hot prefix must actually have tripped a compaction
        assert dev.compactions >= 1, (backend, dev.compactions)
        assert dev._base > 0


def test_windowed_fault_injection_differential(monkeypatch):
    """REPRO_FAULT_INJECT=1 derates every fresh plan to tiny caps, forcing
    the overflow -> compact -> retry recovery deterministically; results
    must stay bit-identical to the unfaulted host oracle."""
    kwargs, actions, places, lengths, forest = wsched_hotkey_expire(seed=1)
    cfg = EngineConfig(rho=2.0, community_mode="components")
    host = StreamingEngine(forest, cfg, **kwargs)
    rh_all = run_actions(host, actions, places, lengths)
    monkeypatch.setenv("REPRO_FAULT_INJECT", "1")
    dev = StreamingEngine(forest, cfg, ExecutionPlan(delta_join="device"),
                          **kwargs)
    rd_all = run_actions(dev, actions, places, lengths)
    for i, (rh, rd) in enumerate(zip(rh_all, rd_all)):
        assert score_map(rd) == score_map(rh), i
        assert rd.similar_pairs == rh.similar_pairs, i
        assert rd.communities == rh.communities, i
    # the derated caps must actually have exercised the recovery path
    assert dev.compactions >= 1


SHARDED_WINDOWED_CODE = r"""
import os
import numpy as np
import jax.numpy as jnp
from repro.api import AnotherMeEngine, EngineConfig, ExecutionPlan, StreamingEngine
from repro.core.types import PAD_ID, TrajectoryBatch
from repro.data import synthetic_setup

def mk(p, ln):
    w = max(int(ln.max()), 1) if ln.size else 1
    return TrajectoryBatch(places=jnp.asarray(p[:, :w].astype(np.int32)),
                           lengths=jnp.asarray(ln.astype(np.int32)),
                           user_id=jnp.arange(p.shape[0], dtype=np.int32))

def score_map(res):
    left = np.asarray(res.scored.left); right = np.asarray(res.scored.right)
    mss = np.asarray(res.scored.mss); lvl = np.asarray(res.scored.level_lcs)
    keep = left != PAD_ID
    return {(int(a), int(b)): (float(m), tuple(int(x) for x in lv))
            for a, b, m, lv in zip(left[keep], right[keep], mss[keep], lvl[keep])}

def run_actions(stream, actions, places, lengths):
    out = []
    for act in actions:
        if act[0] == "update":
            _, lo, hi, ttl = act
            out.append(stream.update(mk(places[lo:hi], lengths[lo:hi]), ttl=ttl))
        else:
            stream.retire(act[1])
    return out

shards = [int(s) for s in os.environ["TEST_SHARDS"].split(",")]
for seed, backend in enumerate(("ssh", "minhash", "brp", "udf")):
    batch, forest = synthetic_setup(20, num_types=6, classes_per_type=3,
                                    num_places=40, min_len=2, max_len=8,
                                    seed=seed)
    places = np.asarray(batch.places); lengths = np.asarray(batch.lengths)
    actions = [("update", 0, 6, None), ("retire", [0, 2, 4]),
               ("update", 6, 12, 2), ("retire", [7, 5]),
               ("update", 12, 16, None), ("update", 16, 20, None)]
    cfg = EngineConfig(backend=backend, rho=2.0, community_mode="components")
    kwargs = dict(window=3, compact_watermark=0.4)
    want_all = run_actions(StreamingEngine(forest, cfg, **kwargs),
                           actions, places, lengths)
    for n_shards in shards:
        st = StreamingEngine(
            forest, cfg,
            ExecutionPlan(n_shards=n_shards, delta_join="device"), **kwargs)
        got_all = run_actions(st, actions, places, lengths)
        for i, (want, got) in enumerate(zip(want_all, got_all)):
            cell = (backend, n_shards, i)
            assert score_map(got) == score_map(want), cell
            assert got.similar_pairs == want.similar_pairs, cell
            assert got.communities == want.communities, cell
            assert got.stats["driver_pair_rows"] == 0, cell
print("OK sharded windowed")
"""


def test_windowed_deletion_differential_sharded(monkeypatch):
    import os

    shards = "2,4"
    devices = 4
    if int(os.environ.get("REPRO_MAX_SHARDS", "0") or "0") >= 8:
        shards, devices = "2,4,8", 8
    monkeypatch.setenv("TEST_SHARDS", shards)
    out = run_subprocess(SHARDED_WINDOWED_CODE, devices=devices)
    assert "OK sharded windowed" in out
