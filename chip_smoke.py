#!/usr/bin/env python3
"""Smoke run of the trajectory engine's main path on a TPU.

    python3 chip_smoke.py             # one chip: one-shot, streaming, serving
    python3 chip_smoke.py --chips 4   # the four-chip mesh path only

One chip: the paper's scalability world (section V.1: 10^6 trajectories,
300 place types, lengths 5-10, k=3) runs through ``AnotherMeEngine`` with
the fused LCS kernel, cold and then warm; a 2^18-row slice of it streams
through a ``StreamingEngine`` with the in-mesh delta join; a
``QueryEngine`` then serves top-k queries against that stream.  Checks:

* the compiled score program contains the Pallas kernel (tpu_custom_call);
* a random sample of scored pairs, rescored by the XLA wavefront path,
  is bit-identical (level_lcs and MSS);
* the quickstart's centralized-truth check gives QA1 = QA2 = 1.000;
* the warm run compiles nothing;
* streaming ends equal to one ``engine.run`` over the same rows;
* serving off the device join equals serving off the host-join oracle.

``--chips 4`` runs the one-shot job on a 4-device mesh in both score modes
and the sharded streaming join, each against its one-device or host-join
reference, and shows the arrays spread over all four devices.

The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``.jax_cache/`` next to this script.  There is no CPU fallback: without a
TPU the run fails.  Any failed check exits non-zero; on success the last
line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
WORLD_N = 1_000_000   # section V.1 scalability world
# Size cut, one power-of-two step: the whole run has 1,200 s.  On a v5e at
# N = 10^6 the cold one-shot run alone took 262 s (208 s of it in the
# join over 1.2e8 candidate pairs) and the smoke ran past 1,100 s without
# finishing its warm run.
RUN_N = WORLD_N // 2
CUT = ("size cut: N 1,000,000 -> 500,000 (the smoke's 1,200 s limit: at "
       "10^6 the cold one-shot run alone took 262 s on a v5e)")
NUM_TYPES = 300
STREAM_ROWS = 1 << 18
STREAM_UPDATES = 8
QUERY_BATCHES, QUERY_BATCH = 4, 256
SAMPLE = 65_536
# distinct 3-shingles per trajectory, lengths 5-10: mean C(len, 3) = 54;
# presizes the join slabs so the stream does not regrow (and recompile) them
SHINGLES_PER_ROW = 64
# four-chip world: one more power-of-two step below the one-chip run, for
# four times the chip time; a one-shot run there holds ~7.6e6 candidate
# pairs, so each shard scores its ~1.9e6 pairs in more than one chunk
MESH_N = RUN_N // 2
MESH_CUT = ("size cut (--chips 4): N 1,000,000 -> 250,000 (one step below "
            "the one-chip run: four chips cost four times the chip time)")
# the four-chip stream: the delta join's sharded programs compile per
# capacity step (a 2^18-row stream took 196 s on one chip with 34
# compiles; 2^15 rows on 4 shards compile about 45 programs, 29 in the
# first update), so the stream is cut further than the one-shot world
MESH_STREAM_ROWS = 1 << 15
MESH_STREAM_CUT = ("size cut (--chips 4): stream 262,144 -> 32,768 rows "
                   "(compile-bound: its join programs compile per capacity "
                   "step, at four times the chip time)")


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        raise CheckFailed(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileCounter:
    """Backend compiles and persistent-cache hits, from jax.monitoring."""

    def __init__(self, jax):
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def start_jax(chips: int):
    """Place the compile cache, then insist on ``chips`` TPU devices."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    devices = jax.devices()
    d = devices[0]
    log(f"jax {jax.__version__}  platform {d.platform}  "
        f"device_kind {d.device_kind}  device_count {len(devices)}")
    log(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    if d.platform != "tpu":
        raise CheckFailed(
            f"no TPU: JAX found platform {d.platform!r} "
            f"({len(devices)} {d.device_kind} device(s)); this smoke run "
            "has no CPU fallback"
        )
    if len(devices) < chips:
        raise CheckFailed(f"--chips {chips} needs {chips} TPU devices, "
                          f"found {len(devices)}")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise CheckFailed(f"the repro package is not next to this script "
                          f"({ROOT / 'src'}): {e}")
    return jax, devices


def world(n: int):
    from repro.data import synthetic_setup

    t = time.perf_counter()
    batch, forest = synthetic_setup(n, num_types=NUM_TYPES, seed=0)
    log(f"world: N={n:,} trajectories, {NUM_TYPES} types, lengths 5-10 "
        f"(generated in {time.perf_counter() - t:.1f}s)")
    return batch, forest


def rows_of(batch, lo: int, hi: int):
    from repro.core.types import TrajectoryBatch
    import jax.numpy as jnp

    return TrajectoryBatch(
        places=batch.places[lo:hi], lengths=batch.lengths[lo:hi],
        user_id=jnp.arange(hi - lo, dtype=jnp.int32),
    )


def config(**kw):
    from repro.api import EngineConfig

    return EngineConfig(backend="ssh", rho=2.0, lcs_impl="fused",
                        community_mode="components", **kw)


def phases(stats: dict) -> str:
    return "  ".join(f"{k[2:]} {v:.2f}s" for k, v in sorted(stats.items())
                     if k.startswith("t_"))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def one_shot(batch, forest, counter) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.api import AnotherMeEngine
    from repro.core import encode_batch
    from repro.core.similarity import score_pairs
    from repro.core.types import PAD_ID
    from repro.perf import resolve_wavefront_dtype

    engine = AnotherMeEngine(forest, config())
    t = time.perf_counter()
    cold = engine.run(batch)
    t_cold = time.perf_counter() - t
    s = cold.stats
    log(f"one-shot cold: {t_cold:.2f}s  candidates {s['num_candidates']:,}  "
        f"similar {s['num_similar']:,}  communities {s['num_communities']:,}")
    log(f"  phases: {phases(s)}")

    # the score program the engine ran: the same jitted function, statics
    # and shapes as ScoreStage
    enc = encode_batch(batch, engine.tables)
    sc = cold.scored
    hlo = score_pairs.lower(
        enc.codes, enc.lengths, sc.left, sc.right, engine.betas,
        impl_name="fused", wavefront_dtype=resolve_wavefront_dtype(None),
    ).compile().as_text()
    check("tpu_custom_call" in hlo,
          "the compiled score program contains the Pallas kernel "
          "(tpu_custom_call)")

    left, right = np.asarray(sc.left), np.asarray(sc.right)
    valid = np.nonzero(left != PAD_ID)[0]
    rng = np.random.default_rng(0)
    pick = np.sort(rng.choice(valid, size=min(SAMPLE, valid.size),
                              replace=False))
    lvl_w, mss_w = score_pairs(
        enc.codes, enc.lengths, jnp.asarray(left[pick]),
        jnp.asarray(right[pick]), engine.betas, impl_name="wavefront",
    )
    at = jnp.asarray(pick)
    same_lvl = np.array_equal(np.asarray(lvl_w), np.asarray(sc.level_lcs[at]))
    same_mss = np.array_equal(np.asarray(mss_w), np.asarray(sc.mss[at]))
    check(same_lvl and same_mss,
          f"{pick.size:,} sampled pairs rescored by the XLA wavefront: "
          "bit-identical level_lcs and mss")

    # the warm run: nothing of the cold run stays on the device
    similar, communities = cold.similar_pairs, cold.communities
    del cold, sc, enc, lvl_w, mss_w, at
    c0 = counter.compiles
    t = time.perf_counter()
    warm = engine.run(batch)
    t_warm = time.perf_counter() - t
    warm_compiles = counter.compiles - c0
    log(f"one-shot warm: {t_warm:.2f}s  phases: {phases(warm.stats)}")
    log(f"  compiles in the warm run: {warm_compiles}")
    check(warm_compiles == 0, "warm one-shot run compiles nothing")
    check(warm.similar_pairs == similar and warm.communities == communities,
          "warm one-shot result equals the cold one")


def quickstart_truth() -> None:
    """examples/quickstart.py's centralized-truth check, fused kernel."""
    from repro.api import AnotherMeEngine, EngineConfig
    from repro.core import (
        centralized_similar_pairs, encode_batch, forest_tables,
        maximal_cliques, qa1, qa2,
    )
    from repro.data import synthetic_setup

    _, forest = synthetic_setup(2_000, seed=0)
    engine = AnotherMeEngine(
        forest, EngineConfig(backend="ssh", rho=2.0, lcs_impl="fused")
    )
    sub, _ = synthetic_setup(400, seed=0)
    res = engine.run(sub)
    enc = encode_batch(sub, forest_tables(forest))
    cl, cr, _ = centralized_similar_pairs(enc, rho=2.0)
    cen = {(int(a), int(b)) for a, b in zip(cl, cr)}
    q1 = qa1(res.communities, maximal_cliques(cen))
    q2 = qa2(res.similar_pairs, cen)
    log(f"quickstart: QA1 = {q1:.3f}  QA2 = {q2:.3f}  (paper: 1.000)")
    check(q1 == 1.0 and q2 == 1.0, "QA1 = QA2 = 1.000 against centralized truth")


def stream_and_serve(batch, forest, rows: int, counter) -> None:
    import numpy as np

    from repro.api import (
        AnotherMeEngine, ExecutionPlan, QueryEngine, StreamingEngine,
    )
    from repro.data import synthetic_trajectories

    step = rows // STREAM_UPDATES
    streams = {}
    for join in ("device", "host"):
        st = StreamingEngine(
            forest, config(),
            ExecutionPlan(delta_join=join, lcs_impl="fused"),
            world_capacity=rows, join_slab_capacity=rows * SHINGLES_PER_ROW,
        )
        c0 = counter.compiles
        t = time.perf_counter()
        for u in range(STREAM_UPDATES):
            res = st.update(rows_of(batch, u * step, (u + 1) * step))
        log(f"stream ({join} join): {STREAM_UPDATES} updates of {step:,} "
            f"rows in {time.perf_counter() - t:.2f}s ({counter.compiles - c0} "
            f"compiles)  similar {len(res.similar_pairs):,}  communities "
            f"{len(res.communities):,}")
        streams[join] = (st, res)
    one = AnotherMeEngine(forest, config()).run(rows_of(batch, 0, rows))
    for join, (_, res) in streams.items():
        check(res.similar_pairs == one.similar_pairs
              and res.communities == one.communities,
              f"stream ({join} join) ends equal to one engine.run over the "
              f"same {rows:,} rows")

    queries = synthetic_trajectories(QUERY_BATCHES * QUERY_BATCH, seed=12345)
    serve = {j: QueryEngine(st, k=10) for j, (st, _) in streams.items()}
    matched = 0
    for q in range(QUERY_BATCHES):
        qb = rows_of(queries, q * QUERY_BATCH, (q + 1) * QUERY_BATCH)
        c0 = counter.compiles
        t = time.perf_counter()
        got = serve["device"].query(qb)
        ms = (time.perf_counter() - t) * 1e3
        want = serve["host"].query(qb)
        log(f"serve batch {q}: {QUERY_BATCH} queries in {ms:.1f}ms "
            f"(compiles {counter.compiles - c0})")
        check(np.array_equal(got.match_ids, want.match_ids)
              and np.array_equal(got.mss, want.mss),
              f"serve batch {q}: device-join top-10 bit-identical to the "
              "host-join oracle")
        matched += int((got.match_ids >= 0).sum())
    log(f"serve: {matched:,} matches over "
        f"{QUERY_BATCHES * QUERY_BATCH} queries")


def run_one_chip(counter) -> None:
    log(CUT)
    batch, forest = world(RUN_N)
    one_shot(batch, forest, counter)
    quickstart_truth()
    stream_and_serve(batch, forest, STREAM_ROWS, counter)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def show_spread(jax, what: str, arrays: dict, chips: int) -> None:
    """Print where ``arrays`` live; fail unless each spans ``chips``."""
    for name, x in arrays.items():
        ids = sorted(d.id for d in x.sharding.device_set)
        log(f"  {what} {name}: shape {tuple(x.shape)} on devices {ids}")
        check(len(ids) == chips, f"{what} {name} spans {chips} devices")
    used = [d.memory_stats() or {} for d in jax.devices()]
    log(f"  {what} bytes_in_use per device: "
        f"{[u.get('bytes_in_use') for u in used]}  peak: "
        f"{[u.get('peak_bytes_in_use') for u in used]}")


def run_four_chips(jax, chips: int) -> None:
    from repro.api import AnotherMeEngine, ExecutionPlan, StreamingEngine

    log(MESH_CUT)
    batch, forest = world(MESH_N)
    t = time.perf_counter()
    want = AnotherMeEngine(forest, config()).run(batch)
    log(f"one device: {time.perf_counter() - t:.2f}s  candidates "
        f"{want.stats['num_candidates']:,}  similar "
        f"{len(want.similar_pairs):,}  communities {len(want.communities):,}")
    for mode in ("replicate", "shuffle"):
        eng = AnotherMeEngine(
            forest, config(), ExecutionPlan(n_shards=chips, score_mode=mode)
        )
        t = time.perf_counter()
        got = eng.run(batch)
        log(f"{chips} shards, {mode}: {time.perf_counter() - t:.2f}s  "
            f"phases: {phases(got.stats)}")
        mesh_ids = sorted(d.id for d in eng.mesh().devices.flat)
        check(len(set(mesh_ids)) == chips,
              f"{mode}: mesh spans {chips} distinct devices {mesh_ids}")
        # the scored buffers as the mesh left them on the devices
        sc = got.scored
        show_spread(jax, mode, dict(left=sc.left, right=sc.right,
                                    level_lcs=sc.level_lcs, mss=sc.mss),
                    chips)
        check(got.similar_pairs == want.similar_pairs
              and got.communities == want.communities,
              f"{chips} shards, {mode}: similar pairs and communities equal "
              "one device")

    log(MESH_STREAM_CUT)
    rows = MESH_STREAM_ROWS
    step = rows // STREAM_UPDATES
    results = {}
    for name, plan in (
        ("device", ExecutionPlan(n_shards=chips, delta_join="device")),
        ("host", ExecutionPlan(delta_join="host")),
    ):
        st = StreamingEngine(forest, config(), plan, world_capacity=rows,
                             join_slab_capacity=rows * SHINGLES_PER_ROW)
        t = time.perf_counter()
        for u in range(STREAM_UPDATES):
            res = st.update(rows_of(batch, u * step, (u + 1) * step))
        log(f"stream ({name} join): {STREAM_UPDATES} updates of {step:,} "
            f"rows in {time.perf_counter() - t:.2f}s  similar "
            f"{len(res.similar_pairs):,}")
        results[name] = res
        if name == "device":
            show_spread(jax, "stream", {"places": st._places_dev,
                                        "slab_keys": st._slab_keys}, chips)
    check(results["device"].similar_pairs == results["host"].similar_pairs
          and results["device"].communities == results["host"].communities,
          f"{chips}-shard device-join stream equals the host-join oracle "
          f"({rows:,} rows)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip main path; 4: the mesh path only")
    args = ap.parse_args()
    t0 = time.perf_counter()
    try:
        jax, devices = start_jax(args.chips)
        counter = CompileCounter(jax)
        if args.chips == 1:
            run_one_chip(counter)
        else:
            run_four_chips(jax, args.chips)
    except CheckFailed as e:
        log(f"FAILED: {e}")
        return 1
    log(f"compiles {counter.compiles}  persistent-cache hits "
        f"{counter.cache_hits}  total {time.perf_counter() - t0:.1f}s")
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
