"""Cross-backend parity matrix for the device-resident sharded pipeline.

Pins the contract of ISSUEs 2 and 3: every cell of

    {ssh, minhash, brp, udf} x {1, 2, 4 shards} x {replicate, shuffle}
                 x {wavefront, ref, fused-interpret}

produces identical similar pairs, identical communities and bit-identical
per-pair scores to the single-device engine (and, at n_shards=1, to the
legacy ``run_anotherme``).  Sharded cells run in a subprocess (device count
binds at jax init); one subprocess per backend keeps the matrix affordable
while still compiling every (shards, mode, impl) program.

Also proves the structural claims:
* with n_shards>1 the engine has NO host EncodeStage (encoding runs inside
  the shard_map program) and reports no ``t_encode`` phase;
* ``lcs_impl="fused-interpret"`` really dispatches the Pallas kernel body
  (``fused.lcs_lanes``) inside the shard_map score stage (counted via
  monkeypatch at trace time);
* ``lcs_impl="fused-interpret"`` really dispatches the gather-free
  ``fused_gather_score`` kernel, on the single-device AND sharded paths.

ISSUE 4 adds the STREAMING axis: {1, 2, 4 shards} x {replicate, shuffle}
x {wavefront, fused-interpret} micro-batched ``StreamingEngine`` runs must
be bit-identical to the single-device streaming reference (itself pinned
to one-shot ``engine.run``), and equal-shape updates must reuse the cached
sharded runner — zero per-update recompiles, asserted through a trace-time
compilation-counting hook plus a fused-kernel dispatch counter.

ISSUE 5 adds the DELTA_JOIN axis: {host, device} x {replicate, shuffle}
x {wavefront, fused-interpret} streaming runs must produce bit-identical
``EngineResult``s, and a real-dispatch proof (``BucketIndex.insert``
monkeypatched with a counter) shows the device path keeps the join state
in-mesh: the driver-resident bucket table is NEVER consulted.

ISSUE 9 adds the AUTOTUNE + OVERLAP axis: a tuning table with NON-default
parameters (int32 diagonals) plus ``overlap_chunks`` in
{2, 4} must stay bit-identical to the untuned serial defaults across
{wavefront, fused-interpret} x SHARDS x {replicate, shuffle}, one-shot
and streaming — with a real-dispatch proof that the tuned record reaches
``lcs_impl_fn`` — and the chunked shuffle runner's per-update trace
history must EQUAL the unchunked one (hop/score overlap adds zero
steady-state recompiles).

ISSUE 10 adds the SUBTRAJECTORY axis: every backend x SHARDS x
{replicate, shuffle} x {wavefront, fused-interpret} run with
``subtraj_window`` set must be bit-identical to the single-device
subtrajectory engine (itself pinned to the brute-force windowed oracle in
``test_subtrajectory.py``), and a re-run of the same batch must reuse the
cached sharded runner — zero steady-state recompiles in windowed mode.
"""
import os

import pytest

from conftest import run_subprocess

BACKENDS = ("ssh", "minhash", "brp", "udf")

# CI widens the shard axis to 8 (REPRO_MAX_SHARDS=8 with
# --xla_force_host_platform_device_count=8); the local default stays at 4
# so the matrix remains affordable on laptops.
_MAX_SHARDS = int(os.environ.get("REPRO_MAX_SHARDS", "4"))
SHARDS = tuple(s for s in (1, 2, 4, 8) if s <= _MAX_SHARDS)
DEVICES = max(_MAX_SHARDS, 4)

MATRIX_CODE = r"""
import numpy as np
from repro.api import AnotherMeEngine, EngineConfig, ExecutionPlan
from repro.core import AnotherMeConfig, run_anotherme
from repro.core.types import PAD_ID
from repro.data import fig1_world

backend = "%(backend)s"
batch, forest = fig1_world()
RHO = 3.0
IMPLS = ("wavefront", "ref", "fused-interpret")


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


base = {}
for impl in IMPLS:
    cfg = EngineConfig(backend=backend, rho=RHO, lcs_impl=impl)
    base[impl] = AnotherMeEngine(forest, cfg).run(batch)

# engine vs engine across impls: integer LCS (and a fixed-order float32
# MSS epilogue in the fused kernel) => bit-identical scores
assert score_map(base["wavefront"]) == score_map(base["ref"])
assert score_map(base["wavefront"]) == score_map(base["fused-interpret"])

# engine vs legacy (single device, ssh/udf share the lossless shingle join)
if backend in ("ssh", "udf"):
    legacy = run_anotherme(batch, forest, AnotherMeConfig(rho=RHO))
    assert base["wavefront"].similar_pairs == legacy.similar_pairs
    assert base["wavefront"].communities == legacy.communities

for impl in IMPLS:
    cfg = EngineConfig(backend=backend, rho=RHO, lcs_impl=impl)
    want_pairs = base[impl].similar_pairs
    want_comms = base[impl].communities
    want_scores = score_map(base[impl])
    for n_shards in %(shards)s:
        modes = ("replicate", "shuffle") if n_shards > 1 else ("replicate",)
        for mode in modes:
            res = AnotherMeEngine(
                forest, cfg,
                ExecutionPlan(n_shards=n_shards, score_mode=mode),
            ).run(batch)
            cell = (backend, n_shards, mode, impl)
            assert res.similar_pairs == want_pairs, cell
            assert res.communities == want_comms, cell
            assert score_map(res) == want_scores, cell
print("OK", backend)
"""


@pytest.mark.parametrize("backend", BACKENDS)
def test_parity_matrix(backend):
    out = run_subprocess(
        MATRIX_CODE % {"backend": backend, "shards": SHARDS},
        devices=DEVICES,
    )
    assert f"OK {backend}" in out


PALLAS_DISPATCH_CODE = r"""
import numpy as np
import repro.kernels.lcs.fused as fused
from repro.api import AnotherMeEngine, EngineConfig, ExecutionPlan
from repro.data import fig1_world

calls = []
real = fused.lcs_lanes

def counting(*args, **kwargs):
    calls.append(kwargs.get("interpret"))
    return real(*args, **kwargs)

fused.lcs_lanes = counting
batch, forest = fig1_world()
cfg = EngineConfig(rho=3.0)
single = AnotherMeEngine(forest, cfg).run(batch)
assert not calls  # default wavefront impl never touches the kernel

sharded = AnotherMeEngine(
    forest, cfg, ExecutionPlan(n_shards=4, lcs_impl="fused-interpret"),
).run(batch)
# traced (and therefore executed) inside the shard_map score stage
assert calls and all(interp is True for interp in calls), calls
assert sharded.similar_pairs == single.similar_pairs
assert sharded.communities == single.communities
print("OK", len(calls))
"""


def test_sharded_pallas_dispatch_is_real():
    """ExecutionPlan(lcs_impl=...) must route the Pallas kernel into the
    shard_map score stage — not silently fall back to the wavefront."""
    out = run_subprocess(PALLAS_DISPATCH_CODE, devices=4)
    assert "OK" in out


FUSED_DISPATCH_CODE = r"""
import numpy as np
import repro.kernels.lcs.fused as fused
from repro.api import AnotherMeEngine, EngineConfig, ExecutionPlan
from repro.data import fig1_world

calls = []
real = fused.fused_gather_score

def counting(*args, **kwargs):
    calls.append(kwargs.get("interpret"))
    return real(*args, **kwargs)

fused.fused_gather_score = counting
batch, forest = fig1_world()
cfg = EngineConfig(rho=3.0)
single = AnotherMeEngine(forest, cfg).run(batch)
assert not calls  # default wavefront impl never touches the fused kernel

fused_single = AnotherMeEngine(
    forest, EngineConfig(rho=3.0, lcs_impl="fused-interpret"),
).run(batch)
assert calls and all(interp is True for interp in calls), calls
n_single = len(calls)

sharded = AnotherMeEngine(
    forest, cfg, ExecutionPlan(n_shards=4, lcs_impl="fused-interpret"),
).run(batch)
# traced (and therefore executed) inside the shard_map score stage too
assert len(calls) > n_single and all(i is True for i in calls), calls
assert fused_single.similar_pairs == single.similar_pairs
assert sharded.similar_pairs == single.similar_pairs
assert sharded.communities == single.communities
print("OK", len(calls))
"""


def test_fused_dispatch_is_real():
    """lcs_impl="fused-interpret" must route the gather-free fused kernel
    into BOTH score paths — not silently fall back to the gather+wavefront
    reference."""
    out = run_subprocess(FUSED_DISPATCH_CODE, devices=4)
    assert "OK" in out


STREAM_MATRIX_CODE = r"""
import numpy as np
import jax.numpy as jnp
from repro.api import AnotherMeEngine, EngineConfig, ExecutionPlan, StreamingEngine
from repro.core.types import PAD_ID, TrajectoryBatch
from repro.data import synthetic_setup

batch, forest = synthetic_setup(24, num_types=6, classes_per_type=3,
                                num_places=40, seed=3)
RHO = 2.0
IMPLS = ("wavefront", "fused-interpret")


def split(batch, k):
    P = np.asarray(batch.places); Ln = np.asarray(batch.lengths)
    cuts = np.linspace(0, P.shape[0], k + 1).astype(int)
    return [TrajectoryBatch(places=jnp.asarray(P[a:b]),
                            lengths=jnp.asarray(Ln[a:b]),
                            user_id=jnp.arange(b - a, dtype=jnp.int32))
            for a, b in zip(cuts[:-1], cuts[1:])]


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


for impl in IMPLS:
    cfg = EngineConfig(rho=RHO, lcs_impl=impl, community_mode="components")
    # the single-device STREAMING run is the reference; it must itself
    # match the one-shot engine bit-exactly
    ref = StreamingEngine(forest, cfg).update_many(split(batch, 3))
    one = AnotherMeEngine(forest, cfg).run(batch)
    assert score_map(ref) == score_map(one), impl
    assert ref.similar_pairs == one.similar_pairs
    assert ref.communities == one.communities
    for n_shards in %(shards)s:
        modes = ("replicate", "shuffle") if n_shards > 1 else ("replicate",)
        for mode in modes:
            st = StreamingEngine(
                forest, cfg,
                ExecutionPlan(n_shards=n_shards, score_mode=mode),
            )
            res = st.update_many(split(batch, 3))
            cell = (n_shards, mode, impl)
            assert res.similar_pairs == ref.similar_pairs, cell
            assert res.communities == ref.communities, cell
            assert score_map(res) == score_map(ref), cell
print("OK stream matrix")
"""


def test_streaming_parity_matrix():
    """Streaming axis of the parity matrix: SHARDS x
    {replicate, shuffle} x {wavefront, fused-interpret} micro-batched runs
    are bit-identical to the single-device streaming reference (which is
    itself pinned to the one-shot engine)."""
    out = run_subprocess(STREAM_MATRIX_CODE % {"shards": SHARDS},
                         devices=DEVICES)
    assert "OK stream matrix" in out


STREAM_RECOMPILE_CODE = r"""
import numpy as np
import jax.numpy as jnp
import repro.kernels.lcs.fused as fused
from repro.api import EngineConfig, ExecutionPlan, StreamingEngine
from repro.core.encoding import SemanticForest
from repro.core.types import TrajectoryBatch

calls = []
real = fused.fused_gather_score

def counting(*args, **kwargs):
    calls.append(kwargs.get("interpret"))
    return real(*args, **kwargs)

fused.fused_gather_score = counting

# identity 2-level forest; every update draws places from its own type
# block, so the per-update delta work is constant and the compiled runner
# must be reused verbatim
T = 64
forest = SemanticForest(parents=(np.arange(T, dtype=np.int32),),
                        sizes=(T, T))
B, L, K = 8, 6, 6

def block_batch(u):
    rng = np.random.default_rng(5)  # same relative pattern every update
    places = (u * 8 + rng.integers(0, 8, size=(B, L))).astype(np.int32)
    return TrajectoryBatch(places=jnp.asarray(places),
                           lengths=jnp.asarray(np.full((B,), L, np.int32)),
                           user_id=jnp.arange(B, dtype=jnp.int32))

for mode in ("replicate", "shuffle"):
    st = StreamingEngine(
        forest, EngineConfig(rho=2.0, lcs_impl="fused-interpret"),
        ExecutionPlan(n_shards=2, score_mode=mode),
        world_capacity=B * K,
    )
    traces = []
    n_calls = []
    for u in range(K):
        res = st.update(block_batch(u))
        traces.append(res.stats["score_traces"])
        n_calls.append(len(calls))
    # the first update compiles the streaming runner (the fused kernel is
    # really dispatched inside it: trace-time call with interpret=True)...
    assert traces[0] == 1 and n_calls[0] >= 1, (mode, traces, n_calls)
    assert all(i is True for i in calls), calls
    # ...and every later update reuses it: NO new trace, NO new kernel
    # dispatch registration — per-update cost is pure execution
    assert traces[-1] == traces[0], (mode, traces)
    assert n_calls[-1] == n_calls[0], (mode, n_calls)
    assert st.runner_builds == 1, (mode, st.runner_builds)

# hop/score overlap adds ZERO recompiles: the chunked shuffle runner's
# full per-update trace history (and runner-build count) must EQUAL the
# unchunked one — any world-growth recompile the serial path takes is
# allowed, any EXTRA trace from chunking is not
hist = {}
for oc in (1, 2):
    st = StreamingEngine(
        forest, EngineConfig(rho=2.0, lcs_impl="fused-interpret"),
        ExecutionPlan(n_shards=2, score_mode="shuffle", overlap_chunks=oc),
        world_capacity=B * K,
    )
    hist[oc] = ([st.update(block_batch(u)).stats["score_traces"]
                 for u in range(K)], st.runner_builds)
assert hist[1] == hist[2], hist
print("OK stream recompile", traces, len(calls), hist[2])
"""


def test_streaming_updates_reuse_cached_sharded_runner():
    """Real-dispatch proof for streaming: the fused kernel is traced into
    the sharded streaming runner exactly once (compilation-counting hook =
    trace-time side effects), and k subsequent equal-shape updates reuse
    the cached runner with zero recompiles."""
    out = run_subprocess(STREAM_RECOMPILE_CODE, devices=4)
    assert "OK stream recompile" in out


AUTOTUNE_OVERLAP_MATRIX_CODE = r"""
import os
import tempfile

import numpy as np
import repro.api.stages as stages
from repro.api import AnotherMeEngine, EngineConfig, ExecutionPlan
from repro.core.types import PAD_ID
from repro.data import fig1_world

# a throwaway tuning table with a NON-default parameter: int32
# diagonals (env default int8) — parity must
# hold precisely because tuned values may only change throughput
os.environ.pop("REPRO_LCS_DTYPE", None)
os.environ["REPRO_TUNING_PATH"] = os.path.join(
    tempfile.mkdtemp(), "TUNING.json"
)
from repro.perf import LCSTuning, TuningTable

batch, forest = fig1_world()
L = int(np.asarray(batch.places).shape[1])
TUNED = LCSTuning(wavefront_dtype="int32")
table = TuningTable()
table.record(1024, forest.num_levels, L, TUNED)  # nearest-P covers all P
table.save()

seen = []
real = stages.lcs_impl_fn

def recording(name, tuning=None):
    seen.append(tuning)
    return real(name, tuning)

stages.lcs_impl_fn = recording

RHO = 3.0


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


for impl in ("wavefront", "fused-interpret"):
    cfg = EngineConfig(backend="ssh", rho=RHO, lcs_impl=impl)
    seen.clear()
    want = AnotherMeEngine(forest, cfg).run(batch)
    # untuned runs never see a tuning record (autotune=False never probes)
    assert all(t is None for t in seen), seen
    for n_shards in %(shards)s:
        modes = ("replicate", "shuffle") if n_shards > 1 else ("replicate",)
        for mode in modes:
            for oc in ((2, 4) if mode == "shuffle" else (4,)):
                seen.clear()
                res = AnotherMeEngine(
                    forest, cfg,
                    ExecutionPlan(n_shards=n_shards, score_mode=mode,
                                  autotune=True, overlap_chunks=oc),
                ).run(batch)
                cell = (impl, n_shards, mode, oc)
                assert res.similar_pairs == want.similar_pairs, cell
                assert res.communities == want.communities, cell
                assert score_map(res) == score_map(want), cell
                if impl == "wavefront" and n_shards > 1:
                    # real-dispatch proof: the tuned record reached the
                    # impl closure (not silently missed to defaults)
                    assert TUNED in seen, (cell, seen)
print("OK autotune overlap matrix")
"""


def test_autotune_overlap_parity_matrix():
    """Autotune + overlap axis: non-default tuned kernel parameters and
    chunked hop/score overlap stay bit-identical to the untuned serial
    defaults across the full one-shot matrix, with a real-dispatch proof
    that the tuned record reaches the impl closure."""
    out = run_subprocess(
        AUTOTUNE_OVERLAP_MATRIX_CODE % {"shards": SHARDS}, devices=DEVICES
    )
    assert "OK autotune overlap matrix" in out


STREAM_AUTOTUNE_OVERLAP_CODE = r"""
import os
import tempfile

import numpy as np
import jax.numpy as jnp
from repro.api import EngineConfig, ExecutionPlan, StreamingEngine
from repro.core.types import PAD_ID, TrajectoryBatch
from repro.data import synthetic_setup

os.environ.pop("REPRO_LCS_DTYPE", None)
os.environ["REPRO_TUNING_PATH"] = os.path.join(
    tempfile.mkdtemp(), "TUNING.json"
)
from repro.perf import LCSTuning, TuningTable

batch, forest = synthetic_setup(24, num_types=6, classes_per_type=3,
                                num_places=40, seed=3)
L = int(np.asarray(batch.places).shape[1])
table = TuningTable()
table.record(1024, forest.num_levels, L,
             LCSTuning(wavefront_dtype="int32"))
table.save()

RHO = 2.0


def split(batch, k):
    P = np.asarray(batch.places); Ln = np.asarray(batch.lengths)
    cuts = np.linspace(0, P.shape[0], k + 1).astype(int)
    return [TrajectoryBatch(places=jnp.asarray(P[a:b]),
                            lengths=jnp.asarray(Ln[a:b]),
                            user_id=jnp.arange(b - a, dtype=jnp.int32))
            for a, b in zip(cuts[:-1], cuts[1:])]


def score_map(res):
    left = np.asarray(res.scored.left)
    right = np.asarray(res.scored.right)
    mss = np.asarray(res.scored.mss)
    keep = left != PAD_ID
    return {(int(a), int(b)): float(m)
            for a, b, m in zip(left[keep], right[keep], mss[keep])}


for impl in ("wavefront", "fused-interpret"):
    cfg = EngineConfig(rho=RHO, lcs_impl=impl, community_mode="components")
    ref = StreamingEngine(forest, cfg).update_many(split(batch, 3))
    for dj in ("host", "device"):
        for oc in (2, 4):
            st = StreamingEngine(
                forest, cfg,
                ExecutionPlan(n_shards=2, score_mode="shuffle",
                              delta_join=dj, autotune=True,
                              overlap_chunks=oc),
            )
            res = st.update_many(split(batch, 3))
            cell = (impl, dj, oc)
            assert res.similar_pairs == ref.similar_pairs, cell
            assert res.communities == ref.communities, cell
            assert score_map(res) == score_map(ref), cell
print("OK stream autotune overlap")
"""


def test_streaming_autotune_overlap_parity():
    """Streaming axis of the autotune + overlap matrix: tuned parameters
    plus chunked shuffle scoring stay bit-identical to the single-device
    streaming reference across both delta_join paths."""
    out = run_subprocess(STREAM_AUTOTUNE_OVERLAP_CODE, devices=4)
    assert "OK stream autotune overlap" in out


DELTA_JOIN_MATRIX_CODE = r"""
import numpy as np
import jax.numpy as jnp
from repro.api import AnotherMeEngine, EngineConfig, ExecutionPlan, StreamingEngine
from repro.core.types import PAD_ID, TrajectoryBatch
from repro.data import synthetic_setup

batch, forest = synthetic_setup(24, num_types=6, classes_per_type=3,
                                num_places=40, seed=3)
RHO = 2.0
IMPLS = ("wavefront", "fused-interpret")


def split(batch, k):
    P = np.asarray(batch.places); Ln = np.asarray(batch.lengths)
    cuts = np.linspace(0, P.shape[0], k + 1).astype(int)
    return [TrajectoryBatch(places=jnp.asarray(P[a:b]),
                            lengths=jnp.asarray(Ln[a:b]),
                            user_id=jnp.arange(b - a, dtype=jnp.int32))
            for a, b in zip(cuts[:-1], cuts[1:])]


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


for impl in IMPLS:
    cfg = EngineConfig(rho=RHO, lcs_impl=impl, community_mode="components")
    one = AnotherMeEngine(forest, cfg).run(batch)
    for mode in ("replicate", "shuffle"):
        results = {}
        for dj in ("host", "device"):
            st = StreamingEngine(
                forest, cfg,
                ExecutionPlan(n_shards=2, score_mode=mode, delta_join=dj),
            )
            results[dj] = st.update_many(split(batch, 3))
        cell = (impl, mode)
        # end-to-end EngineResult bit-identity across the delta_join axis,
        # and against the one-shot engine
        assert score_map(results["device"]) == score_map(results["host"]), cell
        assert score_map(results["device"]) == score_map(one), cell
        assert results["device"].similar_pairs == results["host"].similar_pairs, cell
        assert results["device"].communities == results["host"].communities, cell
        assert results["device"].communities == one.communities, cell
        assert (results["device"].stats["full_world_pairs"]
                == results["host"].stats["full_world_pairs"]), cell
print("OK delta_join matrix")
"""


def test_streaming_delta_join_parity_matrix():
    """delta_join axis of the parity matrix: {host, device} x
    {replicate, shuffle} x {wavefront, fused-interpret} streaming runs are
    bit-identical to each other and to the one-shot engine."""
    out = run_subprocess(DELTA_JOIN_MATRIX_CODE, devices=4)
    assert "OK delta_join matrix" in out


DEVICE_JOIN_DISPATCH_CODE = r"""
import numpy as np
import jax.numpy as jnp
import repro.core.stream_index as stream_index
from repro.api import EngineConfig, ExecutionPlan, StreamingEngine
from repro.core.types import TrajectoryBatch
from repro.data import synthetic_setup

calls = []
real = stream_index.BucketIndex.insert

def counting(self, *args, **kwargs):
    calls.append(args)
    return real(self, *args, **kwargs)

stream_index.BucketIndex.insert = counting

batch, forest = synthetic_setup(16, num_types=6, classes_per_type=3,
                                num_places=40, seed=1)

def split(batch, k):
    P = np.asarray(batch.places); Ln = np.asarray(batch.lengths)
    cuts = np.linspace(0, P.shape[0], k + 1).astype(int)
    return [TrajectoryBatch(places=jnp.asarray(P[a:b]),
                            lengths=jnp.asarray(Ln[a:b]),
                            user_id=jnp.arange(b - a, dtype=jnp.int32))
            for a, b in zip(cuts[:-1], cuts[1:])]

cfg = EngineConfig(rho=2.0, community_mode="components")
dev = StreamingEngine(
    forest, cfg, ExecutionPlan(n_shards=2, delta_join="device"),
).update_many(split(batch, 4))
# the device path NEVER consults the driver-resident bucket table
assert not calls, f"device path called BucketIndex.insert {len(calls)}x"

host = StreamingEngine(
    forest, cfg, ExecutionPlan(n_shards=2, delta_join="host"),
).update_many(split(batch, 4))
# ...while the host path really does (the counter is live)
assert len(calls) == 4, len(calls)
assert dev.similar_pairs == host.similar_pairs
assert dev.communities == host.communities
print("OK device join dispatch", len(calls))
"""


def test_device_join_never_calls_bucket_index():
    """Real-dispatch proof for delta_join="device": the join state lives
    in-mesh — BucketIndex.insert (the driver-side join) is never invoked,
    while the monkeypatched counter confirms the host path still routes
    through it."""
    out = run_subprocess(DEVICE_JOIN_DISPATCH_CODE, devices=4)
    assert "OK device join dispatch" in out


def test_sharded_engine_has_no_host_encode_stage():
    """n_shards>1 folds Encode into the fused shard_map stage: no host
    EncodeStage, so the code table never materializes replicated."""
    from repro.api import AnotherMeEngine, EngineConfig, ExecutionPlan
    from repro.data import fig1_world

    _, forest = fig1_world()
    eng = AnotherMeEngine(forest, EngineConfig(), ExecutionPlan(n_shards=4))
    names = [s.name for s in eng._stages]
    assert "encode" not in names
    assert names[0] == "sharded_encode_join_score"


def test_plan_lcs_impl_override_folds_into_config():
    from repro.api import AnotherMeEngine, EngineConfig, ExecutionPlan
    from repro.data import fig1_world

    _, forest = fig1_world()
    eng = AnotherMeEngine(
        forest, EngineConfig(lcs_impl="wavefront"),
        ExecutionPlan(lcs_impl="fused-pallas"),
    )
    assert eng.config.lcs_impl == "fused-pallas"
    import pytest as _pytest

    with _pytest.raises(ValueError, match="lcs_impl"):
        AnotherMeEngine(forest, EngineConfig(),
                        ExecutionPlan(lcs_impl="no-such-impl"))


SUBTRAJ_MATRIX_CODE = r"""
import numpy as np
from repro.api import AnotherMeEngine, EngineConfig, ExecutionPlan
from repro.data import synthetic_setup

backend = "%(backend)s"
batch, forest = synthetic_setup(48, num_types=8, classes_per_type=4,
                                num_places=60, seed=3)
RHO = 1.05
IMPLS = ("wavefront", "fused-interpret")


def score_map(res):
    sc = res.scored
    cnt = int(sc.count)
    left = np.asarray(sc.left)[:cnt]
    right = np.asarray(sc.right)[:cnt]
    mss = np.asarray(sc.mss)[:cnt]
    lvl = np.asarray(sc.level_lcs)[:cnt]
    return {
        (int(a), int(b)): (float(m), tuple(int(x) for x in lv))
        for a, b, m, lv in zip(left, right, mss, lvl)
    }


for impl in IMPLS:
    cfg = EngineConfig(backend=backend, k=2, rho=RHO, lcs_impl=impl,
                       subtraj_window=5, subtraj_stride=1)
    # the single-device subtrajectory engine is the reference; it is
    # itself pinned to the brute-force windowed oracle in
    # test_subtrajectory.py
    want = AnotherMeEngine(forest, cfg).run(batch)
    for n_shards in %(shards)s:
        modes = ("replicate", "shuffle") if n_shards > 1 else ("replicate",)
        for mode in modes:
            eng = AnotherMeEngine(
                forest, cfg,
                ExecutionPlan(n_shards=n_shards, score_mode=mode),
            )
            res = eng.run(batch)
            cell = (backend, n_shards, mode, impl)
            assert res.similar_pairs == want.similar_pairs, cell
            assert res.communities == want.communities, cell
            assert score_map(res) == score_map(want), cell
            if n_shards > 1:
                # steady state: a same-shape re-run keeps the ONE sticky
                # mesh plan (beside it sits the communities edge buffer's
                # ("similar_edges", N) capacity), changes no sticky entry
                # and compiles no mesh program in windowed mode
                plans = dict(eng._sticky)
                res2 = eng.run(batch)
                mesh_plans = [k for k in eng._sticky
                              if k[0] != "similar_edges"]
                assert len(mesh_plans) == 1 and eng._sticky == plans, cell
                assert not {"plan", "join", "score", "results"} & set(
                    res2.stats["compiles_by_phase"]), cell
                assert score_map(res2) == score_map(res), cell
print("OK subtraj", backend)
"""


@pytest.mark.parametrize("backend", BACKENDS)
def test_subtraj_parity_matrix(backend):
    """Subtrajectory axis of the parity matrix: SHARDS x
    {replicate, shuffle} x {wavefront, fused-interpret} windowed runs are
    bit-identical to the single-device subtrajectory engine, and re-runs
    reuse the cached sharded runner (zero steady-state recompiles)."""
    out = run_subprocess(SUBTRAJ_MATRIX_CODE % {"backend": backend,
                                                "shards": SHARDS},
                         devices=DEVICES)
    assert f"OK subtraj {backend}" in out
