"""The redesigned public API (repro.api): engine/backends/plan contracts.

* AnotherMeEngine output (similar_pairs, communities) is identical to the
  legacy run_anotherme for every registered backend (single device).
* ExecutionPlan(n_shards>1) is identical to n_shards=1 and to the legacy
  shard_map path, for all four backends, on the Fig. 1 example world
  (subprocess: device count binds at jax init).
* The backend registry rejects unknown names with the list of valid keys.
* lcs_impl="ref" really runs (and unknown impl names raise).
* Candidate timing is reported as t_candidates in both branches.
"""
import numpy as np
import pytest

from conftest import run_subprocess
from repro.api import (
    AnotherMeEngine, EngineConfig, ExecutionPlan, available_backends,
    get_backend,
)
from repro.core import (
    AnotherMeConfig, brp_candidates, minhash_candidates, run_anotherme,
    type_codes, udf_pipeline,
)
from repro.data import fig1_world, synthetic_setup

BACKENDS = ("ssh", "minhash", "brp", "udf")


@pytest.fixture(scope="module")
def world():
    return synthetic_setup(
        150, num_types=10, classes_per_type=5, num_places=200, seed=7
    )


def legacy_result(batch, forest, backend, config=AnotherMeConfig()):
    """The pre-redesign equivalent of each registry backend."""
    if backend in ("ssh", "udf"):  # udf: same logic as ssh, black box
        return run_anotherme(batch, forest, config)
    if backend == "minhash":
        fn = lambda e, b: minhash_candidates(
            type_codes(e), b.lengths, num_perm=16, bands=4,
            pair_capacity=1 << 18,
        )
    else:
        fn = lambda e, b: brp_candidates(
            type_codes(e), b.lengths, num_types=forest.num_types,
            pair_capacity=1 << 18,
        )
    return run_anotherme(batch, forest, config, candidate_fn=fn)


@pytest.mark.parametrize("backend", BACKENDS)
def test_engine_matches_legacy_per_backend(world, backend):
    batch, forest = world
    res = AnotherMeEngine(forest, EngineConfig(backend=backend)).run(batch)
    ref = legacy_result(batch, forest, backend)
    assert res.similar_pairs == ref.similar_pairs
    assert res.communities == ref.communities


def test_udf_backend_matches_udf_pipeline(world):
    batch, forest = world
    res = AnotherMeEngine(forest, EngineConfig(backend="udf")).run(batch)
    similar_udf, _ = udf_pipeline(
        np.asarray(batch.places), np.asarray(batch.lengths), forest
    )
    assert res.similar_pairs == similar_udf


def test_engine_fig1_all_backends():
    """Fig. 1: every backend runs by registry name on the worked example;
    SSH/UDF (lossless) must pair Carol with Dave."""
    batch, forest = fig1_world()
    cfg_rho = 3.0
    for backend in BACKENDS:
        res = AnotherMeEngine(
            forest, EngineConfig(backend=backend, rho=cfg_rho)
        ).run(batch)
        ref = legacy_result(batch, forest, backend, AnotherMeConfig(rho=cfg_rho))
        assert res.similar_pairs == ref.similar_pairs, backend
        assert res.communities == ref.communities, backend
    ssh = AnotherMeEngine(forest, EngineConfig(rho=cfg_rho)).run(batch)
    assert (0, 1) in ssh.similar_pairs


def test_registry_unknown_backend_lists_valid_keys():
    with pytest.raises(ValueError) as ei:
        get_backend("no-such-hash")
    msg = str(ei.value)
    assert "no-such-hash" in msg
    for name in BACKENDS:
        assert name in msg


def test_registry_lists_all_four():
    assert set(BACKENDS) <= set(available_backends())


def test_backend_options_forwarded(world):
    batch, forest = world
    res16 = AnotherMeEngine(
        forest, EngineConfig(backend="minhash",
                             backend_options={"num_perm": 16, "bands": 4})
    ).run(batch)
    res4 = AnotherMeEngine(
        forest, EngineConfig(backend="minhash",
                             backend_options={"num_perm": 4, "bands": 2})
    ).run(batch)
    ref = legacy_result(batch, forest, "minhash")
    assert res16.similar_pairs == ref.similar_pairs
    # different banding => different candidate set (sanity that options bite)
    assert res4.stats["num_candidates"] != res16.stats["num_candidates"]


def test_lcs_impl_ref_runs_and_matches(world):
    batch, forest = world
    wave = AnotherMeEngine(forest, EngineConfig(lcs_impl="wavefront")).run(batch)
    ref = AnotherMeEngine(forest, EngineConfig(lcs_impl="ref")).run(batch)
    assert ref.similar_pairs == wave.similar_pairs
    legacy = run_anotherme(batch, forest, AnotherMeConfig(lcs_impl="ref"))
    assert legacy.similar_pairs == wave.similar_pairs


def test_lcs_impl_unknown_raises(world):
    batch, forest = world
    with pytest.raises(ValueError, match="wavefront"):
        AnotherMeEngine(forest, EngineConfig(lcs_impl="diagonal"))
    with pytest.raises(ValueError, match="lcs_impl"):
        run_anotherme(batch, forest, AnotherMeConfig(lcs_impl="diagonal"))


def test_candidate_timing_reported_in_both_branches(world):
    batch, forest = world
    direct = run_anotherme(batch, forest, AnotherMeConfig())
    baseline = legacy_result(batch, forest, "minhash")
    for res in (direct, baseline):
        assert res.stats["t_candidates"] > 0.0
        assert res.stats["t_candidates"] == pytest.approx(
            res.stats["t_keys"] + res.stats["t_join"]
        )
    # the baseline's hash cost must NOT be booked under the key phase
    # (a key-less backend leaves only context-manager noise there)
    assert baseline.stats["t_keys"] < baseline.stats["t_join"]


SHARDED_CODE = r"""
import jax
from repro.api import AnotherMeEngine, EngineConfig, ExecutionPlan
from repro.data import fig1_world, synthetic_setup

assert len(jax.devices()) == 8

# Fig. 1 example world: all four backends, sharded == single-device
batch, forest = fig1_world()
for backend in ("ssh", "minhash", "brp", "udf"):
    cfg = EngineConfig(backend=backend, rho=3.0)
    single = AnotherMeEngine(forest, cfg).run(batch)
    sharded = AnotherMeEngine(forest, cfg, ExecutionPlan(n_shards=8)).run(batch)
    assert sharded.similar_pairs == single.similar_pairs, backend
    assert sharded.communities == single.communities, backend
ssh = AnotherMeEngine(forest, EngineConfig(rho=3.0),
                      ExecutionPlan(n_shards=8)).run(batch)
assert (0, 1) in ssh.similar_pairs
# the join and score programs are timed where they end in a host read or
# a device sync, inside t_execute: no made-up t_score
assert {"t_execute", "t_join", "t_score", "t_results", "compiles"} <= set(
    ssh.stats), sorted(ssh.stats)
assert 0 < ssh.stats["t_join"] + ssh.stats["t_score"] <= ssh.stats["t_execute"]

# a denser world: ssh + minhash, sharded == single
batch, forest = synthetic_setup(120, num_types=10, classes_per_type=5,
                                num_places=150, seed=3)
for backend in ("ssh", "minhash"):
    cfg = EngineConfig(backend=backend)
    single = AnotherMeEngine(forest, cfg).run(batch)
    sharded = AnotherMeEngine(forest, cfg, ExecutionPlan(n_shards=8)).run(batch)
    assert sharded.similar_pairs == single.similar_pairs, backend
    assert sharded.communities == single.communities, backend
print("OK")
"""


def test_sharded_engine_parity():
    out = run_subprocess(SHARDED_CODE, devices=8)
    assert "OK" in out


def test_callable_backend_rejects_sharded_plan(world):
    from repro.api import CallableBackend

    batch, forest = world
    fn = lambda e, b: minhash_candidates(
        type_codes(e), b.lengths, num_perm=16, bands=4, pair_capacity=1 << 18
    )
    with pytest.raises(ValueError, match="n_shards=1"):
        AnotherMeEngine(
            forest, EngineConfig(), ExecutionPlan(n_shards=2),
            backend=CallableBackend(fn),
        )
