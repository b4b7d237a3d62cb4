"""Distributed (shard_map) AnotherMe == single-device, on 8 virtual devices.

Runs in a subprocess because XLA's host device count must be fixed before
jax initializes.
"""
from conftest import run_subprocess

CODE = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.api import BackendContext, MeshStages, get_backend
from repro.core import *
from repro.core.distributed import (
    plan_capacities, gather_similar_pairs, pad_to_shards)
from repro.api.sharded import unpack_stats
from repro.core.encoding import encode_types, forest_tables
from repro.core.shingling import shingles_from_types
from repro.core.types import TrajectoryBatch
from repro.data import synthetic_setup

MODE = "replicate"
assert len(jax.devices()) == 8
batch, forest = synthetic_setup(296, num_types=10, classes_per_type=5,
                                num_places=200, seed=3)
tables = forest_tables(forest)
n_shards = 8
places, lengths = pad_to_shards(
    np.asarray(batch.places), np.asarray(batch.lengths), n_shards)
bp = TrajectoryBatch(jnp.asarray(places), jnp.asarray(lengths),
                     jnp.arange(places.shape[0]))
keys_np = np.asarray(shingles_from_types(
    encode_types(bp.places, tables), bp.lengths, k=3,
    num_types=forest.num_types))
# the host oracle's plan, hand to the engine's staged programs
plan = plan_capacities(keys_np, n_shards, score_mode=MODE)
from repro.core import compat
mesh = compat.make_mesh((n_shards,), ("ex",))
stages = MeshStages(
    mesh, n_shards=n_shards, local_n=plan.local_n, betas=default_betas(3),
    key_fn=get_backend("ssh").shard_key_fn(
        BackendContext(k=3, num_types=forest.num_types)),
    axis_name="ex", score_mode=MODE, lcs_impl="wavefront",
    score_prune=False, prune_tau=0.0, subtraj=None)
rk, rid, st1 = stages.route(plan.shingle_route_cap)(
    bp.places, bp.places, bp.lengths, tables)
left, right, st2 = stages.join(plan.local_pair_cap, plan.pair_route_cap)(
    rk, rid, bp.lengths)
l, r, lvl, mss, st3 = stages.score(plan)(left, right, bp.places, bp.lengths,
                                         tables)
busts = [unpack_stats(st, stages.layout(name))[key].sum()
         for st, name, key in ((st1, "route", "overflow"),
                               (st2, "join", "overflow"),
                               (st2, "join", "route_overflow"),
                               (st3, "score", "overflow"))]
assert sum(busts) == 0, ("capacity overflow", busts)
dist_pairs = gather_similar_pairs(dict(left=l, right=r, mss=mss), rho=2.0)
res = run_anotherme(batch, forest, AnotherMeConfig())
assert dist_pairs == res.similar_pairs, (
    len(dist_pairs - res.similar_pairs), len(res.similar_pairs - dist_pairs))
print("OK", len(dist_pairs))
"""


def test_distributed_matches_single_device():
    out = run_subprocess(CODE, devices=8)
    assert "OK" in out


CODE_SHUFFLE = CODE.replace('MODE = "replicate"', 'MODE = "shuffle"')


def test_distributed_shuffle_scoring_matches():
    """score_mode='shuffle': codes stay sharded, pairs are routed to their
    owners' shards (two extra all_to_all) — per-device memory O(N/shards).
    Must be bit-identical to the replicate mode and the single device."""
    assert 'MODE = "shuffle"' in CODE_SHUFFLE  # guard the replace
    out = run_subprocess(CODE_SHUFFLE, devices=8)
    assert "OK" in out


CODE_DRYRUN = r"""
import jax
from repro.api import BackendContext, DistributedPlan, get_backend
from repro.core import compat
from repro.launch.dryrun import compile_mesh_stages, stages_costs
assert len(jax.devices()) == 8

mesh = compat.make_mesh((8,), ("ex",))
plan = DistributedPlan(n_shards=8, local_n=64, shingle_route_cap=1 << 10,
                       local_pair_cap=1 << 12, pair_route_cap=1 << 10,
                       scored_cap=1 << 12)
key_fn = get_backend("ssh").shard_key_fn(BackendContext(k=3, num_types=30))
compiled = compile_mesh_stages(mesh, plan, key_fn, L=10, num_places=500)
assert list(compiled) == ["route", "join", "score"], list(compiled)
for name in ("route", "join"):
    assert "all-to-all" in compiled[name].as_text(), name
costs = stages_costs(compiled)
assert costs["collectives"]["total_bytes"] > 0, costs["collectives"]
assert costs["memory"]["peak_bytes_est"] > 0
print("OK", costs["collectives"]["total_bytes"])
"""


def test_dryrun_compiles_the_engines_staged_programs():
    """The dry-run lowers the programs the engine runs (route, join,
    score of ``MeshStages``) from a hand-set plan, each fed the shapes the
    stage before returns."""
    out = run_subprocess(CODE_DRYRUN, devices=8)
    assert "OK" in out
