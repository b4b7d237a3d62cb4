"""Compile the LCS scoring kernels and the SSH join for a described TPU v5e.

Nothing runs: each test lowers and compiles at real widths for one chip of
a described ``v5e:2x2`` topology, so Mosaic refusals (block shapes,
unsupported primitives, vector dtypes, relayouts, SMEM and HBM limits)
surface here instead of on the chip.  Each kernel test asserts the Pallas
kernel is in the compiled program (``tpu_custom_call``); the join test
reads which ops its pair enumeration compiled to.

The topology is described inside a fixture (never at import), so every
test worker collects the same tests and only the worker that runs this
file loads the TPU compiler.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

N, H = 1 << 20, 3
V5E_HBM = 16 * 2**30
# pairs per call: the score chunk similarity.score_chunk derives for the
# lane-dense kernel path on a 16 GB v5e at H=3, L=10
P_CHUNK = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # programs compiled for a described chip cannot be read back from the
    # persistent cache without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _table_args(one_chip, L, P):
    return (
        _spec(one_chip, (N, H, L)), _spec(one_chip, (N,)),
        _spec(one_chip, (N, H, L)), _spec(one_chip, (N,)),
        _spec(one_chip, (P,)), _spec(one_chip, (P,)),
    )


@pytest.mark.parametrize("L", [10, 16])
def test_fused_gather_score_compiles(one_chip, L):
    from repro.kernels.lcs.fused import fused_gather_score

    P = P_CHUNK
    compiled = jax.jit(fused_gather_score).lower(
        *_table_args(one_chip, L, P), _spec(one_chip, (H,), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("L", [10, 16])
def test_fused_windowed_gather_score_compiles(one_chip, L):
    from repro.kernels.lcs.fused import fused_windowed_gather_score

    P = P_CHUNK
    fn = jax.jit(functools.partial(fused_windowed_gather_score, window=4))
    compiled = fn.lower(
        *_table_args(one_chip, L, P),
        _spec(one_chip, (P,)), _spec(one_chip, (P,)),
        _spec(one_chip, (H,), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("L", [10, 16])
def test_lcs_pallas_compiles(one_chip, L):
    from repro.kernels.lcs.kernel import lcs_pallas

    B = P_CHUNK * H  # one chunk of pairs, levels folded into the batch
    compiled = jax.jit(lcs_pallas).lower(
        _spec(one_chip, (B, L)), _spec(one_chip, (B, L)),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("impl_name", ["fused", "wavefront"])
def test_chunked_score_pairs_fits_one_chip(one_chip, monkeypatch, impl_name):
    """The paper's scalability world, ~5e7 candidate pairs, scores in one
    program on one 16 GB chip: the chunk loop bounds the temporaries."""
    from repro.core import compat
    from repro.core.similarity import score_pairs

    L, P = 10, 50_000_000
    monkeypatch.setattr(compat, "device_memory_bytes", lambda: V5E_HBM)
    monkeypatch.setattr(compat, "backend_name", lambda: "tpu")
    fn = jax.jit(functools.partial(score_pairs, impl_name=impl_name))
    args = _table_args(one_chip, L, P)
    compiled = fn.lower(
        args[0], args[1], args[4], args[5],
        _spec(one_chip, (H,), jnp.float32),
    ).compile()
    mem = compiled.memory_analysis()
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    assert used < V5E_HBM // 4, used
    assert ("tpu_custom_call" in compiled.as_text()) == (impl_name == "fused")


def test_ssh_pair_enumeration_has_no_binary_search(one_chip):
    """The join expands rows to pair slots with sorts and running scans: no
    ``searchsorted`` loop in ``ssh/pairs_from_rows``, and one gather left
    there (the partner's id).  The shape is small: on a CPU host the TPU
    compiler takes tens of seconds for each sort of more than 16K elements."""
    from repro.core.ssh import ssh_candidates

    compiled = ssh_candidates.lower(
        _spec(one_chip, (64, 120)), pair_capacity=1 << 13
    ).compile()
    ops = [line for line in compiled.as_text().splitlines()
           if "ssh/pairs_from_rows" in line]
    assert any(re.search(r"\) sort\(", line) for line in ops)
    assert not any("searchsorted" in line or " while(" in line for line in ops)
    assert sum(bool(re.search(r"= \S+ gather\(", line)) for line in ops) <= 1


@pytest.fixture(scope="module")
def four_chips(one_chip):
    from jax.experimental import topologies

    from repro.core import compat

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return compat.make_mesh((4,), ("ex",), devices=list(topo.devices))


def test_mesh_programs_sort_unstably(four_chips):
    """The one-shot mesh programs (route, join, score, similar), compiled
    for a described v5e 2x2 at a small size, hold no stable sort: the TPU
    compiler gives a stable sort an iota operand, and at 2^26 entries such
    a sort takes it several times as long to build as an unstable one, so
    a cold job's set-up would grow by minutes."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.api import BackendContext, DistributedPlan, MeshStages, get_backend
    from repro.core.similarity import default_betas

    mesh, n, local_n, L = four_chips, 4, 256, 10
    plan = DistributedPlan(
        n_shards=n, local_n=local_n, shingle_route_cap=1 << 12,
        local_pair_cap=1 << 14, pair_route_cap=1 << 12, scored_cap=1 << 14,
        n_chunks=4, chunk_hop_cap=1 << 11, chunk_rest_cap=1 << 13)
    stages = MeshStages(
        mesh, n_shards=n, local_n=local_n, betas=default_betas(3),
        key_fn=get_backend("ssh").shard_key_fn(
            BackendContext(k=3, num_types=300)),
        axis_name="ex", score_mode="shuffle", lcs_impl="fused",
        score_prune=False, prune_tau=2.0, subtraj=None, rho=2.0)
    rows, flat = NamedSharding(mesh, P("ex", None)), NamedSharding(mesh, P("ex"))
    places = _spec(rows, (n * local_n, L))
    lengths = _spec(flat, (n * local_n,))
    tables = _spec(NamedSharding(mesh, P()), (3, 10_000))

    def sharded(outs):
        return [_spec(flat, x.shape, x.dtype) for x in outs]

    route = stages.route(plan.shingle_route_cap)
    join = stages.join(plan.local_pair_cap, plan.pair_route_cap)
    rk, rid, _ = sharded(jax.eval_shape(route, places, places, lengths,
                                        tables))
    left, right, _ = sharded(jax.eval_shape(join, rk, rid, lengths))
    mss = _spec(flat, left.shape, jnp.float32)
    programs = {
        "route": route.lower(places, places, lengths, tables),
        "join": join.lower(rk, rid, lengths),
        "score": stages.score(plan).lower(left, right, places, lengths,
                                          tables),
        "similar": stages.similar(1 << 10).lower(left, right, mss),
    }
    for name, lowered in programs.items():
        sorts = [line for line in lowered.compile().as_text().splitlines()
                 if re.search(r" sort\(", line)]
        assert sorts, name
        assert not any("is_stable=true" in line for line in sorts), name
