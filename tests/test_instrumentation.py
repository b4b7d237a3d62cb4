"""The program's own recorder (api/instrumentation.py): every phase is a
profiler span, compiles are counted into the phase that made them, and an
engine run reports them."""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import AnotherMeEngine, EngineConfig, Instrumentation
from repro.api.instrumentation import BACKEND_COMPILE
from repro.core.types import TrajectoryBatch
from repro.data import synthetic_setup

PIPELINE_PHASES = ("encode", "keys", "join", "score", "results",
                   "communities")


def host_spans(trace_dir) -> set:
    """Names of the events on the host planes of a profiler trace."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert files, f"no trace written under {trace_dir}"
    return {e.name for p in ProfileData.from_file(files[0]).planes
            if p.name.startswith("/host")
            for line in p.lines for e in line.events}


class BackendCompiles:
    """An independent count of backend compiles, as a profiler or a
    benchmark harness would take it from jax.monitoring."""

    def __init__(self):
        self.n = 0

    def __call__(self, event, secs, **kwargs):
        self.n += event == BACKEND_COMPILE

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


@pytest.fixture(scope="module")
def world():
    # a size no other test uses, so the first run compiles its own shapes
    return synthetic_setup(97, num_types=10, classes_per_type=5,
                           num_places=200, seed=11)


def test_phase_is_a_profiler_span(tmp_path):
    instr = Instrumentation()
    x = jnp.arange(5)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with instr.phase("probe"):
            (x + 1).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert "phase.probe" in host_spans(str(tmp_path))
    assert instr.stats["t_probe"] > 0
    # harness hooks that would wrap the method see it is spanned already
    assert Instrumentation.phase.annotated is True


def test_reentered_phase_accumulates_its_time():
    instr = Instrumentation()
    for _ in range(2):
        with instr.phase("twice"):
            time.sleep(0.01)
    assert instr.stats["t_twice"] >= 0.02


def test_a_phase_counts_its_own_compiles():
    # nested jits: each traces with an event of its own inside its caller's
    inner = jax.jit(lambda x: jnp.sort(x) * 3)
    f = jax.jit(lambda x: inner(x) + jnp.cumsum(x))
    x = jnp.arange(23, dtype=jnp.float32)
    with Instrumentation() as instr:
        with instr.phase("outer"):
            with instr.phase("inner"):
                f(x).block_until_ready()
    s = instr.finalize()
    assert s["compiles"] >= 1 and s["compile_s"] > 0
    # the innermost open phase takes the compile, not its parent
    assert set(s["compiles_by_phase"]) == {"inner"}
    n, secs = s["compiles_by_phase"]["inner"]
    assert n == s["compiles"] and secs == s["compile_s"]
    # nested events are counted once: no more compile time than wall time
    assert s["compile_s"] <= s["t_inner"]

    with Instrumentation() as warm:
        with warm.phase("outer"):
            f(x).block_until_ready()
    w = warm.finalize()
    assert (w["compiles"], w["compile_s"], w["compiles_by_phase"]) == \
        (0, 0, {})


def test_nested_compile_events_count_once():
    """A jit traces the jits it calls inside its own trace event; the
    seconds are their union, not their sum."""
    trace = "/jax/core/compile/jaxpr_trace_duration"
    record = jax.monitoring.record_event_time_span
    with Instrumentation() as instr:
        with instr.phase("p"):
            record(trace, 10.0, 11.0)          # an inner jit
            record(trace, 11.5, 12.0)          # its sibling
            record(trace, 9.0, 13.0)           # the jit that called both
            record(BACKEND_COMPILE, 13.5, 15.0)
        record("/jax/some/other_event", 0.0, 100.0)
    s = instr.finalize()
    assert s["compiles_by_phase"] == {"p": [1, 5.5]}
    assert (s["compiles"], s["compile_s"]) == (1, 5.5)


def test_a_compile_outside_every_phase_counts_under_run():
    g = jax.jit(lambda x: x * 7 - 1)
    x = jnp.arange(19)
    with Instrumentation() as instr:
        g(x).block_until_ready()
        with instr.phase("quiet"):
            pass
    s = instr.finalize()
    assert list(s["compiles_by_phase"]) == ["run"]
    assert s["compiles_by_phase"]["run"][0] == s["compiles"] >= 1
    # outside any run nothing is counted, and no recorder is left open
    g(jnp.arange(20)).block_until_ready()
    assert instr.compiles_by_phase["run"][0] == s["compiles"]


def test_engine_run_reports_spans_results_and_compiles(world, tmp_path):
    batch, forest = world
    engine = AnotherMeEngine(forest,
                             EngineConfig(community_mode="components"))
    with BackendCompiles() as seen, jax.profiler.trace(str(tmp_path)):
        cold = engine.run(batch)
    s = cold.stats
    assert {f"phase.{p}" for p in PIPELINE_PHASES} <= host_spans(
        str(tmp_path))
    assert {f"t_{p}" for p in PIPELINE_PHASES} <= set(s)
    assert "t_shingle" not in s and "t_total" not in s
    # every backend compile of the run is counted, each in one phase
    assert s["compiles"] == seen.n
    assert sum(n for n, _ in s["compiles_by_phase"].values()) == s["compiles"]
    assert set(s["compiles_by_phase"]) <= {*PIPELINE_PHASES, "run"}
    assert s["compile_s"] == pytest.approx(
        sum(t for _, t in s["compiles_by_phase"].values()))

    with BackendCompiles() as seen:
        warm = engine.run(batch)
    assert warm.stats["compiles"] == seen.n == 0
    assert warm.stats["compile_s"] == 0
    assert warm.similar_pairs == cold.similar_pairs


def test_jobs_of_one_size_compile_communities_once(world):
    """Two jobs of one size whose similar-pair counts differ: the second
    runs the communities program the first compiled, its edge buffer of
    the same capacity."""
    batch, forest = world
    engine = AnotherMeEngine(forest,
                             EngineConfig(community_mode="components"))
    first = engine.run(batch).stats
    places = np.asarray(batch.places).copy()
    places[-8:] = places[:8]  # eight rows repeated: more similar pairs
    second = engine.run(TrajectoryBatch(
        places=jnp.asarray(places), lengths=batch.lengths,
        user_id=batch.user_id)).stats
    assert second["num_similar"] > first["num_similar"]
    assert first["communities_edges"] == first["num_similar"]
    assert second["communities_edges"] == second["num_similar"]
    assert second["communities_edge_cap"] == first["communities_edge_cap"]
    assert "communities" not in second["compiles_by_phase"], \
        second["compiles_by_phase"]


def test_streaming_update_reports_compiles(world):
    from repro.api import StreamingEngine

    batch, forest = world
    stream = StreamingEngine(forest, EngineConfig())
    with BackendCompiles() as seen:
        stats = stream.update(batch).stats
    assert stats["compiles"] == seen.n
    assert sum(n for n, _ in stats["compiles_by_phase"].values()) == \
        stats["compiles"]
    assert "t_shingle" not in stats and "t_total" not in stats


def _ssh_program():
    from repro.core.ssh import ssh_candidates

    keys = jax.ShapeDtypeStruct((64, 6), jnp.int32)
    return ssh_candidates.lower(keys, pair_capacity=512)


def _score_program():
    from repro.core.similarity import score_pairs

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    return score_pairs.lower(i32(64, 3, 10), i32(64), i32(256), i32(256),
                             jax.ShapeDtypeStruct((3,), jnp.float32),
                             impl_name="wavefront")


def _communities_program():
    from repro.core.communities import connected_components

    edges = jax.ShapeDtypeStruct((128,), jnp.int32)
    return connected_components.lower(edges, edges, num_nodes=64)


@pytest.mark.parametrize("lowered, scopes", [
    (_ssh_program, ("ssh/sort", "ssh/runs", "ssh/pairs_from_rows",
                    "ssh/dedup")),
    (_score_program, ("score/gather", "score/lcs")),
    (_communities_program, ("communities/labels",)),
], ids=["ssh", "score", "communities"])
def test_device_work_carries_its_stage_names(lowered, scopes):
    """The compiled programs name their device work by stage, so a
    profiler's op view ties each fusion to the code that made it."""
    text = lowered().compile().as_text()
    for scope in scopes:
        assert scope in text, scope
