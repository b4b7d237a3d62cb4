"""The system under test, as the benchmark drives it.

The only module of the benchmark that imports the program (``src/repro``).
It turns the benchmark's numpy inputs into the program's types, calls the
program's public entry points, and hands back plain Python and numpy
results.  The drivers time these calls; the reference never sees them.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def program():
    """Import the program from ``src/`` of the checkout."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.api as api
    from repro.core.encoding import SemanticForest
    from repro.core.types import TrajectoryBatch

    return api, SemanticForest, TrajectoryBatch


def _batch(places: np.ndarray, lengths: np.ndarray):
    import jax.numpy as jnp

    _, _, TrajectoryBatch = program()
    return TrajectoryBatch(
        places=jnp.asarray(places), lengths=jnp.asarray(lengths),
        user_id=jnp.arange(places.shape[0], dtype=jnp.int32),
    )


def _forest(forest: dict):
    _, SemanticForest, _ = program()
    return SemanticForest(parents=tuple(forest["parents"]),
                          sizes=tuple(forest["sizes"]))


class Batch:
    """One-shot jobs: ``AnotherMeEngine.run`` on one batch at a time."""

    def __init__(self, cfg: dict, forest: dict):
        api, _, _ = program()
        self.engine = api.AnotherMeEngine(
            _forest(forest), api.EngineConfig(**cfg["engine"]))

    def run(self, places, lengths):
        """(similar pairs, communities, stats) of one job, on the host."""
        r = self.engine.run(_batch(places, lengths))
        return r.similar_pairs, r.communities, r.stats


class Resident:
    """A sliding-window world in a ``StreamingEngine``, served by a
    ``QueryEngine``.  Global ids are arrival order: the i-th row ever
    ingested is id i."""

    def __init__(self, cfg: dict, forest: dict):
        api, _, _ = program()
        self.stream = api.StreamingEngine(
            _forest(forest), api.EngineConfig(**cfg["engine"]),
            api.ExecutionPlan(**cfg["plan"]), **cfg["stream"])
        self.serve = None
        self.k = cfg["query_k"]
        self.last = None

    def update(self, places, lengths) -> dict:
        self.last = self.stream.update(_batch(places, lengths))
        return self.last.stats

    def result(self):
        """(similar pairs, communities) of the world after the last update."""
        return self.last.similar_pairs, self.last.communities

    def query(self, places, lengths):
        """(match ids [Q, k], mss [Q, k], stats) of one query call."""
        if self.serve is None:
            api, _, _ = program()
            self.serve = api.QueryEngine(self.stream, k=self.k)
        r = self.serve.query(_batch(places, lengths))
        return r.match_ids, r.mss, r.stats


SYSTEMS = {"batch": Batch, "resident": Resident}


def build(cfg: dict, forest: dict):
    return SYSTEMS[cfg["system"]](cfg, forest)


def annotate_phases() -> None:
    """Mark every phase the program times (``Instrumentation.phase``) as a
    host span ``phase.<name>`` in the profiler's trace."""
    import contextlib

    import jax

    program()
    from repro.api import instrumentation

    cls = instrumentation.Instrumentation
    timed = cls.phase
    if getattr(timed, "annotated", False):
        return

    @contextlib.contextmanager
    def phase(self, name):
        with jax.profiler.TraceAnnotation(f"phase.{name}"), timed(self, name):
            yield

    phase.annotated = True
    cls.phase = phase
