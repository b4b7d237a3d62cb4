"""Legacy AnotherMe entry point — deprecation shim over ``repro.api``.

``run_anotherme`` / ``AnotherMeConfig`` predate the composable engine; they
now delegate to :class:`repro.api.AnotherMeEngine` so there is exactly one
implementation of the pipeline.  New code should use the engine directly:

    from repro.api import AnotherMeEngine, EngineConfig
    result = AnotherMeEngine(forest, EngineConfig()).run(batch)

Behavioural fixes folded into the shim (ISSUE 1 satellites):

* ``lcs_impl="ref"`` now actually runs the reference DP (it used to be
  silently rewritten to "wavefront"), and unknown impl names raise a
  ValueError listing the valid options.
* The ``candidate_fn`` branch reports ``t_candidates`` (and no longer books
  the baseline's hash cost under the key phase, ``t_keys``), so Fig. 9-style
  breakdowns attribute hash cost correctly for every approach.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

from repro.core.encoding import SemanticForest
from repro.core.types import ScoredPairs, TrajectoryBatch


@dataclasses.dataclass
class AnotherMeResult:
    """Pipeline output: scored pairs + the paper's two result sets.

    Shared with the new API (``repro.api.EngineResult`` is an alias).
    """

    scored: ScoredPairs
    similar_pairs: set
    communities: set
    stats: dict


@dataclasses.dataclass(frozen=True)
class AnotherMeConfig:
    """Legacy config; maps 1:1 onto :class:`repro.api.EngineConfig`."""

    k: int = 3                      # shingle order (paper default 3)
    rho: float = 2.0                # similarity threshold (paper default 2)
    betas: tuple | None = None      # level weights; None -> uniform 1/n
    lcs_impl: str = "wavefront"     # "wavefront" | "ref" | "fused" |
    #                                 "fused-pallas" | "fused-interpret"
    pair_capacity: int | None = None  # None -> plan from exact join size
    capacity_slack: float = 1.10
    community_mode: str = "cliques"  # "cliques" | "components"
    max_retries: int = 3

    def as_engine_config(self, backend: str = "ssh"):
        from repro.api.engine import EngineConfig

        return EngineConfig(
            k=self.k, rho=self.rho, betas=self.betas, backend=backend,
            lcs_impl=self.lcs_impl, pair_capacity=self.pair_capacity,
            capacity_slack=self.capacity_slack,
            community_mode=self.community_mode, max_retries=self.max_retries,
        )


def run_anotherme(
    batch: TrajectoryBatch,
    forest: SemanticForest,
    config: AnotherMeConfig = AnotherMeConfig(),
    *,
    candidate_fn: Callable | None = None,
) -> AnotherMeResult:
    """Run the full pipeline on one device (deprecated shim).

    ``candidate_fn`` optionally swaps the SSH join for a baseline hash while
    keeping every other phase identical.  Prefer the registry instead:
    ``AnotherMeEngine(forest, EngineConfig(backend="minhash"))``.
    """
    from repro.api.backends import CallableBackend
    from repro.api.engine import AnotherMeEngine

    backend = CallableBackend(candidate_fn) if candidate_fn is not None else None
    engine = AnotherMeEngine(
        forest, config.as_engine_config(), backend=backend
    )
    return engine.run(batch)
