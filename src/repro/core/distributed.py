"""Deprecated location — the sharded pipeline moved to ``repro.api.sharded``.

This module re-exports the old names so existing imports keep working.
The one-shot job on a mesh runs through ``AnotherMeEngine`` with
``ExecutionPlan(n_shards=...)`` (its staged programs are
:class:`repro.api.sharded.MeshStages`).
"""
from repro.api.sharded import (  # noqa: F401
    DistributedPlan,
    _pair_hash,
    _positive_hash,
    _route,
    gather_similar_pairs,
    pad_to_shards,
    plan_capacities,
)
