"""Production meshes.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state; the dry-run sets
XLA_FLAGS before jax first touches a device and only then builds meshes.

Mesh shapes (assignment spec): single pod = (data=16, model=16) — 256 chips
(one v5e pod); multi-pod = (pod=2, data=16, model=16) — 512 chips.  The
"pod" axis carries data-parallel replication across the DCN boundary; all
model collectives stay inside a pod.

``make_executor_mesh`` flattens every axis into one "ex" axis for the
AnotherMe analytics plane (trajectory shards == Spark executors).
"""
from __future__ import annotations

import jax

from repro.core import compat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_executor_mesh(n_devices: int | None = None):
    n = n_devices or len(jax.devices())
    return compat.make_mesh((n,), ("ex",))
