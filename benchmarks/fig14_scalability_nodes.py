"""Fig. 14 — scalability with worker count (paper: 1..20 nodes, 1M
trajectories).  Here: the sharded engine on meshes over the first 1, 2,
4, ... of this process's devices, all in one process (a device belongs to
one process, so workers cannot be child processes).  On the CPU, fake
the devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(``run.sh`` does this under ``JAX_PLATFORMS=cpu``).  Speedup saturates as
shuffle overhead grows — the paper's observed knee.
"""
from __future__ import annotations

import time

from benchmarks.common import Row


def run(full: bool = False) -> list[Row]:
    import jax

    from repro.api import AnotherMeEngine, EngineConfig, ExecutionPlan
    from repro.data import synthetic_setup

    n = 20_000 if full else 4_000
    batch, forest = synthetic_setup(n, num_types=300, seed=0)
    n_dev = len(jax.devices())
    rows = []
    workers = 1
    while workers <= n_dev:
        engine = AnotherMeEngine(
            forest, EngineConfig(community_mode="components"),
            ExecutionPlan(n_shards=workers),
        )
        engine.run(batch)                 # compile + plan + run once
        # warm end-to-end run: the shard_map runner and capacity plan are
        # cached, but host-side encode/key transfer/communities are
        # included — the wall time a user of engine.run sees (the paper
        # also times end-to-end)
        t0 = time.perf_counter()
        engine.run(batch)
        t = time.perf_counter() - t0
        rows.append(Row(f"fig14/anotherme/workers={workers}", t * 1e6,
                        f"N={n};platform={jax.devices()[0].platform}"))
        workers *= 2
    return rows
