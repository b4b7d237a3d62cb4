"""Platform probes and the mesh / shard_map constructors.

Every mesh/shard_map construction in the repo goes through these helpers,
and every platform-dependent choice (kernel dispatch, score chunk sizes,
the tuning table) reads the platform through them.
"""
from __future__ import annotations

import os

import jax


def backend_name() -> str:
    """The active jax backend ("cpu" / "tpu" / "gpu").

    The single source of truth for backend probing: the LCS dispatchers
    (kernels/lcs/ops.py, kernels/lcs/fused.py) and the perf tuning table
    (repro.perf) all key off THIS function, so a test that monkeypatches it
    redirects every dispatch decision at once — two independent probes can
    never disagree about where the code is running.
    """
    return jax.default_backend()


def on_tpu() -> bool:
    """True when the default jax backend is a TPU (see :func:`backend_name`)."""
    return backend_name() == "tpu"


def device_memory_bytes() -> int:
    """Memory of one local device: the accelerator's allocator limit, or
    the host's physical memory for the CPU backend (which reports none)."""
    stats = jax.local_devices()[0].memory_stats() or {}
    if stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    return os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")


def make_mesh(axis_shapes, axis_names, *, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    kwargs = {}
    if devices is not None:
        kwargs["devices"] = list(devices)
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names), **kwargs,
    )


def shard_map(fn, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` without replication/VMA checking."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False,
    )
