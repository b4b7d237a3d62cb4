"""Shared capacity planning for fixed-shape candidate buffers.

Every candidate join in the engine writes into a static ``pair_capacity``
buffer (DESIGN.md: Spark's dynamic memory traded for deterministic
compilable shapes).  The policy — size from the exact join cardinality with
slack, round to a power of two so jit caches hit across batches, retry with
doubled capacity on overflow — used to live inline in ``run_anotherme``;
it is now one object shared by every backend and both execution modes.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

from repro.core.types import CandidatePairs


@dataclasses.dataclass(frozen=True)
class CapacityPlanner:
    """Capacity sizing + overflow-retry policy for candidate buffers.

    slack:       multiplicative headroom over the expected pair count.
    floor_pow2:  minimum capacity is ``2**floor_pow2`` (keeps tiny worlds
                 from generating one jit cache entry per batch size).
    max_retries: doubling retries after an overflow before giving up.
    autotune:    consult the cached :mod:`repro.perf` tuning table when
                 planning score-stage kernel parameters (block sizes,
                 diagonal dtypes).  Off by default: with no table on disk
                 the lookup is a silent no-op, but plans should not even
                 probe the filesystem unless asked.
    """

    slack: float = 1.10
    floor_pow2: int = 10
    max_retries: int = 3
    autotune: bool = False

    def initial_capacity(self, expected_pairs: int) -> int:
        """Power-of-two capacity covering ``expected_pairs`` with slack."""
        want = max(int(expected_pairs * self.slack), 1)
        return 1 << max(self.floor_pow2, int(np.ceil(np.log2(want))))

    def update_capacity(self, count: int, *, floor_pow2: int = 4) -> int:
        """Power-of-two capacity for one streaming micro-batch's buffers.

        Like :meth:`initial_capacity` but with a small floor: per-update
        delta buffers (new rows to append, delta pairs to score) should cost
        O(delta), not O(2**floor_pow2) of the world-sized policy — while
        still quantizing to powers of two so consecutive updates of similar
        size reuse every jit cache.
        """
        want = max(int(max(count, 1) * self.slack), 1)
        return 1 << max(floor_pow2, int(np.ceil(np.log2(want))))

    def grow_capacity(self, current: int, needed: int) -> int:
        """Amortized-doubling growth plan for an append-only world buffer.

        Returns ``current`` unchanged while it covers ``needed``; otherwise
        the smallest power-of-two doubling of ``current`` that does.  Every
        grow at least doubles, so N appended rows trigger O(log N)
        reallocations (and O(log N) recompilations of the world-shaped
        programs) with total copy cost O(N) — the classic dynamic-array
        amortization, applied to device-resident buffers where each
        reallocation also invalidates a jit cache entry.
        """
        cap = max(current, 1)
        while cap < needed:
            cap *= 2
        return cap

    def run_with_retry(
        self, build: Callable[[int], CandidatePairs], capacity: int
    ) -> tuple[CandidatePairs, int]:
        """Call ``build(capacity)``, doubling capacity while it overflows.

        Returns (candidates, final_capacity).  A persistent overflow after
        ``max_retries`` doublings is returned as-is — the overflow counter
        stays nonzero so the caller can surface it, never silently drop it.
        """
        cand = build(capacity)
        for _ in range(self.max_retries):
            if int(cand.overflow) == 0:
                break
            capacity *= 2
            cand = build(capacity)
        return cand, capacity

    def plan_tuning(self, pairs: int, levels: int, length: int):
        """Tuned LCS kernel parameters for a score stage of this shape.

        Returns the cached :class:`repro.perf.LCSTuning` for the
        ``(pairs, levels, length)`` cell (nearest-P fallback) when
        ``autotune=True`` and the table has a usable entry, else ``None``
        — callers keep their defaults.  Like every tuning consultation
        this resolves EAGERLY at plan/build time, never inside a trace:
        the result becomes static kernel arguments, so autotuning can
        change throughput but never shapes, traces, or results.
        """
        if not self.autotune:
            return None
        from repro.perf import TuningTable

        return TuningTable.load().lookup(pairs, levels, length)

    def plan_stream_join(
        self, keys_flat, n_shards: int, stats, *, floor_pow2: int = 4
    ):
        """Exact per-owner capacity plan for the in-mesh streaming delta
        join (``delta_join="device"``).

        Delegates to :func:`repro.api.sharded.plan_stream_join`: the
        bucket-slab, key-route and probe buffers are sized from the exact
        per-owner loads the :class:`~repro.core.device_index.StreamJoinStats`
        count mirror derives under the device's own key hash, and the two
        pair-stage buffers from the pre-dedup emission totals (a safe
        bound on post-dedup skew).  Capacities quantize to powers of two;
        the streaming engine keeps them sticky across updates so the
        compiled join program is reused — zero steady-state recompiles.
        """
        from repro.api.sharded import plan_stream_join

        return plan_stream_join(
            keys_flat, n_shards, stats, floor_pow2=floor_pow2
        )

    def plan_query(
        self,
        num_queries: int,
        k_max: int,
        *,
        n_shards: int,
        cap_local: int,
        world_L: int,
        q_len_max: int,
        cand_total=None,
        keys_flat=None,
        stats=None,
        floor_pow2: int = 2,
    ):
        """Exact capacity plan for one query-serving micro-batch.

        Delegates to :func:`repro.api.serving.plan_query_capacities`: the
        query, top-k and candidate buffers are sized from the exact
        candidate cardinality — the host BucketIndex probe's count
        (``cand_total``) or, for device-resident worlds, the per-owner
        new-vs-old loads the :class:`~repro.core.device_index.StreamJoinStats`
        mirror derives from ``keys_flat``/``stats``.  Capacities quantize
        to powers of two; :class:`QueryEngine` keeps them sticky across
        micro-batches so both compiled serving programs are reused —
        zero steady-state recompiles under query traffic.
        """
        from repro.api.serving import plan_query_capacities

        return plan_query_capacities(
            num_queries, k_max, n_shards=n_shards, cap_local=cap_local,
            world_L=world_L, q_len_max=q_len_max, cand_total=cand_total,
            keys_flat=keys_flat, stats=stats, floor_pow2=floor_pow2,
        )
