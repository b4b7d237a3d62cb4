"""`StreamingEngine`: micro-batch ingestion with incremental maintenance.

    from repro.api import StreamingEngine, EngineConfig, ExecutionPlan

    stream = StreamingEngine(forest, EngineConfig(backend="ssh", rho=2.0))
    for micro_batch in feed:
        result = stream.update(micro_batch)   # EngineResult, same type as
                                              # AnotherMeEngine.run

The one-shot engine re-encodes, re-joins, re-scores and re-clusters the
full world on every call; the motivating workloads (friend recommendation
over continuously collected LBS trajectories) are incremental, so this
layer makes per-update cost proportional to the DELTA instead of the world:

* world state is device-resident and append-only — the [N, H, L] code
  table (single-device) or the round-robin sharded places slabs (sharded)
  grow by amortized doubling (:meth:`CapacityPlanner.grow_capacity`), and
  each update transfers only the new rows;
* candidate generation is incremental: every backend's join keys are a
  pure per-row function, so a :class:`~repro.core.stream_index.BucketIndex`
  inserts the new rows' keys and emits exactly the pairs whose LATER member
  arrived in this update (new-vs-(old ∪ new) bucket collisions) — the
  union over updates equals the one-shot join over the concatenated batch;
* with ``ExecutionPlan(delta_join="device")`` the bucket state itself
  leaves the driver: it becomes key-sharded device-resident sorted slabs
  (``core/device_index.py``), each update ships only the new rows' key
  occurrences into a shard_map program that routes them to their owner
  shard, probes/merges the resident slab, and emits the deduped delta
  pairs in-mesh, feeding the score program directly — neither the world's
  keys nor the pair list ever materializes on the driver (the per-update
  ``driver_*`` stats account for every byte that does transfer).  The
  host ``BucketIndex`` path (``delta_join="host"``, the default) is kept
  as the oracle the differential harness pins the device join against;
* scoring runs the existing ``lcs_impl`` dispatch over the delta pairs
  only (``score_prune`` prunes the delta first), against the resident
  world table;
* communities are maintained incrementally: surviving edges fold into a
  host :class:`~repro.core.communities.UnionFind` (the exact oracle path)
  or into a resumable jit ``connected_components`` seeded with the
  previous fixpoint via star edges ``(label[v], v)`` (the device path);
  both yield the identical partition a one-shot run would produce.

The streaming-vs-oneshot equivalence suite (tests/test_streaming.py and
the streaming axis of tests/test_api_parity_matrix.py) pins all of this
bit-exactly: for ANY split of a batch into micro-batches, the final scored
edge set, per-pair MSS, and community partition match one ``engine.run``
over the concatenation, on the single-device and sharded paths alike.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.engine import AnotherMeEngine, EngineConfig, ExecutionPlan
from repro.api.errors import CapacityExceeded
from repro.api.instrumentation import Instrumentation
from repro.api.sharded import (
    StreamShardPlan, _positive_hash_np, _pow2, make_streaming_join_pipeline,
    make_streaming_score_pipeline, plan_stream_capacities, plan_stream_join,
    sticky_join_plan,
)
from repro.core import communities as comm
from repro.core.device_index import (
    ShardSummaries, StreamJoinStats, compact_slab, mark_dead_rows,
)
from repro.core.encoding import encode_codes, encode_types
from repro.core.pipeline import AnotherMeResult as EngineResult
from repro.core.similarity import (
    PRUNE_EPS, mss_upper_bound, score_pairs, wavefront_dtype_from_env,
)
from repro.core.stream_index import BucketIndex
from repro.core.types import (
    EncodedBatch, PAD_ID, PAD_KEY, PAD_PLACE, ScoredPairs, TrajectoryBatch,
)

COMPONENTS_IMPLS = ("unionfind", "jit")
DELTA_JOINS = ("host", "device")

# a row with no TTL never expires on its own
NEVER_EXPIRES = np.iinfo(np.int64).max

# REPRO_FAULT_INJECT=1 derates every fresh join/score plan to artificially
# tiny caps, forcing the overflow -> compact -> retry recovery path on
# every run (CI exercises it deterministically; results stay bit-identical
# because overflowed runs are never committed).  Read per-call so tests
# can flip it with monkeypatch.setenv.
def _fault_inject() -> bool:
    return bool(int(os.environ.get("REPRO_FAULT_INJECT", "0") or "0"))


def _derate_cap(cap: int) -> int:
    """Fault-injection derating: shrink a planned capacity hard enough to
    force overflow retries, but keep it a power of two >= 4 so the retry
    doubling converges within the extra fault-injection retry budget."""
    return max(4, _pow2(max(cap // 8, 1)))


class StreamingEngine:
    """Incremental AnotherMe over a fixed semantic forest.

    One instance owns the growing world state; :meth:`update` ingests one
    micro-batch and returns the CURRENT world's :class:`EngineResult` —
    accumulated scored pairs, the full similar set, and the maintained
    communities — so the final update's result is directly comparable to
    a one-shot ``AnotherMeEngine.run`` over the concatenated batches.

    ``components_impl`` selects the community maintenance path used when
    ``config.community_mode == "components"``: ``"unionfind"`` (host,
    exact, amortized O(alpha) per edge) or ``"jit"`` (device min-label
    propagation resumed from the previous labels).  ``"cliques"`` mode
    re-runs the Bron-Kerbosch oracle over the accumulated edge set —
    labels there are exact but not incremental (DESIGN.md discusses when
    each is appropriate).
    """

    def __init__(
        self,
        forest,
        config: EngineConfig = EngineConfig(),
        plan: ExecutionPlan = ExecutionPlan(),
        *,
        components_impl: str = "unionfind",
        world_capacity: int | None = None,
        join_slab_capacity: int | None = None,
        window: int | None = None,
        max_resident_bytes: int | None = None,
        compact_watermark: float = 0.5,
    ):
        if components_impl not in COMPONENTS_IMPLS:
            raise ValueError(
                f"unknown components_impl {components_impl!r}; valid: "
                f"{list(COMPONENTS_IMPLS)}"
            )
        if plan.delta_join not in DELTA_JOINS:
            raise ValueError(
                f"unknown delta_join {plan.delta_join!r}; valid: "
                f"{list(DELTA_JOINS)}"
            )
        if config.subtraj_window is not None:
            # Window ids are t * nw + j with nw derived from the world max
            # length L — but the streaming world's L GROWS across updates,
            # which would re-number every window id already resident in the
            # bucket index / join slabs.  Subtrajectory streaming needs a
            # fixed-L world contract first (ROADMAP); reject loudly rather
            # than silently joining stale coordinates.
            raise NotImplementedError(
                "subtraj_window is not supported by StreamingEngine: the "
                "streaming world's max length grows across updates, which "
                "would invalidate resident window ids.  Use the batch "
                "AnotherMeEngine for subtrajectory search."
            )
        # the one-shot engine validates config/plan and owns the shared
        # pieces: forest tables, betas, backend, planner, mesh
        self._eng = AnotherMeEngine(forest, config, plan)
        self.forest = forest
        self.config = self._eng.config  # plan.lcs_impl already folded in
        self.plan = plan
        self.tables = self._eng.tables
        self.betas = self._eng.betas
        self.backend = self._eng.backend
        self.backend_ctx = self._eng.backend_ctx
        self.planner = self._eng.planner
        self.components_impl = components_impl
        H = int(self.tables.shape[0])
        self._H = H
        # world state (global-order host mirror + device-resident tables)
        self.n = 0               # trajectories arrived (global ids 0..n-1)
        self.L = 1               # world max trajectory length (grows)
        self._cap = 0            # world buffer capacity (amortized doubling)
        # bounded-memory state: the resident buffers hold ONLY the id
        # window [base, n) — slot i is global id base + i.  ``base`` only
        # moves at compaction (prefix rebase: every id below it is dead),
        # and is kept a multiple of n_shards so the round-robin owner
        # ``g % n_shards`` is invariant under the shift — device programs
        # operate on LOCAL ids (g - base) and never see the base move.
        self._base = 0
        self._alive_np = np.zeros((0,), bool)     # [cap] liveness, local
        self._expiry_np = np.zeros((0,), np.int64)  # [cap] expiry update
        if window is not None and window <= 0:
            raise ValueError(f"window must be positive, got {window}")
        self.window = window
        self.max_resident_bytes = max_resident_bytes
        if not (0.0 < compact_watermark <= 1.0):
            raise ValueError(
                f"compact_watermark must be in (0, 1], got {compact_watermark}"
            )
        self.compact_watermark = float(compact_watermark)
        self.retired_total = 0   # rows ever retired (TTL + explicit)
        self.compactions = 0     # watermark compactions run
        self.compact_ms_total = 0.0  # cumulative compaction stall latency
        self._cap_floor = max(16, int(world_capacity or 0))  # preallocation
        #   hint: a caller expecting ~N trajectories passes world_capacity=N
        #   so the world buffers never reallocate (and the world-shaped
        #   programs never recompile) below that size
        self._places_np = np.full((0, 1), PAD_PLACE, np.int32)
        self._lengths_np = np.zeros((0,), np.int32)
        self._codes_dev = None   # single-device resident [cap, H, L]
        self._len_dev = None     # single-device resident [cap]
        self._places_dev = None  # sharded resident round-robin [cap, L]
        # delta-join routing: "host" probes the driver-resident BucketIndex
        # (the oracle); "device" keeps the bucket state key-sharded in-mesh
        # and the world lives in the sharded layout even at n_shards=1
        self.delta_join = plan.delta_join
        self._mesh_world = plan.n_shards > 1 or self.delta_join == "device"
        # incremental candidate index (one impl for every backend's keys)
        self._index = BucketIndex()
        # device-resident key-sharded bucket slabs (delta_join="device")
        self._slab_keys = None   # [n_shards * slab_cap] sorted, PAD at end
        self._slab_rows = None   # aligned row ids
        self._slab_cap = 0
        self._join_stats = StreamJoinStats(plan.n_shards)
        self._join_plan = None
        self._score_caps = None  # sticky (pair_cap, rest_cap) of the
        #   device-pair score program, sized from the join's in-mesh
        #   post-dedup count reduction (tighter than the join's own
        #   pre-dedup pair_cap)
        # per-world-shard length summaries, maintained on insert — the
        # serve-time REPOSE prune bounds (api/serving.py reads these; they
        # are world metadata, so the host path keeps them too)
        self.shard_summaries = ShardSummaries(
            plan.n_shards if self._mesh_world else 1
        )
        self._slab_floor = int(join_slab_capacity or 0)  # presize hint: a
        #   caller expecting ~E total resident key occurrences passes
        #   join_slab_capacity=E so the slabs never regrow (and the join
        #   program never recompiles) below that size, like world_capacity
        self._examined_total = 0
        self._join_runner_cache: dict = {}
        self.join_traces = [0]   # join-program compile counter (the
        #                          zero-steady-state-recompile proof hook)
        # per-update driver transfer accounting (the harness asserts the
        # device path ships no pair list and no world keys)
        self._xfer = {"bytes_in": 0, "pair_rows": 0, "key_rows": 0}
        # accumulated scored pairs (amortized-doubling host buffers)
        self._acc_cap = 0
        self._acc_n = 0
        self._acc_left = np.empty((0,), np.int32)
        self._acc_right = np.empty((0,), np.int32)
        self._acc_lvl = np.empty((0, H), np.int32)
        self._acc_mss = np.empty((0,), np.float32)
        self._overflow = 0
        # incremental communities
        self.similar_pairs: set = set()
        self._uf = comm.UnionFind()
        self._labels = np.empty((0,), np.int32)  # jit path fixpoint
        # compiled-program bookkeeping
        self._runner_cache: dict = {}
        self._stream_plan: StreamShardPlan | None = None
        self.score_traces = [0]   # sharded runner trace counter (the
        #                           no-per-update-recompile proof hook)
        self.runner_builds = 0
        self.updates = 0

    # -- public entry points -------------------------------------------------

    def update(self, batch: TrajectoryBatch,
               *, ttl: int | None = None) -> EngineResult:
        """Ingest one micro-batch; return the current world's result.

        ttl: updates this batch's rows stay resident for (they are
        retired at the start of the ``ttl``-th subsequent update).  The
        engine-level ``window=N`` acts as a ceiling: rows expire after
        ``min(ttl, window)`` updates when both are set.
        """
        with Instrumentation() as instr:
            return self._update(batch, ttl, instr)

    def _update(self, batch: TrajectoryBatch, ttl: int | None,
                instr: Instrumentation) -> EngineResult:
        self._xfer = {"bytes_in": 0, "pair_rows": 0, "key_rows": 0}
        places = np.asarray(batch.places, np.int32)
        if places.ndim != 2:
            places = places.reshape((places.shape[0], -1) if places.size
                                    else (0, 1))
        lengths = np.asarray(batch.lengths, np.int32).reshape(-1)
        d = places.shape[0]
        # sliding-window / TTL sweep FIRST: rows whose window closed must
        # be gone before this update's rows arrive, so an expiring row
        # never pairs with a new one — exactly the one-shot-over-the-
        # window semantics the differential harness pins
        with instr.phase("expire"):
            num_expired = self._expire_due()
        with instr.phase("keys"):
            keys_np = self._new_row_keys(places, lengths) if d else None
        # admission control BEFORE any mutation: if this update cannot fit
        # the resident-byte budget, refuse it with the world untouched
        self._admission_check(d, places.shape[1] if d else 0, keys_np)
        n_old = self.n
        with instr.phase("ingest"):
            if d:
                self._ingest(places, lengths, ttl=ttl)
        num_pruned = 0
        if self.delta_join == "device":
            with instr.phase("delta_join"):
                left_dev, right_dev, num_delta, max_delta, examined = (
                    self._device_delta_join(keys_np, n_old)
                    if d else (None, None, 0, 0, 0)
                )
            with instr.phase("score"):
                if num_delta:
                    (s_left, s_right, s_lvl, s_mss,
                     num_pruned) = self._score_device_pairs(
                        left_dev, right_dev, max_delta, num_delta)
                else:
                    s_left = s_right = np.empty((0,), np.int32)
                    s_lvl = np.empty((0, self._H), np.int32)
                    s_mss = np.empty((0,), np.float32)
                self._accumulate_scored(s_left, s_right, s_lvl, s_mss)
        else:
            with instr.phase("delta_join"):
                if d:
                    lo, hi, examined = self._index.insert(keys_np,
                                                          first_id=n_old)
                else:
                    lo = hi = np.empty((0,), np.int32)
                    examined = 0
            num_delta = int(lo.shape[0])
            if self.config.score_prune and num_delta:
                with instr.phase("prune"):
                    lo, hi, num_pruned = self._prune_delta(lo, hi)
            with instr.phase("score"):
                if lo.shape[0]:
                    s_left, s_right, s_lvl, s_mss = self._score_delta(lo, hi)
                else:
                    s_left = s_right = np.empty((0,), np.int32)
                    s_lvl = np.empty((0, self._H), np.int32)
                    s_mss = np.empty((0,), np.float32)
                self._accumulate_scored(s_left, s_right, s_lvl, s_mss)
        with instr.phase("communities"):
            edge_mask = s_mss > np.float32(self.config.rho)
            new_edges = list(zip(s_left[edge_mask].tolist(),
                                 s_right[edge_mask].tolist()))
            communities = self._fold_edges(new_edges)
        self.updates += 1
        self._examined_total += int(examined)
        instr.record(
            num_new=d, world_size=self.n, world_capacity=self._cap,
            # bounded-memory accounting: the live row count, the resident
            # device footprint, the tombstone fraction awaiting
            # compaction, and the compaction history (count + cumulative
            # stall latency)
            world_live=self.live_size, world_base=self._base,
            num_expired=num_expired, retired_total=self.retired_total,
            resident_bytes=self.resident_bytes(),
            dead_fraction=self.dead_fraction(),
            compactions=self.compactions,
            compact_ms_total=self.compact_ms_total,
            pairs_examined=examined, full_world_pairs=self._examined_total,
            num_delta_pairs=num_delta, num_candidates=self._acc_n,
            num_similar=len(self.similar_pairs),
            num_similar_new=len(new_edges),
            num_communities=len(communities),
            score_traces=self.score_traces[0],
            runner_builds=self.runner_builds,
            join_overflow=self._overflow,
            # driver transfer accounting: what actually crossed the
            # host->device boundary this update (the differential harness
            # asserts the device join ships no pair list and holds no
            # world-key state on the driver)
            delta_join=self.delta_join,
            driver_bytes_in=self._xfer["bytes_in"],
            driver_pair_rows=self._xfer["pair_rows"],
            driver_key_rows=self._xfer["key_rows"],
            host_index_entries=self._index.num_keys_inserted,
            # the device path's residual driver state: one COUNT per
            # distinct key (planning statistics — row ids, and therefore
            # pairs, are not reconstructible from it), vs the host
            # index's one entry per (key, row) occurrence above
            driver_mirror_keys=self._join_stats.num_keys,
            join_traces=self.join_traces[0],
        )
        if self.delta_join == "device":
            # the differential harness asserts the score buffers are sized
            # from the in-mesh post-dedup count reduction, never from the
            # join's pre-dedup emission bound
            instr.record(
                join_pair_cap=(self._join_plan.pair_cap
                               if self._join_plan else 0),
                score_pair_cap=(self._score_caps[0]
                                if self._score_caps else 0),
            )
        if self.config.score_prune:
            instr.record(num_pruned=num_pruned)
        return EngineResult(
            scored=self._scored(), similar_pairs=set(self.similar_pairs),
            communities=communities, stats=instr.finalize(),
        )

    def update_many(self, batches) -> EngineResult:
        """Ingest a sequence of micro-batches; return the final result."""
        result = None
        for batch in batches:
            result = self.update(batch)
        if result is None:
            raise ValueError("update_many needs at least one micro-batch")
        return result

    @property
    def world_size(self) -> int:
        return self.n

    @property
    def live_size(self) -> int:
        """Trajectories currently resident and alive."""
        return int(self._alive_np[: self.n - self._base].sum())

    # -- bounded memory: retirement, tombstones, compaction ------------------

    def retire(self, ids) -> int:
        """Retire trajectories by global id; returns how many were live.

        Retired rows leave the logical world immediately: they stop
        emitting candidate pairs (slab tombstones / host bucket eviction),
        their accumulated scored pairs and similarity edges are purged,
        and their communities un-merge — the engine's result equals a
        one-shot run over the surviving rows.  PHYSICAL reclamation is
        deferred: tombstones occupy their slab slots until the dead
        fraction trips ``compact_watermark`` and a compaction repacks the
        resident state.  Already-retired (or already-compacted-away) ids
        are ignored, so the call is idempotent.
        """
        req = sorted({int(i) for i in np.asarray(
            list(ids), dtype=np.int64).reshape(-1).tolist()})
        for i in req:
            if i < 0 or i >= self.n:
                raise ValueError(
                    f"cannot retire id {i}: world holds ids 0..{self.n - 1}"
                )
        base = self._base
        dead = [i for i in req
                if i >= base and self._alive_np[i - base]]
        if not dead:
            return 0
        self._retire(np.asarray(dead, np.int64))
        self._maybe_compact()
        return len(dead)

    def resident_bytes(self) -> int:
        """Bytes of device-resident world state (code/place tables +
        join slabs) — the quantity ``max_resident_bytes`` bounds."""
        total = 0
        if self._codes_dev is not None:
            total += self._codes_dev.size * 4 + self._len_dev.size * 4
        if self._places_dev is not None:
            total += self._places_dev.size * 4
        if self._slab_keys is not None:
            total += self._slab_keys.size * 4 + self._slab_rows.size * 4
        return int(total)

    def dead_fraction(self) -> float:
        """Tombstone fraction awaiting compaction (max of the row-level
        fraction and, on the device join path, the per-owner slab
        fraction — the watermark input)."""
        span = self.n - self._base
        frac = (span - self.live_size) / span if span else 0.0
        if self.delta_join == "device":
            frac = max(frac, self._join_stats.dead_fraction())
        return float(frac)

    def _resident_bytes_at(self, world_cap: int, slab_cap: int,
                           world_L: int | None = None) -> int:
        """Projected resident bytes at the given capacities (admission)."""
        L = self.L if world_L is None else world_L
        if self._mesh_world:
            world = world_cap * L * 4
        else:
            world = world_cap * self._H * L * 4 + world_cap * 4
        slab = 2 * self.plan.n_shards * slab_cap * 4 \
            if self.delta_join == "device" else 0
        return world + slab

    def _admission_check_bytes(self, projected: int, what: str) -> None:
        if self.max_resident_bytes is None:
            return
        if projected > self.max_resident_bytes:
            raise CapacityExceeded(
                f"{what} needs {projected} resident bytes, over the "
                f"max_resident_bytes budget of {self.max_resident_bytes}; "
                "the update was refused and the world is unchanged — "
                "retire rows, raise the budget, or shrink the batch",
                needed_bytes=projected,
                budget_bytes=self.max_resident_bytes,
            )

    def _admission_check(self, d: int, Lb: int, keys_np) -> None:
        """Pre-flight admission: would this update's buffer growth exceed
        ``max_resident_bytes``?  Mirrors ``_ingest``'s growth arithmetic
        and the join planner's slab sizing, and runs BEFORE any state
        mutation — a refusal leaves the world bit-identical."""
        if self.max_resident_bytes is None or not d:
            return
        new_L = max(self.L, Lb)
        span = self.n - self._base
        n_sh = self.plan.n_shards
        new_cap = self.planner.grow_capacity(
            max(self._cap, self._cap_floor), span + d
        )
        if n_sh > 1:
            new_cap = n_sh * self.planner.grow_capacity(
                1, -(-new_cap // n_sh)
            )
        slab_cap = self._slab_cap
        if self.delta_join == "device" and keys_np is not None:
            ks = np.sort(np.asarray(keys_np), axis=1)
            valid = ks != PAD_KEY
            valid[:, 1:] &= ks[:, 1:] != ks[:, :-1]
            k_flat = ks[valid].astype(np.int32)
            if k_flat.size:
                jplan = self.planner.plan_stream_join(
                    k_flat, n_sh, self._join_stats
                )
                slab_cap = max(slab_cap, jplan.slab_cap)
        self._admission_check_bytes(
            self._resident_bytes_at(new_cap, slab_cap, new_L),
            f"ingesting {d} rows",
        )

    def _expire_due(self) -> int:
        """Retire every live row whose TTL/window closed (expiry update
        <= the current update index).  Runs before ingestion, so an
        expiring row never pairs with an arriving one."""
        span = self.n - self._base
        if not span:
            return 0
        due = np.nonzero(
            self._alive_np[:span]
            & (self._expiry_np[:span] <= self.updates)
        )[0]
        if due.size == 0:
            return 0
        self._retire(due.astype(np.int64) + self._base)
        self._maybe_compact()
        return int(due.size)

    def _retire(self, dead: np.ndarray) -> None:
        """Logically delete ``dead`` (sorted global ids, all live) from
        every layer that caches world state."""
        base = self._base
        dl = (dead - base).astype(np.int64)
        self._alive_np[dl] = False
        self.retired_total += int(dead.size)
        # the rows' join keys are recomputed from the host mirror (keys
        # are a pure per-row function, so they are always recoverable)
        keys_np = self._new_row_keys(
            self._places_np[dl], self._lengths_np[dl]
        )
        if self.delta_join == "device":
            ks = np.sort(np.asarray(keys_np), axis=1)
            valid = ks != PAD_KEY
            valid[:, 1:] &= ks[:, 1:] != ks[:, :-1]
            k_flat = ks[valid].astype(np.int32)
            if k_flat.size:
                owners = _positive_hash_np(k_flat) % self.plan.n_shards
                self._join_stats.retire(k_flat, owners)
            if self._slab_keys is not None:
                # tombstone the slab in place: rows become PAD_ID, keys
                # stay (sort order and examined accounting intact).  The
                # dead list ships PAD-padded at a pow2 cap so repeats of
                # similar size reuse the compiled marker
                m_cap = self.planner.update_capacity(int(dead.size))
                buf = np.full((m_cap,), PAD_ID, np.int32)
                buf[: dead.size] = dl.astype(np.int32)
                self._xfer["bytes_in"] += buf.nbytes
                self._slab_rows = self._mark_dead_runner()(
                    self._slab_rows, jnp.asarray(buf)
                )
        else:
            self._index.retire(dead.tolist(), keys_np)
        # purge accumulated scored pairs and similarity edges touching a
        # dead row (the result contract: == one-shot over the survivors).
        # The purge writes FRESH buffers — results already returned hold
        # (possibly zero-copy) views of the old ones, and the append-only
        # discipline that kept those views valid must survive deletion
        if self._acc_n:
            left = self._acc_left[: self._acc_n]
            right = self._acc_right[: self._acc_n]
            keep = self._alive_np[left - base] & self._alive_np[right - base]
            k = int(keep.sum())
            for name in ("_acc_left", "_acc_right", "_acc_lvl", "_acc_mss"):
                old = getattr(self, name)
                fresh = old.copy()
                fresh[:k] = old[: self._acc_n][keep]
                setattr(self, name, fresh)
            self._acc_n = k
        dead_set = set(int(i) for i in dead.tolist())
        self.similar_pairs = {
            (a, b) for (a, b) in self.similar_pairs
            if a not in dead_set and b not in dead_set
        }
        self._unmerge_communities(dl)
        # serve-prune summaries: a maximum cannot be maintained under
        # deletion — recompute from the live mirror so the REPOSE bounds
        # stay sound AND tight
        span = self.n - base
        self.shard_summaries.rebuild(
            base, self._lengths_np[:span], self._alive_np[:span]
        )

    def _unmerge_communities(self, dead_local: np.ndarray) -> None:
        """Community un-merging: deletion can SPLIT a component, which no
        incremental label update discovers — re-solve only the components
        that contained a dead node, warm-starting from the survivors."""
        if self.config.community_mode == "cliques":
            return  # cliques re-derive from similar_pairs on every fold
        base = self._base
        span = self.n - base
        labels = np.arange(span, dtype=np.int32)
        labels[: min(self._labels.shape[0], span)] = \
            self._labels[: min(self._labels.shape[0], span)]
        edges_local = [(a - base, b - base) for (a, b) in self.similar_pairs]
        if self.components_impl == "unionfind":
            self._labels = comm.components_after_deletion(
                labels, dead_local.tolist(), edges_local
            )
        else:
            # the warm-started jit path: untouched components enter as
            # stars of their stale labels, touched ones dissolve to
            # singletons and re-form from the surviving edges in-device
            lab = labels.astype(np.int64)
            touched = np.unique(lab[dead_local])
            tmask = np.isin(lab, touched)
            idx = np.nonzero(tmask)[0]
            lab[idx] = idx
            tset = set(idx.tolist())
            delta = [e for e in edges_local
                     if e[0] in tset or e[1] in tset]
            cap = max(self._cap, span)
            seed = np.arange(cap, dtype=np.int32)
            seed[:span] = lab
            e_cap = self.planner.update_capacity(len(delta))
            el = np.full((e_cap,), PAD_ID, np.int32)
            er = np.full((e_cap,), PAD_ID, np.int32)
            for i, (a, b) in enumerate(delta):
                el[i], er[i] = a, b
            left = np.concatenate([seed, el])
            right = np.concatenate([np.arange(cap, dtype=np.int32), er])
            out = comm.connected_components(
                jnp.asarray(left), jnp.asarray(right), num_nodes=cap,
                init_labels=jnp.asarray(seed),
            )
            self._labels = np.asarray(out)[:span]
        self._uf.reset_from_labels(self._labels)

    def _maybe_compact(self) -> None:
        if self.dead_fraction() >= self.compact_watermark:
            self._compact()

    def _compact(self) -> None:
        """Watermark compaction: repack the resident state to the live
        window.  The world base advances past the dead prefix (a PREFIX
        rebase: global ids are stable, device programs see only local ids
        and a dynamic shift, so nothing world-shaped recompiles); the
        slabs drop every tombstone and may SHRINK — this is the one
        boundary where capacity plans are allowed to contract, so steady
        state between compactions stays recompile-free."""
        t0 = time.perf_counter()
        base = self._base
        span = self.n - base
        n_sh = self.plan.n_shards if self._mesh_world else 1
        live_idx = np.nonzero(self._alive_np[:span])[0]
        # the base stays a multiple of n_shards so round-robin owners are
        # invariant under the shift
        first = int(live_idx[0]) if live_idx.size else span
        shift = (first // n_sh) * n_sh
        if shift:
            keep = span - shift
            self._places_np[:keep] = self._places_np[shift:span]
            self._lengths_np[:keep] = self._lengths_np[shift:span]
            self._alive_np[:keep] = self._alive_np[shift:span]
            self._expiry_np[:keep] = self._expiry_np[shift:span]
            self._alive_np[keep:span] = False
            self._expiry_np[keep:span] = NEVER_EXPIRES
            sh = jnp.asarray(shift, jnp.int32)
            if self._codes_dev is not None:
                self._codes_dev, self._len_dev = self._roll_single_runner()(
                    self._codes_dev, self._len_dev, sh
                )
            if self._places_dev is not None:
                self._places_dev = self._roll_sharded_runner()(
                    self._places_dev,
                    jnp.asarray(shift // n_sh, jnp.int32),
                )
            if self._labels.shape[0] > shift:
                self._labels = self._labels[shift:] - shift
            else:
                self._labels = np.empty((0,), np.int32)
            self._uf.reset_from_labels(self._labels)
        if self.delta_join == "device":
            if self._slab_keys is not None:
                self._compact_slabs(shift)
            self._join_stats.compact()
        # capacity plans may shrink ONLY here: the next update replans
        # from the post-compaction mirror and compiles fresh programs
        self._join_plan = None
        self._score_caps = None
        self._stream_plan = None
        self._base = base + shift
        self.compactions += 1
        self.compact_ms_total += (time.perf_counter() - t0) * 1e3

    def _compact_slabs(self, shift: int) -> None:
        """Device slab compaction: stable-partition each shard's slab
        (tombstones out, survivors rebased by ``shift``), shrinking the
        per-shard capacity to the post-compaction plan."""
        n_sh = self.plan.n_shards
        live = self._join_stats.owner_entries - self._join_stats.owner_dead
        want = int(max(np.max(live), 1) * self.planner.slack) \
            if live.size else 1
        out_cap = max(4, _pow2(want))
        if self._slab_floor:
            out_cap = max(out_cap, _pow2(-(-self._slab_floor // n_sh)))
        for _ in range(self.planner.max_retries + 1):
            k2 = self._slab_keys.reshape(n_sh, self._slab_cap)
            r2 = self._slab_rows.reshape(n_sh, self._slab_cap)
            keys_o, rows_o, _, ovf = self._compact_slab_runner(
                self._slab_cap, out_cap
            )(k2, r2, jnp.asarray(shift, jnp.int32))
            if int(np.asarray(ovf).sum()) == 0:
                break
            out_cap *= 2  # mirror drift is a bug, but never commit lossily
        self._slab_keys = keys_o.reshape(-1)
        self._slab_rows = rows_o.reshape(-1)
        self._slab_cap = out_cap

    # -- cached jit helpers for the deletion path ----------------------------

    def _mark_dead_runner(self):
        import jax

        if not hasattr(self, "_mark_dead_jit"):
            self._mark_dead_jit = jax.jit(mark_dead_rows)
        return self._mark_dead_jit

    def _compact_slab_runner(self, in_cap: int, out_cap: int):
        import jax

        if not hasattr(self, "_compact_cache"):
            self._compact_cache = {}
        fn = self._compact_cache.get((in_cap, out_cap))
        if fn is None:

            @jax.jit
            def run(k2, r2, shift):
                return jax.vmap(
                    lambda kk, rr: compact_slab(kk, rr, shift,
                                                out_cap=out_cap)
                )(k2, r2)

            self._compact_cache[(in_cap, out_cap)] = fn = run
        return fn

    def _roll_single_runner(self):
        import jax

        if not hasattr(self, "_roll_single_jit"):

            @jax.jit
            def roll(codes, lens, shift):
                cap = codes.shape[0]
                idx = (jnp.arange(cap, dtype=jnp.int32) + shift) % cap
                return jnp.take(codes, idx, axis=0), jnp.take(lens, idx)

            self._roll_single_jit = roll
        return self._roll_single_jit

    def _world_sharding(self):
        """Row-sharded placement of the round-robin places slab: shard s
        holds physical rows ``[s * cap_local, (s + 1) * cap_local)``."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self._eng.mesh(), P(self.plan.axis_name, None))

    def _roll_sharded_runner(self):
        import jax

        n_sh = self.plan.n_shards

        if not hasattr(self, "_roll_sharded_jit"):

            @functools.partial(jax.jit, out_shardings=self._world_sharding())
            def roll(places, shift_local):
                cap, L = places.shape
                cl = cap // n_sh
                p3 = places.reshape(n_sh, cl, L)
                idx = (jnp.arange(cl, dtype=jnp.int32) + shift_local) % cl
                return jnp.take(p3, idx, axis=1).reshape(cap, L)

            self._roll_sharded_jit = roll
        return self._roll_sharded_jit

    # -- ingestion: world growth + device-resident appends -------------------

    def _ingest(self, places: np.ndarray, lengths: np.ndarray,
                *, ttl: int | None = None) -> None:
        d, Lb = places.shape
        a_cap = self.planner.update_capacity(d)
        new_L = max(self.L, Lb)
        span = self.n - self._base  # resident rows (live + tombstoned)
        needed = span + d  # append slab padding rows are drop-scattered,
        #                    so they never force a growth on their own
        n_sh = self.plan.n_shards
        new_cap = self.planner.grow_capacity(
            max(self._cap, self._cap_floor), needed
        )
        if n_sh > 1:  # keep the round-robin slabs uniform
            new_cap = n_sh * self.planner.grow_capacity(
                1, -(-new_cap // n_sh)
            )
        rebuild = (new_L != self.L) or (new_cap != self._cap)
        if rebuild:
            grown = np.full((new_cap, new_L), PAD_PLACE, np.int32)
            grown[:span, : self.L] = self._places_np[:span]
            self._places_np = grown
            glen = np.zeros((new_cap,), np.int32)
            glen[:span] = self._lengths_np[:span]
            self._lengths_np = glen
            galive = np.zeros((new_cap,), bool)
            galive[:span] = self._alive_np[:span]
            self._alive_np = galive
            gexp = np.full((new_cap,), NEVER_EXPIRES, np.int64)
            gexp[:span] = self._expiry_np[:span]
            self._expiry_np = gexp
            self.L, self._cap = new_L, new_cap
        # host mirror append; the mirrors are LOCAL-indexed (slot i holds
        # global id base + i).  Device branches below read self.n as the
        # NEW world size and n0 as the first new row's global id
        n0 = self.n
        n0l = n0 - self._base
        self._places_np[n0l : n0l + d, :Lb] = places
        self._places_np[n0l : n0l + d, Lb:] = PAD_PLACE
        self._lengths_np[n0l : n0l + d] = lengths
        self._alive_np[n0l : n0l + d] = True
        eff_ttl = ttl if self.window is None \
            else (self.window if ttl is None else min(ttl, self.window))
        self._expiry_np[n0l : n0l + d] = (
            NEVER_EXPIRES if eff_ttl is None else self.updates + eff_ttl
        )
        self.n = n0 + d
        self.shard_summaries.insert(n0, lengths)
        # device-resident append: only the new rows transfer.  Each branch
        # below counts exactly the arrays it converts to device buffers,
        # so driver_bytes_in stays an exact transfer ledger
        pad_places = np.full((a_cap, self.L), PAD_PLACE, np.int32)
        pad_places[:d, :Lb] = places
        pad_lengths = np.zeros((a_cap,), np.int32)
        pad_lengths[:d] = lengths
        if not self._mesh_world:
            if rebuild or self._codes_dev is None:
                # copies: on the CPU backend jnp.asarray of an aligned
                # numpy array aliases it, and these mirrors change in place
                self._codes_dev = encode_codes(
                    jnp.array(self._places_np), self.tables
                )
                self._len_dev = jnp.array(self._lengths_np)
                self._xfer["bytes_in"] += (
                    self._places_np.nbytes + self._lengths_np.nbytes
                )
            else:
                idx = np.full((a_cap,), self._cap, np.int32)  # pads drop
                idx[:d] = n0l + np.arange(d, dtype=np.int32)
                self._xfer["bytes_in"] += (
                    pad_places.nbytes + pad_lengths.nbytes + idx.nbytes
                )
                self._codes_dev, self._len_dev = self._append_single(
                    self._codes_dev, self._len_dev,
                    jnp.asarray(pad_places), jnp.asarray(pad_lengths),
                    jnp.asarray(idx),
                )
        else:
            cl = self._cap // n_sh
            if rebuild or self._places_dev is None:
                phys = np.full((self._cap, self.L), PAD_PLACE, np.int32)
                span = self.n - self._base
                g = np.arange(span, dtype=np.int64)
                # local ids preserve the global round-robin owner: base is
                # a multiple of n_shards, so g % n_sh == (g + base) % n_sh
                phys[(g % n_sh) * cl + g // n_sh] = self._places_np[:span]
                self._places_dev = jax.device_put(phys, self._world_sharding())
                self._xfer["bytes_in"] += phys.nbytes
            else:
                g = np.arange(n0l, n0l + a_cap, dtype=np.int64)
                idx = (g % n_sh) * cl + g // n_sh
                idx[d:] = self._cap  # out of range -> dropped
                idx = idx.astype(np.int32)
                self._xfer["bytes_in"] += pad_places.nbytes + idx.nbytes
                self._places_dev = self._append_sharded(
                    self._places_dev, jnp.asarray(pad_places),
                    jnp.asarray(idx),
                )

    def _append_single(self, codes_buf, len_buf, new_places, new_lengths,
                       idx):
        import jax

        if not hasattr(self, "_append_single_jit"):
            tables = self.tables

            @jax.jit
            def append(codes_buf, len_buf, new_places, new_lengths, idx):
                new_codes = encode_codes(new_places, tables)
                codes_buf = codes_buf.at[idx].set(new_codes, mode="drop")
                len_buf = len_buf.at[idx].set(new_lengths, mode="drop")
                return codes_buf, len_buf

            self._append_single_jit = append
        return self._append_single_jit(codes_buf, len_buf, new_places,
                                       new_lengths, idx)

    def _append_sharded(self, places_dev, new_places, idx):
        import jax

        if not hasattr(self, "_append_sharded_jit"):

            @functools.partial(jax.jit, out_shardings=self._world_sharding())
            def append(places_dev, new_places, idx):
                return places_dev.at[idx].set(new_places, mode="drop")

            self._append_sharded_jit = append
        return self._append_sharded_jit(places_dev, new_places, idx)

    # -- incremental candidate generation ------------------------------------

    def _new_row_keys(self, places: np.ndarray, lengths: np.ndarray):
        """Join keys of the new rows only, from the coarsest-level view.

        Every registered backend derives its keys from the type codes +
        lengths (the sharded engine's planning contract), and a row's keys
        are independent of the batch it arrives in — so keys computed once
        at arrival stay valid for the lifetime of the index.
        """
        types = encode_types(jnp.asarray(places), self.tables)
        view = EncodedBatch(codes=types[:, None, :],
                            lengths=jnp.asarray(lengths))
        mini = TrajectoryBatch(
            places=jnp.asarray(places), lengths=jnp.asarray(lengths),
            user_id=jnp.arange(places.shape[0], dtype=jnp.int32),
        )
        keys = self.backend.join_keys(view, mini, self.backend_ctx)
        if keys is None:
            raise ValueError(
                f"candidate backend {self.backend.name!r} produces no join "
                "keys; streaming ingestion requires a key-based backend"
            )
        return np.asarray(keys)

    def _prune_delta(self, lo, hi):
        """MSS upper-bound prune of the delta pairs (same f32 test as the
        one-shot pass, so the surviving pair set is identical)."""
        bsum = float(np.asarray(self.betas, np.float32).sum())
        lens = self._lengths_np
        b = self._base
        ub = mss_upper_bound(lens[lo - b], lens[hi - b], bsum)
        keep = ub > np.float32(self.config.rho - PRUNE_EPS)
        return lo[keep], hi[keep], int(lo.shape[0] - keep.sum())

    # -- delta scoring through the existing lcs_impl dispatch ----------------

    def _score_delta(self, lo, hi):
        if not self._mesh_world:
            return self._score_delta_single(lo, hi)
        return self._score_delta_sharded(lo, hi)

    def _pad_pairs(self, lo, hi, cap):
        left = np.full((cap,), PAD_ID, np.int32)
        right = np.full((cap,), PAD_ID, np.int32)
        left[: lo.shape[0]] = lo
        right[: hi.shape[0]] = hi
        return left, right

    def _score_delta_single(self, lo, hi):
        impl = self.config.lcs_impl
        p_cap = self.planner.update_capacity(lo.shape[0])
        left, right = self._pad_pairs(lo, hi, p_cap)
        # the device table is local-indexed: ship LOCAL ids (g - base) so
        # the gather hits the right slot; the returned arrays stay global
        left_l, right_l = self._pad_pairs(
            lo - self._base, hi - self._base, p_cap
        )
        # pair_rows counts the candidate pairs the driver ships (one per
        # (lo, hi) row); bytes_in counts the padded buffers that transfer
        self._xfer["pair_rows"] += int(lo.shape[0])
        self._xfer["bytes_in"] += left_l.nbytes + right_l.nbytes
        jl, jr = jnp.asarray(left_l), jnp.asarray(right_l)
        tuning = self.planner.plan_tuning(p_cap, self._H, self.L)
        from repro.perf import resolve_wavefront_dtype

        lvl, mss = score_pairs(
            self._codes_dev, self._len_dev, jl, jr, self.betas,
            impl_name=impl,
            wavefront_dtype=resolve_wavefront_dtype(tuning),
        )
        k = lo.shape[0]
        return (left[:k], right[:k], np.asarray(lvl)[:k],
                np.asarray(mss)[:k])

    def _score_delta_sharded(self, lo, hi):
        n_sh = self.plan.n_shards
        cl = self._cap // n_sh
        # plan AND ship local ids — the plan's per-destination loads must
        # be computed under the same hashes the device program applies
        lo, hi = lo - self._base, hi - self._base
        prev = self._stream_plan
        sticky = prev is not None and prev.cap_local == cl
        # pair_cap_floor: a sticky plan may hold pair_cap above this
        # update's need, which moves the chunk-slice boundaries — the
        # fresh plan must compute its per-chunk loads under the layout
        # the runner will actually use
        splan = plan_stream_capacities(
            lo, hi, n_sh, cl, score_mode=self.plan.score_mode,
            overlap_chunks=self.plan.overlap_chunks,
            pair_cap_floor=prev.pair_cap if sticky else 0,
        )
        if sticky:
            # sticky capacities: monotone max keeps the compiled runner hot
            splan = StreamShardPlan(
                n_shards=n_sh, cap_local=cl,
                pair_cap=max(splan.pair_cap, prev.pair_cap),
                hop_cap=max(splan.hop_cap, prev.hop_cap),
                out_cap=max(splan.out_cap, prev.out_cap),
                n_chunks=splan.n_chunks,
            )
            if self.plan.score_mode == "replicate":
                splan = dataclasses.replace(splan, out_cap=splan.pair_cap)
        for _ in range(self.planner.max_retries + 1):
            out = self._run_stream_runner(splan, lo, hi)
            if int(np.asarray(out["overflow"]).sum()) == 0:
                break
            splan = dataclasses.replace(
                splan, hop_cap=max(splan.hop_cap, 1) * 2,
                out_cap=splan.out_cap * 2,
            )
        self._stream_plan = splan
        self._overflow += int(np.asarray(out["overflow"]).sum())
        return self._collect_scored(out)

    def _collect_scored(self, out):
        left = np.asarray(out["left"]).reshape(-1)
        right = np.asarray(out["right"]).reshape(-1)
        mss = np.asarray(out["mss"]).reshape(-1)
        lvl = np.asarray(out["level_lcs"]).reshape(-1, self._H)
        valid = left != PAD_ID
        # device programs speak local ids; results surface as global
        left = left[valid] + self._base
        right = right[valid] + self._base
        lvl, mss = lvl[valid], mss[valid]
        # canonical order: results come back in shuffle-resting order
        order = np.lexsort((right, left))
        return left[order], right[order], lvl[order], mss[order]

    def _score_runner(self, splan, *, score_prune: bool):
        """One cached streaming score runner per (plan, mode, impl, dtype,
        world shape, prune) — shared by the host-pair and device-pair
        paths so their cache keys cannot drift apart."""
        # tuning resolves eagerly at build time (static kernel args); a
        # miss is None = untuned defaults
        tuning = self.planner.plan_tuning(splan.pair_cap, self._H, self.L)
        key = (splan, self.plan.score_mode, self.config.lcs_impl,
               wavefront_dtype_from_env(), self.L, self._H, score_prune,
               tuning)
        runner = self._runner_cache.get(key)
        if runner is None:
            runner = make_streaming_score_pipeline(
                self._eng.mesh(), splan, betas=self.betas,
                axis_name=self.plan.axis_name,
                score_mode=self.plan.score_mode,
                lcs_impl=self.config.lcs_impl,
                trace_counter=self.score_traces,
                score_prune=score_prune,
                prune_tau=self.config.rho,
                tuning=tuning,
            )
            self._runner_cache[key] = runner
            self.runner_builds += 1
        return runner

    def _run_stream_runner(self, splan, lo, hi):
        # host path: pairs were already pruned host-side, so the score
        # program never prunes
        runner = self._score_runner(splan, score_prune=False)
        n_sh, p = splan.n_shards, int(lo.shape[0])
        chunk = -(-p // n_sh) if p else 0
        left = np.full((n_sh, splan.pair_cap), PAD_ID, np.int32)
        right = np.full((n_sh, splan.pair_cap), PAD_ID, np.int32)
        for s in range(n_sh):
            sl = lo[s * chunk : (s + 1) * chunk]
            left[s, : sl.shape[0]] = sl
            sr = hi[s * chunk : (s + 1) * chunk]
            right[s, : sr.shape[0]] = sr
        self._xfer["pair_rows"] += int(lo.shape[0])
        self._xfer["bytes_in"] += left.nbytes + right.nbytes
        return runner(
            self._places_dev, jnp.asarray(left.reshape(-1)),
            jnp.asarray(right.reshape(-1)), self.tables,
        )

    # -- in-mesh incremental delta join (delta_join="device") ----------------

    def _device_delta_join(self, keys_np, n_old: int):
        """Ship ONLY the new rows' key occurrences into the in-mesh join.

        The resident bucket state (key-sharded sorted slabs) is probed and
        merged on-device; the deduped delta pairs come to rest in-mesh as
        ``[n_shards, pair_cap]`` buffers that feed the score program
        directly.  Returns ``(left_dev, right_dev, num_delta, max_delta,
        examined)`` where ``max_delta`` is the in-mesh pmax of the
        per-shard post-dedup counts — the tight score-buffer bound.

        State is committed functionally: the join program RETURNS the
        merged slabs, and the engine adopts them (and folds the update
        into the planning-count mirror) only after a run with zero
        overflow — so the overflow-retry loop replans and re-runs from
        unchanged state.
        """
        keys_np = np.asarray(keys_np)
        # per-row key SET (vectorized: sort each row, drop PAD and
        # adjacent duplicates), matching BucketIndex.insert's defensive
        # dedup, so the examined count stays the exact per-bucket C(n, 2)
        # partition
        ks = np.sort(keys_np, axis=1)
        valid = ks != PAD_KEY
        valid[:, 1:] &= ks[:, 1:] != ks[:, :-1]
        row_idx, col_idx = np.nonzero(valid)
        k_flat = ks[row_idx, col_idx].astype(np.int32)
        if k_flat.size == 0:
            return None, None, 0, 0, 0
        n_sh = self.plan.n_shards
        fresh = self.planner.plan_stream_join(k_flat, n_sh,
                                              self._join_stats)
        if _fault_inject():
            # derate every stage of the FRESH plan (sticky maxima still
            # apply) so the overflow -> compact -> retry path runs
            fresh = dataclasses.replace(
                fresh,
                key_route_cap=_derate_cap(fresh.key_route_cap),
                nn_cap=_derate_cap(fresh.nn_cap),
                no_cap=_derate_cap(fresh.no_cap),
                pair_route_cap=_derate_cap(fresh.pair_route_cap),
                pair_cap=_derate_cap(fresh.pair_cap),
            )
        jplan = sticky_join_plan(fresh, self._join_plan)
        if self._slab_cap > jplan.slab_cap:
            # the resident arrays only shrink at a compaction boundary
            # (_compact rebuilds them); between boundaries the plan must
            # match their actual allocation
            jplan = dataclasses.replace(jplan, slab_cap=self._slab_cap)
        if self._slab_floor:
            floor = _pow2(-(-self._slab_floor // n_sh))
            if floor > jplan.slab_cap:
                jplan = dataclasses.replace(jplan, slab_cap=floor)
        out = None
        retries = self.planner.max_retries + (4 if _fault_inject() else 0)
        compacted = False
        for _ in range(retries + 1):
            self._ensure_slab(jplan.slab_cap)
            # local row ids (recomputed per attempt: a mid-loop compaction
            # moves the base under us)
            r_flat = (n_old - self._base + row_idx).astype(np.int32)
            chunk = -(-k_flat.shape[0] // n_sh)
            in_k = np.full((n_sh, jplan.key_in_cap), PAD_KEY, np.int32)
            in_r = np.full((n_sh, jplan.key_in_cap), PAD_ID, np.int32)
            for s in range(n_sh):
                seg = slice(s * chunk, (s + 1) * chunk)
                in_k[s, : k_flat[seg].shape[0]] = k_flat[seg]
                in_r[s, : r_flat[seg].shape[0]] = r_flat[seg]
            # key_rows counts the (key, row-id) occurrences the driver
            # ships (one per valid tuple); bytes_in the padded buffers
            self._xfer["key_rows"] += int(k_flat.shape[0])
            self._xfer["bytes_in"] += in_k.nbytes + in_r.nbytes
            out = self._join_runner(jplan)(
                self._slab_keys, self._slab_rows,
                jnp.asarray(in_k.reshape(-1)), jnp.asarray(in_r.reshape(-1)),
            )
            ovf = np.asarray(out["overflow"]).sum(axis=0)
            if int(ovf.sum()) == 0:
                break
            if int(ovf[2]) and not compacted \
                    and int(self._join_stats.owner_dead.sum()):
                # slab overflow with tombstones resident: reclaim the dead
                # slots FIRST and retry at the (possibly smaller) post-
                # compaction plan — growth is the last resort, not the
                # first response to a slab that is mostly tombstones
                self._compact()
                compacted = True
                jplan = self.planner.plan_stream_join(
                    k_flat, n_sh, self._join_stats
                )
                if self._slab_cap > jplan.slab_cap:
                    jplan = dataclasses.replace(
                        jplan, slab_cap=self._slab_cap
                    )
                continue
            # exact planning makes steady-state overflow impossible; this
            # belt-and-braces path doubles whatever stage busted
            jplan = dataclasses.replace(
                jplan,
                key_route_cap=jplan.key_route_cap * 2,
                nn_cap=jplan.nn_cap * 2, no_cap=jplan.no_cap * 2,
                pair_route_cap=jplan.pair_route_cap * 2,
                pair_cap=jplan.pair_cap * 2,
                slab_cap=jplan.slab_cap * (2 if int(ovf[2]) else 1),
            )
            self._admission_check_bytes(
                self._resident_bytes_at(self._cap, jplan.slab_cap),
                "in-mesh delta join retry doubling",
            )
        if int(np.asarray(out["overflow"]).sum()):
            # never adopt a slab whose merge dropped entries: committing it
            # would silently lose every future pair involving the dropped
            # rows.  Exact planning makes this unreachable; reaching it
            # means the planning invariant broke, so fail loudly.
            raise CapacityExceeded(
                "in-mesh delta join still overflowed after "
                f"{retries} retries (per-shard overflow "
                f"{np.asarray(out['overflow']).tolist()}); refusing to "
                "commit a lossy bucket state"
            )
        self._slab_keys = out["slab_keys"]
        self._slab_rows = out["slab_rows"]
        self._join_stats.commit(k_flat, _positive_hash_np(k_flat) % n_sh)
        self._join_plan = jplan
        num_delta = int(np.asarray(out["count"]).sum())
        max_delta = int(np.asarray(out["max_count"])[0])
        examined = int(np.asarray(out["examined"]).sum())
        return out["left"], out["right"], num_delta, max_delta, examined

    def _ensure_slab(self, slab_cap: int) -> None:
        """Allocate or regrow the resident slabs to ``slab_cap`` per shard.

        Regrowth pads each shard's segment at the END (valid entries stay
        compacted at the front, PAD_KEY sorts last) entirely on-device —
        the resident keys never round-trip through the host.
        """
        n_sh = self.plan.n_shards
        if self._slab_keys is None:
            self._slab_cap = slab_cap
            self._slab_keys = jnp.full((n_sh * slab_cap,), PAD_KEY, jnp.int32)
            self._slab_rows = jnp.full((n_sh * slab_cap,), PAD_ID, jnp.int32)
        elif slab_cap > self._slab_cap:
            pad = ((0, 0), (0, slab_cap - self._slab_cap))
            k = self._slab_keys.reshape(n_sh, self._slab_cap)
            r = self._slab_rows.reshape(n_sh, self._slab_cap)
            self._slab_keys = jnp.pad(
                k, pad, constant_values=PAD_KEY).reshape(-1)
            self._slab_rows = jnp.pad(
                r, pad, constant_values=PAD_ID).reshape(-1)
            self._slab_cap = slab_cap

    def _join_runner(self, jplan):
        runner = self._join_runner_cache.get(jplan)
        if runner is None:
            runner = make_streaming_join_pipeline(
                self._eng.mesh(), jplan, axis_name=self.plan.axis_name,
                trace_counter=self.join_traces,
            )
            self._join_runner_cache[jplan] = runner
            self.runner_builds += 1
        return runner

    def _score_device_pairs(self, left_dev, right_dev, max_delta,
                            num_delta):
        """Score the in-mesh delta pairs straight off their device buffers.

        The pairs rest on their pair-hash shard; "replicate" scores them
        in place against the all_gathered in-mesh encodings, "shuffle"
        runs the shared owner hops.  ``score_prune`` is applied IN-MESH by
        the score program (the pairs never visit the host to be pruned
        there).

        The score buffers are sized from the join's in-mesh count
        reduction, NOT from the join plan's pre-dedup emission bound:
        dedup compacts every shard's valid pairs to the front, so the
        resting ``[n_shards, join_pair_cap]`` buffers slice down to
        ``pow2(max_delta)`` columns exactly (replicate scores in place,
        bounded per shard by ``max_delta``; the shuffle hops and resting
        buffers are bounded by the GLOBAL post-dedup count ``num_delta``,
        since a redistribution can pile every pair onto one owner).  Both
        caps are sticky (monotone max) so they inherit the join plan's
        zero-steady-state-recompile property.
        """
        n_sh = self.plan.n_shards
        join_cap = int(left_dev.shape[-1])
        pair_cap = min(_pow2(max_delta), join_cap)
        rest_cap = min(_pow2(num_delta), join_cap)
        if self._score_caps is not None:
            pair_cap = min(max(pair_cap, self._score_caps[0]), join_cap)
            rest_cap = min(max(rest_cap, self._score_caps[1]), join_cap)
        self._score_caps = (pair_cap, rest_cap)
        if pair_cap < join_cap:
            left_dev = left_dev[:, :pair_cap]
            right_dev = right_dev[:, :pair_cap]
        shuffle = self.plan.score_mode == "shuffle"
        splan = StreamShardPlan(
            n_shards=n_sh, cap_local=self._cap // n_sh, pair_cap=pair_cap,
            hop_cap=rest_cap if shuffle else 0,
            out_cap=rest_cap if shuffle else pair_cap,
        )
        for _ in range(self.planner.max_retries + 1):
            out = self._run_device_score(splan, left_dev, right_dev)
            if int(np.asarray(out["overflow"]).sum()) == 0:
                break
            splan = dataclasses.replace(
                splan, hop_cap=max(splan.hop_cap, 1) * 2,
                out_cap=splan.out_cap * 2,
            )
        self._overflow += int(np.asarray(out["overflow"]).sum())
        num_pruned = int(np.asarray(out["pruned"]).sum())
        return (*self._collect_scored(out), num_pruned)

    def _run_device_score(self, splan, left_dev, right_dev):
        # device path: pruning (if configured) runs IN-MESH — the pairs
        # are not on the host to be pruned there
        runner = self._score_runner(splan,
                                    score_prune=self.config.score_prune)
        return runner(self._places_dev, left_dev.reshape(-1),
                      right_dev.reshape(-1), self.tables)

    # -- accumulation + incremental communities ------------------------------

    def _accumulate_scored(self, left, right, lvl, mss):
        k = left.shape[0]
        if self._acc_n + k > self._acc_cap:
            cap = self.planner.grow_capacity(
                max(self._acc_cap, 16), self._acc_n + k
            )
            for name in ("_acc_left", "_acc_right", "_acc_lvl", "_acc_mss"):
                old = getattr(self, name)
                shape = (cap,) + old.shape[1:]
                grown = np.full(shape, PAD_ID, old.dtype) \
                    if old.dtype == np.int32 and old.ndim == 1 \
                    else np.zeros(shape, old.dtype)
                grown[: self._acc_n] = old[: self._acc_n]
                setattr(self, name, grown)
            self._acc_cap = cap
        s = slice(self._acc_n, self._acc_n + k)
        self._acc_left[s] = left
        self._acc_right[s] = right
        self._acc_lvl[s] = lvl
        self._acc_mss[s] = mss
        self._acc_n += k

    def _scored(self) -> ScoredPairs:
        n = self._acc_n
        return ScoredPairs(
            left=jnp.asarray(self._acc_left[:n]),
            right=jnp.asarray(self._acc_right[:n]),
            level_lcs=jnp.asarray(self._acc_lvl[:n]),
            mss=jnp.asarray(self._acc_mss[:n]),
            count=jnp.asarray(n, jnp.int32),
            overflow=jnp.asarray(self._overflow, jnp.int32),
        )

    def _fold_edges(self, new_edges) -> set:
        self.similar_pairs.update(
            (int(a), int(b)) for a, b in new_edges
        )
        # the union-find / label state lives in LOCAL index space (node i
        # = global id base + i) so compaction can slide it with the world
        base = self._base
        self._uf.add(self.n - base - self._uf.num_nodes)
        for a, b in new_edges:
            self._uf.union(int(a) - base, int(b) - base)
        mode = self.config.community_mode
        if mode == "cliques":
            return comm.maximal_cliques(self.similar_pairs)
        if mode != "components":
            raise ValueError(
                f"unknown community_mode {mode!r}; valid modes: "
                "['cliques', 'components']"
            )
        if self.components_impl == "unionfind":
            self._labels = self._uf.labels()
            return self._sets_to_global(
                comm.components_as_sets(self._labels)
            )
        return self._jit_components(new_edges)

    def _sets_to_global(self, sets: set) -> set:
        """Translate local-index community sets to global trajectory ids."""
        base = self._base
        if not base:
            return sets
        return {frozenset(i + base for i in s) for s in sets}

    def _jit_components(self, new_edges) -> set:
        """Resumable min-label propagation: the previous fixpoint becomes
        star edges ``(label[v], v)`` — each old component collapses to a
        star — so only the DELTA edges (plus the stars) run through
        :func:`connected_components`, seeded with the stale labels.  Shapes
        are padded to the world capacity / a power-of-two edge cap so
        steady-state updates reuse the compiled program.
        """
        if self.n <= self._base:
            return set()
        base = self._base
        cap = self._cap
        seed = np.arange(cap, dtype=np.int32)
        seed[: self._labels.shape[0]] = self._labels
        e_cap = self.planner.update_capacity(len(new_edges))
        el = np.full((e_cap,), PAD_ID, np.int32)
        er = np.full((e_cap,), PAD_ID, np.int32)
        for i, (a, b) in enumerate(new_edges):
            el[i], er[i] = a - base, b - base
        left = np.concatenate([seed, el])
        right = np.concatenate([np.arange(cap, dtype=np.int32), er])
        labels = comm.connected_components(
            jnp.asarray(left), jnp.asarray(right), num_nodes=cap,
            init_labels=jnp.asarray(seed),
        )
        self._labels = np.asarray(labels)[: self.n - base]
        return self._sets_to_global(comm.components_as_sets(self._labels))
