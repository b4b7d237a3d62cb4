"""`AnotherMeEngine`: one entry point for the whole pipeline.

    from repro.api import AnotherMeEngine, EngineConfig, ExecutionPlan

    engine = AnotherMeEngine(forest, EngineConfig(backend="ssh", rho=2.0))
    result = engine.run(batch)                       # single-device jit

    engine = AnotherMeEngine(forest, EngineConfig(backend="minhash"),
                             ExecutionPlan(n_shards=8))
    result = engine.run(batch)                       # shard_map execution

The engine composes the typed stages of api/stages.py — Encode, Candidate,
Score, Communities — and selects single-device jit or shard_map execution
from a single :class:`ExecutionPlan` instead of two divergent code paths:
with ``n_shards > 1`` the Encode+Candidate+Score stages are replaced by one
fused device-resident shard_map stage (api/sharded.py) while Communities is
shared verbatim — raw trajectories are sharded once, encoding runs in-mesh,
and the code table never materializes replicated on the host.  Candidate
generation is chosen by registry name (api/backends.py) and capacity policy
lives in the shared CapacityPlanner (api/capacity.py); phase timing is
collected by the instrumentation wrapper so the stage logic itself stays
pure and jit-cacheable across repeated runs with identical static shapes.

``lcs_impl`` (EngineConfig, overridable per ExecutionPlan) selects the LCS
implementation on BOTH paths: the Pallas kernel runs inside shard_map
exactly as it does under single-device jit.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.backends import (
    BackendContext, CandidateBackend, get_backend,
)
from repro.api.capacity import CapacityPlanner
from repro.api.instrumentation import Instrumentation
from repro.api.sharded import (
    DistributedPlan, MeshStages, pow2_capacity,
    pad_to_shards, plan_join, plan_score, sticky_plan, unpack_stats,
)
from repro.api.stages import (
    CandidateStage, CommunitiesStage, EncodeStage, PipelineContext, ScoreStage,
    record_similar, validate_lcs_impl,
)
from repro.core import compat
from repro.core.encoding import SemanticForest, encode_types, forest_tables
from repro.core.pipeline import AnotherMeResult as EngineResult
from repro.core.similarity import default_betas
from repro.core.types import EncodedBatch, ScoredPairs, TrajectoryBatch


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Algorithm parameters (paper defaults; section V.1)."""

    k: int = 3                      # shingle order
    rho: float = 2.0                # similarity threshold
    betas: tuple | None = None      # level weights; None -> uniform 1/n
    backend: str = "ssh"            # candidate backend registry name
    backend_options: Mapping | None = None  # kwargs for the backend factory
    lcs_impl: str = "wavefront"     # "wavefront" | "ref" | "fused" |
    #                                 "fused-pallas" | "fused-interpret"
    score_prune: bool = False       # MSS upper-bound pruning before exact
    #                                 scoring (tau = rho); changes the
    #                                 scored buffer (hopeless pairs are
    #                                 dropped) but never the similar set
    pair_capacity: int | None = None  # None -> plan from exact join size
    capacity_slack: float = 1.10
    community_mode: str = "cliques"  # "cliques" | "components"
    max_retries: int = 3
    subtraj_window: int | None = None  # subtrajectory mode: key + score
    #                                 sliding windows of width W instead of
    #                                 whole trajectories; candidate pairs
    #                                 carry (traj, offset) window ids and a
    #                                 host max-over-windows reduction folds
    #                                 scores back to trajectory pairs
    #                                 (core/subtraj.py).  W >= L degenerates
    #                                 to whole-trajectory results.
    subtraj_stride: int = 1         # window start stride s (offsets 0, s,
    #                                 2s, ...); ignored unless
    #                                 subtraj_window is set


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Where and how the pipeline executes.

    n_shards=1 runs the jitted single-device stages; n_shards>1 runs the
    shard_map pipeline on the first n_shards devices (or ``devices``),
    padding the batch to a multiple of n_shards with empty trajectories.
    """

    n_shards: int = 1
    score_mode: str = "replicate"   # "replicate" | "shuffle" (sharded only)
    axis_name: str = "ex"
    devices: tuple | None = None    # default: jax.devices()[:n_shards]
    shard_slack: float = 1.3        # slack for the sharded capacity plan
    lcs_impl: str | None = None     # override EngineConfig.lcs_impl (both
    #                                 execution paths); None -> use config
    delta_join: str = "host"        # streaming only: "host" keeps the
    #                                 incremental bucket table on the driver
    #                                 (core/stream_index.py — the oracle);
    #                                 "device" key-shards it into resident
    #                                 slabs and joins in-mesh, so neither
    #                                 world keys nor the pair list transit
    #                                 the driver (core/device_index.py);
    #                                 ignored by AnotherMeEngine.run
    autotune: bool = False          # consult the cached repro.perf tuning
    #                                 table (TUNING.json) for score-stage
    #                                 kernel parameters; resolved eagerly,
    #                                 bit-identical results guaranteed
    overlap_chunks: int = 1         # shuffle-mode gather/score overlap:
    #                                 the least number of chunks (power of
    #                                 two) the pair buffer is split into so
    #                                 chunk i+1's owner hops run while chunk
    #                                 i scores; AnotherMeEngine.run raises
    #                                 it to what keeps one chunk's hop
    #                                 buffers within a quarter of a
    #                                 device's memory (sharded.plan_score);
    #                                 ignored in "replicate" mode and on
    #                                 the delta_join="device" scoring path
    #                                 (its pairs rest in-mesh under the
    #                                 join plan's layout, which the exact
    #                                 per-chunk planner cannot see)

    def __post_init__(self):
        oc = self.overlap_chunks
        if oc < 1 or (oc & (oc - 1)):
            raise ValueError(
                f"overlap_chunks must be a power of two >= 1, got {oc}"
            )


class AnotherMeEngine:
    """Composable AnotherMe pipeline over a fixed semantic forest.

    One engine instance owns the forest tables, the candidate backend, the
    capacity planner, and (for sharded plans) a cache of compiled shard_map
    runners, so repeated ``run`` calls with identical static shapes reuse
    every jit cache.
    """

    def __init__(
        self,
        forest: SemanticForest,
        config: EngineConfig = EngineConfig(),
        plan: ExecutionPlan = ExecutionPlan(),
        *,
        backend: CandidateBackend | None = None,
    ):
        if plan.lcs_impl is not None:
            # the plan's override folds into the config so every stage —
            # single-device ScoreStage or the staged shard_map programs —
            # reads one authoritative lcs_impl
            config = dataclasses.replace(config, lcs_impl=plan.lcs_impl)
        validate_lcs_impl(config.lcs_impl)
        if plan.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {plan.n_shards}")
        oc = plan.overlap_chunks
        if oc < 1 or (oc & (oc - 1)):
            raise ValueError(
                f"overlap_chunks must be a power of two >= 1, got {oc}"
            )
        self.forest = forest
        self.config = config
        self.plan = plan
        self.tables = forest_tables(forest)
        self.betas = (
            jnp.asarray(config.betas, jnp.float32)
            if config.betas is not None
            else default_betas(forest.num_levels)
        )
        self.backend = backend if backend is not None else get_backend(
            config.backend, **dict(config.backend_options or {})
        )
        if plan.n_shards > 1 and not self.backend.supports_sharded:
            raise ValueError(
                f"candidate backend {self.backend.name!r} produces no join "
                "keys and only supports ExecutionPlan(n_shards=1); use a "
                "registered key-based backend for sharded execution"
            )
        if config.subtraj_window is not None:
            if config.subtraj_window < 1:
                raise ValueError(
                    f"subtraj_window must be positive, got "
                    f"{config.subtraj_window}"
                )
            if config.subtraj_stride < 1:
                raise ValueError(
                    f"subtraj_stride must be positive, got "
                    f"{config.subtraj_stride}"
                )
            if not self.backend.supports_sharded:
                raise ValueError(
                    f"candidate backend {self.backend.name!r} produces no "
                    "join keys; the subtrajectory mode needs key-based "
                    "candidates to carry (traj, offset) window coordinates"
                )
        self.backend_ctx = BackendContext(
            k=config.k, num_types=forest.num_types,
            window=config.subtraj_window, stride=config.subtraj_stride,
        )
        self.planner = CapacityPlanner(
            slack=config.capacity_slack, max_retries=config.max_retries,
            autotune=plan.autotune,
        )
        if plan.n_shards == 1:
            self._stages = (
                EncodeStage(), CandidateStage(), ScoreStage(), CommunitiesStage(),
            )
        else:
            # encoding folds into the shard_map program: no host EncodeStage
            self._stages = (
                _ShardedEncodeJoinScoreStage(self), CommunitiesStage(),
            )
        self._mesh = None
        self._stages_cache: dict = {}
        self._sticky: dict = {}        # plans and capacities, by batch shape

    # -- public entry point --------------------------------------------------

    def run(self, batch: TrajectoryBatch) -> EngineResult:
        """Run the full pipeline on one batch; same signature either way."""
        if self.plan.n_shards > 1:
            batch = self._padded(batch)
        with Instrumentation() as instr:
            ctx = PipelineContext(
                batch=batch, forest=self.forest, tables=self.tables,
                betas=self.betas, config=self.config, backend=self.backend,
                backend_ctx=self.backend_ctx, planner=self.planner,
                instr=instr, sticky=self._sticky,
            )
            for stage in self._stages:
                stage.run(ctx)
            return EngineResult(
                scored=ctx.scored, similar_pairs=ctx.similar_pairs,
                communities=ctx.communities, stats=instr.finalize(),
            )

    # -- sharded-execution plumbing ------------------------------------------

    def _padded(self, batch: TrajectoryBatch) -> TrajectoryBatch:
        places, lengths = pad_to_shards(
            np.asarray(batch.places), np.asarray(batch.lengths),
            self.plan.n_shards,
        )
        if places.shape[0] == batch.num_trajectories:
            return batch
        return TrajectoryBatch(
            places=jnp.asarray(places), lengths=jnp.asarray(lengths),
            user_id=jnp.arange(places.shape[0], dtype=jnp.int32),
        )

    def mesh(self) -> jax.sharding.Mesh:
        if self._mesh is None:
            n = self.plan.n_shards
            devices = self.plan.devices or tuple(jax.devices())[:n]
            if len(devices) < n:
                raise ValueError(
                    f"ExecutionPlan(n_shards={n}) needs {n} devices, "
                    f"have {len(jax.devices())}"
                )
            self._mesh = compat.make_mesh(
                (n,), (self.plan.axis_name,), devices=devices
            )
        return self._mesh

    def _mesh_stages(self, key_fn, local_n: int, subtraj=None) -> MeshStages:
        """The staged shard_map programs for one batch shape (cached, so
        repeated runs reuse every compiled program)."""
        key = (key_fn is None, local_n, subtraj)
        stages = self._stages_cache.get(key)
        if stages is None:
            stages = MeshStages(
                self.mesh(), n_shards=self.plan.n_shards, local_n=local_n,
                betas=self.betas, key_fn=key_fn,
                axis_name=self.plan.axis_name,
                score_mode=self.plan.score_mode,
                lcs_impl=self.config.lcs_impl,
                score_prune=self.config.score_prune,
                prune_tau=self.config.rho, subtraj=subtraj,
                rho=self.config.rho,
            )
            self._stages_cache[key] = stages
        return stages


class _ShardedEncodeJoinScoreStage:
    """Encode + Candidate + Score as staged shard_map programs (Fig. 5).

    The device work is fully resident: raw places are sharded once,
    encoding runs in-mesh, and neither the code table nor the join keys
    transit the host (keyless backends, "udf", build their keys on the
    host and have them shuffled in).  Every capacity is planned from
    counts the stage before made in the mesh (:class:`MeshStages`), as a
    power of two kept sticky per batch shape, so jobs of one size run the
    programs the first one compiled.  A capacity bust retries with larger
    buffers, like the single-device planner.
    """

    name = "sharded_encode_join_score"

    def __init__(self, engine: AnotherMeEngine):
        self.engine = engine

    def run(self, ctx: PipelineContext) -> None:
        eng = self.engine
        plan, config, instr = eng.plan, eng.config, ctx.instr
        batch, n = ctx.batch, plan.n_shards

        # subtrajectory mode: (window, stride, nw) from the PADDED length —
        # static shape facts every layer below keys its caches on
        subtraj = None
        if config.subtraj_window is not None:
            from repro.core.subtraj import num_windows

            L = int(batch.places.shape[1])
            subtraj = (
                min(config.subtraj_window, L), config.subtraj_stride,
                num_windows(L, config.subtraj_window, config.subtraj_stride),
            )

        key_fn = ctx.backend.shard_key_fn(ctx.backend_ctx)
        first = batch.places
        if key_fn is None:
            with instr.phase("keys"):
                # a keyless backend's keys exist only on the host
                types = encode_types(batch.places, ctx.tables)
                ctx.keys = ctx.backend.join_keys(
                    EncodedBatch(codes=types[:, None, :],
                                 lengths=batch.lengths),
                    batch, ctx.backend_ctx)
                first = jnp.asarray(ctx.keys)

        local_n = batch.num_trajectories // n
        stages = eng._mesh_stages(key_fn, local_n, subtraj)
        shape = (first.shape, batch.places.shape, ctx.tables.shape, subtraj)
        prev = eng._sticky.get(shape)
        dplan, out, counts = self._execute(
            ctx, stages, prev, first, local_n, instr)
        overflow = np.int32(self._overflow(counts))
        count = np.int32(counts["score"]["resting"].sum())

        with instr.phase("results"):
            if subtraj is None:
                dplan = self._similar(ctx, stages, dplan, out)
                # the scored buffers stay on the devices
                ctx.scored = ScoredPairs(
                    left=out[0], right=out[1], level_lcs=out[2], mss=out[3],
                    count=count, overflow=overflow,
                )
            else:
                # fold scored window pairs to trajectory pairs (max-over-
                # windows) before anything downstream sees them —
                # communities, similar_pairs, and the returned scored
                # buffer all speak trajectory ids
                from repro.core.subtraj import aggregate_window_pairs

                left, right, level_lcs, mss = (np.asarray(x) for x in out)
                tl, tr, tlvl, tmss = aggregate_window_pairs(
                    left, right, level_lcs, mss, nw=subtraj[2]
                )
                ctx.scored = ScoredPairs(
                    left=tl, right=tr, level_lcs=tlvl, mss=tmss,
                    count=np.int32(tl.shape[0]), overflow=overflow,
                )
                keep = tmss > np.float32(config.rho)
                record_similar(ctx, tl[keep], tr[keep])
        eng._sticky[shape] = dplan
        self._record(instr, dplan, counts, overflow)
        instr.record(
            num_candidates=int(count),
            num_similar=len(ctx.similar_pairs),
        )
        if subtraj is not None:
            instr.record(
                num_window_pairs=int(count),
                num_traj_pairs=int(ctx.scored.left.shape[0]),
                subtraj_windows=subtraj[2],
            )

    def _similar(self, ctx, stages, dplan, out):
        """The similar pairs on the host: each shard's similar pairs are
        counted and moved to the front of a sticky power-of-two buffer in
        the mesh, and only that buffer is copied; it is the communities
        program's edge buffer as it is.  Returns the plan with the
        buffer's capacity."""
        n = dplan.n_shards
        sims = np.asarray(stages.similar_count()(out[3])[0])
        # the count is exact, so the slack only keeps the buffer sticky
        slack = max(self.engine.plan.shard_slack, 1.0)
        cap = max(pow2_capacity(sims.max(), slack), dplan.similar_cap)
        left, right = (np.asarray(x).reshape(n, cap)
                       for x in stages.similar(cap)(out[0], out[1], out[3]))
        record_similar(ctx, *(
            np.concatenate([x[s, :k] for s, k in enumerate(sims)])
            for x in (left, right)), buffer=(left.ravel(), right.ravel()))
        return dataclasses.replace(dplan, similar_cap=cap)

    @staticmethod
    def _overflow(counts) -> int:
        return int(sum(counts[stage][key].sum() for stage, key in (
            ("route", "overflow"), ("join", "overflow"),
            ("join", "route_overflow"), ("score", "overflow"))))

    def _execute(self, ctx, stages, prev, first, local_n, instr):
        """Plan and run the route, join and score programs.  Returns (the
        plan, the resting (left, right, level_lcs, mss), each stage's
        counts)."""
        eng = self.engine
        plan, batch = eng.plan, ctx.batch
        n, slack, retries = plan.n_shards, plan.shard_slack, \
            eng.planner.max_retries
        args = (first, batch.places, batch.lengths, ctx.tables)

        retried = 0

        def grown(cap, need):
            return max(cap * 2, pow2_capacity(need, slack))

        with instr.phase("plan"):
            # the key loads of shuffle 1 (a pass of their own before the
            # first plan), then each shard's pre-dedup join size
            if prev is None:
                loads = np.asarray(stages.loads()(*args)[0])
                cap1 = pow2_capacity(loads.max(), slack)
            else:
                cap1 = prev.shingle_route_cap
            for attempt in range(retries + 1):
                rk, rid, st = stages.route(cap1)(*args)
                route = unpack_stats(st, stages.layout("route"))
                if route["overflow"].sum() == 0 or attempt == retries:
                    break
                cap1 = grown(cap1, route["loads"].max())
                retried += 1
            caps = plan_join(route, n, slack)
            caps["shingle_route_cap"] = cap1
            if prev is not None:
                caps = {k: max(v, getattr(prev, k)) for k, v in caps.items()}

        with instr.phase("execute"):
            with instr.phase("join"):
                pair_cap = caps["local_pair_cap"]
                route_cap = caps["pair_route_cap"]
                for attempt in range(retries + 1):
                    left, right, st = stages.join(pair_cap, route_cap)(
                        rk, rid, batch.lengths)
                    join = unpack_stats(st, stages.layout("join"))
                    busted = join["overflow"].sum() \
                        + join["route_overflow"].sum()
                    if busted == 0 or attempt == retries:
                        break
                    if join["overflow"].sum():
                        pair_cap *= 2
                    route_cap = grown(route_cap, join["route_loads"].max())
                    retried += 1
            H, L = eng.forest.num_levels, int(batch.places.shape[1])
            dplan = sticky_plan(DistributedPlan(
                n_shards=n, local_n=local_n, shingle_route_cap=cap1,
                local_pair_cap=pair_cap, pair_route_cap=route_cap,
                **plan_score(
                    join, n, slack, score_mode=plan.score_mode,
                    score_prune=eng.config.score_prune,
                    min_chunks=max(plan.overlap_chunks,
                                   prev.n_chunks if prev else 1),
                    hop_row_bytes=2 * 4 * (-(-H * L // 128) * 128),
                    memory_bytes=compat.device_memory_bytes(),
                ),
            ), prev)
            with instr.phase("score"):
                for attempt in range(retries + 1):
                    tuning = eng.planner.plan_tuning(
                        dplan.pruned_cap or dplan.scored_cap, H, L)
                    *out, st = stages.score(dplan, tuning)(
                        left, right, batch.places, batch.lengths, ctx.tables)
                    out[-1].block_until_ready()
                    score = unpack_stats(st, stages.layout("score"))
                    if score["overflow"].sum() == 0 or attempt == retries:
                        break
                    dplan = dataclasses.replace(dplan, **{
                        k: getattr(dplan, k) * 2 for k in (
                            "scored_cap", "owner_route_cap", "pruned_cap",
                            "chunk_hop_cap", "chunk_rest_cap")})
                    retried += 1
        instr.record(retries=retried)
        return dplan, out, dict(route=route, join=join, score=score)

    def _record(self, instr, dplan, counts, overflow):
        """The plan and the in-mesh counters of the job."""
        n = dplan.n_shards
        slots = n * n
        hop_slots = slots * dplan.n_chunks * (
            dplan.chunk_hop_cap if dplan.n_chunks > 1
            else dplan.owner_route_cap)
        route, join, score = counts["route"], counts["join"], counts["score"]
        instr.exchange("key_route", int(route["carried"][0, 0]),
                       slots * dplan.shingle_route_cap)
        instr.exchange("dedup_route", int(join["carried"][0, 0]),
                       slots * dplan.pair_route_cap)
        if self.engine.plan.score_mode == "shuffle":
            instr.exchange("owner_hop1", int(score["carried_hop1"][0, 0]),
                           hop_slots)
            instr.exchange("owner_hop2", int(score["carried_hop2"][0, 0]),
                           hop_slots)
        scored = [int(x) for x in score["resting"][:, 0]]
        instr.record(
            shard_plan=dataclasses.asdict(dplan), n_shards=n,
            join_overflow=int(overflow), scored_per_chip=scored,
            scored_max=max(scored), scored_min=min(scored),
        )
        if self.engine.config.score_prune:
            instr.record(num_pruned=int(score["pruned"].sum()))
