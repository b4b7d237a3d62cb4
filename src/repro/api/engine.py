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
    gather_similar_pairs, make_sharded_pipeline, pad_to_shards,
)
from repro.api.stages import (
    CandidateStage, CommunitiesStage, EncodeStage, PipelineContext, ScoreStage,
    validate_lcs_impl,
)
from repro.core import compat
from repro.core.encoding import SemanticForest, encode_types, forest_tables
from repro.core.pipeline import AnotherMeResult as EngineResult
from repro.core.similarity import default_betas
from repro.core.types import EncodedBatch, PAD_ID, ScoredPairs, TrajectoryBatch


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
    #                                 split the pair buffer into this many
    #                                 chunks (power of two) so chunk i+1's
    #                                 owner hops run while chunk i scores;
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
            # single-device ScoreStage or the fused shard_map stage — reads
            # one authoritative lcs_impl
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
        self._runner_cache: dict = {}
        self._plan_cache: dict = {}

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
                instr=instr,
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

    def _sharded_runner(self, dplan, key_fn, shapes, subtraj=None):
        from repro.core.similarity import wavefront_dtype_from_env

        # tuning resolves HERE — eagerly, at runner-build time — into
        # static kernel args (never inside the trace); a miss (autotune
        # off, no table, no matching cell) is None = untuned defaults
        tuning = self.planner.plan_tuning(
            dplan.pruned_cap or dplan.scored_cap,
            self.forest.num_levels, shapes[1][1],
        )
        # the runner build resolves REPRO_LCS_DTYPE (lcs_impl_fn); keying
        # the cache on the resolved dtype AND the tuning record keeps the
        # A/B probe and the tuning table live across runs of one engine,
        # matching the single-device path
        cache_key = (
            dplan, self.plan.score_mode, self.config.lcs_impl,
            self.config.score_prune, key_fn is None, shapes,
            wavefront_dtype_from_env(), tuning, subtraj,
        )
        runner = self._runner_cache.get(cache_key)
        if runner is None:
            runner = make_sharded_pipeline(
                self.mesh(), dplan, betas=self.betas, key_fn=key_fn,
                axis_name=self.plan.axis_name, score_mode=self.plan.score_mode,
                lcs_impl=self.config.lcs_impl,
                score_prune=self.config.score_prune,
                prune_tau=self.config.rho,
                tuning=tuning,
                subtraj=subtraj,
            )
            self._runner_cache[cache_key] = runner
        return runner


class _ShardedEncodeJoinScoreStage:
    """Encode + Candidate + Score fused into one shard_map program (Fig. 5).

    The device program is fully resident: raw places are sharded once,
    encoding runs in-mesh, and the code table never transits the host.
    Capacity planning works from the coarsest-level ("type") view only — a
    single [N, L] host gather, the driver's statistics pass — from which the
    backend's actual join keys are built (plan_sharded); key-producing
    backends rebuild keys on-device per shard, key-less ones ("udf") have
    their host keys shuffled in.  A capacity bust retries with doubled
    buffers, like the single-device planner.
    """

    name = "sharded_encode_join_score"

    def __init__(self, engine: AnotherMeEngine):
        self.engine = engine

    def run(self, ctx: PipelineContext) -> None:
        eng = self.engine
        plan, config, instr = eng.plan, eng.config, ctx.instr

        # subtrajectory mode: (window, stride, nw) from the PADDED length —
        # static shape facts every layer below keys its caches on
        subtraj = None
        if config.subtraj_window is not None:
            from repro.core.subtraj import num_windows

            L = int(ctx.batch.places.shape[1])
            subtraj = (
                min(config.subtraj_window, L), config.subtraj_stride,
                num_windows(L, config.subtraj_window, config.subtraj_stride),
            )

        with instr.phase("keys"):
            # coarsest-level view for planning only: [N, L], not the
            # [N, n_levels, L] code table (which stays device-resident)
            types = encode_types(ctx.batch.places, ctx.tables)
            plan_encoded = EncodedBatch(codes=types[:, None, :],
                                        lengths=ctx.batch.lengths)
            keys = ctx.backend.join_keys(plan_encoded, ctx.batch,
                                         ctx.backend_ctx)
            keys_np = np.asarray(keys)
        ctx.keys = keys

        # plan capacities host-side once per distinct key matrix; warm runs
        # (same data) skip the numpy planning pass and any retry doublings
        with instr.phase("plan"):
            plan_key = (keys_np.shape, hash(keys_np.tobytes()),
                        plan.score_mode, subtraj)
            dplan = eng._plan_cache.get(plan_key)
            if dplan is None:
                prune_kw = {}
                if config.score_prune:
                    # windowed pairs prune on per-WINDOW lengths: the key
                    # matrix has one row per window, and the MSS bound of a
                    # window pair is betas_sum * min of the window lengths
                    if subtraj is None:
                        lengths_np = np.asarray(ctx.batch.lengths)
                    else:
                        from repro.core.subtraj import window_lengths

                        lengths_np = window_lengths(
                            np.asarray(ctx.batch.lengths),
                            max_len=int(ctx.batch.places.shape[1]),
                            window=subtraj[0], stride=subtraj[1],
                        )
                    prune_kw = dict(
                        lengths_np=lengths_np,
                        prune_tau=config.rho,
                        betas_sum=float(np.asarray(eng.betas, np.float32).sum()),
                    )
                dplan = eng.planner.plan_sharded(
                    keys_np, plan.n_shards, slack=plan.shard_slack,
                    score_mode=plan.score_mode,
                    overlap_chunks=plan.overlap_chunks,
                    windows_per_row=1 if subtraj is None else subtraj[2],
                    **prune_kw,
                )
        key_fn = ctx.backend.shard_key_fn(ctx.backend_ctx)

        with instr.phase("execute"):
            out, dplan = self._execute(ctx, dplan, key_fn, keys_np, subtraj)
        eng._plan_cache[plan_key] = dplan
        instr.record(
            shard_plan=dataclasses.asdict(dplan),
            join_overflow=int(np.asarray(out["overflow"]).sum()),
        )
        if config.score_prune:
            instr.record(num_pruned=int(np.asarray(out["pruned"]).sum()))

        with instr.phase("results"):
            left = np.asarray(out["left"]).reshape(-1)
            right = np.asarray(out["right"]).reshape(-1)
            mss = np.asarray(out["mss"]).reshape(-1)
            level_lcs = np.asarray(out["level_lcs"])
            level_lcs = level_lcs.reshape(-1, level_lcs.shape[-1])
            valid = left != PAD_ID
            overflow = jnp.asarray(int(np.asarray(out["overflow"]).sum()),
                                   jnp.int32)
            if subtraj is not None:
                # fold scored window pairs to trajectory pairs (max-over-
                # windows) before anything downstream sees them —
                # communities, similar_pairs, and the returned scored
                # buffer all speak trajectory ids
                from repro.core.subtraj import aggregate_window_pairs

                tl, tr, tlvl, tmss = aggregate_window_pairs(
                    left, right, level_lcs, mss, nw=subtraj[2]
                )
                ctx.scored = ScoredPairs(
                    left=jnp.asarray(tl), right=jnp.asarray(tr),
                    level_lcs=jnp.asarray(tlvl), mss=jnp.asarray(tmss),
                    count=jnp.asarray(tl.shape[0], jnp.int32),
                    overflow=overflow,
                )
                ctx.similar_pairs = {
                    (int(a), int(b))
                    for a, b, m in zip(tl, tr, tmss)
                    if m > np.float32(config.rho)
                }
            else:
                ctx.scored = ScoredPairs(
                    left=jnp.asarray(left), right=jnp.asarray(right),
                    level_lcs=jnp.asarray(level_lcs), mss=jnp.asarray(mss),
                    count=jnp.asarray(int(valid.sum()), jnp.int32),
                    overflow=overflow,
                )
                ctx.similar_pairs = gather_similar_pairs(out, rho=config.rho)
        instr.record(
            num_candidates=int(valid.sum()),
            num_similar=len(ctx.similar_pairs),
        )
        if subtraj is not None:
            instr.record(
                num_window_pairs=int(valid.sum()),
                num_traj_pairs=int(ctx.scored.left.shape[0]),
                subtraj_windows=subtraj[2],
            )

    def _execute(self, ctx, dplan, key_fn, keys_np, subtraj=None):
        eng = self.engine
        batch = ctx.batch
        first = jnp.asarray(keys_np) if key_fn is None else batch.places
        shapes = (first.shape, batch.places.shape, ctx.tables.shape)
        for attempt in range(eng.planner.max_retries + 1):
            runner = eng._sharded_runner(dplan, key_fn, shapes, subtraj)
            out = runner(first, batch.places, batch.lengths, ctx.tables)
            out["mss"].block_until_ready()
            if int(np.asarray(out["overflow"]).sum()) == 0:
                break
            if attempt < eng.planner.max_retries:
                dplan = dataclasses.replace(
                    dplan,
                    shingle_route_cap=dplan.shingle_route_cap * 2,
                    local_pair_cap=dplan.local_pair_cap * 2,
                    pair_route_cap=dplan.pair_route_cap * 2,
                    scored_cap=dplan.scored_cap * 2,
                    owner_route_cap=dplan.owner_route_cap * 2,
                    pruned_cap=dplan.pruned_cap * 2,
                    chunk_hop_cap=dplan.chunk_hop_cap * 2,
                    chunk_rest_cap=dplan.chunk_rest_cap * 2,
                )
        return out, dplan
