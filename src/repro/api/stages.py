"""Typed pipeline stages: Encode -> Candidate -> Score -> Communities.

Each stage is a small object with a ``run(ctx)`` method that reads and
writes one :class:`PipelineContext`.  Stages hold no timing code (that is
the instrumentation wrapper's job) and no capacity policy (that is the
planner's), so the same stage objects serve the single-device engine, the
sharded engine (which swaps the middle stages for staged shard_map
programs, see api/sharded.MeshStages), and any future composition.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Protocol

import jax.numpy as jnp
import numpy as np

from repro.api.backends import BackendContext, CandidateBackend
from repro.api.capacity import CapacityPlanner
from repro.api.instrumentation import Instrumentation
import repro.core.communities as comm
from repro.core.encoding import SemanticForest, encode_batch
from repro.core.similarity import (
    PRUNE_EPS, lcs_impl, mss_upper_bound, score_pairs, score_windowed_pairs,
)
from repro.core.ssh import ssh_candidates
from repro.core.types import (
    CandidatePairs, EncodedBatch, PAD_ID, ScoredPairs, TrajectoryBatch,
)

LCS_IMPLS = ("wavefront", "ref", "fused", "fused-pallas", "fused-interpret")


def validate_lcs_impl(name: str) -> str:
    if name not in LCS_IMPLS:
        raise ValueError(
            f"unknown lcs_impl {name!r}; valid implementations: {list(LCS_IMPLS)}"
        )
    return name


def lcs_impl_fn(name: str, tuning=None):
    """The scoring impl for an ``lcs_impl`` name (``similarity.lcs_impl``):
    a kernel dispatch mode, or a jax-traceable batched LCS
    ``(a [B,L], b [B,L]) -> [B]``.

    Shared by the sharded, streaming and serving score blocks, so
    ``lcs_impl`` selects the same implementation on every path.

    ``tuning`` is an optional :class:`repro.perf.LCSTuning` record (from
    ``CapacityPlanner.plan_tuning``), resolved HERE — at the call boundary,
    eagerly, exactly like the REPRO_LCS_DTYPE probe — into the static
    wavefront dtype.  The returned impl
    carries only static values, so a tuned impl traces identically to an
    untuned one modulo those constants.
    """
    from repro.perf import resolve_wavefront_dtype

    validate_lcs_impl(name)
    # env pin > tuned > default
    return lcs_impl(name, resolve_wavefront_dtype(tuning))


@dataclasses.dataclass
class PipelineContext:
    """Mutable blackboard the stages read from / write to."""

    batch: TrajectoryBatch
    forest: SemanticForest
    tables: Any
    betas: jnp.ndarray
    config: Any                   # EngineConfig (kept untyped: no cycle)
    backend: CandidateBackend
    backend_ctx: BackendContext
    planner: CapacityPlanner
    instr: Instrumentation
    # stage outputs
    encoded: EncodedBatch | None = None
    keys: jnp.ndarray | None = None
    candidates: CandidatePairs | None = None
    scored: ScoredPairs | None = None
    similar_pairs: set | None = None
    similar_edges: tuple | None = None  # (left, right): see record_similar
    communities: set | None = None
    # capacities kept across the engine's jobs, by batch shape
    sticky: dict = dataclasses.field(default_factory=dict)


class Stage(Protocol):
    name: str

    def run(self, ctx: PipelineContext) -> None: ...


class EncodeStage:
    """Phase (i): multi-level semantic encoding of the batch."""

    name = "encode"

    def run(self, ctx: PipelineContext) -> None:
        with ctx.instr.phase("encode"):
            ctx.encoded = encode_batch(ctx.batch, ctx.tables)
            ctx.encoded.codes.block_until_ready()


class CandidateStage:
    """Phase (ii): join keys + candidate pairs via the configured backend.

    Key-based backends go through the shared sort-merge join with planned
    capacity and overflow retries; key-less backends (legacy callables)
    produce CandidatePairs directly.
    """

    name = "candidates"

    def run(self, ctx: PipelineContext) -> None:
        backend, instr = ctx.backend, ctx.instr
        with instr.phase("keys"):
            keys = backend.join_keys(ctx.encoded, ctx.batch, ctx.backend_ctx)
            if keys is not None:
                keys = jnp.asarray(keys)
                keys.block_until_ready()
        ctx.keys = keys

        with instr.phase("join"):
            if keys is None:
                cap = ctx.config.pair_capacity or 0
                cand = backend.candidates(
                    ctx.encoded, ctx.batch, ctx.backend_ctx, pair_capacity=cap
                )
            else:
                cap = ctx.config.pair_capacity
                if cap is None:
                    cap = ctx.planner.initial_capacity(backend.expected_pairs(keys))
                cand, cap = ctx.planner.run_with_retry(
                    lambda c: ssh_candidates(keys, pair_capacity=c), cap
                )
            cand.left.block_until_ready()
        ctx.candidates = cand
        instr.record(
            pair_capacity=int(cand.left.shape[0]) if keys is None else cap,
            num_candidates=int(cand.count),
            join_overflow=int(cand.overflow),
        )


class ScoreStage:
    """Phase (iii): multi-level LCS + MSS scoring, then the rho threshold.

    With ``config.score_prune`` the stage first runs the MSS upper-bound
    pruning pass (REPOSE-style): pairs whose free bound
    ``sum_h beta_h * min(len_a, len_b)`` cannot clear ``rho`` are compacted
    away before exact scoring, into a buffer the CapacityPlanner sizes from
    the survivor count — the pruned pairs never touch a kernel.
    """

    name = "score"

    def run(self, ctx: PipelineContext) -> None:
        cfg, cand = ctx.config, ctx.candidates
        impl = validate_lcs_impl(cfg.lcs_impl)
        L = int(ctx.encoded.codes.shape[2])
        subtraj = _subtraj_of(cfg, L)
        if getattr(cfg, "score_prune", False):
            with ctx.instr.phase("prune"):
                if subtraj is None:
                    prune_lengths = ctx.encoded.lengths
                else:
                    # windowed candidates index per-WINDOW lengths: the MSS
                    # bound of a window pair is betas_sum * min(wlen_a, wlen_b)
                    from repro.core.subtraj import window_lengths

                    prune_lengths = window_lengths(
                        np.asarray(ctx.encoded.lengths), max_len=L,
                        window=subtraj[0], stride=subtraj[1],
                    )
                cand, num_pruned = prune_candidates(
                    cand, prune_lengths, ctx.betas, cfg.rho, ctx.planner
                )
            ctx.candidates = cand
            ctx.instr.record(
                num_pruned=num_pruned,
                post_prune_capacity=int(cand.left.shape[0]),
            )
        with ctx.instr.phase("score"):
            # tuning is consulted HERE — eager, outside any trace — and
            # becomes static kernel args; None keeps the untuned defaults
            from repro.perf import resolve_wavefront_dtype

            P = int(cand.left.shape[0])
            H = int(ctx.encoded.codes.shape[1])
            tuning = ctx.planner.plan_tuning(P, H, L)
            static = dict(
                impl_name=impl,
                wavefront_dtype=resolve_wavefront_dtype(tuning),
            )
            if subtraj is None:
                level_lcs, mss = score_pairs(
                    ctx.encoded.codes, ctx.encoded.lengths,
                    cand.left, cand.right, ctx.betas, **static,
                )
            else:
                W, stride, nw = subtraj
                level_lcs, mss = score_windowed_pairs(
                    ctx.encoded.codes, ctx.encoded.lengths,
                    cand.left, cand.right, ctx.betas,
                    nw=nw, window=W, stride=stride, **static,
                )
            mss.block_until_ready()

        if subtraj is not None:
            # fold scored window pairs to trajectory pairs (max-over-
            # windows); downstream stages and the result speak traj ids
            from repro.core.subtraj import aggregate_window_pairs

            with ctx.instr.phase("results"):
                tl, tr, tlvl, tmss = aggregate_window_pairs(
                    cand.left, cand.right, level_lcs, mss, nw=subtraj[2]
                )
                keep = tmss > np.float32(cfg.rho)
                record_similar(ctx, tl[keep], tr[keep])
                ctx.scored = ScoredPairs(
                    left=jnp.asarray(tl), right=jnp.asarray(tr),
                    level_lcs=jnp.asarray(tlvl), mss=jnp.asarray(tmss),
                    count=jnp.asarray(tl.shape[0], jnp.int32),
                    overflow=cand.overflow,
                )
            ctx.instr.record(
                num_window_pairs=int(cand.count),
                num_traj_pairs=int(tl.shape[0]),
                num_similar=len(ctx.similar_pairs),
                subtraj_windows=subtraj[2],
            )
            return

        with ctx.instr.phase("results"):
            left_np = np.asarray(cand.left)
            right_np = np.asarray(cand.right)
            similar_mask = (left_np != PAD_ID) & (np.asarray(mss) > cfg.rho)
            record_similar(ctx, left_np[similar_mask], right_np[similar_mask])
        ctx.scored = ScoredPairs(
            left=cand.left, right=cand.right, level_lcs=level_lcs, mss=mss,
            count=cand.count, overflow=cand.overflow,
        )
        ctx.instr.record(num_similar=len(ctx.similar_pairs))


def record_similar(ctx: PipelineContext, left, right, buffer=None) -> None:
    """Leave the similar pairs ``zip(left, right)`` on the context twice:
    as the set of tuples the result returns (``ctx.similar_pairs``), and as
    the communities program's edge buffer (``ctx.similar_edges``), two
    int32 arrays of a fixed capacity with PAD_ID in the slots no pair
    fills.  ``buffer`` is such a pair of arrays the caller already holds
    (the mesh's similar buffers); else the pairs go to the front of a new
    one, whose capacity is a power of two over the count with the
    planner's slack, kept per batch shape while it holds the pairs, so
    jobs of one size run one compiled communities program."""
    left = np.asarray(left, np.int32)
    right = np.asarray(right, np.int32)
    ctx.similar_pairs = set(zip(left.tolist(), right.tolist()))
    if buffer is None:
        key = ("similar_edges", ctx.batch.num_trajectories)
        cap = ctx.sticky.get(key, 0)
        if cap < left.size:
            cap = ctx.sticky[key] = ctx.planner.initial_capacity(left.size)
        buffer = tuple(np.full(cap, PAD_ID, np.int32) for _ in range(2))
        buffer[0][:left.size] = left
        buffer[1][:left.size] = right
    ctx.similar_edges = buffer


class CommunitiesStage:
    """Phase (iv): communities of interest from the similar-pair graph.

    Components run over the edge buffer ``ctx.similar_edges`` (edge order
    and PAD_ID slots change no label), cliques over the similar-pair set;
    both are on the context on the single-device and the sharded paths.
    """

    name = "communities"

    def run(self, ctx: PipelineContext) -> None:
        cfg = ctx.config
        with ctx.instr.phase("communities"):
            if cfg.community_mode == "cliques":
                ctx.communities = comm.maximal_cliques(ctx.similar_pairs)
            elif cfg.community_mode == "components":
                left, right = ctx.similar_edges
                labels = comm.connected_components(
                    jnp.asarray(left), jnp.asarray(right),
                    num_nodes=ctx.batch.num_trajectories,
                )
                ctx.communities = comm.components_as_sets(np.asarray(labels))
                ctx.instr.record(
                    communities_edges=int(np.count_nonzero(left != PAD_ID)),
                    communities_edge_cap=int(left.shape[0]),
                )
            else:
                raise ValueError(
                    f"unknown community_mode {cfg.community_mode!r}; "
                    "valid modes: ['cliques', 'components']"
                )
        ctx.instr.record(num_communities=len(ctx.communities))


def prune_candidates(
    cand: CandidatePairs,
    lengths,
    betas,
    tau: float,
    planner: CapacityPlanner,
) -> tuple[CandidatePairs, int]:
    """MSS upper-bound pruning: drop pairs that cannot reach ``tau``.

    The bound is free — ``sum_h beta_h * min(len_a, len_b)`` needs lengths
    only — and safe: ``MSS <= bound``, so a dropped pair can never satisfy
    ``mss > tau`` (a PRUNE_EPS of slack keeps exact-threshold ties on the
    scored side).  Survivors are compacted to the front of a fresh buffer
    sized by the planner from the survivor count, so the exact-scoring
    kernel downstream runs over the post-prune pair set, not the full
    candidate buffer.  Returns (compacted candidates, number pruned).
    """
    left = np.asarray(cand.left)
    right = np.asarray(cand.right)
    lengths = np.asarray(lengths)
    valid = left != PAD_ID
    safe_l = np.where(valid, left, 0)
    safe_r = np.where(valid, right, 0)
    bsum = float(np.asarray(betas, np.float32).sum())
    ub = mss_upper_bound(lengths[safe_l], lengths[safe_r], bsum)
    keep = valid & (ub > np.float32(tau - PRUNE_EPS))
    idx = np.nonzero(keep)[0]
    cap = planner.initial_capacity(len(idx))
    new_left = np.full((cap,), PAD_ID, np.int32)
    new_right = np.full((cap,), PAD_ID, np.int32)
    new_left[: len(idx)] = left[idx]
    new_right[: len(idx)] = right[idx]
    pruned = CandidatePairs(
        left=jnp.asarray(new_left), right=jnp.asarray(new_right),
        count=jnp.asarray(len(idx), jnp.int32), overflow=cand.overflow,
    )
    return pruned, int(valid.sum()) - len(idx)


def _subtraj_of(cfg, max_len: int):
    """``(window, stride, nw)`` of the subtrajectory mode, or None.

    The effective window caps at the padded length (W >= L degenerates to
    whole-trajectory) and ``nw`` derives from the PADDED length, so the
    triple is a static shape fact (see repro.core.subtraj)."""
    if getattr(cfg, "subtraj_window", None) is None:
        return None
    from repro.core.subtraj import num_windows

    return (
        min(cfg.subtraj_window, max_len), cfg.subtraj_stride,
        num_windows(max_len, cfg.subtraj_window, cfg.subtraj_stride),
    )
