"""Sharded AnotherMe: the Spark shuffle mapped onto shard_map collectives.

Every Spark stage of the paper's Fig. 5 has a direct analogue here:

  Spark executors            -> devices on a flat "ex" mesh axis
  semantic encoding (D2->D3) -> in-mesh gather through the replicated
                                forest tables: each shard encodes its OWN
                                rows, so the [N, n_levels, L] code table
                                never materializes on the host
  hash-shuffle on shingle    -> lax.all_to_all of fixed-capacity buckets
    (D4 repartition)            routed by hash(join key) % n_shards
  local sort-merge join      -> ssh.pairs_from_rows on received rows
  shuffle pairs for dedup    -> second all_to_all routed by hash(lo, hi)
    ("score each pair once")    so every pair lands on exactly ONE shard;
                                the local dedup is then globally exact
  executor-local scoring     -> batched LCS on local pairs, through the
                                same ``lcs_impl`` selection as the
                                single-device path (wavefront / ref /
                                Pallas kernel)

What the redesign adds over the original ``core/distributed.py``: the join
key construction is pluggable.  ``key_fn`` (from a registry backend's
``shard_key_fn``) builds keys on-device per shard — shingles for "ssh",
band signatures for "minhash", bucket projections for "brp" — always from
the shard's in-mesh encoded codes.  With ``key_fn=None`` the keys are
precomputed host-side and shuffled in as a sharded input (the "udf"
backend's driver-side wall).  Everything after the keys — route, join,
dedup, score — is one shared implementation.

Static capacities (rows per destination bucket, pairs per shard) are planned
host-side from exact cardinalities (plan_capacities) using the *same* int32
hashes the device program applies, and every stage carries an overflow
counter, so a capacity bust is detected, never silent.

The same code runs on 1 device (n_shards=1 degenerates to the single-device
pipeline) and on the 512-device production mesh in the dry-run.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compat
from repro.core.encoding import encode_codes
from repro.core.shingling import shingles_from_types
from repro.core.similarity import PRUNE_EPS, mss_upper_bound, score_indexed
from repro.core.ssh import _runs, dedup_pairs, pairs_from_rows
from repro.core.types import PAD_ID, PAD_KEY

_MIX = np.int32(np.uint32(2654435761 % (1 << 31)))  # Knuth multiplicative mix


def _positive_hash(x: jnp.ndarray) -> jnp.ndarray:
    h = (x * _MIX) ^ (x >> 13)
    return jnp.abs(h)


def _pair_hash(lo: jnp.ndarray, hi: jnp.ndarray) -> jnp.ndarray:
    return jnp.abs(_positive_hash(lo) * np.int32(92821) + _positive_hash(hi))


def _positive_hash_np(x: np.ndarray) -> np.ndarray:
    """Host replica of :func:`_positive_hash` with exact int32 wraparound, so
    capacity planning sees the same shard destinations as the device."""
    x = np.asarray(x).astype(np.int32)
    with np.errstate(over="ignore"):
        h = (x * _MIX) ^ (x >> 13)
    return np.abs(h)


def _pair_hash_np(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        h = _positive_hash_np(lo) * np.int32(92821) + _positive_hash_np(hi)
    return np.abs(h)


def _route(
    values: tuple, dest: jnp.ndarray, valid: jnp.ndarray, *, n_shards: int,
    capacity: int, pads: tuple, axis_name: str,
):
    """Scatter rows into [n_shards, capacity] buckets and all_to_all them.

    values: tuple of int32 [R] or [R, W] arrays routed together (rows travel
    with their payload columns); pads: per-array pad value.
    Returns (tuple of [n_shards * capacity(, W)] received rows, overflow).
    """
    dest = jnp.where(valid, dest, n_shards)  # n_shards = drop bucket
    order = jnp.argsort(dest, stable=True)
    dest_s = dest[order]
    rank, _ = _runs(jnp.where(dest_s == n_shards, PAD_KEY, dest_s))
    ok = (dest_s < n_shards) & (rank < capacity)
    slot = jnp.where(ok, dest_s * capacity + rank, n_shards * capacity)
    overflow = jnp.sum((dest_s < n_shards) & (rank >= capacity))
    outs = []
    for v, pad in zip(values, pads):
        width = v.shape[1:] if v.ndim > 1 else ()
        buf = jnp.full((n_shards * capacity,) + width, pad, dtype=v.dtype)
        buf = buf.at[slot].set(v[order], mode="drop")
        buf = buf.reshape((n_shards, capacity) + width)
        recv = jax.lax.all_to_all(
            buf, axis_name, split_axis=0, concat_axis=0, tiled=True
        )
        outs.append(recv.reshape((n_shards * capacity,) + width))
    return tuple(outs), overflow


def _prune_keep(len_l, len_r, betas, prune_tau, valid):
    """The one float32 MSS upper-bound prune test.

    Every prune site — the one-shot in-mesh pass, the streaming replicate
    and shuffle branches, and (via the same ``mss_upper_bound`` +
    ``PRUNE_EPS`` discipline) the host-side ``_prune_delta`` — must agree
    bit-exactly on which pairs survive, so the bound is defined once.
    """
    ub = mss_upper_bound(len_l, len_r, jnp.sum(betas))
    return valid & (ub > prune_tau - PRUNE_EPS)


def _fit(x: jnp.ndarray, cap: int, pad_val) -> jnp.ndarray:
    """Pad or truncate the leading axis of ``x`` to exactly ``cap`` rows.

    Truncation is only safe on buffers whose valid rows are already
    compacted to the front (dedup / argsort upstream); callers surface the
    excess through an overflow counter.
    """
    m = x.shape[0]
    if m >= cap:
        return x[:cap]
    padw = [(0, cap - m)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, padw, constant_values=pad_val)


def _hop_gather_codes(
    left, right, codes_local, *, owner_of, slot_of, n_shards, axis_name,
    hop_cap, out_cap,
):
    """Two-hop pair/code shuffle shared by the one-shot and streaming paths.

    Route pairs to owner(left), attach that shard's code rows, then to
    owner(right), attach, and come to rest wherever owner(right) is (the
    pairs are already globally deduped upstream).  Ownership is pluggable:
    the one-shot pipeline owns rows in blocks (``g // local_n``), the
    streaming world round-robins them (``g % n_shards``) so growth stays
    balanced; ``slot_of`` maps a global id to the owner's local row.
    Received rows sit scattered across per-source buckets, so valid rows are
    compacted to the front before the fit to ``out_cap`` — a plain
    truncation could drop valid pairs while keeping padding.  Returns
    (left, right, left_codes, right_codes, overflow).
    """
    H, L = codes_local.shape[1], codes_local.shape[2]
    local_n = codes_local.shape[0]
    # hop 1: to owner(left)
    (l1, r1), o1 = _route(
        (left, right), owner_of(left), left != PAD_ID,
        n_shards=n_shards, capacity=hop_cap, pads=(PAD_ID, PAD_ID),
        axis_name=axis_name,
    )
    safe = slot_of(jnp.where(l1 == PAD_ID, 0, l1))
    cl = codes_local[jnp.clip(safe, 0, local_n - 1)].reshape(
        l1.shape[0], H * L
    )
    # hop 2: to owner(right), payload = left codes
    (l2, r2, cl2), o2 = _route(
        (l1, r1, cl), owner_of(r1), l1 != PAD_ID,
        n_shards=n_shards, capacity=hop_cap,
        pads=(PAD_ID, PAD_ID, 0), axis_name=axis_name,
    )
    safe_r = slot_of(jnp.where(r2 == PAD_ID, 0, r2))
    cr = codes_local[jnp.clip(safe_r, 0, local_n - 1)]
    cl_rows = cl2.reshape(l2.shape[0], H, L)
    order = jnp.argsort(l2 == PAD_ID, stable=True)
    l2, r2 = l2[order], r2[order]
    cl_rows, cr = cl_rows[order], cr[order]
    n_valid = jnp.sum(l2 != PAD_ID).astype(jnp.int32)
    ovf_fit = jnp.maximum(n_valid - out_cap, 0)
    return (_fit(l2, out_cap, PAD_ID), _fit(r2, out_cap, PAD_ID),
            _fit(cl_rows, out_cap, 0), _fit(cr, out_cap, 0),
            o1 + o2 + ovf_fit)


@dataclasses.dataclass(frozen=True)
class DistributedPlan:
    n_shards: int
    local_n: int          # trajectories per shard
    shingle_route_cap: int  # rows per (src, dst) bucket in shuffle 1
    local_pair_cap: int     # pre-dedup pairs per shard after local join
    pair_route_cap: int     # rows per (src, dst) bucket in shuffle 2
    scored_cap: int         # deduped pairs per shard
    owner_route_cap: int = 0  # rows per (src, dst) bucket in the shuffle-mode
    #                           owner hops; 0 -> uniform fallback
    pruned_cap: int = 0     # post-prune pairs per shard when the MSS
    #                         upper-bound pruning pass runs; 0 -> scored_cap
    n_chunks: int = 1       # shuffle-mode overlap: split the pair buffer
    #                         into this many chunks so chunk i+1's owner
    #                         hops run while chunk i scores; 1 -> the
    #                         original single-pass gather-then-score
    chunk_hop_cap: int = 0  # rows per (src, dst) bucket in ONE chunk's
    #                         owner hops; 0 -> uniform fallback
    chunk_rest_cap: int = 0  # resting pairs per shard for ONE chunk;
    #                          0 -> uniform fallback


def plan_capacities(
    keys_np: np.ndarray,
    n_shards: int,
    *,
    slack: float = 1.3,
    quiet: bool = True,
    score_mode: str = "replicate",
    exact_pair_limit: int = 5_000_000,
    lengths_np: np.ndarray | None = None,
    prune_tau: float | None = None,
    betas_sum: float = 1.0,
    overlap_chunks: int = 1,
    windows_per_row: int = 1,
) -> DistributedPlan:
    """Host-side exact capacity planning from the actual join keys.

    Mirrors what a Spark driver learns from partition statistics; keeps every
    device buffer tight instead of worst-case.  Works for any backend's keys
    (shingles, minhash bands, brp buckets): only PAD_KEY rows are excluded.

    All shard destinations are computed with the device's own int32 hashes
    (:func:`_positive_hash_np` / :func:`_pair_hash_np`), so per-bucket loads
    are exact even for adversarially skewed key distributions — including
    the pair-dedup shuffle and, with ``score_mode="shuffle"``, the per-owner
    loads of the two code-gather hops (ROADMAP "shuffle 1"-style planning
    for every stage).  Above ``exact_pair_limit`` pre-dedup pairs the pair
    list is not materialized and the uniform-hash bound takes over (the
    overflow counters + retry doubling still catch any bust).

    With ``prune_tau`` and ``lengths_np`` set, the plan also sizes
    ``pruned_cap`` — the post-prune pair buffer — from the exact per-shard
    survivor counts of the MSS upper-bound pruning pass
    (``betas_sum * min(len_a, len_b) > tau``), using the same float32 bound
    the device applies.  In ``score_mode="shuffle"`` pruning happens BEFORE
    the owner hops, so the hop buckets and the resting buffer are sized
    from survivors only.

    ``overlap_chunks > 1`` (shuffle mode only) additionally sizes the
    per-chunk hop/resting buffers for the overlapped gather: the pre-hop
    pair buffer is split into that many contiguous slices, and because the
    device buffer layout is DETERMINISTIC — ``dedup_pairs`` sorts by
    (lo, hi) with PAD at the end, and the prune compaction preserves that
    order — the planner can replay exactly which pairs land in which chunk
    slice and size ``chunk_hop_cap`` / ``chunk_rest_cap`` from the actual
    per-(chunk, owner) loads, keeping the overflow accounting exact under
    chunking too.

    ``windows_per_row > 1`` declares subtrajectory keys: ``keys_np`` has one
    row PER WINDOW (``n = n_traj * nw``, window id ``t * nw + j``), while
    shards own whole TRAJECTORIES.  ``local_n`` stays in trajectory units
    and every ownership computation maps a window id to its trajectory
    first (``id // nw``); per-window loads (shuffle 1, the join, the dedup
    shuffle) are still counted exactly per window row.  ``lengths_np``, when
    given, must then be per-WINDOW lengths ``[n_traj * nw]`` so the prune
    replay indexes it with window ids directly.
    """
    n, s = keys_np.shape
    nw = windows_per_row
    local_n = int(np.ceil((n // nw) / n_shards))
    keys_flat = keys_np.reshape(-1)
    ids_flat = np.repeat(np.arange(n, dtype=np.int64), s)
    valid = keys_flat != PAD_KEY
    kf, idf = keys_flat[valid], ids_flat[valid]
    # shuffle 1 loads: rows from one src shard to one dst shard (a window
    # row lives on the shard owning its trajectory)
    src = (idf // nw) // local_n
    dst = _positive_hash_np(kf) % n_shards
    load1 = np.zeros((n_shards, n_shards), np.int64)
    np.add.at(load1, (src, dst), 1)
    cap1 = int(np.ceil(load1.max() * slack)) + 8

    # local join size per dst shard: sum over keys of rank contributions
    order = np.lexsort((idf, kf))
    kf_s, idf_s = kf[order], idf[order]
    dst_s = dst[order]
    run_start = np.ones(kf_s.shape, bool)
    run_start[1:] = kf_s[1:] != kf_s[:-1]
    idx = np.arange(kf_s.shape[0])
    starts = np.maximum.accumulate(np.where(run_start, idx, 0))
    ranks = idx - starts
    pair_count = np.zeros(n_shards, np.int64)
    np.add.at(pair_count, dst_s, ranks)
    cap2 = int(np.ceil(max(pair_count.max(), 1) * slack)) + 64

    total_pairs = int(ranks.sum())
    owner_cap = 0
    pruned_cap = 0
    chunk_hop = chunk_rest = 0
    if total_pairs <= exact_pair_limit:
        # materialize the pre-dedup pair list host-side (the driver's
        # statistics pass): element at sorted position p with in-run rank r
        # pairs with the r earlier members of its key run
        rows = np.repeat(idx, ranks)
        excl = np.cumsum(ranks) - ranks
        t = np.arange(rows.shape[0], dtype=np.int64) - np.repeat(excl, ranks)
        partners = rows - np.repeat(ranks, ranks) + t
        a_ids, b_ids = idf_s[rows], idf_s[partners]
        lo = np.minimum(a_ids, b_ids).astype(np.int32)
        hi = np.maximum(a_ids, b_ids).astype(np.int32)
        # shuffle 2 loads: pairs travel from their join shard to their
        # pair-hash dedup shard (self-pairs still occupy route slots)
        src2 = dst_s[rows]
        dst2 = _pair_hash_np(lo, hi) % n_shards
        load2 = np.zeros((n_shards, n_shards), np.int64)
        np.add.at(load2, (src2, dst2), 1)
        cap3 = int(np.ceil(max(load2.max(), 1) * slack)) + 64
        # deduped pairs per dedup shard (exact scored_cap)
        keep = lo != hi
        uniq = np.unique(
            (lo[keep].astype(np.int64) << 32) | hi[keep].astype(np.int64)
        )
        ulo = (uniq >> 32).astype(np.int32)
        uhi = (uniq & 0xFFFFFFFF).astype(np.int32)
        ded_dst = _pair_hash_np(ulo, uhi) % n_shards
        scored_need = int(np.bincount(ded_dst, minlength=n_shards).max()) \
            if uniq.size else 1
        prune = prune_tau is not None and lengths_np is not None
        if prune and uniq.size:
            # survivors of the MSS upper-bound prune, same f32 test as the
            # device pass; pruning runs after the dedup fit, so scored_cap
            # keeps its pre-prune sizing and pruned_cap sizes what is left
            ub = mss_upper_bound(lengths_np[ulo], lengths_np[uhi], betas_sum)
            surv = ub > np.float32(prune_tau - PRUNE_EPS)
        else:
            surv = np.ones(ulo.shape, bool)
        if score_mode == "shuffle":
            # per-owner loads of the code-gather hops: dedup shard ->
            # owner(left) -> owner(right); pairs come to rest on
            # owner(right).  Pruning happens before the hops, so with it on
            # only survivors travel — hop buckets and the resting buffer
            # are sized from the survivor subset.
            own_lo = ((ulo // nw) // local_n)[surv]
            own_hi = ((uhi // nw) // local_n)[surv]
            h1 = np.zeros((n_shards, n_shards), np.int64)
            np.add.at(h1, (ded_dst[surv], own_lo), 1)
            h2 = np.zeros((n_shards, n_shards), np.int64)
            np.add.at(h2, (own_lo, own_hi), 1)
            owner_cap = int(np.ceil(max(h1.max(), h2.max(), 1) * slack)) + 64
            rest_need = int(np.bincount(own_hi, minlength=n_shards).max()) \
                if own_hi.size else 1
            if prune:
                # the post-prune buffer first holds survivors compacted AT
                # the dedup shard (before the hops), then the resting
                # loads at owner(right) — size for both skews
                surv_need = int(
                    np.bincount(ded_dst[surv], minlength=n_shards).max()
                ) if surv.any() else 1
                pruned_cap = int(
                    np.ceil(max(surv_need, rest_need, 1) * slack)
                ) + 64
            else:
                scored_need = max(scored_need, rest_need)
        elif prune:
            surv_need = int(
                np.bincount(ded_dst[surv], minlength=n_shards).max()
            ) if surv.any() else 1
            pruned_cap = int(np.ceil(max(surv_need, 1) * slack)) + 64
        cap4 = int(np.ceil(max(scored_need, 1) * slack)) + 64
        if score_mode == "shuffle" and overlap_chunks > 1:
            # chunked-overlap planning: replay the deterministic device
            # buffer layout — dedup_pairs sorts by (lo, hi) with PAD at the
            # end (np.unique gives the same global order here) and the
            # prune compaction preserves it — to find which surviving pair
            # occupies which chunk slice of which shard's buffer, then size
            # ONE chunk's hop buckets / resting buffer from the worst chunk
            if prune:
                pruned_cap += (-pruned_cap) % overlap_chunks
                pre_cap = pruned_cap
            else:
                cap4 += (-cap4) % overlap_chunks
                pre_cap = cap4
            sub = pre_cap // overlap_chunks
            sel = np.nonzero(surv)[0]
            d_sel = ded_dst[sel]
            rank = np.zeros(sel.shape[0], np.int64)
            for s in range(n_shards):
                m = d_sel == s
                rank[m] = np.arange(int(m.sum()))
            chunk_of = np.minimum(rank // sub, overlap_chunks - 1)
            olo = (ulo[sel] // nw) // local_n
            ohi = (uhi[sel] // nw) // local_n
            ch1 = np.zeros((overlap_chunks, n_shards, n_shards), np.int64)
            np.add.at(ch1, (chunk_of, d_sel, olo), 1)
            ch2 = np.zeros((overlap_chunks, n_shards, n_shards), np.int64)
            np.add.at(ch2, (chunk_of, olo, ohi), 1)
            crest = np.zeros((overlap_chunks, n_shards), np.int64)
            np.add.at(crest, (chunk_of, ohi), 1)
            chunk_hop = int(np.ceil(max(ch1.max(), ch2.max(), 1) * slack)) + 64
            chunk_rest = int(np.ceil(max(crest.max(), 1) * slack)) + 64
    else:
        # uniform-hash bound with extra slack (skew caught by overflow+retry)
        cap3 = int(
            np.ceil(max(total_pairs / (n_shards * n_shards), 1) * slack * 2)
        ) + 64
        cap4 = int(np.ceil(max(total_pairs / n_shards, 1) * slack * 2)) + 64
        if score_mode == "shuffle" and overlap_chunks > 1:
            cap4 += (-cap4) % overlap_chunks  # device needs even chunk slices
    return DistributedPlan(
        n_shards=n_shards, local_n=local_n, shingle_route_cap=cap1,
        local_pair_cap=cap2, pair_route_cap=cap3, scored_cap=cap4,
        owner_route_cap=owner_cap, pruned_cap=pruned_cap,
        n_chunks=overlap_chunks if score_mode == "shuffle" else 1,
        chunk_hop_cap=chunk_hop, chunk_rest_cap=chunk_rest,
    )


def make_sharded_pipeline(
    mesh: jax.sharding.Mesh,
    plan: DistributedPlan,
    *,
    betas: jnp.ndarray,
    key_fn: Callable | None,
    axis_name: str = "ex",
    score_mode: str = "replicate",
    lcs_impl: str = "wavefront",
    score_prune: bool = False,
    prune_tau: float = 0.0,
    tuning=None,
    subtraj: tuple[int, int, int] | None = None,
):
    """Build the jitted shard_map encode+join+score pipeline.

    key_fn: jax-traceable ``(local_type_codes [n, L], local_lengths [n]) ->
      keys [n, S]`` run per shard (a backend's ``shard_key_fn``) on the
      shard's in-mesh encoded codes, or None, in which case the first input
      of the returned fn carries precomputed keys instead of places.

    Call signature of the returned fn:
      fn(first, places [N, L] int32, lengths [N] int32,
         tables [n_levels, num_places] int32)
        -> dict of per-shard stacked outputs:
           left/right [n, scored_cap], level_lcs [n, scored_cap, H],
           mss [n, scored_cap], overflow [n, 3]

      first: with a key_fn, unused (pass places again); without, [N, S]
      keys precomputed host-side and shuffled in (the "udf" driver wall).

    Encoding runs INSIDE the shard_map program: each shard gathers its own
    rows through the replicated forest ``tables`` (small — the semantic
    forest, [n_levels, num_places]), so the [N, n_levels, L] code table
    never materializes on the host, for either score mode.

    score_mode:
      "replicate" — each shard all_gathers the per-shard encodings into a
        device-resident replica of the table and scores its deduped pairs
        locally (fine to ~10M trajectories: the table is
        N * levels * L * 4 bytes).
      "shuffle"   — the table stays sharded; two extra all_to_all rounds
        route each pair to owner(left) then owner(right), attaching the
        owner's code rows on the way (a Spark broadcast-join vs shuffle-join
        switch).  Per-device memory is then O(N/shards) — the 1000-node
        regime.

    lcs_impl selects the scoring implementation exactly as on the
    single-device path: "wavefront" / "ref", or the Pallas kernel through
    "fused" (auto) / "fused-pallas" (forced) / "fused-interpret" — every impl
    scores through ``similarity.score_indexed`` (chunked) against the
    device-resident code table ("replicate") or the hop-gathered operand
    stacks ("shuffle").

    score_prune runs the MSS upper-bound pruning pass IN-MESH, right after
    the pair dedup and before any code row moves for scoring: per-shard
    lengths are all_gathered (an [N] int32 vector, not the code table), the
    free bound ``sum_h beta_h * min(len_a, len_b)`` is tested against
    ``prune_tau``, and survivors are compacted into the planned
    ``pruned_cap`` buffer.  In "shuffle" mode this happens before the owner
    hops, so pruned pairs never travel.

    With ``plan.n_chunks > 1`` (shuffle mode) the pair buffer is split into
    chunks and the owner hops are SOFTWARE-PIPELINED: chunk 0's hops are
    issued, then for each subsequent chunk the next hops are issued BEFORE
    the previous chunk's resting pairs are scored, so the collective for
    chunk i+1 and the LCS compute for chunk i have no data dependence and
    the scheduler is free to overlap them (alpa's comm/compute overlap
    discipline; on a single host the same split pays off as cache blocking
    — one chunk's operands stay resident while it scores).  Chunking only
    reorders WHICH rows travel together; every pair still hops and scores
    exactly once with the same operands, so per-pair scores are
    bit-identical and the overflow accounting stays exact (per-chunk
    buffers come from the same exact-loads planner).  ``n_chunks`` is a
    static plan field, so chunking adds zero steady-state recompiles.

    ``tuning`` (optional :class:`repro.perf.LCSTuning`) is resolved
    EAGERLY here at build time into static kernel args via
    ``lcs_impl_fn`` — never inside the trace.

    ``subtraj=(W, stride, nw)`` switches the pipeline to subtrajectory
    mode: the per-shard key rows are the nw sliding WINDOWS of each local
    trajectory (``key_fn`` windows in-mesh; precomputed ``first`` keys are
    already windowed host-side), every candidate id is a WINDOW id
    ``t * nw + j`` carrying (traj, offset) coordinates end-to-end, shard
    ownership stays per-TRAJECTORY (``plan.local_n`` is in trajectory
    units, see ``plan_capacities(windows_per_row=...)``), the owner hops
    still move the full [H, L] trajectory rows exactly once per pair side,
    and scoring windows them in-register (fused kernel) or via a width-W
    gather (jnp impls).  All three values are static, so subtrajectory
    runs compile their own specialization and ``subtraj=None`` traces are
    byte-identical to the pre-windowing pipeline.
    """
    from jax.sharding import PartitionSpec as P

    from repro.api.stages import lcs_impl_fn

    n_shards = plan.n_shards
    if subtraj is not None:
        W, stride, nw = subtraj
    else:
        W, stride, nw = 0, 1, 1
    impl = lcs_impl_fn(lcs_impl, tuning)
    out_cap = (plan.pruned_cap or plan.scored_cap) if score_prune \
        else plan.scored_cap
    n_chunks = plan.n_chunks if score_mode == "shuffle" else 1
    if n_chunks > 1:
        if out_cap % n_chunks:
            raise ValueError(
                f"pair buffer ({out_cap}) must divide into n_chunks="
                f"{n_chunks} slices; plan_capacities rounds it up"
            )
        _sub = out_cap // n_chunks
        chunk_hop_cap = plan.chunk_hop_cap or (_sub // n_shards + 64)
        chunk_rest_cap = plan.chunk_rest_cap or _sub
        rest_total = n_chunks * chunk_rest_cap
    else:
        rest_total = out_cap

    def shard_fn(first, places, lengths, tables):
        # first: LOCAL keys rows (key_fn=None mode) or unused; places,
        # lengths: LOCAL rows; tables: the replicated semantic forest.
        me = jax.lax.axis_index(axis_name).astype(jnp.int32)
        gid0 = me * plan.local_n

        # phase (i): in-mesh encoding of OUR rows
        codes = encode_codes(places, tables)  # [local_n, H, L]

        # phase (ii)a: join keys of OUR rows
        if key_fn is not None:
            keys = key_fn(codes[:, 0, :], lengths)  # [local_n, S]
        else:
            keys = first  # [local_n, S] precomputed host-side

        s = keys.shape[1]
        flat_keys = keys.reshape(-1)
        if subtraj is None:
            flat_ids = jnp.repeat(
                jnp.arange(plan.local_n, dtype=jnp.int32) + gid0, s
            )
        else:
            # one key row per WINDOW: global window ids t * nw + j for the
            # local trajectories t in [gid0, gid0 + local_n)
            flat_ids = jnp.repeat(
                jnp.arange(plan.local_n * nw, dtype=jnp.int32) + gid0 * nw, s
            )
        valid = flat_keys != PAD_KEY
        dest = _positive_hash(flat_keys) % n_shards
        (rk, rid), ovf1 = _route(
            (flat_keys, flat_ids), dest, valid,
            n_shards=n_shards, capacity=plan.shingle_route_cap,
            pads=(PAD_KEY, PAD_ID), axis_name=axis_name,
        )

        # local sort-merge join on received rows
        lo, hi, ovf2 = pairs_from_rows(rk, rid, pair_capacity=plan.local_pair_cap)

        # shuffle 2: route pairs by pair hash so dedup is globally exact
        pvalid = lo != PAD_ID
        pdest = _pair_hash(lo, hi) % n_shards
        (rlo, rhi), ovf3 = _route(
            (lo, hi), pdest, pvalid,
            n_shards=n_shards, capacity=plan.pair_route_cap,
            pads=(PAD_ID, PAD_ID), axis_name=axis_name,
        )
        # dedup over the FULL received buffer (valid rows sit scattered in
        # per-source buckets; dedup's sort compacts them to the front), then
        # fit to scored_cap with the excess surfaced as overflow
        cand = dedup_pairs(rlo, rhi)
        left = _fit(cand.left, plan.scored_cap, PAD_ID)
        right = _fit(cand.right, plan.scored_cap, PAD_ID)
        ovf4 = jnp.maximum(cand.count - plan.scored_cap, 0)

        # MSS upper-bound pruning pass: drop pairs that cannot reach tau
        # BEFORE any code row moves for scoring.  Only the [N] lengths
        # vector is gathered (int32, tiny) — never the code table.
        n_pruned = jnp.zeros((), jnp.int32)
        if score_prune:
            lengths_all = jax.lax.all_gather(
                lengths, axis_name, axis=0, tiled=True
            )
            pl_valid = left != PAD_ID
            sl = jnp.where(pl_valid, left, 0)
            sr = jnp.where(pl_valid, right, 0)
            if subtraj is None:
                len_l, len_r = lengths_all[sl], lengths_all[sr]
            else:
                # per-WINDOW lengths from the [N] trajectory lengths
                len_l = jnp.clip(
                    lengths_all[sl // nw] - (sl % nw) * stride, 0, W
                )
                len_r = jnp.clip(
                    lengths_all[sr // nw] - (sr % nw) * stride, 0, W
                )
            keep = _prune_keep(len_l, len_r, betas, prune_tau, pl_valid)
            n_keep = jnp.sum(keep).astype(jnp.int32)
            n_pruned = jnp.sum(pl_valid).astype(jnp.int32) - n_keep
            order = jnp.argsort(jnp.logical_not(keep), stable=True)
            slots = jnp.arange(out_cap, dtype=jnp.int32)
            # out_cap may exceed scored_cap (skewed owners): pad, then mask
            left = jnp.where(
                slots < n_keep, _fit(left[order], out_cap, PAD_ID), PAD_ID
            )
            right = jnp.where(
                slots < n_keep, _fit(right[order], out_cap, PAD_ID), PAD_ID
            )
            ovf4 = ovf4 + jnp.maximum(n_keep - out_cap, 0)

        # phase (iii): scoring, through the selected lcs_impl
        if score_mode == "replicate":
            # on-device replication of the in-mesh encodings (never on host)
            codes_all = jax.lax.all_gather(codes, axis_name, axis=0, tiled=True)
            level_lcs, mss = _score(codes_all, codes_all, left, right)
            ovf5 = jnp.zeros((), jnp.int32)
        elif n_chunks == 1:
            left, right, codes_l, codes_r, ovf5 = _gather_pair_codes(
                left, right, codes, gid0, plan, n_shards, axis_name, out_cap
            )
            level_lcs, mss = _score_gathered(codes_l, codes_r, out_cap,
                                             left, right)
        else:
            # software-pipelined chunked gather+score: issue the owner hops
            # for chunk i+1 BEFORE scoring chunk i's resting pairs, so the
            # collective and the LCS compute have no data dependence
            def hop(i):
                sl = slice(i * _sub, (i + 1) * _sub)
                return _hop_gather_codes(
                    left[sl], right[sl], codes,
                    owner_of=lambda g: (g if subtraj is None else g // nw)
                    // plan.local_n,
                    slot_of=lambda g: (g if subtraj is None else g // nw)
                    - gid0,
                    n_shards=n_shards, axis_name=axis_name,
                    hop_cap=chunk_hop_cap, out_cap=chunk_rest_cap,
                )

            def score_chunk(p):
                return (
                    p[:2]
                    + _score_gathered(p[2], p[3], chunk_rest_cap, p[0], p[1])
                    + (p[4],)
                )

            parts = []
            pending = hop(0)
            for i in range(1, n_chunks):
                nxt = hop(i)
                parts.append(score_chunk(pending))
                pending = nxt
            parts.append(score_chunk(pending))
            left = jnp.concatenate([p[0] for p in parts])
            right = jnp.concatenate([p[1] for p in parts])
            level_lcs = jnp.concatenate([p[2] for p in parts])
            mss = jnp.concatenate([p[3] for p in parts])
            ovf5 = sum(p[4] for p in parts)
        mss = jnp.where(left == PAD_ID, -1.0, mss)
        overflow = jnp.stack([ovf1 + ovf2, ovf3, ovf4 + ovf5]).astype(jnp.int32)
        return left, right, level_lcs, mss, overflow, n_pruned.reshape(1)

    def _lengths_of(code_rows):
        # lengths reconstructed from the padding sentinel in level 0
        return jnp.sum(code_rows[:, 0, :] >= 0, axis=-1).astype(jnp.int32)

    def _score(table_l, table_r, left, right, rows_l=None, rows_r=None):
        """Score pairs ``left``/``right`` (global ids: trajectories, or
        windows in subtrajectory mode) against the code tables.  ``rows_*``
        index each pair's trajectory row in its table (default: the id's
        own trajectory).  Window ids decode to (traj, offset) here, at the
        point of scoring; the owner hops always move full rows."""
        li = jnp.where(left == PAD_ID, 0, left)
        ri = jnp.where(right == PAD_ID, 0, right)
        ta, tb = li // nw, ri // nw
        window = {}
        if subtraj is not None:
            window = dict(window=W, off_a=(li % nw) * stride,
                          off_b=(ri % nw) * stride)
        return score_indexed(
            table_l, _lengths_of(table_l), table_r, _lengths_of(table_r),
            ta if rows_l is None else rows_l,
            tb if rows_r is None else rows_r, betas, impl=impl, **window,
        )

    def _score_gathered(codes_l, codes_r, cap, left, right):
        """Score one resting operand stack (post-hop) -> (level_lcs, mss):
        row i of each stack is pair i's trajectory row."""
        iota = jnp.arange(cap, dtype=jnp.int32)
        return _score(codes_l, codes_r, left, right, iota, iota)

    def _gather_pair_codes(left, right, codes_local, gid0, plan, n, axis,
                           out_cap):
        """Shuffle-mode scoring via the shared two-hop gather
        (:func:`_hop_gather_codes`) with the one-shot BLOCK ownership:
        row g lives on shard ``g // local_n`` at slot ``g - gid0``.  Hop
        buckets are sized from the exactly-planned per-owner loads
        (plan.owner_route_cap); without a plan the uniform fallback applies
        and overflow counters catch skew.  ``out_cap`` is the resting
        buffer size — the post-prune capacity when the pruning pass ran,
        else plan.scored_cap.
        """
        cap = plan.owner_route_cap or (out_cap // n + 64)
        return _hop_gather_codes(
            left, right, codes_local,
            owner_of=lambda g: (g if subtraj is None else g // nw)
            // plan.local_n,
            slot_of=lambda g: (g if subtraj is None else g // nw) - gid0,
            n_shards=n, axis_name=axis, hop_cap=cap, out_cap=out_cap,
        )

    spec_in = (
        P(axis_name, None), P(axis_name, None), P(axis_name), P(None, None),
    )
    spec_out = (P(axis_name), P(axis_name), P(axis_name), P(axis_name),
                P(axis_name), P(axis_name))
    fn = compat.shard_map(
        shard_fn, mesh=mesh, in_specs=spec_in, out_specs=spec_out
    )

    @jax.jit
    def run(first, places, lengths, tables):
        left, right, level_lcs, mss, overflow, pruned = fn(
            first, places, lengths, tables
        )
        return {
            "left": left.reshape(n_shards, -1),
            "right": right.reshape(n_shards, -1),
            "level_lcs": level_lcs.reshape(n_shards, rest_total, -1),
            "mss": mss.reshape(n_shards, -1),
            "overflow": overflow.reshape(n_shards, -1),
            "pruned": pruned.reshape(n_shards),
        }

    return run


@dataclasses.dataclass(frozen=True)
class StreamShardPlan:
    """Static shapes of one streaming sharded score program.

    The streaming world is laid out ROUND-ROBIN: global row g lives on
    shard ``g % n_shards`` at local slot ``g // n_shards``, so appends keep
    every shard within one row of balanced as the world grows (the one-shot
    pipeline's block layout would pile every new row onto the last shard).
    All capacities are powers of two so consecutive updates with similar
    delta sizes hit the same compiled runner.
    """

    n_shards: int
    cap_local: int   # physical world rows per shard (world cap / n_shards)
    pair_cap: int    # delta pairs per shard (host-assigned input slices)
    hop_cap: int     # rows per (src, dst) bucket in the owner hops (shuffle);
    #                  with n_chunks > 1 this is the PER-CHUNK bucket size
    out_cap: int     # resting pairs per shard after the hops (PER CHUNK when
    #                  n_chunks > 1); in "replicate" mode pairs score in
    #                  place: == pair_cap
    n_chunks: int = 1  # shuffle-mode overlap: split each shard's pair slice
    #                    into this many sub-chunks so chunk i+1's owner hops
    #                    run while chunk i scores (power of two; must divide
    #                    pair_cap)


def _pow2(x: int, floor_pow2: int = 4) -> int:
    return 1 << max(floor_pow2, int(np.ceil(np.log2(max(int(x), 1)))))


def plan_stream_capacities(
    lo: np.ndarray,
    hi: np.ndarray,
    n_shards: int,
    cap_local: int,
    *,
    score_mode: str = "replicate",
    floor_pow2: int = 4,
    overlap_chunks: int = 1,
    pair_cap_floor: int = 0,
    windows_per_row: int = 1,
) -> StreamShardPlan:
    """Exact skew-aware capacity plan for ONE micro-batch's delta pairs.

    The delta pairs are already deduped host-side (the bucket index emits
    each pair once), so planning reduces to the score shuffle: pairs are
    assigned to source shards in contiguous chunks, and for
    ``score_mode="shuffle"`` the two owner hops are sized from the actual
    per-(src, dst) loads under round-robin ownership (``owner = id %
    n_shards``) — the same exact-loads discipline as
    :func:`plan_capacities`, just over the delta instead of the world.
    Capacities quantize to powers of two; the streaming engine keeps them
    sticky (monotone max over updates) so steady-state updates reuse the
    compiled runner.

    ``overlap_chunks > 1`` (shuffle mode only) sizes the PER-CHUNK hop and
    resting buffers for the software-pipelined gather: each shard's
    ``pair_cap`` slice is split into that many sub-slices, and because the
    host assigns pairs to slices deterministically (contiguous chunks,
    front slots), the per-(chunk, owner) loads are exact.  Sticky plans may
    hold ``pair_cap`` above this update's need, which MOVES the chunk
    boundaries — ``pair_cap_floor`` (the sticky value) lets a fresh plan
    compute chunk loads under the layout the runner will actually use.

    ``windows_per_row > 1`` declares the delta pair ids to be WINDOW ids
    (``t * nw + j``, see :mod:`repro.core.subtraj`): round-robin ownership
    is then per TRAJECTORY (``owner = (id // nw) % n_shards``), matching
    the resident world layout where only whole trajectory rows are stored.
    (The StreamingEngine itself rejects subtrajectory mode — a growing
    world max-length would re-number every stored window id — but the
    planner stays windows-aware so batch-style callers can size streaming
    score programs over windowed deltas.)
    """
    p = int(lo.shape[0])
    chunk = -(-p // n_shards) if p else 0  # ceil
    pair_cap = max(_pow2(chunk, floor_pow2), pair_cap_floor or 0)
    if score_mode == "replicate":
        return StreamShardPlan(
            n_shards=n_shards, cap_local=cap_local, pair_cap=pair_cap,
            hop_cap=0, out_cap=pair_cap,
        )
    n_chunks = max(int(overlap_chunks), 1)
    sub = pair_cap // n_chunks if n_chunks > 1 else pair_cap
    if p:
        lo = np.asarray(lo, np.int64)
        hi = np.asarray(hi, np.int64)
        idx = np.arange(p, dtype=np.int64)
        src = idx // max(chunk, 1)
        pos = idx - src * max(chunk, 1)    # front slot in the shard's slice
        cidx = np.minimum(pos // max(sub, 1), n_chunks - 1)
        own_lo = (lo // windows_per_row) % n_shards
        own_hi = (hi // windows_per_row) % n_shards
        h1 = np.zeros((n_chunks, n_shards, n_shards), np.int64)
        np.add.at(h1, (cidx, src, own_lo), 1)
        h2 = np.zeros((n_chunks, n_shards, n_shards), np.int64)
        np.add.at(h2, (cidx, own_lo, own_hi), 1)
        rest = np.zeros((n_chunks, n_shards), np.int64)
        np.add.at(rest, (cidx, own_hi), 1)
        hop_need = int(max(h1.max(), h2.max()))
        rest_need = int(rest.max())
    else:
        hop_need = rest_need = 1
    return StreamShardPlan(
        n_shards=n_shards, cap_local=cap_local, pair_cap=pair_cap,
        hop_cap=_pow2(hop_need, floor_pow2),
        out_cap=_pow2(rest_need, floor_pow2),
        n_chunks=n_chunks,
    )


@dataclasses.dataclass(frozen=True)
class StreamJoinPlan:
    """Static shapes of one in-mesh streaming delta-join program.

    The resident bucket state is key-sharded: every (key, row id)
    occurrence lives on shard ``hash(key) % n_shards`` inside a sorted
    slab of ``slab_cap`` slots (core/device_index.py).  Per update only
    the NEW rows' key occurrences enter the mesh — ``key_in_cap`` per
    source shard — are all_to_all'd to their owners (``key_route_cap``
    per (src, dst) bucket), probed against the slab into the
    ``nn_cap``/``no_cap`` pair buffers, pair-hash shuffled for global
    dedup (``pair_route_cap``), and come to rest ``pair_cap`` per shard.
    All capacities quantize to powers of two and the engine keeps them
    sticky (monotone max), so steady-state updates reuse one compiled
    program.
    """

    n_shards: int
    slab_cap: int       # resident (key, row) occurrences per shard
    key_in_cap: int     # incoming key occurrences per source shard
    key_route_cap: int  # rows per (src, dst) bucket in the key route
    nn_cap: int         # new-vs-new pair slots per owner shard
    no_cap: int         # new-vs-old pair slots per owner shard
    pair_route_cap: int  # rows per (src, dst) bucket in the dedup shuffle
    pair_cap: int       # deduped resting delta pairs per shard


def plan_stream_join(
    keys_flat: np.ndarray,
    n_shards: int,
    stats,
    *,
    floor_pow2: int = 4,
) -> StreamJoinPlan:
    """Exact skew-aware capacity plan for ONE update's in-mesh delta join.

    keys_flat: the new rows' per-row-deduped key occurrences (flat, row
    order) — the only join data the driver touches.  ``stats`` is the
    :class:`~repro.core.device_index.StreamJoinStats` count mirror; its
    ``plan_update`` yields the exact per-owner new-vs-old / new-vs-new
    emission counts and slab-entry deltas under the device's own int32
    key hash, so the slab, probe and route buffers are sized from actual
    per-owner loads, not uniform-hash bounds.  The two pair-stage caps the
    driver cannot compute exactly without the pair list itself
    (``pair_route_cap``, ``pair_cap``) use the per-owner / global
    pre-dedup emission totals — safe upper bounds on any post-dedup skew,
    so a steady-state overflow is impossible (the retry-doubling path
    stays as a belt-and-braces check).
    """
    k = int(keys_flat.shape[0])
    owners = _positive_hash_np(keys_flat) % n_shards if k else \
        np.zeros((0,), np.int64)
    nvo, nvn, ent = stats.plan_update(keys_flat, owners)
    chunk = -(-k // n_shards) if k else 0
    if k:
        src = np.arange(k, dtype=np.int64) // max(chunk, 1)
        load = np.zeros((n_shards, n_shards), np.int64)
        np.add.at(load, (src, owners), 1)
        route_need = int(load.max())
    else:
        route_need = 1
    emit = nvo + nvn
    return StreamJoinPlan(
        n_shards=n_shards,
        slab_cap=_pow2(int((stats.owner_entries + ent).max()), floor_pow2),
        key_in_cap=_pow2(chunk, floor_pow2),
        key_route_cap=_pow2(route_need, floor_pow2),
        nn_cap=_pow2(int(nvn.max()), floor_pow2),
        no_cap=_pow2(int(nvo.max()), floor_pow2),
        pair_route_cap=_pow2(int(emit.max()), floor_pow2),
        pair_cap=_pow2(int(emit.sum()), floor_pow2),
    )


def sticky_join_plan(
    plan: StreamJoinPlan, prev: StreamJoinPlan | None
) -> StreamJoinPlan:
    """Monotone max over every capacity: consecutive updates with similar
    delta shapes resolve to the SAME plan, so the compiled join runner is
    reused verbatim (the zero-steady-state-recompile contract)."""
    if prev is None:
        return plan
    return StreamJoinPlan(
        n_shards=plan.n_shards,
        slab_cap=max(plan.slab_cap, prev.slab_cap),
        key_in_cap=max(plan.key_in_cap, prev.key_in_cap),
        key_route_cap=max(plan.key_route_cap, prev.key_route_cap),
        nn_cap=max(plan.nn_cap, prev.nn_cap),
        no_cap=max(plan.no_cap, prev.no_cap),
        pair_route_cap=max(plan.pair_route_cap, prev.pair_route_cap),
        pair_cap=max(plan.pair_cap, prev.pair_cap),
    )


def make_streaming_join_pipeline(
    mesh: jax.sharding.Mesh,
    plan: StreamJoinPlan,
    *,
    axis_name: str = "ex",
    trace_counter: list | None = None,
):
    """Build the jitted shard_map in-mesh delta-join program.

    The device-side replacement for ``BucketIndex.insert``: bucket state
    stays key-sharded and device-resident, the driver ships only the new
    rows' key occurrences, and the deduped delta pairs come to rest
    in-mesh (their device buffers feed the streaming score program
    directly — the pair list never materializes on the host).

    Call signature of the returned fn::

      fn(slab_keys [n_shards * slab_cap] int32,   # resident sorted slabs
         slab_rows [n_shards * slab_cap] int32,
         keys      [n_shards * key_in_cap] int32,  # new occurrences,
         rows      [n_shards * key_in_cap] int32)  # PAD-padded chunks
        -> dict: slab_keys/slab_rows (merged — commit only on success),
                 left/right [n_shards, pair_cap] deduped delta pairs,
                 count [n_shards], max_count [n_shards] (the in-mesh pmax
                 of the post-dedup counts, replicated — the tight score
                 pair cap), examined [n_shards], overflow [n_shards, 4]

    Stages per shard: (1) all_to_all the incoming occurrences to
    ``hash(key) % n_shards`` through the shared :func:`_route` machinery;
    (2) :func:`~repro.core.device_index.probe_pairs` against the resident
    slab (new-vs-old + new-vs-new, exact ``examined`` accounting);
    (3) pair-hash all_to_all + :func:`~repro.core.ssh.dedup_pairs` so
    every delta pair rests on exactly one shard (cross-owner duplicates
    from pairs sharing keys with different owners collapse here);
    (4) :func:`~repro.core.device_index.merge_insert` folds the incoming
    occurrences into the slab (functional: the caller commits the
    returned slabs only when no overflow fired, so retries are safe).

    ``trace_counter`` increments at TRACE time only — the compilation
    counting hook the differential harness asserts on.
    """
    from jax.sharding import PartitionSpec as P

    from repro.core.device_index import merge_insert, probe_pairs

    n_shards = plan.n_shards

    def shard_fn(slab_k, slab_r, keys, rows):
        if trace_counter is not None:
            trace_counter[0] += 1  # executes per compile, not per update
        valid = keys != PAD_KEY
        dest = _positive_hash(keys) % n_shards
        (rk, rr), o1 = _route(
            (keys, rows), dest, valid,
            n_shards=n_shards, capacity=plan.key_route_cap,
            pads=(PAD_KEY, PAD_ID), axis_name=axis_name,
        )
        lo, hi, examined, o2 = probe_pairs(
            slab_k, slab_r, rk, rr, nn_cap=plan.nn_cap, no_cap=plan.no_cap
        )
        pvalid = lo != PAD_ID
        pdest = _pair_hash(lo, hi) % n_shards
        (rlo, rhi), o3 = _route(
            (lo, hi), pdest, pvalid,
            n_shards=n_shards, capacity=plan.pair_route_cap,
            pads=(PAD_ID, PAD_ID), axis_name=axis_name,
        )
        cand = dedup_pairs(rlo, rhi)
        left = _fit(cand.left, plan.pair_cap, PAD_ID)
        right = _fit(cand.right, plan.pair_cap, PAD_ID)
        o4 = jnp.maximum(cand.count - plan.pair_cap, 0)
        slab_k2, slab_r2, o5 = merge_insert(slab_k, slab_r, rk, rr)
        count = jnp.minimum(cand.count, plan.pair_cap)
        # in-mesh count reduction: the worst per-shard POST-dedup resting
        # count, replicated to every shard.  The driver sizes the score
        # program's pair buffers from this instead of the pre-dedup
        # emission bound baked into plan.pair_cap (cross-owner duplicates
        # and the global-vs-per-shard gap both vanish), so the resting
        # buffers are sliced down before a single padded pair is scored
        max_count = jax.lax.pmax(count, axis_name)
        overflow = jnp.stack([o1 + o2, o3 + o4, o5,
                              jnp.zeros((), jnp.int32)]).astype(jnp.int32)
        return (slab_k2, slab_r2, left, right, count.reshape(1),
                max_count.reshape(1), examined.reshape(1), overflow)

    spec_in = (P(axis_name), P(axis_name), P(axis_name), P(axis_name))
    spec_out = (P(axis_name), P(axis_name), P(axis_name), P(axis_name),
                P(axis_name), P(axis_name), P(axis_name), P(axis_name))
    fn = compat.shard_map(
        shard_fn, mesh=mesh, in_specs=spec_in, out_specs=spec_out
    )

    @jax.jit
    def run(slab_keys, slab_rows, keys, rows):
        sk, sr, left, right, count, max_count, examined, overflow = fn(
            slab_keys, slab_rows, keys, rows
        )
        return {
            "slab_keys": sk,
            "slab_rows": sr,
            "left": left.reshape(n_shards, -1),
            "right": right.reshape(n_shards, -1),
            "count": count.reshape(n_shards),
            "max_count": max_count.reshape(n_shards),
            "examined": examined.reshape(n_shards),
            "overflow": overflow.reshape(n_shards, -1),
        }

    return run


def make_streaming_score_pipeline(
    mesh: jax.sharding.Mesh,
    plan: StreamShardPlan,
    *,
    betas: jnp.ndarray,
    axis_name: str = "ex",
    score_mode: str = "replicate",
    lcs_impl: str = "wavefront",
    trace_counter: list | None = None,
    score_prune: bool = False,
    prune_tau: float = 0.0,
    tuning=None,
):
    """Build the jitted shard_map DELTA score program for streaming updates.

    Unlike :func:`make_sharded_pipeline` there is no join here: candidate
    generation is incremental (the host bucket index emits only
    new-vs-world pairs), so the device program just encodes each shard's
    resident world rows in-mesh and scores the already-deduped delta pairs
    through the selected ``lcs_impl``.

    Call signature of the returned fn::

      fn(places [n_shards * cap_local, L] int32,   # round-robin physical
         left   [n_shards * pair_cap] int32,       # global ids, PAD_ID pad
         right  [n_shards * pair_cap] int32,
         tables [n_levels, num_places] int32)
        -> dict: left/right [n, out_cap], level_lcs [n, out_cap, H],
                 mss [n, out_cap], overflow [n]

    Row lengths are reconstructed in-mesh from the encoding sentinels, so
    the world state a shard holds is exactly its places slab — the code
    table never materializes on the host, matching the one-shot invariant.

    score_mode "replicate" all_gathers the per-shard encodings and scores
    each pair slice in place (output slot == input slot); "shuffle" keeps
    the table sharded and runs the shared two-hop owner gather
    (:func:`_hop_gather_codes`) under round-robin ownership, with hop
    buckets sized by :func:`plan_stream_capacities`.

    ``trace_counter`` is a single-element list incremented at TRACE time
    (the Python body runs only when XLA compiles a new program) — the
    compilation-counting hook the no-recompile regression tests assert on.

    ``score_prune`` runs the MSS upper-bound pruning pass IN-MESH (the
    host delta-join path prunes host-side before the pairs ship; the
    device delta-join path never sees the pairs on the host, so pruning
    happens here): lengths are reconstructed from the encoding sentinels,
    the same float32 bound as the one-shot pass is tested against
    ``prune_tau``, and hopeless pairs are masked to PAD — in "shuffle"
    mode BEFORE the owner hops (only the [N] lengths vector is gathered,
    and masked pairs are invalid to the router, so they never travel or
    gather code rows).  The surviving scored set is bit-identical to
    pruning host-side; the per-shard prune count returns as ``pruned``.

    With ``plan.n_chunks > 1`` (shuffle mode) each shard's pair slice is
    split into sub-chunks and the owner hops software-pipeline against
    scoring exactly as in :func:`make_sharded_pipeline`: chunk i+1's hops
    are issued before chunk i scores, per-pair results stay bit-identical,
    and ``n_chunks`` is static in the plan so the zero-steady-state-
    recompile contract (``trace_counter``) is untouched.

    ``tuning`` (optional :class:`repro.perf.LCSTuning`) resolves eagerly
    at build time into static kernel args via ``lcs_impl_fn``.
    """
    from jax.sharding import PartitionSpec as P

    from repro.api.stages import lcs_impl_fn

    n_shards = plan.n_shards
    impl = lcs_impl_fn(lcs_impl, tuning)
    out_cap = plan.out_cap
    n_chunks = plan.n_chunks if score_mode == "shuffle" else 1
    if n_chunks > 1:
        if plan.pair_cap % n_chunks:
            raise ValueError(
                f"pair_cap ({plan.pair_cap}) must divide into n_chunks="
                f"{n_chunks} slices (both are powers of two)"
            )
        _sub = plan.pair_cap // n_chunks
        rest_total = n_chunks * out_cap   # out_cap is PER CHUNK here
    else:
        rest_total = out_cap

    def _lengths_of(code_rows):
        # lengths reconstructed from the padding sentinel in level 0
        return jnp.sum(code_rows[:, 0, :] >= 0, axis=-1).astype(jnp.int32)

    def _score_gathered(codes_l, codes_r, cap):
        """Score one resting operand stack (post-hop) -> (level_lcs, mss)."""
        iota = jnp.arange(cap, dtype=jnp.int32)
        return score_indexed(
            codes_l, _lengths_of(codes_l), codes_r, _lengths_of(codes_r),
            iota, iota, betas, impl=impl,
        )

    def _phys(g, valid):
        # physical index of global id g in the round-robin world layout:
        # (g % n) * cap_local + g // n
        safe = jnp.where(valid, g, 0)
        return (safe % n_shards) * plan.cap_local + safe // n_shards

    def shard_fn(places, left, right, tables):
        if trace_counter is not None:
            trace_counter[0] += 1  # executes per compile, not per update
        codes = encode_codes(places, tables)  # [cap_local, H, L]
        n_pruned = jnp.zeros((), jnp.int32)
        if score_mode == "replicate":
            codes_all = jax.lax.all_gather(codes, axis_name, axis=0,
                                           tiled=True)
            valid = left != PAD_ID
            li = _phys(left, valid)
            ri = _phys(right, valid)
            if score_prune:
                len_all = _lengths_of(codes_all)
                keep = _prune_keep(len_all[li], len_all[ri], betas,
                                   prune_tau, valid)
                n_pruned = (jnp.sum(valid) - jnp.sum(keep)).astype(jnp.int32)
                left = jnp.where(keep, left, PAD_ID)
                right = jnp.where(keep, right, PAD_ID)
            len_all = _lengths_of(codes_all)
            level_lcs, mss = score_indexed(
                codes_all, len_all, codes_all, len_all, li, ri, betas,
                impl=impl,
            )
            out_l, out_r = left, right
            ovf = jnp.zeros((), jnp.int32)
        else:
            if score_prune:
                # prune BEFORE the owner hops (the one-shot discipline):
                # only the [N] int32 lengths vector is gathered — never a
                # code row — and pruned pairs, masked to PAD, are invalid
                # to _route, so they never travel or gather codes
                len_all = jax.lax.all_gather(
                    _lengths_of(codes), axis_name, axis=0, tiled=True
                )
                valid = left != PAD_ID
                keep = _prune_keep(len_all[_phys(left, valid)],
                                   len_all[_phys(right, valid)],
                                   betas, prune_tau, valid)
                n_pruned = (jnp.sum(valid) - jnp.sum(keep)).astype(jnp.int32)
                left = jnp.where(keep, left, PAD_ID)
                right = jnp.where(keep, right, PAD_ID)
            def hop(l_part, r_part):
                return _hop_gather_codes(
                    l_part, r_part, codes,
                    owner_of=lambda g: g % n_shards,
                    slot_of=lambda g: g // n_shards,
                    n_shards=n_shards, axis_name=axis_name,
                    hop_cap=plan.hop_cap, out_cap=out_cap,
                )

            if n_chunks == 1:
                out_l, out_r, codes_l, codes_r, ovf = hop(left, right)
                level_lcs, mss = _score_gathered(codes_l, codes_r, out_cap)
            else:
                # software pipeline: issue chunk i+1's owner hops BEFORE
                # scoring chunk i's resting pairs (no data dependence
                # between them, so the scheduler may overlap)
                parts = []
                pending = hop(left[:_sub], right[:_sub])
                for i in range(1, n_chunks):
                    sl = slice(i * _sub, (i + 1) * _sub)
                    nxt = hop(left[sl], right[sl])
                    parts.append(
                        pending[:2]
                        + _score_gathered(pending[2], pending[3], out_cap)
                        + (pending[4],)
                    )
                    pending = nxt
                parts.append(
                    pending[:2]
                    + _score_gathered(pending[2], pending[3], out_cap)
                    + (pending[4],)
                )
                out_l = jnp.concatenate([p[0] for p in parts])
                out_r = jnp.concatenate([p[1] for p in parts])
                level_lcs = jnp.concatenate([p[2] for p in parts])
                mss = jnp.concatenate([p[3] for p in parts])
                ovf = sum(p[4] for p in parts)
        mss = jnp.where(out_l == PAD_ID, -1.0, mss)
        return (out_l, out_r, level_lcs, mss,
                ovf.reshape(1).astype(jnp.int32), n_pruned.reshape(1))

    spec_in = (P(axis_name, None), P(axis_name), P(axis_name), P(None, None))
    spec_out = (P(axis_name), P(axis_name), P(axis_name), P(axis_name),
                P(axis_name), P(axis_name))
    fn = compat.shard_map(
        shard_fn, mesh=mesh, in_specs=spec_in, out_specs=spec_out
    )

    @jax.jit
    def run(places, left, right, tables):
        out_l, out_r, level_lcs, mss, overflow, pruned = fn(
            places, left, right, tables
        )
        return {
            "left": out_l.reshape(n_shards, -1),
            "right": out_r.reshape(n_shards, -1),
            "level_lcs": level_lcs.reshape(n_shards, rest_total, -1),
            "mss": mss.reshape(n_shards, -1),
            "overflow": overflow.reshape(n_shards),
            "pruned": pruned.reshape(n_shards),
        }

    return run


def make_distributed_anotherme(
    mesh: jax.sharding.Mesh,
    plan: DistributedPlan,
    *,
    tables: jnp.ndarray,
    k: int,
    num_types: int,
    betas: jnp.ndarray,
    axis_name: str = "ex",
    dedup: bool = True,
    score_mode: str = "replicate",
    lcs_impl: str = "wavefront",
):
    """Legacy entry point: the SSH-shingle sharded pipeline.

    Thin adapter over :func:`make_sharded_pipeline` with the shingle key_fn;
    prefer ``AnotherMeEngine`` with ``ExecutionPlan(n_shards=...)``.  The
    forest ``tables`` are required because encoding runs in-mesh; the
    returned fn takes ``(places [N, L], lengths [N])``.
    """

    def key_fn(local_types, local_lengths):
        return shingles_from_types(
            local_types, local_lengths, k=k, num_types=num_types, dedup=dedup
        )

    inner = make_sharded_pipeline(
        mesh, plan, betas=betas, key_fn=key_fn,
        axis_name=axis_name, score_mode=score_mode, lcs_impl=lcs_impl,
    )
    tables = jnp.asarray(tables)

    def run(places, lengths):
        return inner(places, places, lengths, tables)

    return run


def gather_similar_pairs(out: dict, rho: float) -> set[tuple[int, int]]:
    """Host-side collection of the globally-deduped similar pair set."""
    left = np.asarray(out["left"]).reshape(-1)
    right = np.asarray(out["right"]).reshape(-1)
    mss = np.asarray(out["mss"]).reshape(-1)
    keep = (left != PAD_ID) & (mss > rho)
    return {(int(a), int(b)) for a, b in zip(left[keep], right[keep])}


def pad_to_shards(places: np.ndarray, lengths: np.ndarray, n_shards: int):
    """Pad N up to a multiple of n_shards with empty trajectories."""
    n = places.shape[0]
    n_pad = (-n) % n_shards
    if n_pad:
        places = np.concatenate(
            [places, np.full((n_pad, places.shape[1]), -1, places.dtype)]
        )
        lengths = np.concatenate([lengths, np.zeros((n_pad,), lengths.dtype)])
    return places, lengths
