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
from exact counts: the engine runs the job as staged programs
(:class:`MeshStages`), each planned from the small count tables the stage
before made in the mesh, with every capacity a power of two kept sticky
per batch shape (:func:`plan_join`, :func:`plan_score`, :func:`sticky_plan`).
``plan_capacities`` replays the same counts on the host with numpy copies
of the device hashes; it is the tests' oracle, and no run plans by it.  Every stage carries an overflow counter, so a capacity
bust is detected, never silent.

Rows move between shards by sorts, not gathers: a destination bucket is a
contiguous slice of the rows sorted by destination (:func:`_bucket`).

The same code runs on 1 device (n_shards=1 degenerates to the single-device
pipeline) and on the 512-device production mesh in the dry-run.
"""
from __future__ import annotations

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compat
from repro.core.encoding import encode_codes
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


def _dest_counts(dest: jnp.ndarray, valid: jnp.ndarray, n_shards: int):
    """int32 [n_shards]: valid rows bound for each destination."""
    return jnp.stack([
        jnp.sum(valid & (dest == d)) for d in range(n_shards)
    ]).astype(jnp.int32)


_KEY_LIMIT = np.iinfo(np.int32).max  # a packed sort key's largest value


def _bucket(
    values: tuple, dest: jnp.ndarray, valid: jnp.ndarray, *, n_shards: int,
    capacity: int, pads: tuple, positions: bool = False,
    span: int | None = None,
):
    """Group 1-D rows into ``[n_shards * capacity]`` destination buckets.

    One sort by destination carries the rows (invalid rows sort last), and
    each bucket is one contiguous slice of the sorted rows, so no row is
    gathered or scattered: bucket ``d`` holds the first ``capacity`` rows
    bound for ``d``, then pads.  The sort key packs more than the
    destination into one int32 where it fits, as the TPU compiler builds a
    sort several times faster with fewer operands and no stability:

    * with ``span`` (the first value lies in ``[0, span)`` on valid rows)
      the key is ``destination * span + value``, so the first value rides
      in the key and a bucket's rows come in its order;
    * else the key is ``destination * R + row``, unique, so a bucket's
      rows come in their original order.

    Returns (bucketed rows, overflow, counts [n_shards] of the valid rows
    bound for each destination before the cut, rows kept); with
    ``positions`` the bucketed rows end with each row's index in the input
    (``-1`` in pads).
    """
    r = dest.shape[0]
    key = jnp.where(valid, dest, n_shards).astype(jnp.int32)
    row = jnp.arange(r, dtype=jnp.int32)
    if span is not None and not positions \
            and (n_shards + 1) * span <= _KEY_LIMIT:
        first = jnp.where(valid, values[0], 0)
        key, *rows = jax.lax.sort((key * span + first,) + tuple(values[1:]),
                                  num_keys=1, is_stable=False)
        rows.insert(0, key % span)
        bounds = jnp.arange(n_shards + 1, dtype=jnp.int32) * span
    elif (n_shards + 1) * r <= _KEY_LIMIT:
        key, *rows = jax.lax.sort((key * r + row,) + tuple(values),
                                  num_keys=1, is_stable=False)
        bounds = jnp.arange(n_shards + 1, dtype=jnp.int32) * r
        if positions:
            rows.append(key % r)
    else:
        extra = (row,) if positions else ()
        key, *rows = jax.lax.sort((key,) + tuple(values) + extra,
                                  num_keys=1, is_stable=True)
        bounds = jnp.arange(n_shards + 1, dtype=jnp.int32)
    pads = tuple(pads) + ((-1,) if positions else ())
    edges = jnp.searchsorted(key, bounds).astype(jnp.int32)
    counts = edges[1:] - edges[:-1]
    kept = jnp.minimum(counts, capacity)
    slot = jnp.arange(capacity, dtype=jnp.int32)
    out = []
    for v, pad in zip(rows, pads):
        v = jnp.concatenate([v, jnp.full((capacity,), pad, v.dtype)])
        out.append(jnp.concatenate([
            jnp.where(slot < kept[d],
                      jax.lax.dynamic_slice_in_dim(v, edges[d], capacity),
                      pad)
            for d in range(n_shards)
        ]))
    return tuple(out), jnp.sum(counts - kept), counts, jnp.sum(kept)


def _exchange(buf: jnp.ndarray, n_shards: int, axis_name: str):
    """all_to_all of ``[n_shards * capacity, ...]`` destination buckets:
    bucket ``d`` goes to shard ``d``; bucket ``s`` of the result came from
    shard ``s``."""
    shape = buf.shape
    recv = jax.lax.all_to_all(
        buf.reshape((n_shards, shape[0] // n_shards) + shape[1:]),
        axis_name, split_axis=0, concat_axis=0, tiled=True,
    )
    return recv.reshape(shape)


def _route(
    values: tuple, dest: jnp.ndarray, valid: jnp.ndarray, *, n_shards: int,
    capacity: int, pads: tuple, axis_name: str, positions: bool = False,
    span: int | None = None,
):
    """Bucket 1-D rows by destination (:func:`_bucket`) and all_to_all them.

    values: tuple of [R] arrays routed together; pads: per-array pad value.
    Returns (tuple of [n_shards * capacity] received rows, overflow, counts
    [n_shards] of the valid rows bound for each destination, rows
    carried); with ``positions`` the received rows end with each row's
    index in its source's input (``-1`` in pads).
    """
    bufs, overflow, counts, kept = _bucket(
        values, dest, valid, n_shards=n_shards, capacity=capacity, pads=pads,
        positions=positions, span=span,
    )
    recv = tuple(_exchange(b, n_shards, axis_name) for b in bufs)
    return recv, overflow, counts, kept


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
    hop_cap, out_cap, span=None,
):
    """Two-hop pair/code shuffle shared by the one-shot and streaming paths.

    Route pairs to owner(left), attach that shard's code rows, then to
    owner(right), and come to rest there (the pairs are already globally
    deduped upstream).  Ownership is pluggable: the one-shot pipeline owns
    rows in blocks (``g // local_n``), the streaming world round-robins them
    (``g % n_shards``) so growth stays balanced; ``slot_of`` maps a global
    id to the owner's local row.  The left code rows are gathered once, in
    the order hop 2 ships them, and stay where they arrive: each resting
    pair points at its left row there (``rows_l``) and at its right row in
    this shard's own table (``rows_r``).  Each received bucket's valid rows
    are its prefix, so laying the prefixes end to end brings the resting
    pairs to the front without a sort.  Returns (left, right, table_l
    [n_shards * hop_cap, H, L], rows_l, rows_r, overflow, (rows carried by
    hop 1, rows carried by hop 2)).  ``span`` bounds the ids, if known
    (:func:`_bucket`).
    """
    H, L = codes_local.shape[1], codes_local.shape[2]
    local_n = codes_local.shape[0]
    pads = (PAD_ID, PAD_ID)
    # hop 1: to owner(left)
    (l1, r1), o1, _, c1 = _route(
        (left, right), owner_of(left), left != PAD_ID,
        n_shards=n_shards, capacity=hop_cap, pads=pads, axis_name=axis_name,
        span=span,
    )
    # hop 2: to owner(right), with the left code rows this shard owns
    (rb, lb), o2, _, c2 = _bucket(
        (r1, l1), owner_of(r1), l1 != PAD_ID,
        n_shards=n_shards, capacity=hop_cap, pads=pads, span=span,
    )
    safe = jnp.clip(slot_of(jnp.where(lb == PAD_ID, 0, lb)), 0, local_n - 1)
    cl = codes_local.reshape(local_n, H * L)[safe]
    l2, r2, cl = (_exchange(x, n_shards, axis_name) for x in (lb, rb, cl))
    # rest: the prefixes of the received buckets, end to end
    size = n_shards * hop_cap
    seg = jnp.sum((l2 != PAD_ID).reshape(n_shards, hop_cap), axis=1)
    off = jnp.cumsum(seg) - seg
    pos = jnp.arange(size, dtype=jnp.int32)
    out = [jnp.full((size,), PAD_ID, jnp.int32)] * 2 + [
        jnp.zeros((size,), jnp.int32)]
    for s in range(n_shards):
        part = slice(s * hop_cap, (s + 1) * hop_cap)
        out = [jax.lax.dynamic_update_slice_in_dim(o, x[part], off[s], 0)
               for o, x in zip(out, (l2, r2, pos))]
    ovf_fit = jnp.maximum(jnp.sum(seg) - out_cap, 0)
    left, right = _fit(out[0], out_cap, PAD_ID), _fit(out[1], out_cap, PAD_ID)
    rows_l = _fit(out[2], out_cap, 0)
    rows_r = jnp.clip(slot_of(jnp.where(right == PAD_ID, 0, right)),
                      0, local_n - 1)
    return (left, right, cl.reshape(size, H, L), rows_l, rows_r,
            (o1 + o2 + ovf_fit).astype(jnp.int32), (c1, c2))


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
    similar_cap: int = 0    # similar pairs per shard brought to the host
    #                         (MeshStages.similar); 0 -> not planned yet
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
    per-chunk hop/resting buffers for the chunked gather: slot ``p`` of the
    pre-hop pair buffer belongs to chunk ``p % overlap_chunks``, and
    because the device buffer layout is DETERMINISTIC — ``dedup_pairs``
    sorts by (lo, hi) with PAD at the end, and the prune compaction
    preserves that order — the planner can replay exactly which pairs land
    in which chunk and size ``chunk_hop_cap`` / ``chunk_rest_cap`` from the
    actual per-(chunk, owner) loads, keeping the overflow accounting exact
    under chunking too.

    The engine no longer calls this: it plans from counts made in the mesh
    (:func:`plan_from_counts`).  It stays as the oracle the tests hold
    those counts to.

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
        flip = (_pair_hash_np(ulo, uhi) >> 16) & 1 == 1
        ox, oy = np.where(flip, uhi, ulo), np.where(flip, ulo, uhi)
        if score_mode == "shuffle":
            # per-owner loads of the code-gather hops: dedup shard ->
            # owner(x) -> owner(y), (x, y) the pair in hop order
            # (_orient); pairs come to rest on owner(y).  Pruning happens
            # before the hops, so with it on only survivors travel — hop
            # buckets and the resting buffer are sized from the survivor
            # subset.
            own_lo = ((ox // nw) // local_n)[surv]
            own_hi = ((oy // nw) // local_n)[surv]
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
            # occupies which chunk of which shard's buffer, then size ONE
            # chunk's hop buckets / resting buffer from the worst chunk
            if prune:
                pruned_cap += (-pruned_cap) % overlap_chunks
            else:
                cap4 += (-cap4) % overlap_chunks
            sel = np.nonzero(surv)[0]
            d_sel = ded_dst[sel]
            rank = np.zeros(sel.shape[0], np.int64)
            for s in range(n_shards):
                m = d_sel == s
                rank[m] = np.arange(int(m.sum()))
            chunk_of = rank % overlap_chunks
            olo = (ox[sel] // nw) // local_n
            ohi = (oy[sel] // nw) // local_n
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


class _OneShot:
    """The shard-level steps of the one-shot job, for the staged programs
    of :class:`MeshStages`.  Every method runs inside ``shard_map`` on one
    shard's rows."""

    def __init__(self, *, n_shards, local_n, betas, key_fn, axis_name,
                 score_mode, impl, score_prune, prune_tau, subtraj):
        self.n = n_shards
        self.local_n = local_n
        self.betas = betas
        self.key_fn = key_fn
        self.axis = axis_name
        self.score_mode = score_mode
        self.score_prune = score_prune
        self.prune_tau = prune_tau
        self.subtraj = subtraj
        self.W, self.stride, self.nw = subtraj or (0, 1, 1)
        self.span = n_shards * local_n * self.nw  # ids lie in [0, span)
        self.impl = impl

    # -- ids and ownership ---------------------------------------------------

    def gid0(self):
        return jax.lax.axis_index(self.axis).astype(jnp.int32) * self.local_n

    def traj(self, g):
        """Trajectory of an id (a window id in subtrajectory mode)."""
        return g if self.subtraj is None else g // self.nw

    def owner_of(self, g):
        return self.traj(g) // self.local_n

    # -- keys and the key shuffle --------------------------------------------

    def keys(self, first, places, lengths, tables):
        """(codes [local_n, H, L], flat keys, keys per row, valid, dest)
        of this shard's rows: one key row per trajectory, or per window."""
        codes = encode_codes(places, tables)
        if self.key_fn is not None:
            keys = self.key_fn(codes[:, 0, :], lengths)
        else:
            keys = first  # precomputed host-side
        flat_keys = keys.reshape(-1)
        valid = flat_keys != PAD_KEY
        return codes, flat_keys, keys.shape[1], valid, \
            _positive_hash(flat_keys) % self.n

    def route_keys(self, flat_keys, width, valid, dest, cap):
        """Shuffle 1: (keys, ids) to ``hash(key) % n_shards``.  Only the
        keys travel through the bucket sort: a key's id is its source row,
        ``position // width`` on this shard."""
        (rk, pos), ovf, counts, carried = _route(
            (flat_keys,), dest, valid, n_shards=self.n, capacity=cap,
            pads=(PAD_KEY,), axis_name=self.axis, positions=True,
        )
        # (the source shard of received bucket s is s)
        src = jnp.repeat(jnp.arange(self.n, dtype=jnp.int32), cap)
        base = src * (self.local_n * self.nw)
        rid = jnp.where(pos >= 0, base + pos // width, PAD_ID)
        return (rk, rid), ovf, counts, carried

    # -- join and the dedup shuffle ------------------------------------------

    def join(self, rk, rid, pair_cap, route_cap):
        """Local sort-merge join, then shuffle 2 by pair hash and a dedup
        that is globally exact.  Returns (left, right) deduped and
        compacted to the front of the received buffer, their count, the
        join's and the shuffle's overflow, the shuffle's destination counts
        and the rows it carried."""
        lo, hi, ovf = pairs_from_rows(rk, rid, pair_capacity=pair_cap,
                                      presorted=True)
        (rlo, rhi), ovf_route, counts, carried = _route(
            (lo, hi), _pair_hash(lo, hi) % self.n, lo != PAD_ID,
            n_shards=self.n, capacity=route_cap, pads=(PAD_ID, PAD_ID),
            axis_name=self.axis, span=self.span,
        )
        cand = dedup_pairs(rlo, rhi)
        return (cand.left, cand.right, cand.count, ovf, ovf_route, counts,
                carried)

    def keep(self, left, right, lengths):
        """The MSS upper-bound prune's survivors among valid pairs; only the
        [N] lengths vector is gathered, never a code row."""
        valid = left != PAD_ID
        if not self.score_prune:
            return valid
        lengths_all = jax.lax.all_gather(lengths, self.axis, axis=0,
                                         tiled=True)
        sl = jnp.where(valid, left, 0)
        sr = jnp.where(valid, right, 0)
        if self.subtraj is None:
            len_l, len_r = lengths_all[sl], lengths_all[sr]
        else:
            # per-WINDOW lengths from the [N] trajectory lengths
            nw, st, W = self.nw, self.stride, self.W
            len_l = jnp.clip(lengths_all[sl // nw] - (sl % nw) * st, 0, W)
            len_r = jnp.clip(lengths_all[sr // nw] - (sr % nw) * st, 0, W)
        return _prune_keep(len_l, len_r, self.betas, self.prune_tau, valid)

    def owner_loads(self, left, right, keep):
        """int32 [n_shards * n_shards]: this shard's kept pairs by the
        owners of their two ids in hop order (:func:`_orient`) — the owner
        hops' loads."""
        x, y = _orient(jnp.where(keep, left, 0), jnp.where(keep, right, 0))
        cls = self.owner_of(x) * self.n + self.owner_of(y)
        return _dest_counts(cls, keep, self.n * self.n)

    def prepare(self, left, right, lengths, scored_cap, out_cap):
        """Fit the deduped pairs to ``scored_cap``, then (with the prune on)
        compact the survivors into ``out_cap``.  Returns (left, right,
        overflow, pruned)."""
        count = jnp.sum(left != PAD_ID)
        left = _fit(left, scored_cap, PAD_ID)
        right = _fit(right, scored_cap, PAD_ID)
        ovf = jnp.maximum(count - scored_cap, 0)
        n_pruned = jnp.zeros((), jnp.int32)
        if self.score_prune:
            keep = self.keep(left, right, lengths)
            n_keep = jnp.sum(keep).astype(jnp.int32)
            n_pruned = jnp.sum(left != PAD_ID).astype(jnp.int32) - n_keep
            order = jnp.argsort(jnp.logical_not(keep), stable=True)
            slots = jnp.arange(out_cap, dtype=jnp.int32)
            # out_cap may exceed scored_cap (skewed owners): pad, then mask
            left = jnp.where(slots < n_keep,
                             _fit(left[order], out_cap, PAD_ID), PAD_ID)
            right = jnp.where(slots < n_keep,
                              _fit(right[order], out_cap, PAD_ID), PAD_ID)
            ovf = ovf + jnp.maximum(n_keep - out_cap, 0)
        return left, right, ovf.astype(jnp.int32), n_pruned

    # -- scoring -------------------------------------------------------------

    def _score(self, table_l, table_r, left, right, rows_l=None, rows_r=None):
        """Score pairs ``left``/``right`` (global ids: trajectories, or
        windows in subtrajectory mode) against the code tables.  ``rows_*``
        index each pair's trajectory row in its table (default: the id's
        own trajectory).  Window ids decode to (traj, offset) here, at the
        point of scoring; the owner hops always move full rows."""
        li = jnp.where(left == PAD_ID, 0, left)
        ri = jnp.where(right == PAD_ID, 0, right)
        window = {}
        if self.subtraj is not None:
            window = dict(window=self.W, off_a=(li % self.nw) * self.stride,
                          off_b=(ri % self.nw) * self.stride)
        return score_indexed(
            table_l, _lengths_of(table_l), table_r, _lengths_of(table_r),
            self.traj(li) if rows_l is None else rows_l,
            self.traj(ri) if rows_r is None else rows_r,
            self.betas, impl=self.impl, **window,
        )

    def score(self, left, right, codes, *, n_chunks, hop_cap, rest_cap):
        """Score the pairs; in shuffle mode each first comes to rest on the
        owner of one of its ids (:func:`_orient`).  Returns (left, right,
        level_lcs, mss, overflow, rows carried by hop 1 and hop 2).

        With ``n_chunks > 1`` slot ``p`` of the pair buffer goes in chunk
        ``p % n_chunks``: the buffer is sorted by (left, right), so each
        chunk is an even sample of every owner's load.  The owner hops of
        chunk ``i + 1`` are issued before chunk ``i`` scores, so the
        collective and the LCS compute have no data dependence."""
        zero = jnp.zeros((), jnp.int32)
        if self.score_mode == "replicate":
            # on-device replication of the in-mesh encodings (never on host)
            codes_all = jax.lax.all_gather(codes, self.axis, axis=0,
                                           tiled=True)
            lvl, mss = self._score(codes_all, codes_all, left, right)
            return left, right, lvl, mss, zero, (zero, zero)
        gid0 = self.gid0()

        def hop(lr):
            return _hop_gather_codes(
                lr[0], lr[1], codes, owner_of=self.owner_of,
                slot_of=lambda g: self.traj(g) - gid0, n_shards=self.n,
                axis_name=self.axis, hop_cap=hop_cap, out_cap=rest_cap,
                span=self.span,
            )

        def rest(h):
            lvl, mss = self._score(h[2], codes, h[0], h[1], h[3], h[4])
            return h[0], h[1], lvl, mss, h[5], h[6]

        pair = _orient(left, right)
        if n_chunks == 1:
            flat = rest(hop(pair))
        else:
            flat = self._chunked(hop, rest, pair, n_chunks)
        x, y = flat[0], flat[1]
        return (jnp.minimum(x, y), jnp.maximum(x, y)) + flat[2:]

    @staticmethod
    def _chunked(hop, rest, pair, n_chunks):
        chunks = tuple(x.reshape(-1, n_chunks).T for x in pair)

        def step(pending, lr):
            nxt = hop(lr)
            return nxt, rest(pending)

        last, parts = jax.lax.scan(step, hop(tuple(c[0] for c in chunks)),
                                   tuple(c[1:] for c in chunks))
        return jax.tree_util.tree_map(
            lambda p, t: jnp.concatenate([p.reshape((-1,) + t.shape[1:]), t])
            if t.ndim else jnp.sum(p) + t, parts, rest(last),
        )


def _orient(left, right):
    """Each pair's two ids in the order the owner hops visit them, drawn
    from a bit of the pair hash: the pair rests on the owner of the second.
    With block ownership the larger id of a canonical pair lies on a later
    shard far more often (7 of 16 pairs on the last of four), so resting
    on the owner of ``right`` would load one shard almost twice the mean.
    LCS is symmetric, so the scores do not depend on the order."""
    flip = (_pair_hash(left, right) >> 16) & 1 == 1
    return jnp.where(flip, right, left), jnp.where(flip, left, right)


def _lengths_of(code_rows):
    # lengths reconstructed from the padding sentinel in level 0
    return jnp.sum(code_rows[:, 0, :] >= 0, axis=-1).astype(jnp.int32)


def _psum(x, axis_name):
    return jax.lax.psum(x.astype(jnp.int32), axis_name)


def _score_shapes(plan: DistributedPlan, score_mode: str, score_prune: bool):
    """(pre-hop pair buffer, chunks, hop bucket, resting buffer per chunk)
    of a plan's score stage."""
    out_cap = (plan.pruned_cap or plan.scored_cap) if score_prune \
        else plan.scored_cap
    n_chunks = plan.n_chunks if score_mode == "shuffle" else 1
    if n_chunks > 1:
        if out_cap % n_chunks:
            raise ValueError(
                f"pair buffer ({out_cap}) must divide into n_chunks="
                f"{n_chunks} slices; plan_capacities rounds it up"
            )
        sub = out_cap // n_chunks
        return (out_cap, n_chunks, plan.chunk_hop_cap or (sub // plan.n_shards
                                                          + 64),
                plan.chunk_rest_cap or sub)
    return (out_cap, 1,
            plan.owner_route_cap or (out_cap // plan.n_shards + 64), out_cap)


# the stats each staged program returns per shard, in order: (name, size)
def _stats_layout(n: int, stage: str, score_mode: str) -> tuple:
    if stage == "route":
        return (("loads", n), ("pair_total", 1), ("overflow", 1),
                ("carried", 1))
    if stage == "join":
        owner = (("owner_loads", n * n),) if score_mode == "shuffle" else ()
        return (("count", 1), ("kept", 1), ("overflow", 1),
                ("route_overflow", 1), ("route_loads", n),
                ("carried", 1)) + owner
    return (("overflow", 1), ("pruned", 1), ("resting", 1),
            ("carried_hop1", 1), ("carried_hop2", 1))


def _pack(layout, values: dict):
    return jnp.concatenate([
        jnp.asarray(values[k], jnp.int32).reshape(size) for k, size in layout
    ])


def unpack_stats(stats, layout) -> dict:
    """{name: int64 [n_shards, size]} of a staged program's stats."""
    stats = np.asarray(stats, np.int64)
    width = sum(size for _, size in layout)
    stats = stats.reshape(-1, width)
    out, at = {}, 0
    for k, size in layout:
        out[k] = stats[:, at:at + size]
        at += size
    return out


class MeshStages:
    """The one-shot job as staged shard_map programs, for the engine.

    Each stage is planned from counts the stage before made in the mesh,
    so no capacity rests on a bound and nothing but small count tables
    comes to the host:

    * ``loads()`` — per-(source, destination) key loads of shuffle 1, run
      once, before any plan exists;
    * ``route(cap)`` — encode, keys and shuffle 1; the received keys are
      sorted and counted into each shard's pre-dedup join size (the run
      ranks, as ``core/ssh.exact_pair_count`` counts them on one device);
    * ``join(pair_cap, route_cap)`` — the local join, shuffle 2 and the
      dedup; counts each shard's deduped (and, with the prune, surviving)
      pairs and their owner-hop loads;
    * ``score(...)`` — the prune's compaction, the owner hops (shuffle
      mode) and the LCS score;
    * ``similar_count()`` and ``similar(cap)`` — each shard's similar pairs
      counted, then moved to the front of a ``cap`` buffer, so only they
      come to the host.

    Buffers stay on the devices between stages.  Each program is compiled
    once per set of capacities (``jax.jit`` caches, keyed by the static
    capacities), so with power-of-two capacities kept sticky every job of
    one size runs the same programs.
    """

    def __init__(self, mesh, *, n_shards, local_n, betas, key_fn, axis_name,
                 score_mode, lcs_impl, score_prune, prune_tau, subtraj,
                 rho: float = 0.0):
        from jax.sharding import PartitionSpec as P

        self.mesh = mesh
        self.n = n_shards
        self.threshold = _above(rho)
        self.score_mode = score_mode
        self.score_prune = score_prune
        self.lcs_impl = lcs_impl
        self.one = _OneShot(
            n_shards=n_shards, local_n=local_n, betas=betas, key_fn=key_fn,
            axis_name=axis_name, score_mode=score_mode, impl=None,
            score_prune=score_prune, prune_tau=prune_tau, subtraj=subtraj,
        )
        self.row = P(axis_name, None)
        self.flat = P(axis_name)
        self.rep = P(None, None)
        self._cache: dict = {}

    def layout(self, stage: str) -> tuple:
        return _stats_layout(self.n, stage, self.score_mode)

    def _build(self, key, shard_fn, in_specs, n_out):
        fn = self._cache.get(key)
        if fn is None:
            fn = jax.jit(compat.shard_map(
                shard_fn, mesh=self.mesh, in_specs=in_specs,
                out_specs=(self.flat,) * n_out,
            ))
            self._cache[key] = fn
        return fn

    def loads(self):
        one = self.one

        def shard_fn(first, places, lengths, tables):
            _, _, _, valid, dest = one.keys(first, places, lengths, tables)
            return (_dest_counts(dest, valid, one.n),)

        return self._build(("loads",), shard_fn,
                           (self.row, self.row, self.flat, self.rep), 1)

    def route(self, cap: int):
        """fn(first, places, lengths, tables) -> (keys, ids, stats)."""
        one, layout = self.one, self.layout("route")

        def shard_fn(first, places, lengths, tables):
            _, fk, width, valid, dest = one.keys(first, places, lengths,
                                                 tables)
            (rk, rid), ovf, counts, carried = one.route_keys(
                fk, width, valid, dest, cap)
            rk, rid = jax.lax.sort((rk, rid), num_keys=1,
                                   is_stable=False)
            rank, kvalid = _runs(rk)
            stats = _pack(layout, dict(
                loads=counts, pair_total=jnp.sum(jnp.where(kvalid, rank, 0)),
                overflow=ovf, carried=_psum(carried, one.axis)))
            return rk, rid, stats

        return self._build(("route", cap), shard_fn,
                           (self.row, self.row, self.flat, self.rep), 3)

    def join(self, pair_cap: int, route_cap: int):
        """fn(keys, ids, lengths) -> (left, right, stats)."""
        one, layout = self.one, self.layout("join")

        def shard_fn(rk, rid, lengths):
            left, right, count, ovf, ovf_route, counts, carried = one.join(
                rk, rid, pair_cap, route_cap)
            keep = one.keep(left, right, lengths)
            vals = dict(count=count, kept=jnp.sum(keep), overflow=ovf,
                        route_overflow=ovf_route, route_loads=counts,
                        carried=_psum(carried, one.axis))
            if one.score_mode == "shuffle":
                vals["owner_loads"] = one.owner_loads(left, right, keep)
            return left, right, _pack(layout, vals)

        return self._build(("join", pair_cap, route_cap), shard_fn,
                           (self.flat, self.flat, self.flat), 3)

    def score(self, plan: DistributedPlan, tuning=None):
        """fn(left, right, places, lengths, tables) -> (left, right,
        level_lcs, mss, stats) of the pairs at rest.  ``tuning`` (a
        :class:`repro.perf.LCSTuning` or None) resolves here, eagerly, into
        the scoring impl's static arguments."""
        from repro.api.stages import lcs_impl_fn
        from repro.core.similarity import wavefront_dtype_from_env

        one, layout = copy.copy(self.one), self.layout("score")
        one.impl = lcs_impl_fn(self.lcs_impl, tuning)
        out_cap, n_chunks, hop_cap, rest_cap = _score_shapes(
            plan, self.score_mode, self.score_prune)
        scored_cap = plan.scored_cap

        def shard_fn(left, right, places, lengths, tables):
            codes = encode_codes(places, tables)
            left, right, ovf, n_pruned = one.prepare(
                left, right, lengths, scored_cap, out_cap)
            left, right, lvl, mss, ovf_hops, (c1, c2) = one.score(
                left, right, codes, n_chunks=n_chunks, hop_cap=hop_cap,
                rest_cap=rest_cap)
            mss = jnp.where(left == PAD_ID, -1.0, mss)
            stats = _pack(layout, dict(
                overflow=ovf + ovf_hops, pruned=n_pruned,
                resting=jnp.sum(left != PAD_ID),
                carried_hop1=_psum(c1, one.axis),
                carried_hop2=_psum(c2, one.axis)))
            return left, right, lvl, mss, stats

        return self._build(
            ("score", scored_cap, out_cap, n_chunks, hop_cap, rest_cap,
             tuning, wavefront_dtype_from_env()),
            shard_fn,
            (self.flat, self.flat, self.row, self.flat, self.rep), 5)


    def similar_count(self):
        """fn(mss) -> int32 [n_shards]: each shard's similar pairs."""
        t = self.threshold

        def shard_fn(mss):
            return (jnp.sum(mss > t).astype(jnp.int32).reshape(1),)

        return self._build(("similar_count",), shard_fn, (self.flat,), 1)

    def similar(self, cap: int):
        """fn(left, right, mss) -> (left, right) [n_shards * cap]: each
        shard's similar pairs at the front of its ``cap`` slots, PAD after
        them."""
        t = self.threshold

        def shard_fn(left, right, mss):
            # the similar slots' indices to the front (a one-operand sort),
            # then only ``cap`` rows gathered
            sim = mss > t
            m = sim.shape[0]
            idx = jax.lax.sort(jnp.where(
                sim, jnp.arange(m, dtype=jnp.int32), m), is_stable=False)
            idx = _fit(idx, cap, m)
            ok = idx < m
            idx = jnp.where(ok, idx, 0)
            return (jnp.where(ok, left[idx], PAD_ID),
                    jnp.where(ok, right[idx], PAD_ID))

        return self._build(("similar", cap), shard_fn, (self.flat,) * 3, 2)


def _above(rho: float) -> np.float32:
    """The float32 ``t`` with ``x > t`` exactly when ``x > rho``, for every
    float32 ``x``: the host compares float32 scores with the float64
    ``rho``, the device in float32."""
    t = np.float32(rho)
    return np.nextafter(t, np.float32(-np.inf)) if t > rho else t


def pow2_capacity(x, slack: float) -> int:
    """Power-of-two capacity covering ``x`` rows with ``slack``."""
    return _pow2(int(np.ceil(max(float(x), 1.0) * slack)))


def plan_join(route: dict, n_shards: int, slack: float) -> dict:
    """Capacities of shuffle 1, the local join and shuffle 2, from the
    route stage's counts (:func:`unpack_stats` of ``MeshStages.route``):
    shuffle 1 and the join from their exact loads, shuffle 2 from its
    source's pre-dedup pairs spread over the destinations (the join stage
    counts its exact loads, which a retry takes)."""
    pre = int(route["pair_total"].max())
    return dict(
        shingle_route_cap=pow2_capacity(route["loads"].max(), slack),
        local_pair_cap=pow2_capacity(pre, slack),
        pair_route_cap=pow2_capacity(pre / n_shards, slack),
    )


def plan_score(join: dict, n_shards: int, slack: float, *, score_mode: str,
               score_prune: bool, min_chunks: int = 1,
               hop_row_bytes: int = 0, memory_bytes: int = 0) -> dict:
    """Capacities of the score stage from the join stage's counts.

    The pair buffer covers each shard's deduped pairs and the pruned
    buffer its survivors.  In shuffle mode the owner hops are sized from
    the exact (owner(left), owner(right)) loads; their code rows take
    ``hop_row_bytes`` a hop slot, and the hops run in as many chunks
    (a power of two, at least ``min_chunks``) as keep one chunk's hop
    buffers within a quarter of ``memory_bytes``.  Chunk ``c`` holds slots
    ``c, c + n_chunks, ...`` of a buffer sorted by pair, so it carries an
    even share of every load."""
    count = int(join["count"].max())
    kept = int(join["kept"].max())
    plan = dict(scored_cap=pow2_capacity(count, slack), pruned_cap=0,
                owner_route_cap=0, n_chunks=1, chunk_hop_cap=0,
                chunk_rest_cap=0)
    if score_prune:
        plan["pruned_cap"] = pow2_capacity(kept, slack)
    if score_mode != "shuffle":
        return plan
    h = join["owner_loads"].reshape(-1, n_shards, n_shards)
    hop_need = max(int(h.sum(axis=2).max()), int(h.sum(axis=0).max()))
    rest_need = int(h.sum(axis=(0, 1)).max())
    pre_key = "pruned_cap" if score_prune else "scored_cap"
    hop_rows = n_shards * pow2_capacity(hop_need, slack)
    n_chunks = max(min_chunks, 1)
    if memory_bytes and hop_row_bytes:
        n_chunks = max(n_chunks, _pow2(
            -(-hop_rows * hop_row_bytes // (memory_bytes // 4)), 0))
    n_chunks = min(n_chunks, plan[pre_key])
    if n_chunks == 1:
        plan[pre_key] = max(plan[pre_key], pow2_capacity(rest_need, slack))
        plan["owner_route_cap"] = pow2_capacity(hop_need, slack)
        return plan
    plan.update(
        n_chunks=n_chunks,
        chunk_hop_cap=pow2_capacity(-(-hop_need // n_chunks), slack),
        chunk_rest_cap=pow2_capacity(-(-rest_need // n_chunks), slack),
    )
    return plan


def sticky_plan(plan: DistributedPlan, prev: DistributedPlan | None
                ) -> DistributedPlan:
    """Monotone max over every capacity, so jobs of one size resolve to
    one plan and reuse its compiled programs."""
    if prev is None:
        return plan
    return dataclasses.replace(plan, **{
        f.name: max(getattr(plan, f.name), getattr(prev, f.name))
        for f in dataclasses.fields(plan)
        if f.name not in ("n_shards", "local_n")
    })


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
        (rk, rr), o1, _, _ = _route(
            (keys, rows), dest, valid,
            n_shards=n_shards, capacity=plan.key_route_cap,
            pads=(PAD_KEY, PAD_ID), axis_name=axis_name,
        )
        lo, hi, examined, o2 = probe_pairs(
            slab_k, slab_r, rk, rr, nn_cap=plan.nn_cap, no_cap=plan.no_cap
        )
        pvalid = lo != PAD_ID
        pdest = _pair_hash(lo, hi) % n_shards
        (rlo, rhi), o3, _, _ = _route(
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

    Unlike the one-shot job (:class:`MeshStages`) there is no join here:
    candidate generation is incremental (the host bucket index emits only
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
    scoring exactly as in the one-shot job (:meth:`_OneShot.score`): chunk
    i+1's hops are issued before chunk i scores, per-pair results stay
    bit-identical, and ``n_chunks`` is static in the plan so the
    zero-steady-state-recompile contract (``trace_counter``) is untouched.

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

    def _score_hopped(h, codes):
        """Score one hop's resting pairs -> (level_lcs, mss): left rows in
        the received code rows, right rows in this shard's own."""
        return score_indexed(
            h[2], _lengths_of(h[2]), codes, _lengths_of(codes), h[3], h[4],
            betas, impl=impl,
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
                h = hop(left, right)
                out_l, out_r, ovf = h[0], h[1], h[5]
                level_lcs, mss = _score_hopped(h, codes)
            else:
                # software pipeline: issue chunk i+1's owner hops BEFORE
                # scoring chunk i's resting pairs (no data dependence
                # between them, so the scheduler may overlap)
                parts = []
                pending = hop(left[:_sub], right[:_sub])
                for i in range(1, n_chunks):
                    sl = slice(i * _sub, (i + 1) * _sub)
                    nxt = hop(left[sl], right[sl])
                    parts.append(pending[:2] + _score_hopped(pending, codes)
                                 + (pending[5],))
                    pending = nxt
                parts.append(pending[:2] + _score_hopped(pending, codes)
                             + (pending[5],))
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


def gather_similar_pairs(out: dict, rho: float) -> set[tuple[int, int]]:
    """Host-side collection of the globally-deduped similar pair set."""
    left = np.asarray(out["left"]).reshape(-1)
    right = np.asarray(out["right"]).reshape(-1)
    mss = np.asarray(out["mss"]).reshape(-1)
    keep = (left != PAD_ID) & (mss > rho)
    return set(zip(left[keep].tolist(), right[keep].tolist()))


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
