"""Phase (ii) part 2: the SSH candidate join (paper Algorithm 2, Fig. 5).

Spark pipeline:  D3 --explode--> D4 --self-join on shingle--> D5 (pairs).
TPU pipeline:    sort-merge join — one ``lax.sort`` by shingle key, then
*exact compact* pair enumeration over equal-key runs:

  each sorted row r with in-run rank k contributes exactly k pairs (with the
  k earlier members of its run).  An exclusive cumsum of ranks assigns every
  pair a unique output slot, row-major.  Rows are expanded to their slots
  by one merge sort of a marker per row with an entry per slot; running
  scans carry each row's id, index and end onto its slots, and a second
  sort brings the slots to the front in order.  Three sorts, running
  scans and one gather (the partner's id), zero data-dependent shapes, zero
  wasted slots — the static-shape analogue of Spark's shuffle join.

Pairs appearing under multiple shingles are deduplicated with a second sort
on the canonical (lo, hi) key, honouring the paper's "each pair is scored
exactly once no matter how many shingles it shares" (section IV.3).

Capacity discipline: the pair buffer is a static ``pair_capacity``; if the
true pair count exceeds it we report ``overflow`` and the host-level driver
(pipeline.py) retries with doubled capacity — Spark's dynamic memory traded
for deterministic compilable shapes (DESIGN.md section 2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.types import CandidatePairs, PAD_ID, PAD_KEY


_SCAN_ROW = 1024


def running(op, x: jnp.ndarray, *, reverse: bool = False) -> jnp.ndarray:
    """Inclusive running ``op`` ("sum", "min" or "max") of a 1-D array.

    A two-level scan: within rows of 1,024 entries, then over the rows'
    totals.  The results equal ``jnp.cumsum`` / ``lax.cummin`` /
    ``lax.cummax`` exactly (int32 sums wrap alike); the TPU compiler builds
    one flat scan of 2^26 entries in seconds to minutes (a reverse one, or
    one whose length is not a power of two, in over half a minute), and
    this one in about a second."""
    ident = {"sum": 0, "min": jnp.iinfo(x.dtype).max,
             "max": jnp.iinfo(x.dtype).min}[op]
    scan = {"sum": jax.lax.cumsum, "min": jax.lax.cummin,
            "max": jax.lax.cummax}[op]
    comb = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}[op]
    n = x.shape[0]
    if reverse:
        x = jnp.flip(x)
    rows = jnp.concatenate(
        [x, jnp.full((-n % _SCAN_ROW,), ident, x.dtype)]
    ).reshape(-1, _SCAN_ROW)
    inner = scan(rows, axis=1)
    tot = scan(inner[:, -1])
    before = jnp.concatenate([jnp.full((1,), ident, x.dtype), tot[:-1]])
    out = comb(inner, before[:, None]).reshape(-1)[:n]
    return jnp.flip(out) if reverse else out


def _runs(sorted_keys: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row (rank within equal-key run, validity) for ascending keys."""
    r = sorted_keys.shape[0]
    idx = jnp.arange(r, dtype=jnp.int32)
    start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_keys[1:] != sorted_keys[:-1]]
    )
    run_start = running("max", jnp.where(start, idx, -1))
    rank = idx - run_start
    return rank, sorted_keys != PAD_KEY


def pairs_from_rows(
    keys: jnp.ndarray, ids: jnp.ndarray, *, pair_capacity: int,
    presorted: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Exact-compact pair enumeration over flat (key, id) rows.

    Returns (lo [P_cap], hi [P_cap], overflow) — canonical but NOT deduped
    (the same pair may appear under several shared shingles).  Slot ``p``
    holds the ``t``-th pair of the row whose slots ``[excl, excl + rank)``
    contain it: that row with the ``t``-th member of its run, ``t = p -
    excl``.  Past ``pair_capacity`` the row-major tail is cut and counted
    in ``overflow``.  Shared by the single-device join and the distributed
    post-shuffle local join, whose rows come ``presorted`` by key.
    """
    if not presorted:
        with jax.named_scope("ssh/sort"):
            keys, ids = jax.lax.sort((keys, ids), num_keys=1)
    with jax.named_scope("ssh/runs"):
        rank, valid = _runs(keys)
        contrib = jnp.where(valid, rank, 0)
        excl = running("sum", contrib) - contrib  # exclusive prefix
        total = excl[-1] + contrib[-1]

    with jax.named_scope("ssh/pairs_from_rows"):
        r, cap = keys.shape[0], pair_capacity
        # Merge one marker per row, keyed 2 * excl, with one entry per slot
        # p, keyed 2p + 1 (uint32: neither key wraps).  The markers before
        # slot p are rows 0..row(p), as excl rises with the row, and the
        # first marker after it is row(p) + 1, at excl[row(p)] + rank[row(p)]
        # (or total).  So running scans give each slot its row's id (a
        # running sum of id steps), its row (a running count of markers)
        # and that row's end; its partner is row + p - end.
        step = ids - jnp.concatenate([jnp.zeros((1,), ids.dtype), ids[:-1]])
        key, step = jax.lax.sort(
            (
                jnp.concatenate([
                    2 * excl.astype(jnp.uint32),
                    2 * jnp.arange(cap, dtype=jnp.uint32) + 1,
                ]),
                jnp.concatenate([step, jnp.zeros((cap,), step.dtype)]),
            ),
            num_keys=1,
            is_stable=False,  # ties are markers at one key: any order
        )
        is_slot = (key & 1) == 1
        half = (key >> 1).astype(jnp.int32)
        a = running("sum", step)  # wraps in int32, still telescopes exactly
        row = running("sum", (~is_slot).astype(jnp.int32)) - 1
        end = running("min", jnp.where(is_slot, total, half), reverse=True)
        partner = row + half - end
        # the slot entries, to the front in slot order
        _, a, partner = jax.lax.sort(
            (jnp.where(is_slot, key, jnp.uint32(2**32 - 1)), a, partner),
            num_keys=1,
            is_stable=False,
        )
        ok = jnp.arange(cap, dtype=jnp.int32) < total
        a = jnp.where(ok, a[:cap], PAD_ID)
        partner = jnp.clip(partner[:cap], 0, r - 1)
        b = jnp.where(ok, ids[partner], PAD_ID)
        overflow = jnp.maximum(total - cap, 0)
        return jnp.minimum(a, b), jnp.maximum(a, b), overflow


@functools.partial(jax.jit, static_argnames=("pair_capacity",))
def ssh_candidates(
    shingle_keys: jnp.ndarray,
    *,
    pair_capacity: int,
    id_offset: jnp.ndarray | int = 0,
) -> CandidatePairs:
    """Candidate pairs from per-trajectory shingle keys.

    shingle_keys: int32 [N, S], PAD_KEY-padded, distinct per row.
    id_offset:    added to local row indices to form global trajectory ids
                  (used by the distributed pipeline's shard-local phase).
    returns CandidatePairs with canonical (left < right) deduplicated pairs.
    """
    n, s = shingle_keys.shape
    keys = shingle_keys.reshape(-1)
    ids = jnp.repeat(
        jnp.arange(n, dtype=jnp.int32) + jnp.asarray(id_offset, jnp.int32), s
    )
    lo, hi, overflow = pairs_from_rows(keys, ids, pair_capacity=pair_capacity)
    with jax.named_scope("ssh/dedup"):
        return dedup_pairs(lo, hi, overflow=overflow)


@jax.jit
def dedup_pairs(
    lo: jnp.ndarray, hi: jnp.ndarray, overflow: jnp.ndarray | int = 0
) -> CandidatePairs:
    """Canonicalize + deduplicate pair lists (PAD_ID slots sort to the end)."""
    # ties are equal in both operands, so stability cannot show; unstable
    # sorts compile several times faster on the TPU
    lo, hi = jax.lax.sort((lo, hi), num_keys=2, is_stable=False)
    dup = jnp.concatenate(
        [jnp.zeros((1,), bool), (lo[1:] == lo[:-1]) & (hi[1:] == hi[:-1])]
    )
    bad = dup | (lo == hi) | (lo == PAD_ID)
    lo = jnp.where(bad, PAD_ID, lo)
    hi = jnp.where(bad, PAD_ID, hi)
    # compact valid slots to front
    lo, hi = jax.lax.sort((lo, hi), num_keys=2, is_stable=False)
    count = jnp.sum(lo != PAD_ID).astype(jnp.int32)
    return CandidatePairs(
        left=lo, right=hi, count=count, overflow=jnp.asarray(overflow, jnp.int32)
    )


def exact_pair_count(shingle_keys: jnp.ndarray) -> int:
    """Host helper: the true (pre-dedup) join size, for capacity planning."""
    keys = jnp.sort(shingle_keys.reshape(-1), stable=False)
    rank, valid = _runs(keys)
    return int(jnp.sum(jnp.where(valid, rank, 0)))
