"""Online top-k "find another me" serving over the resident world.

    from repro.api import QueryEngine, StreamingEngine

    stream = StreamingEngine(forest, config, plan)
    for batch in feed:
        stream.update(batch)
    serve = QueryEngine(stream, k=5)
    res = serve.query(query_batch)       # QueryResult
    res.match_ids[q], res.mss[q]         # top-k world rows per query

PRs 1-5 built ingestion: a device-resident world (single-device code
table or round-robin sharded places slabs) plus an incremental join index
(host ``BucketIndex`` or the key-sharded device slabs).  This module adds
the product surface the paper's title promises — pose a trajectory
against that resident world and get the top-k most-similar users back —
as the first subsystem where LATENCY, not throughput, is the scoreboard:

* queries are NOT ingested: the index is probed through the shared
  read-only ``probe(keys)`` protocol (``BucketIndex.probe`` on the host,
  :func:`~repro.core.device_index.probe_rows` in-mesh) and the world
  state is untouched, so queries commute with ``StreamingEngine.update``
  calls and concurrent queries commute with each other;
* concurrent queries micro-batch through ONE shared compiled program
  with pow2-sticky capacities (:class:`QueryPlan`, planned by
  ``CapacityPlanner.plan_query`` from exact candidate cardinalities) —
  steady-state query traffic never recompiles, proven by the
  ``serve_traces`` / ``probe_traces`` trace-counter hooks;
* candidates score off the resident world codes through the same
  ``lcs_impl`` dispatch as ingestion (fused Pallas kernel included: the
  kernel's two-table form takes the query codes as table A and the
  resident world as table B), then reduce IN-MESH through a segmented
  per-query top-k — sort by (query, -mss, row), rank-in-run scatter to
  ``[Q, k]`` per shard, all_gather, k-way merge — so only ``[Q, k]``
  ids+scores ever transit the driver;
* results are deterministic: matches require ``mss > rho`` (per-query
  ``rho``), are ordered by (mss descending, row id ascending), and empty
  slots hold ``(PAD_ID, -1.0)``;
* with ``serve_prune=True`` a REPOSE-style per-shard pass walks world
  shards in descending resident-length order
  (:class:`~repro.core.device_index.ShardSummaries`, maintained on
  insert) and skips every (query, shard) cell whose free MSS bound
  ``betas_sum * min(len_q, max_len[shard])`` cannot beat the query's
  ``rho`` — or, once k matches exist, its running kth-best.  Skipping
  never changes results: a skipped shard's candidates are strictly
  below the current kth-best, so they cannot enter the top-k even
  through the row-id tie-break.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.errors import CapacityExceeded
from repro.api.sharded import (
    _positive_hash, _positive_hash_np, _pow2, _route,
)
from repro.core import compat
from repro.core.encoding import encode_codes
from repro.core.similarity import (
    PRUNE_EPS, mss_upper_bound, score_indexed, wavefront_dtype_from_env,
)
from repro.core.types import PAD_ID, PAD_KEY, PAD_PLACE

# Empty top-k slots: (NO_MATCH, NO_MATCH_MSS) — PAD_ID can never be a row
# id of a match (world ids are dense from 0) and -1.0 is below any real
# MSS (level LCS counts are non-negative).
NO_MATCH = PAD_ID
NO_MATCH_MSS = np.float32(-1.0)


# ---------------------------------------------------------------------------
# capacity planning (pow2-sticky, the PR 4/5 discipline)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class QueryPlan:
    """Static shapes of one compiled query-serving program pair.

    Shapes quantize to powers of two and the engine keeps them sticky
    (monotone max while the world shape holds), so consecutive query
    micro-batches of similar size reuse both compiled programs verbatim
    — the serving analogue of the streaming zero-recompile contract.
    """

    n_shards: int
    cap_local: int      # resident world rows per shard (world cap if 1)
    L_pad: int          # scoring width: max(world L, longest query)
    q_cap: int          # padded queries per micro-batch
    k_cap: int          # padded top-k slots per query
    cand_cap: int       # candidate (row, query) slots per shard
    key_in_cap: int = 0     # query key occurrences per source shard
    key_route_cap: int = 0  # rows per (src, dst) bucket in the key route


def plan_query_capacities(
    num_queries: int,
    k_max: int,
    *,
    n_shards: int,
    cap_local: int,
    world_L: int,
    q_len_max: int,
    cand_total: int | None = None,
    keys_flat: np.ndarray | None = None,
    stats=None,
    floor_pow2: int = 2,
) -> QueryPlan:
    """Exact capacity plan for ONE query micro-batch.

    Two probe modes, matching the two resident index forms:

    * host (``cand_total``): the BucketIndex probe already ran, so the
      candidate count is exact — buffers hold contiguous per-shard
      chunks of it;
    * device (``keys_flat`` + ``stats``): the
      :class:`~repro.core.device_index.StreamJoinStats` count mirror
      yields the exact per-owner resident-match counts of the query
      keys under the device's own hash (the ``plan_stream_join``
      discipline, new-vs-old only — queries never pair with each
      other), sizing the key route and the probe output without the
      pair list ever touching the driver.
    """
    q_cap = _pow2(num_queries, floor_pow2)
    k_cap = _pow2(max(k_max, 1), floor_pow2)
    L_pad = max(int(world_L), int(q_len_max), 1)
    if cand_total is not None:
        chunk = -(-int(cand_total) // n_shards) if cand_total else 0
        return QueryPlan(
            n_shards=n_shards, cap_local=cap_local, L_pad=L_pad,
            q_cap=q_cap, k_cap=k_cap,
            cand_cap=_pow2(chunk, floor_pow2),
        )
    k = int(keys_flat.shape[0])
    owners = _positive_hash_np(keys_flat) % n_shards if k else \
        np.zeros((0,), np.int64)
    nvo, _, _ = stats.plan_update(keys_flat, owners)
    chunk = -(-k // n_shards) if k else 0
    if k:
        src = np.arange(k, dtype=np.int64) // max(chunk, 1)
        load = np.zeros((n_shards, n_shards), np.int64)
        np.add.at(load, (src, owners), 1)
        route_need = int(load.max())
    else:
        route_need = 1
    return QueryPlan(
        n_shards=n_shards, cap_local=cap_local, L_pad=L_pad,
        q_cap=q_cap, k_cap=k_cap,
        cand_cap=_pow2(int(nvo.max()), floor_pow2),
        key_in_cap=_pow2(chunk, floor_pow2),
        key_route_cap=_pow2(route_need, floor_pow2),
    )


def sticky_query_plan(
    plan: QueryPlan, prev: QueryPlan | None
) -> QueryPlan:
    """Monotone max over every capacity while the world shape holds.

    A world reshape (growth reallocated the resident buffers, so
    ``cap_local`` moved) invalidates the compiled programs anyway — the
    sticky state resets rather than pinning stale capacities forever.
    """
    if prev is None or prev.n_shards != plan.n_shards \
            or prev.cap_local != plan.cap_local:
        return plan
    return QueryPlan(
        n_shards=plan.n_shards, cap_local=plan.cap_local,
        L_pad=max(plan.L_pad, prev.L_pad),
        q_cap=max(plan.q_cap, prev.q_cap),
        k_cap=max(plan.k_cap, prev.k_cap),
        cand_cap=max(plan.cand_cap, prev.cand_cap),
        key_in_cap=max(plan.key_in_cap, prev.key_in_cap),
        key_route_cap=max(plan.key_route_cap, prev.key_route_cap),
    )


# ---------------------------------------------------------------------------
# in-mesh segmented top-k (the [Q, k] reduction)
# ---------------------------------------------------------------------------
def _local_topk(qid, row, mss, *, q_cap, k_cap, rho_vec):
    """Segmented per-query top-k over one device's scored candidates.

    Sort by (query, -mss, row): each query's candidates become a run,
    best first, ties broken toward the smaller row id.  Adjacent
    duplicate (query, row) slots — the same candidate probed through
    several shared keys, scored to the identical mss — are dropped, the
    survivors ranked within their run, and the first ``k_cap`` scattered
    into a ``[q_cap, k_cap]`` table.  Scores are carried NEGATED
    (ascending sort order everywhere, ``+inf`` = empty slot).
    """
    qsafe = jnp.clip(qid, 0, q_cap - 1)
    valid = (row != PAD_ID) & (mss > rho_vec[qsafe])
    qk = jnp.where(valid, qid, q_cap).astype(jnp.int32)
    neg = jnp.where(valid, -mss, jnp.inf).astype(jnp.float32)
    rk = jnp.where(valid, row, PAD_ID)
    qs, ns, rs = jax.lax.sort((qk, neg, rk), num_keys=3)
    dup = jnp.concatenate([
        jnp.zeros((1,), bool),
        (qs[1:] == qs[:-1]) & (rs[1:] == rs[:-1]) & (qs[1:] < q_cap),
    ])
    nd = (~dup) & (qs < q_cap)
    idx = jnp.arange(qs.shape[0], dtype=jnp.int32)
    start = jnp.concatenate([jnp.ones((1,), bool), qs[1:] != qs[:-1]])
    run_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(start, idx, 0)
    )
    c = jnp.cumsum(nd.astype(jnp.int32))
    base = jnp.where(run_start > 0, c[jnp.maximum(run_start - 1, 0)], 0)
    rank = c - base - 1  # rank among this run's distinct survivors
    keep = nd & (rank < k_cap)
    flat = jnp.where(keep, qs * k_cap + rank, q_cap * k_cap)
    top_row = jnp.full((q_cap * k_cap,), PAD_ID, jnp.int32) \
        .at[flat].set(rs, mode="drop").reshape(q_cap, k_cap)
    top_neg = jnp.full((q_cap * k_cap,), jnp.inf, jnp.float32) \
        .at[flat].set(ns, mode="drop").reshape(q_cap, k_cap)
    return top_row, top_neg


def _merge_topk(rows2d, negs2d, *, k_cap):
    """K-way merge of per-query top-k columns from several sources.

    Sort each query's row by (negated mss, row id), drop adjacent
    duplicate rows (the same candidate surfacing from two shards carries
    a bit-identical score, so copies sort adjacent), re-sort the gaps to
    the end, keep the best ``k_cap``.
    """
    valid = rows2d != PAD_ID
    neg = jnp.where(valid, negs2d, jnp.inf)
    rows = jnp.where(valid, rows2d, PAD_ID)
    ns, rs = jax.lax.sort((neg, rows), num_keys=2, dimension=1)
    dup = jnp.concatenate([
        jnp.zeros_like(rs[:, :1], dtype=bool),
        (rs[:, 1:] == rs[:, :-1]) & (rs[:, 1:] != PAD_ID),
    ], axis=1)
    ns = jnp.where(dup, jnp.inf, ns)
    rs = jnp.where(dup, PAD_ID, rs)
    ns, rs = jax.lax.sort((ns, rs), num_keys=2, dimension=1)
    return rs[:, :k_cap], ns[:, :k_cap]


def _serve_score_block(
    codes_all, w_len, cand_row, cand_qid, q_places, rho_vec, active,
    tables, *, plan, betas, impl, phys_of,
):
    """Shared per-device serving stage: encode queries, gate candidates
    by the per-round (query, world-shard) prune mask, score them off the
    resident table, and reduce to this device's [q_cap, k_cap] top-k."""
    if codes_all.shape[-1] < plan.L_pad:
        codes_all = jnp.pad(
            codes_all,
            ((0, 0), (0, 0), (0, plan.L_pad - codes_all.shape[-1])),
            constant_values=-1,  # stays a non-matching sentinel column
        )
    q_codes = encode_codes(q_places, tables)  # [q_cap, H, L_pad]
    q_len = jnp.sum(q_codes[:, 0, :] >= 0, axis=-1).astype(jnp.int32)
    valid = cand_row != PAD_ID
    qsafe = jnp.clip(cand_qid, 0, plan.q_cap - 1)
    shard = jnp.where(valid, cand_row % plan.n_shards, 0)
    row = jnp.where(valid & active[qsafe, shard], cand_row, PAD_ID)
    alive = row != PAD_ID
    ri = phys_of(jnp.where(alive, row, 0))
    _, mss = score_indexed(
        q_codes, q_len, codes_all, w_len, qsafe, ri, betas, impl=impl,
    )
    mss = jnp.where(alive, mss, jnp.float32(NO_MATCH_MSS))
    return _local_topk(
        cand_qid, row, mss, q_cap=plan.q_cap, k_cap=plan.k_cap,
        rho_vec=rho_vec,
    )


# ---------------------------------------------------------------------------
# compiled program builders
# ---------------------------------------------------------------------------
def make_query_score_pipeline(
    mesh,
    plan: QueryPlan,
    *,
    betas,
    axis_name: str = "ex",
    lcs_impl: str = "wavefront",
    trace_counter: list | None = None,
):
    """Build the shared compiled query score + in-mesh top-k program.

    ``mesh=None`` builds the single-device form (the world is the
    resident ``[cap, H, L]`` code table); with a mesh, each shard encodes
    its own round-robin places slab in-mesh, all_gathers the encodings
    (serving is the ~10M-row replicate regime: latency beats table
    locality), scores its resting candidates, and reduces its local
    per-query top-k; an all_gather of the tiny ``[q_cap, k_cap]`` tables
    plus a k-way merge then leaves only [Q, k] results to read.

    Mesh call signature::

      fn(places [S * cap_local, Lw], cand_row [S * cand_cap] (global
         world ids), cand_qid [S * cand_cap], q_places [q_cap, L_pad],
         rho_vec [q_cap] f32, active [q_cap, S] bool,
         prev_row/prev_neg [q_cap, k_cap] (the carried top-k state),
         tables)
        -> dict: top_row / top_neg [q_cap, k_cap] (merged with prev)

    Single-device signature replaces ``places`` with the resident
    ``codes [cap, H, Lw]`` + ``w_len [cap]`` (no encode, no collectives).
    ``trace_counter`` increments at TRACE time only — the serving
    zero-steady-state-recompile proof hook.
    """
    from jax.sharding import PartitionSpec as P

    from repro.api.stages import lcs_impl_fn

    # resolved HERE, at the eager call boundary (wavefront_dtype_from_env
    # must never run inside a traced body)
    impl = lcs_impl_fn(lcs_impl)

    if mesh is None:

        @jax.jit
        def run_single(codes, w_len, cand_row, cand_qid, q_places,
                       rho_vec, active, prev_row, prev_neg, tables):
            if trace_counter is not None:
                trace_counter[0] += 1  # per compile, not per query batch
            t_row, t_neg = _serve_score_block(
                codes, w_len, cand_row, cand_qid, q_places, rho_vec,
                active, tables, plan=plan, betas=betas,
                impl=impl, phys_of=lambda g: g,
            )
            m_row, m_neg = _merge_topk(
                jnp.concatenate([t_row, prev_row], axis=1),
                jnp.concatenate([t_neg, prev_neg], axis=1),
                k_cap=plan.k_cap,
            )
            return {"top_row": m_row, "top_neg": m_neg}

        return run_single

    n_shards = plan.n_shards

    def shard_fn(places, cand_row, cand_qid, q_places, rho_vec, active,
                 prev_row, prev_neg, tables):
        if trace_counter is not None:
            trace_counter[0] += 1  # per compile, not per query batch
        codes = encode_codes(places, tables)  # own slab, in-mesh
        codes_all = jax.lax.all_gather(codes, axis_name, axis=0,
                                       tiled=True)
        w_len = jnp.sum(codes_all[:, 0, :] >= 0, axis=-1) \
            .astype(jnp.int32)

        def phys_of(g):  # round-robin world layout
            return (g % n_shards) * plan.cap_local + g // n_shards

        t_row, t_neg = _serve_score_block(
            codes_all, w_len, cand_row, cand_qid, q_places, rho_vec,
            active, tables, plan=plan, betas=betas,
            impl=impl, phys_of=phys_of,
        )
        g_row = jax.lax.all_gather(t_row, axis_name)  # [S, q_cap, k_cap]
        g_neg = jax.lax.all_gather(t_neg, axis_name)
        rows2d = jnp.concatenate(
            [jnp.moveaxis(g_row, 0, 1).reshape(plan.q_cap, -1), prev_row],
            axis=1,
        )
        negs2d = jnp.concatenate(
            [jnp.moveaxis(g_neg, 0, 1).reshape(plan.q_cap, -1), prev_neg],
            axis=1,
        )
        return _merge_topk(rows2d, negs2d, k_cap=plan.k_cap)

    spec_in = (P(axis_name, None), P(axis_name), P(axis_name),
               P(None, None), P(None), P(None, None),
               P(None, None), P(None, None), P(None, None))
    spec_out = (P(axis_name, None), P(axis_name, None))
    fn = compat.shard_map(
        shard_fn, mesh=mesh, in_specs=spec_in, out_specs=spec_out
    )

    @jax.jit
    def run(places, cand_row, cand_qid, q_places, rho_vec, active,
            prev_row, prev_neg, tables):
        m_row, m_neg = fn(places, cand_row, cand_qid, q_places, rho_vec,
                          active, prev_row, prev_neg, tables)
        # every shard computed the identical merge; read one replica
        return {
            "top_row": m_row.reshape(n_shards, plan.q_cap, plan.k_cap)[0],
            "top_neg": m_neg.reshape(n_shards, plan.q_cap, plan.k_cap)[0],
        }

    return run


def make_query_probe_pipeline(
    mesh,
    plan: QueryPlan,
    *,
    axis_name: str = "ex",
    trace_counter: list | None = None,
):
    """Build the in-mesh READ-ONLY candidate probe program.

    The serving twin of :func:`make_streaming_join_pipeline` stages (1)
    and (2) with everything mutable removed: query key occurrences route
    to their owner shard, :func:`~repro.core.device_index.probe_rows`
    range-probes the resident slab — no new-vs-new stage, no
    ``merge_insert``, the slabs are pure inputs — and the (world row,
    query) candidates come to rest on the key-owner shard, deduped
    locally (copies via several same-owner shared keys sort adjacent;
    cross-owner copies collapse later in the top-k merge, where their
    bit-identical scores make them adjacent again).

    ``fn(slab_keys [S * slab_cap], slab_rows, keys [S * key_in_cap],
    qids) -> dict: cand_row / cand_qid [S, cand_cap], count [S],
    examined [S], overflow [S]``.
    """
    from jax.sharding import PartitionSpec as P

    from repro.core.device_index import probe_rows

    n_shards = plan.n_shards

    def shard_fn(slab_k, slab_r, keys, qids):
        if trace_counter is not None:
            trace_counter[0] += 1  # per compile, not per query batch
        valid = keys != PAD_KEY
        dest = _positive_hash(keys) % n_shards
        (rk, rq), o1, _, _ = _route(
            (keys, qids), dest, valid,
            n_shards=n_shards, capacity=plan.key_route_cap,
            pads=(PAD_KEY, PAD_ID), axis_name=axis_name,
        )
        row, qid, examined, o2 = probe_rows(
            slab_k, slab_r, rk, rq, cap=plan.cand_cap
        )
        row_s, qid_s = jax.lax.sort((row, qid), num_keys=2)
        dup = jnp.concatenate([
            jnp.zeros((1,), bool),
            (row_s[1:] == row_s[:-1]) & (qid_s[1:] == qid_s[:-1])
            & (row_s[1:] != PAD_ID),
        ])
        row_d = jnp.where(dup, PAD_ID, row_s)
        qid_d = jnp.where(dup, PAD_ID, qid_s)
        count = jnp.sum(row_d != PAD_ID).astype(jnp.int32)
        return (row_d, qid_d, count.reshape(1), examined.reshape(1),
                (o1 + o2).astype(jnp.int32).reshape(1))

    spec_in = (P(axis_name), P(axis_name), P(axis_name), P(axis_name))
    spec_out = (P(axis_name), P(axis_name), P(axis_name), P(axis_name),
                P(axis_name))
    fn = compat.shard_map(
        shard_fn, mesh=mesh, in_specs=spec_in, out_specs=spec_out
    )

    @jax.jit
    def run(slab_keys, slab_rows, keys, qids):
        row, qid, count, examined, overflow = fn(
            slab_keys, slab_rows, keys, qids
        )
        return {
            "cand_row": row.reshape(n_shards, -1),
            "cand_qid": qid.reshape(n_shards, -1),
            "count": count.reshape(n_shards),
            "examined": examined.reshape(n_shards),
            "overflow": overflow.reshape(n_shards),
        }

    return run


# ---------------------------------------------------------------------------
# the read-only probe protocol adapters (no branching in the engine)
# ---------------------------------------------------------------------------
class _HostProber:
    """Candidate probe against the driver-resident ``BucketIndex``."""

    def __init__(self, engine: "QueryEngine"):
        self.engine = engine

    def prepare(self, keys_np, k_flat, q_flat):
        qidx, rows, examined = self.engine.stream._index.probe(keys_np)
        return {
            "qidx": qidx, "rows": rows, "examined": int(examined),
            "plan_kwargs": {"cand_total": int(qidx.shape[0])},
        }

    def finish(self, pre, qplan: QueryPlan):
        e = self.engine
        S, cap = qplan.n_shards, qplan.cand_cap
        qidx, rows = pre["qidx"], pre["rows"]
        # the BucketIndex speaks global ids; the score program gathers
        # world slots by LOCAL index (slot = id - base), so translate
        # before shipping — query() adds the base back to the results
        rows = rows - np.int32(e.stream._base)
        total = int(qidx.shape[0])
        buf_r = np.full((S, cap), PAD_ID, np.int32)
        buf_q = np.full((S, cap), PAD_ID, np.int32)
        chunk = -(-total // S) if total else 0
        for s in range(S):
            seg = slice(s * chunk, (s + 1) * chunk)
            buf_r[s, : rows[seg].shape[0]] = rows[seg]
            buf_q[s, : qidx[seg].shape[0]] = qidx[seg]
        e._xfer_bytes += buf_r.nbytes + buf_q.nbytes
        stats = {"candidates": total, "probe_examined": pre["examined"]}
        return (jnp.asarray(buf_r.reshape(-1)),
                jnp.asarray(buf_q.reshape(-1)), qplan, stats)


class _SlabProber:
    """Candidate probe against the device-resident key-sharded slabs.

    Only the query key occurrences transit the driver; the candidate
    list is born in-mesh and stays there, resting in the exact buffers
    the score program consumes.
    """

    def __init__(self, engine: "QueryEngine"):
        self.engine = engine

    def prepare(self, keys_np, k_flat, q_flat):
        return {
            "k_flat": k_flat, "q_flat": q_flat,
            "plan_kwargs": {
                "keys_flat": k_flat,
                "stats": self.engine.stream._join_stats,
            },
        }

    def finish(self, pre, qplan: QueryPlan):
        e = self.engine
        stream = e.stream
        k_flat, q_flat = pre["k_flat"], pre["q_flat"]
        S = qplan.n_shards
        out = None
        for _ in range(e.planner.max_retries + 1):
            chunk = -(-k_flat.shape[0] // S)
            in_k = np.full((S, qplan.key_in_cap), PAD_KEY, np.int32)
            in_q = np.full((S, qplan.key_in_cap), PAD_ID, np.int32)
            for s in range(S):
                seg = slice(s * chunk, (s + 1) * chunk)
                in_k[s, : k_flat[seg].shape[0]] = k_flat[seg]
                in_q[s, : q_flat[seg].shape[0]] = q_flat[seg]
            e._xfer_bytes += in_k.nbytes + in_q.nbytes
            out = e._probe_runner(qplan)(
                stream._slab_keys, stream._slab_rows,
                jnp.asarray(in_k.reshape(-1)),
                jnp.asarray(in_q.reshape(-1)),
            )
            if int(np.asarray(out["overflow"]).sum()) == 0:
                break
            # exact planning makes this unreachable; belt-and-braces
            qplan = dataclasses.replace(
                qplan, cand_cap=qplan.cand_cap * 2,
                key_route_cap=qplan.key_route_cap * 2,
            )
        if int(np.asarray(out["overflow"]).sum()):
            # a truncated candidate list would silently drop matches —
            # refuse the query instead (typed, so callers can shed load)
            raise CapacityExceeded(
                "query probe still overflowed after "
                f"{e.planner.max_retries} retries (per-shard overflow "
                f"{np.asarray(out['overflow']).tolist()}); refusing to "
                "serve a truncated candidate set"
            )
        stats = {
            "candidates": int(np.asarray(out["count"]).sum()),
            "probe_examined": int(np.asarray(out["examined"]).sum()),
        }
        return (out["cand_row"].reshape(-1), out["cand_qid"].reshape(-1),
                qplan, stats)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class QueryResult:
    """Per-query top-k matches against the resident world.

    match_ids: int32 [Q, k_max] world row ids, best first (mss
        descending, row id ascending), ``PAD_ID`` in empty slots.
    mss: float32 [Q, k_max] matching scores, ``-1.0`` in empty slots.
    stats: one dict of serving counters for this micro-batch.
    """

    match_ids: np.ndarray
    mss: np.ndarray
    stats: dict


class QueryEngine:
    """Top-k query serving over a :class:`StreamingEngine`'s world.

    Constructed FROM the streaming engine, never owning its state: every
    ``query`` reads the world as it stands (queries interleave freely
    with ``update`` calls) and mutates nothing — the read-only probe
    protocol guarantees the index is untouched.

    k: default result count (per-query override via ``query(k=...)``).
    serve_prune: enable the REPOSE-style per-shard pruning pass (module
        docstring); results are identical either way.
    """

    def __init__(self, stream, *, k: int = 10, serve_prune: bool = False):
        self.stream = stream
        self.default_k = int(k)
        self.serve_prune = bool(serve_prune)
        self.planner = stream.planner
        self.betas = stream.betas
        self.config = stream.config
        self.plan = stream.plan
        self.serve_traces = [0]  # score-program compile counter (the
        #                          zero-steady-state-recompile proof)
        self.probe_traces = [0]  # probe-program compile counter
        self.runner_builds = 0
        self.queries_served = 0
        self._qplan: QueryPlan | None = None
        self._compactions_seen = stream.compactions
        self._runner_cache: dict = {}
        self._probe_cache: dict = {}
        self._xfer_bytes = 0
        # the probe protocol adapter: both expose prepare()/finish(),
        # so query() below never branches on the world's index form
        self._prober = (_SlabProber(self)
                        if stream.delta_join == "device"
                        else _HostProber(self))

    # -- public entry point --------------------------------------------------

    def query(self, batch, *, k=None, rho=None) -> QueryResult:
        """Top-k matches for one micro-batch of query trajectories.

        batch: a :class:`TrajectoryBatch` (or anything with ``places``
            [Q, L] and ``lengths`` [Q]).
        k: result count — an int for all queries or an [Q] array.
        rho: similarity threshold (matches require ``mss > rho``) — a
            float for all queries or an [Q] array; defaults to
            ``config.rho``.
        """
        places = np.asarray(batch.places, np.int32)
        if places.ndim != 2:
            places = places.reshape((places.shape[0], -1) if places.size
                                    else (0, 1))
        lengths = np.asarray(batch.lengths, np.int32).reshape(-1)
        Q = places.shape[0]
        k_vec = np.broadcast_to(
            np.asarray(self.default_k if k is None else k, np.int32), (Q,)
        ).copy()
        k_vec = np.maximum(k_vec, 0)
        rho_vec = np.broadcast_to(np.asarray(
            self.config.rho if rho is None else rho, np.float32), (Q,)
        ).copy()
        k_max = int(k_vec.max()) if Q else 0
        self._xfer_bytes = 0
        # the sticky plan may shrink ONLY at a compaction boundary — the
        # serving analogue of the streaming shrink rule (between
        # boundaries caps are monotone, so traffic never recompiles)
        if self.stream.compactions != self._compactions_seen:
            self._qplan = None
            self._compactions_seen = self.stream.compactions
        stats = {
            "queries": Q, "world_size": self.stream.n,
            "world_live": self.stream.live_size, "candidates": 0,
            "probe_examined": 0, "rounds_run": 0, "rounds_skipped": 0,
            "cells_skipped": 0,
        }
        if Q == 0 or self.stream.n == 0:
            return self._finish_result(
                np.full((Q, max(k_max, 0)), PAD_ID, np.int32),
                np.full((Q, max(k_max, 0)), NO_MATCH_MSS, np.float32),
                k_vec, k_max, stats,
            )
        keys_np = self.stream._new_row_keys(places, lengths)
        k_flat, q_flat = _flat_row_keys(keys_np)
        if k_flat.size == 0:
            return self._finish_result(
                np.full((Q, k_max), PAD_ID, np.int32),
                np.full((Q, k_max), NO_MATCH_MSS, np.float32),
                k_vec, k_max, stats,
            )
        pre = self._prober.prepare(keys_np, k_flat, q_flat)
        S = self._world_shards()
        qplan = sticky_query_plan(
            self.planner.plan_query(
                Q, k_max, n_shards=S, cap_local=self._world_cap() // S,
                world_L=self.stream.L,
                q_len_max=int(lengths.max()) if Q else 1,
                **pre["plan_kwargs"],
            ),
            self._qplan,
        )
        cand_row, cand_qid, qplan, probe_stats = self._prober.finish(
            pre, qplan
        )
        self._qplan = qplan
        stats.update(probe_stats)
        if stats["candidates"] == 0:
            return self._finish_result(
                np.full((Q, k_max), PAD_ID, np.int32),
                np.full((Q, k_max), NO_MATCH_MSS, np.float32),
                k_vec, k_max, stats,
            )
        top_row, top_neg = self._run_rounds(
            qplan, cand_row, cand_qid, places, lengths, k_vec, rho_vec,
            stats,
        )
        rows_np = np.asarray(top_row)[:Q]
        negs_np = np.asarray(top_neg)[:Q]
        ids = rows_np[:, :k_max] if k_max else rows_np[:, :0]
        neg = negs_np[:, :k_max] if k_max else negs_np[:, :0]
        mss = np.where(ids != PAD_ID, -neg, NO_MATCH_MSS) \
            .astype(np.float32)
        # device programs speak local slots; matches surface as global ids
        ids = np.where(ids != PAD_ID, ids + np.int32(self.stream._base),
                       PAD_ID)
        return self._finish_result(ids.copy(), mss, k_vec, k_max, stats)

    # -- internals -----------------------------------------------------------

    def _world_shards(self) -> int:
        return self.plan.n_shards if self.stream._mesh_world else 1

    def _world_cap(self) -> int:
        return self.stream._cap

    def _finish_result(self, ids, mss, k_vec, k_max, stats):
        if k_max:
            cols = np.arange(k_max, dtype=np.int32)[None, :]
            drop = cols >= k_vec[:, None]
            ids = np.where(drop, PAD_ID, ids)
            mss = np.where(drop, NO_MATCH_MSS, mss).astype(np.float32)
        self.queries_served += int(stats["queries"])
        stats.update(
            serve_traces=self.serve_traces[0],
            probe_traces=self.probe_traces[0],
            runner_builds=self.runner_builds,
            driver_bytes_in=self._xfer_bytes,
        )
        return QueryResult(match_ids=ids, mss=mss, stats=dict(stats))

    def _run_rounds(self, qplan, cand_row, cand_qid, places, lengths,
                    k_vec, rho_vec, stats):
        """Execute the shared score program once (no pruning) or once per
        surviving world shard (REPOSE rounds), carrying the [q_cap, k_cap]
        top-k state in-mesh between rounds."""
        Q = places.shape[0]
        S = qplan.n_shards
        q_places = np.full((qplan.q_cap, qplan.L_pad), PAD_PLACE, np.int32)
        w = min(places.shape[1], qplan.L_pad)
        q_places[:Q, :w] = places[:, :w]
        # positions past each query's length must be the PAD sentinel —
        # encode_codes derives in-program lengths from it
        cols = np.arange(qplan.L_pad, dtype=np.int32)[None, :]
        q_places[:Q] = np.where(cols < lengths[:, None], q_places[:Q],
                                PAD_PLACE)
        rho_pad = np.full((qplan.q_cap,), np.inf, np.float32)
        rho_pad[:Q] = rho_vec
        self._xfer_bytes += q_places.nbytes + rho_pad.nbytes
        q_places_dev = jnp.asarray(q_places)
        rho_dev = jnp.asarray(rho_pad)
        prev_row = jnp.full((qplan.q_cap, qplan.k_cap), PAD_ID, jnp.int32)
        prev_neg = jnp.full((qplan.q_cap, qplan.k_cap), jnp.inf,
                            jnp.float32)
        runner = self._score_runner(qplan)
        world_args = self._world_args()

        def run_round(active_np, prow, pneg):
            active = jnp.asarray(active_np)
            self._xfer_bytes += active_np.nbytes
            out = runner(*world_args, cand_row, cand_qid, q_places_dev,
                         rho_dev, active, prow, pneg,
                         self.stream.tables)
            stats["rounds_run"] += 1
            return out["top_row"], out["top_neg"]

        if not self.serve_prune:
            return run_round(
                np.ones((qplan.q_cap, S), bool), prev_row, prev_neg
            )
        # REPOSE rounds: shards in descending resident-length order; a
        # (query, shard) cell is skipped when the free MSS bound cannot
        # beat rho, or — once k matches exist — the running kth-best.
        # Both tests keep the extra PRUNE_EPS margin on the KEEP side,
        # so a skipped cell is strictly unable to alter the top-k.
        summ = self.stream.shard_summaries
        bsum = float(np.asarray(self.betas, np.float32).sum())
        ub = mss_upper_bound(
            np.minimum(lengths, qplan.L_pad)[:, None],
            np.broadcast_to(summ.max_len[None, :], (Q, S)), bsum,
        )  # f32 [Q, S]
        order = np.argsort(-summ.max_len, kind="stable")
        kth = np.full((Q,), -np.inf, np.float32)
        have_k = k_vec == 0
        kth[have_k] = np.inf
        row_state, neg_state = prev_row, prev_neg
        ran_any = False
        for pos, s in enumerate(order.tolist()):
            act = ub[:, s] > rho_vec - PRUNE_EPS
            act &= ~have_k | (ub[:, s] > kth - PRUNE_EPS)
            if not act.any():
                # ub is monotone in the shard's max_len and kth only
                # grows, so every remaining shard is skippable too
                stats["rounds_skipped"] += len(order) - pos
                stats["cells_skipped"] += (len(order) - pos) * Q
                break
            stats["cells_skipped"] += int(Q - act.sum())
            active = np.zeros((qplan.q_cap, S), bool)
            active[:Q, s] = act
            row_state, neg_state = run_round(active, row_state, neg_state)
            ran_any = True
            mss_state = -np.asarray(neg_state)[:Q]  # sorted best-first
            found = np.asarray(row_state)[:Q] != PAD_ID
            counts = found.sum(axis=1)
            have_k = counts >= np.maximum(k_vec, 1)
            have_k |= k_vec == 0
            idx = np.clip(np.maximum(k_vec, 1) - 1, 0,
                          qplan.k_cap - 1)
            kth = np.where(
                have_k, mss_state[np.arange(Q), idx], -np.inf
            ).astype(np.float32)
            kth[k_vec == 0] = np.inf
        if not ran_any:
            return prev_row, prev_neg
        return row_state, neg_state

    def _world_args(self):
        stream = self.stream
        if stream._mesh_world:
            return (stream._places_dev,)
        return (stream._codes_dev, stream._len_dev)

    def _score_runner(self, qplan: QueryPlan):
        key = (qplan, self.config.lcs_impl, wavefront_dtype_from_env(),
               self.stream._H)
        runner = self._runner_cache.get(key)
        if runner is None:
            mesh = self.stream._eng.mesh() if self.stream._mesh_world \
                else None
            runner = make_query_score_pipeline(
                mesh, qplan, betas=self.betas,
                axis_name=self.plan.axis_name,
                lcs_impl=self.config.lcs_impl,
                trace_counter=self.serve_traces,
            )
            self._runner_cache[key] = runner
            self.runner_builds += 1
        return runner

    def _probe_runner(self, qplan: QueryPlan):
        runner = self._probe_cache.get(qplan)
        if runner is None:
            runner = make_query_probe_pipeline(
                self.stream._eng.mesh(), qplan,
                axis_name=self.plan.axis_name,
                trace_counter=self.probe_traces,
            )
            self._probe_cache[qplan] = runner
            self.runner_builds += 1
        return runner


def _flat_row_keys(keys_np: np.ndarray):
    """Per-row-deduped flat (key, row-index) occurrences — the same
    vectorized discipline as the streaming device join's key flattening,
    with query indices standing in for world row ids."""
    ks = np.sort(np.asarray(keys_np), axis=1)
    valid = ks != PAD_KEY
    valid[:, 1:] &= ks[:, 1:] != ks[:, :-1]
    row_idx, col_idx = np.nonzero(valid)
    return (ks[row_idx, col_idx].astype(np.int32),
            row_idx.astype(np.int32))
