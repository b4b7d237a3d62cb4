"""Open loop of top-k queries against the resident world.

Arrivals are a Poisson process at the mix's fixed rate, drawn from the
seed before the window opens, so the load does not depend on how fast the
system answers.  Whenever the engine is free, every query already due (up
to ``max_batch``) goes into one ``QueryEngine.query`` call, padded with
empty rows to ``max_batch`` so that one compiled shape serves all calls.
A query's latency runs from the time it was due to the time its result is
on the host.  Queries due after the window closes are not sent; those due
before it are answered, up to ``drain_s`` past the close, and any still
unanswered then count as failed, with infinite latency.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import data, reference
from chipbench.drivers.common import (
    build_world, clock, failed_call, live_world,
)


def _padded(places, lengths, rows, width):
    p = np.full((rows, width), data.PAD_PLACE, np.int32)
    n = np.zeros((rows,), np.int32)
    p[:places.shape[0]], n[:places.shape[0]] = places, lengths
    return p, n


def setup(cell) -> None:
    mix, world = cell.traffic, cell.cfg["world"]
    build_world(cell, mix["warm_updates"])
    # warm with queries of the longest length: they hold the most keys and
    # candidates, so the sticky capacities cover every batch of the window
    longest = dict(world, min_len=world["max_len"])
    for b in range(mix["warm_batches"]):
        cell.system.query(*data.batch(longest, mix["max_batch"], cell.seed,
                                      data.WARM, b))
    arrivals(cell)


def arrivals(cell) -> None:
    """The window's due times, queries and checked sample, from the seed."""
    mix, world = cell.traffic, cell.cfg["world"]
    r = data.rng(cell.seed, data.ARRIVAL)
    gaps = r.exponential(1.0 / mix["rate_per_s"],
                         size=int(mix["rate_per_s"] * cell.seconds * 1.5) + 64)
    due = np.cumsum(gaps)
    cell.due = due[due < cell.seconds]
    cell.queries = data.batch(world, cell.due.size, cell.seed, data.QUERY)
    n = cell.due.size
    cell.sample = np.sort(data.rng(cell.seed, data.SAMPLE).choice(
        n, size=min(mix["check_sample"], n), replace=False))


def window(cell) -> None:
    mix = cell.traffic
    cap, width = mix["max_batch"], cell.cfg["world"]["max_len"]
    due, (places, lengths) = cell.due, cell.queries
    n = due.size
    done = np.full(n, np.inf)
    answers = {}
    in_sample = np.zeros(n, bool)
    in_sample[cell.sample] = True
    batch_ms, cands, sizes = [], 0, 0
    trace_at = 0.4 * cell.seconds
    t0 = clock()
    i = 0
    while i < n:
        now = clock() - t0
        if now > cell.seconds + mix["drain_s"]:
            break
        if due[i] > now:
            with cell.span("harness.wait"):
                time.sleep(min(due[i] - now, 0.01))
            continue
        if now >= trace_at:
            cell.trace_begin()
            if now >= trace_at + mix["trace_s"]:
                cell.trace_end()
        j = min(int(np.searchsorted(due, now, side="right")), i + cap)
        qp, ql = _padded(places[i:j], lengths[i:j], cap, width)
        ts = clock()
        try:
            with cell.span("query"):
                ids, mss, stats = cell.system.query(qp, ql)
        except Exception as e:  # a failed call fails its queries
            failed_call(cell, f"queries {i}:{j}", e)
            i = j
            continue
        te = clock()
        done[i:j] = te - t0
        batch_ms.append((te - ts) * 1e3)
        cands += stats["candidates"]
        sizes += j - i
        for q in np.flatnonzero(in_sample[i:j]) + i:
            answers[int(q)] = (ids[q - i], mss[q - i])
        i = j
    cell.trace_end()
    cell.rec["attempted"] = n
    cell.rec["failed"] = int(np.isinf(done).sum())
    cell.rec["latency_ms"] = (done - due) * 1e3
    cell.rec["batch_ms"] = batch_ms
    cell.rec["candidates"] = cands
    cell.rec["queries_served"] = sizes
    cell.answers = answers


def check(cell) -> list:
    """The sampled queries' top-k against the reference's exact top-k."""
    answers, cell.answers = cell.answers, None
    cell.free_system()
    world = live_world(cell)
    places, lengths = cell.queries
    q_codes = reference.codes(cell.tables, places[cell.sample])
    q_keys = reference.subsequence_keys(
        q_codes[:, 0, :], lengths[cell.sample], cell.cfg["engine"]["k"],
        int(cell.tables[0].max()) + 1)
    want = world.top_k(q_codes, q_keys, cell.cfg["query_k"])
    # the exact score of every match the program returned
    got = {}
    for s, q in enumerate(cell.sample.tolist()):
        if q in answers:
            ids, mss = answers[q]
            real = ids != np.iinfo(np.int32).max
            got[s] = (ids[real].astype(np.int64), mss[real])
    rows = [(s, int(i)) for s, (ids, _) in got.items() for i in ids
            if int(i) in world.id_set]
    qs = np.array([s for s, _ in rows], np.int64)
    gs = np.array([i for _, i in rows], np.int64)
    score = dict(zip(rows, reference.level_lcs(
        q_codes, qs, world.codes, world.rows(gs)).sum(axis=1).tolist()))
    H = cell.tables.shape[0]
    wrong = sum(s not in got or not _exact_top_k(s, *got[s], want[s], score, H)
                for s in range(cell.sample.size))
    return [("queries_wrong", wrong, cell.traffic["limits"]["queries_wrong"])]


def _exact_top_k(s, ids, mss, want, score, H) -> bool:
    """Whether one query's matches are an exact top-k: distinct live ids
    whose exact scores are the reference's top-k scores, best first (any
    order among equal scores), each reported MSS the exact one to float32
    rounding."""
    if ids.size != len(want) or np.unique(ids).size != ids.size:
        return False
    if any((s, int(i)) not in score for i in ids):
        return False
    exact = np.array([score[(s, int(i))] for i in ids], np.float64)
    if exact.tolist() != [w for _, w in want]:
        return False
    return bool(np.all(np.abs(mss.astype(np.float64) - exact / H) <= 1e-5))
