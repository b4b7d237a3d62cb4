"""Plain reference of "find another me" (arXiv:2204.00878, Definitions 2-5).

Independent of the program under test: it takes the places and lengths the
benchmark generated, the forest's level tables, and the configuration's
parameters, and nothing the program made.

* A trajectory's level-h encoding maps every place through the forest.
* ``|M_h|`` is the longest common subsequence of two level-h encodings,
  by the textbook row recurrence in ``jax.numpy`` (blocks of pairs on the
  device; no kernel).
* With uniform level weights ``beta_h = 1/H`` a pair is similar when
  ``sum_h |M_h| > rho * H`` -- compared in integers, so the answer is the
  exact one and not a rounding of it.
* Which pairs to score: a code at a finer level determines its parent, so
  ``|M_h| <= |M_0|`` and ``MSS <= |M_0|``.  A similar pair therefore has
  ``|M_0| > rho``; when ``rho >= k - 1`` that means a common subsequence
  of ``k`` type codes, and every such pair shares a type-level
  k-subsequence key.  Scoring every pair that shares one misses nothing
  (``filter_is_exact`` checks the condition; the tests check the claim
  against all pairs at a small size).
* Communities are the connected components (of two or more members) of
  the similar-pair graph, found by ``scipy.sparse.csgraph``.
"""
from __future__ import annotations

import functools
import itertools

import numpy as np

PAD_A, PAD_B = -1, -2
BLOCK = 1 << 19  # pairs scored per device call


def filter_is_exact(engine: dict) -> bool:
    return engine["rho"] >= engine["k"] - 1


def codes(tables: np.ndarray, places: np.ndarray) -> np.ndarray:
    """int32 [n, H, L]: level codes, -1 past each row's end."""
    safe = np.where(places < 0, 0, places)
    c = tables[:, safe].transpose(1, 0, 2)
    return np.where((places < 0)[:, None, :], -1, c).astype(np.int32)


def subsequence_keys(types: np.ndarray, lengths: np.ndarray, k: int,
                     q: int) -> np.ndarray:
    """int64 [n, C(L, k)]: every type-level k-subsequence as one key, -1
    where the subsequence runs past the row's end or repeats an earlier
    key of the row."""
    n, L = types.shape
    combos = np.array(list(itertools.combinations(range(L), k)), np.int64)
    keys = np.zeros((n, len(combos)), np.int64)
    for i in range(k):
        keys = keys * q + types[:, combos[:, i]]
    keys[combos[:, -1][None, :] >= lengths[:, None]] = -1
    keys.sort(axis=1)
    keys[:, 1:][keys[:, 1:] == keys[:, :-1]] = -1
    return keys


def _flat(keys: np.ndarray, ids: np.ndarray):
    """(key, id) occurrences sorted by key."""
    rows, cols = np.nonzero(keys >= 0)
    k, i = keys[rows, cols], ids[rows]
    order = np.lexsort((i, k))
    return k[order], i[order]


def pairs_sharing_a_key(keys: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """int64 [P, 2] distinct (lo, hi) id pairs that share at least one key."""
    k, i = _flat(keys, ids)
    if k.size == 0:
        return np.zeros((0, 2), np.int64)
    pos = np.arange(k.size)
    start = np.r_[True, k[1:] != k[:-1]]
    first = np.maximum.accumulate(np.where(start, pos, 0))
    rank = pos - first
    total = int(rank.sum())
    at = np.repeat(pos, rank)
    off = np.arange(total) - np.repeat(np.cumsum(rank) - rank, rank)
    a, b = i[at], i[first[at] + off]
    lo, hi = np.minimum(a, b), np.maximum(a, b)
    span = int(ids.max()) + 1
    code = np.unique(lo * span + hi)
    return np.stack([code // span, code % span], axis=1)


def pairs_with_world(qkeys: np.ndarray, wkeys: np.ndarray,
                     wids: np.ndarray) -> np.ndarray:
    """int64 [P, 2] distinct (query index, world id) pairs sharing a key."""
    wk, wi = _flat(wkeys, wids)
    qk, qi = _flat(qkeys, np.arange(qkeys.shape[0]))
    lo = np.searchsorted(wk, qk, side="left")
    hi = np.searchsorted(wk, qk, side="right")
    cnt = hi - lo
    at = np.repeat(np.arange(qk.size), cnt)
    off = np.arange(int(cnt.sum())) - np.repeat(np.cumsum(cnt) - cnt, cnt)
    q, w = qi[at], wi[lo[at] + off]
    span = int(wids.max()) + 1
    code = np.unique(q * span + w)
    return np.stack([code // span, code % span], axis=1)


@functools.lru_cache(maxsize=None)
def _lcs_program(L: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def lcs(a, b):
        """a, b int32 [L, R] (R sequences on the minor axis, sentinel-padded)
        -> int32 [R]: the row recurrence, one row of the table per step."""
        def row(i, prev):
            ai = a[i]
            cur = [jnp.zeros_like(ai)]
            for j in range(L):
                cur.append(jnp.where(ai == b[j], prev[j] + 1,
                                     jnp.maximum(prev[j + 1], cur[j])))
            return jnp.stack(cur)

        zero = jnp.zeros((L + 1, a.shape[1]), jnp.int32)
        return jax.lax.fori_loop(0, L, row, zero)[L]

    return lcs


def level_lcs(table_a: np.ndarray, rows_a: np.ndarray, table_b: np.ndarray,
              rows_b: np.ndarray) -> np.ndarray:
    """int32 [P, H]: ``|M_h|`` of the row pairs ``(table_a[rows_a[p]],
    table_b[rows_b[p]])``; tables int32 [n, H, L], -1 past each row's end."""
    P = rows_a.shape[0]
    _, H, L = table_a.shape
    fn = _lcs_program(L)
    out = np.zeros((P, H), np.int32)
    block = min(BLOCK, 1 << max(10, (P - 1).bit_length()))
    for s in range(0, P, block):
        m = min(block, P - s)
        a = np.full((L, block, H), PAD_A, np.int32)
        b = np.full((L, block, H), PAD_B, np.int32)
        a[:, :m] = table_a[rows_a[s:s + m]].transpose(2, 0, 1)
        bm = table_b[rows_b[s:s + m]].transpose(2, 0, 1)
        b[:, :m] = np.where(bm < 0, PAD_B, bm)
        r = fn(a.reshape(L, block * H), b.reshape(L, block * H))
        out[s:s + m] = np.asarray(r).reshape(block, H)[:m]
    return out


class World:
    """Rows with global ids, their level codes and their join keys."""

    def __init__(self, tables, engine, places, lengths, ids):
        if not filter_is_exact(engine):
            raise ValueError("the reference scores pairs sharing a k-key, "
                             "which is exact only for rho >= k - 1")
        self.engine = engine
        self.codes = codes(tables, places)
        self.ids = np.asarray(ids, np.int64)
        self.id_set = set(self.ids.tolist())
        self.keys = subsequence_keys(self.codes[:, 0, :], lengths,
                                     engine["k"], tables[0].max() + 1)

    def rows(self, gids: np.ndarray) -> np.ndarray:
        """Row positions of ascending global ids."""
        return np.searchsorted(self.ids, gids)

    def similar(self, level: np.ndarray) -> np.ndarray:
        H = level.shape[1]
        return level.sum(axis=1) > self.engine["rho"] * H

    def similar_pairs(self) -> set:
        pairs = pairs_sharing_a_key(self.keys, self.ids)
        level = level_lcs(self.codes, self.rows(pairs[:, 0]),
                          self.codes, self.rows(pairs[:, 1]))
        keep = self.similar(level)
        return {(int(a), int(b)) for a, b in pairs[keep]}

    def top_k(self, q_codes, q_keys, k: int) -> list:
        """Per query: [(world id, sum of |M_h|)] of its k best matches, best
        first, ties by the smaller id."""
        pairs = pairs_with_world(q_keys, self.keys, self.ids)
        level = level_lcs(q_codes, pairs[:, 0],
                          self.codes, self.rows(pairs[:, 1]))
        keep = self.similar(level)
        q, w, s = pairs[keep, 0], pairs[keep, 1], level[keep].sum(axis=1)
        order = np.lexsort((w, -s, q))
        out = [[] for _ in range(q_codes.shape[0])]
        for qi, wi, si in zip(q[order], w[order], s[order]):
            if len(out[qi]) < k:
                out[qi].append((int(wi), int(si)))
        return out


def communities(pairs: set, ids: np.ndarray) -> set:
    """Connected components of two or more members, as frozensets of ids."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    ids = np.asarray(ids, np.int64)
    if not pairs:
        return set()
    p = np.array(sorted(pairs), np.int64)
    r = np.searchsorted(ids, p)
    n = ids.size
    g = coo_matrix((np.ones(len(p)), (r[:, 0], r[:, 1])), shape=(n, n))
    _, label = connected_components(g, directed=False)
    order = np.argsort(label, kind="stable")
    groups = np.split(ids[order], np.flatnonzero(np.diff(label[order])) + 1)
    return {frozenset(int(x) for x in g) for g in groups if g.size > 1}
