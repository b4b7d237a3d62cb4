"""Phase (iv): communities of common interest + the paper's QA metrics.

The centralized oracle (paper section V.1) forms **maximal cliques** over the
similarity graph (edges = pairs with MSS > rho); we implement Bron-Kerbosch
with pivoting as the exact host-side oracle.  For the scalable distributed
path we additionally provide **connected components** via jit-compiled
min-label propagation with pointer jumping (O(log N) rounds), which is the
standard large-scale community proxy; accuracy experiments (QA1) use the
clique definition on both sides, exactly as the paper does.

QA1 = |communities_dis ∩ communities_cen| / |communities_cen|   (Eq. 2)
QA2 = |pairs_dis ∩ pairs_cen| / |pairs_cen|                      (Eq. 3)
"""
from __future__ import annotations

import functools
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import PAD_ID


# ---------------------------------------------------------------------------
# scalable path: connected components, jit + collective friendly
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("num_nodes", "max_iters"))
def connected_components(
    left: jnp.ndarray,
    right: jnp.ndarray,
    *,
    num_nodes: int,
    max_iters: int = 64,
    init_labels: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Min-label propagation over an edge list (PAD_ID edges ignored).

    Returns int32 [num_nodes] component labels (the min node id reachable).
    Convergence in O(diameter) rounds, accelerated by pointer jumping; the
    while_loop exits early on fixpoint, at the same labels in any edge
    order.  An edge with a PAD_ID end, in slot ``j``, becomes a self-loop
    of node ``j mod (num_nodes + 1)``: a self-loop changes no label,
    and a padded buffer spreads its pad slots over every index rather
    than sending them all to one.

    ``init_labels`` (int32 [num_nodes]) warm-starts the propagation — the
    streaming engine seeds it with the previous update's fixpoint, so one
    micro-batch of new edges converges in O(log delta) rounds instead of
    O(log N).  The seed contract: ``init_labels[v]`` must be a node id in
    ``v``'s component under the CURRENT edge list with
    ``init_labels[v] <= v`` — any stale fixpoint of a sub-graph of the
    current graph satisfies this (labels only merge downward as edges are
    added), and the result is then the exact same fixpoint as a cold start.
    Seeds are clamped to ``min(init_labels[v], v)`` so a cold-start-shaped
    seed (``arange``) is always valid.
    """
    iota = jnp.arange(num_nodes + 1, dtype=jnp.int32)
    pad = (left == PAD_ID) | (right == PAD_ID)
    loop = jnp.arange(left.shape[0], dtype=jnp.int32) % (num_nodes + 1)
    lo = jnp.where(pad, loop, left)
    hi = jnp.where(pad, loop, right)
    if init_labels is None:
        init = iota
    else:
        seed = jnp.minimum(init_labels.astype(jnp.int32), iota[:num_nodes])
        init = jnp.concatenate(
            [seed, jnp.full((1,), num_nodes, jnp.int32)]
        )

    def body(state):
        labels, _, it = state
        m = jnp.minimum(labels[lo], labels[hi])
        new = labels.at[lo].min(m).at[hi].min(m)
        new = new.at[num_nodes].set(num_nodes)
        # pointer jumping: label <- label[label]
        new = jnp.minimum(new, new[new])
        changed = jnp.any(new != labels)
        return new, changed, it + 1

    def cond(state):
        _, changed, it = state
        return changed & (it < max_iters)

    with jax.named_scope("communities/labels"):
        labels, _, _ = jax.lax.while_loop(
            cond, body, (init, jnp.asarray(True), jnp.asarray(0, jnp.int32))
        )
        return labels[:num_nodes]


def components_as_sets(labels: np.ndarray, min_size: int = 2) -> set[frozenset]:
    """Host conversion: labels -> {frozenset(member ids)} of size >= min_size."""
    labels = np.asarray(labels)
    order = np.argsort(labels, kind="stable")
    lab = labels[order]
    starts = np.flatnonzero(np.r_[True, lab[1:] != lab[:-1]])
    sizes = np.diff(np.r_[starts, lab.size])
    big = sizes >= min_size
    return {frozenset(order[s:s + z].tolist())
            for s, z in zip(starts[big].tolist(), sizes[big].tolist())}


# ---------------------------------------------------------------------------
# incremental path: union-find over an accumulated edge stream
# ---------------------------------------------------------------------------
class UnionFind:
    """Incremental connected components: union by size + path compression.

    The host-side oracle for streaming ingestion — edges arrive in
    micro-batches and each ``union`` costs amortized ~O(alpha(N)); the
    labeling after any prefix of unions equals ``connected_components`` over
    the same edge set (canonicalized to min-member labels).  Node capacity
    grows on demand (``add``) with amortized-doubling reallocation, matching
    the engine's world-buffer policy.
    """

    def __init__(self, num_nodes: int = 0):
        self._parent = np.arange(num_nodes, dtype=np.int64)
        self._size = np.ones(num_nodes, dtype=np.int64)
        self.num_nodes = num_nodes

    def add(self, num_new: int) -> None:
        """Append ``num_new`` fresh singleton nodes."""
        if num_new <= 0:
            return
        n = self.num_nodes + num_new
        if n > self._parent.shape[0]:
            cap = max(16, 1 << int(np.ceil(np.log2(n))))
            parent = np.arange(cap, dtype=np.int64)
            size = np.ones(cap, dtype=np.int64)
            parent[: self.num_nodes] = self._parent[: self.num_nodes]
            size[: self.num_nodes] = self._size[: self.num_nodes]
            self._parent, self._size = parent, size
        self.num_nodes = n

    def find(self, x: int) -> int:
        """Root of ``x`` with path halving (iterative compression)."""
        p = self._parent
        while p[x] != x:
            p[x] = p[p[x]]
            x = p[x]
        return int(x)

    def union(self, a: int, b: int) -> bool:
        """Merge the components of ``a`` and ``b``; True if they differed."""
        ra, rb = self.find(a), self.find(b)
        if ra == rb:
            return False
        if self._size[ra] < self._size[rb]:
            ra, rb = rb, ra
        self._parent[rb] = ra
        self._size[ra] += self._size[rb]
        return True

    def labels(self) -> np.ndarray:
        """Canonical int32 [num_nodes] labels: the MIN member id per
        component — bit-compatible with :func:`connected_components`, so the
        streaming engine can hand them over as that function's
        ``init_labels`` seed (and vice versa)."""
        n = self.num_nodes
        roots = np.fromiter(
            (self.find(i) for i in range(n)), dtype=np.int64, count=n
        )
        canon = np.full(n, np.iinfo(np.int64).max, np.int64)
        np.minimum.at(canon, roots, np.arange(n, dtype=np.int64))
        return canon[roots].astype(np.int32) if n else np.empty(0, np.int32)

    def components(self, min_size: int = 2) -> set[frozenset]:
        """{frozenset(member ids)} of size >= min_size, like
        :func:`components_as_sets`."""
        return components_as_sets(self.labels(), min_size=min_size)

    def reset_from_labels(self, labels: np.ndarray) -> None:
        """Reinitialize to the partition encoded by min-member ``labels``.

        ``labels[v]`` must be the min member of ``v``'s component (the
        :meth:`labels` / :func:`connected_components` canonical form) —
        then ``parent[v] = labels[v]`` is a valid depth-1 forest (the min
        member roots itself) and subsequent unions continue incrementally.
        This is how deletion re-enters the incremental path: components
        are re-solved once (``components_after_deletion``) and the
        union-find warm-restarts from the surviving partition instead of
        replaying the entire edge history.
        """
        labels = np.asarray(labels, np.int64).reshape(-1)
        n = labels.shape[0]
        cap = max(16, int(2 ** np.ceil(np.log2(max(n, 1)))))
        self._parent = np.arange(cap, dtype=np.int64)
        self._parent[:n] = labels
        self._size = np.ones(cap, dtype=np.int64)
        if n:
            counts = np.bincount(labels, minlength=n)
            roots = np.nonzero(counts)[0]
            self._size[roots] = counts[roots]
        self.num_nodes = n


def components_after_deletion(
    labels: np.ndarray,
    dead: Sequence[int],
    surviving_edges: Iterable[tuple[int, int]],
) -> np.ndarray:
    """Community *un*-merging: re-label after deleting the ``dead`` nodes.

    Connected components are incrementally maintainable under edge
    ADDITION (labels only merge downward), but deletion can SPLIT a
    component — e.g. expiring the bridge node of a path — which no local
    label update can discover.  The warm re-solve: only components that
    CONTAIN a dead node ("touched") are recomputed, from the surviving
    edges restricted to them; untouched components keep their labels
    verbatim (their min member is alive, so the canonical form is stable).
    Cost O(n + E_touched) instead of replaying the world's edge history.

    labels:          int [n] current min-member labels (nodes 0..n-1).
    dead:            node ids being deleted (become self-labeled
                     singletons; the caller must already have dropped
                     every edge referencing them).
    surviving_edges: the post-deletion edge set (edges inside untouched
                     components are skipped internally).

    Returns the new int32 [n] min-member labels — bit-identical to a cold
    :func:`connected_components` / union-find fixpoint over
    ``surviving_edges``.
    """
    labels = np.asarray(labels, np.int64).copy()
    n = labels.shape[0]
    dead = np.asarray(sorted(set(int(x) for x in dead)), np.int64)
    if dead.size == 0:
        return labels.astype(np.int32)
    touched = np.unique(labels[dead])
    touched_mask = np.isin(labels, touched)
    idx = np.nonzero(touched_mask)[0]
    labels[idx] = idx  # touched components dissolve to singletons...
    uf = UnionFind()
    uf.reset_from_labels(labels)
    touched_nodes = set(idx.tolist())
    for a, b in surviving_edges:  # ...and re-form from surviving edges
        if int(a) in touched_nodes or int(b) in touched_nodes:
            uf.union(int(a), int(b))
    return uf.labels()


# ---------------------------------------------------------------------------
# exact oracle: maximal cliques (Bron-Kerbosch with pivoting)
# ---------------------------------------------------------------------------
def maximal_cliques(edges: Iterable[tuple[int, int]], min_size: int = 2) -> set[frozenset]:
    """All maximal cliques of size >= min_size.  Host-side, exact."""
    adj: dict[int, set[int]] = {}
    for a, b in edges:
        if a == b:
            continue
        adj.setdefault(a, set()).add(b)
        adj.setdefault(b, set()).add(a)
    cliques: set[frozenset] = set()

    def bk(r: set, p: set, x: set):
        if not p and not x:
            if len(r) >= min_size:
                cliques.add(frozenset(r))
            return
        pivot_pool = p | x
        pivot = max(pivot_pool, key=lambda v: len(adj.get(v, ())), default=None)
        for v in list(p - adj.get(pivot, set())):
            bk(r | {v}, p & adj[v], x & adj[v])
            p.remove(v)
            x.add(v)

    bk(set(), set(adj.keys()), set())
    return cliques


# ---------------------------------------------------------------------------
# paper metrics
# ---------------------------------------------------------------------------
def pairs_to_set(left, right) -> set[tuple[int, int]]:
    left = np.asarray(left)
    right = np.asarray(right)
    ok = left != PAD_ID
    return {
        (int(min(a, b)), int(max(a, b)))
        for a, b in zip(left[ok].tolist(), right[ok].tolist())
    }


def qa1(communities_dis: set[frozenset], communities_cen: set[frozenset]) -> float:
    """Eq. 2 — fraction of centralized communities recovered."""
    if not communities_cen:
        return 1.0
    return len(communities_dis & communities_cen) / len(communities_cen)


def qa2(pairs_dis: set[tuple], pairs_cen: set[tuple]) -> float:
    """Eq. 3 — fraction of centralized similar pairs recovered."""
    if not pairs_cen:
        return 1.0
    return len(pairs_dis & pairs_cen) / len(pairs_cen)
