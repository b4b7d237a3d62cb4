"""The plain reference (``reference.World``) for worlds too large to score
from the host in a run's time.

The same pairs and the same recurrence: the pairs that share a type-level
k-subsequence key, each scored by the textbook row recurrence
(``reference._lcs_program``).  Only where the work runs differs: the pairs
are enumerated in blocks of key runs on host threads, the level codes are
put on every device once, and blocks of pairs go to all the devices at
once as two index vectors, in one program compiled once, so the host
neither gathers nor transposes code rows.  It imports nothing of the
program.
"""
from __future__ import annotations

import functools
import sys
import time

import numpy as np

from chipbench import reference

BLOCK = 1 << 21  # pairs scored per device per call


@functools.lru_cache(maxsize=None)
def _block_program(H: int, L: int, n_devices: int):
    """(mesh, fn): one program over the first ``n_devices`` devices, so it
    compiles once (a jit called on each of four devices compiles four
    times, about 16 s each on a v5e host)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    lcs = reference._lcs_program(L)
    mesh = Mesh(np.array(jax.devices()[:n_devices]), ("d",))

    def level_lcs(table, rows_a, rows_b):
        """int32 [H, B]: ``|M_h|`` of ``(table[rows_a], table[rows_b])``,
        table int32 [n, H * L]; past a row's end side a reads -1 and side
        b -2, so pads never match.  A side is a gather of whole rows (an
        element-wise gather costs the device about 20 ns an element), then
        pairs go to the minor axis, so no small axis is padded to a whole
        tile."""
        B = rows_a.shape[0]

        def side(rows):
            x = table[rows].T.reshape(H, L, B)
            return x.transpose(1, 0, 2).reshape(L, H * B)

        b = side(rows_b)
        return lcs(side(rows_a), jnp.where(b < 0, reference.PAD_B, b)
                   ).reshape(H, B)

    # (unchecked: the recurrence's loop starts from zeros that do not vary
    # over the mesh, its rows do)
    return mesh, jax.jit(jax.shard_map(
        level_lcs, mesh=mesh, in_specs=(P(), P("d"), P("d")),
        out_specs=P(None, "d"), check_vma=False))


def level_lcs(table: np.ndarray, rows_a: np.ndarray,
              rows_b: np.ndarray) -> np.ndarray:
    """int32 [P, H]: ``|M_h|`` of the row pairs of ``table`` (int32
    [n, H, L], -1 past each row's end), in blocks of ``BLOCK`` pairs on
    each device at once."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    P = rows_a.shape[0]
    n, H, L = table.shape
    nd = len(jax.devices())
    mesh, fn = _block_program(H, L, nd)
    flat = jax.device_put(np.ascontiguousarray(table.reshape(n, H * L)),
                          NamedSharding(mesh, PartitionSpec()))
    spread = NamedSharding(mesh, PartitionSpec("d"))
    step = nd * min(BLOCK, 1 << max(10, (-(-P // nd) - 1).bit_length()))
    parts = []
    for s in range(0, P, step):
        m = min(step, P - s)
        a = np.zeros(step, np.int32)
        b = np.zeros(step, np.int32)
        a[:m], b[:m] = rows_a[s:s + m], rows_b[s:s + m]
        parts.append((m, fn(flat, jax.device_put(a, spread),
                            jax.device_put(b, spread))))
    out = np.zeros((P, H), np.int32)
    at = 0
    for m, r in parts:
        out[at:at + m] = np.asarray(r).T[:m]
        at += m
    return out


def pairs_sharing_a_key(keys: np.ndarray, ids: np.ndarray,
                        threads: int | None = None) -> np.ndarray:
    """``reference.pairs_sharing_a_key`` in blocks of key runs: each block's
    pairs are enumerated and sorted in a thread of its own (numpy sorts
    without the GIL), and one stable sort merges the sorted blocks."""
    import os
    from concurrent.futures import ThreadPoolExecutor

    threads = threads or min(32, os.cpu_count() or 8)
    rows, cols = np.nonzero(keys >= 0)
    if rows.size == 0:
        return np.zeros((0, 2), np.int64)
    span = int(ids.max()) + 1
    flat = np.sort(keys[rows, cols] * span + ids[rows])  # by key, then id
    k, i = flat // span, flat % span
    pos = np.arange(k.size)
    start = np.r_[True, k[1:] != k[:-1]]
    first = np.maximum.accumulate(np.where(start, pos, 0))
    rank = pos - first
    # block edges at run starts, about equal numbers of pairs apart
    done = np.cumsum(rank)
    cut = np.searchsorted(done, done[-1] * np.arange(1, threads) / threads)
    edges = np.unique(np.r_[0, first[np.minimum(cut, k.size - 1)], k.size])

    def block(lo, hi):
        r = rank[lo:hi]
        at = np.repeat(pos[lo:hi], r)
        off = np.arange(at.size) - np.repeat(np.cumsum(r) - r, r)
        a, b = i[at], i[first[at] + off]
        code = np.minimum(a, b) * span + np.maximum(a, b)
        code.sort()
        return code[np.r_[True, code[1:] != code[:-1]]] if code.size \
            else code

    with ThreadPoolExecutor(threads) as pool:
        parts = list(pool.map(block, edges[:-1], edges[1:]))
    code = np.concatenate(parts)
    code.sort(kind="stable")  # merges the sorted blocks
    code = code[np.r_[True, code[1:] != code[:-1]]] if code.size else code
    return np.stack([code // span, code % span], axis=1)


class BlockedWorld(reference.World):
    """``reference.World`` whose pairs are enumerated in threads and scored
    on the devices."""

    def rows(self, gids: np.ndarray) -> np.ndarray:
        # ids 0 .. n-1 (as the jobs number them) are their own rows
        if self.ids.size and self.ids[0] == 0 \
                and self.ids[-1] == self.ids.size - 1:
            return gids
        return super().rows(gids)

    def similar_pairs(self) -> set:
        t0 = time.perf_counter()
        pairs = pairs_sharing_a_key(self.keys, self.ids)
        t1 = time.perf_counter()
        level = level_lcs(self.codes, self.rows(pairs[:, 0]),
                          self.rows(pairs[:, 1]))
        t2 = time.perf_counter()
        keep = self.similar(level)
        out = set(zip(pairs[keep, 0].tolist(), pairs[keep, 1].tolist()))
        print(f"reference: {pairs.shape[0]} pairs in {t1 - t0:.1f} s, "
              f"scored in {t2 - t1:.1f} s, {len(out)} similar in "
              f"{time.perf_counter() - t2:.1f} s", file=sys.stderr,
              flush=True)
        return out
