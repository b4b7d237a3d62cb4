"""Pallas TPU kernel: batched LCS with pairs on the vector lanes.

TPU-native rewrite of the paper's CPU dynamic program (section IV.3).  The
classic row recurrence

    dp[i][j] = dp[i-1][j-1] + 1                  if a[i-1] == b[j-1]
               max(dp[i-1][j], dp[i][j-1])       otherwise

is evaluated for a whole tile of pairs at once: operands arrive
**lane-dense**, ``[H, L, R, 128]`` int32 with one pair per (row, lane)
slot, so every DP cell is one elementwise op on an ``[8, 128]`` vreg that
advances 1,024 pairs.  Nothing in the body moves data across lanes or
sublanes — no reversal, no roll, no concatenation, no unaligned slice —
which is what Mosaic lowers without relayouts.  The cells are int32 (Mosaic
has no int8 vector compares); values never exceed L.

The body runs a ``fori_loop`` over the L rows of ``a`` carrying the previous
DP row as L+1 tiles, with the L columns unrolled, so code size is O(L) and
work O(L^2) per level.  Side-A pads (-1) and side-B pads (-2) never compare
equal, so a sentinel-masked row's LCS is the LCS of its valid entries,
wherever they sit in the row (the windowed path relies on this).

Both public LCS kernels share this body: :func:`lcs_pallas` over
pre-padded ``[B, L]`` row pairs, and the table-indexed kernels of
``kernels/lcs/fused.py`` over gathered-and-masked operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8


def _lcs_kernel(a_ref, b_ref, o_ref):
    """a_ref/b_ref [H, L, tr, 128] -> o_ref [H, tr, 128] LCS per pair.

    ``tr`` is at most one vreg of rows (:func:`block_rows`), so each level
    is one pass of [tr, 128] tiles.
    """
    H, L, tr, _ = a_ref.shape

    def level(h, _):
        b = [b_ref[h, j] for j in range(L)]
        zero = jnp.zeros((tr, LANES), jnp.int32)

        def dp_row(i, prev):
            ai = a_ref[h, i]
            cur = [zero]
            for j in range(1, L + 1):
                cur.append(jnp.where(
                    ai == b[j - 1], prev[j - 1] + 1,
                    jnp.maximum(prev[j], cur[j - 1]),
                ))
            return tuple(cur)

        last = jax.lax.fori_loop(0, L, dp_row, (zero,) * (L + 1))
        o_ref[h] = last[L]
        return 0

    jax.lax.fori_loop(0, H, level, 0)


def block_rows(pairs: int) -> tuple[int, int]:
    """(rows per grid block, padded rows) for ``pairs`` lane-dense pairs.

    A block is one [8, 128] vreg (1,024 pairs).  A batch of at most 8
    rows is one block of exactly its own rows, which Mosaic accepts
    since the block then spans the whole array; a larger batch pads its
    rows to whole vregs.
    """
    rows = -(-max(pairs, 1) // LANES)
    if rows <= SUBLANES:
        return rows, rows
    return SUBLANES, -(-rows // SUBLANES) * SUBLANES


@functools.partial(jax.jit, static_argnames=("interpret",))
def lcs_lanes(
    a: jnp.ndarray, b: jnp.ndarray, *, interpret: bool = False
) -> jnp.ndarray:
    """Lane-dense LCS: a, b int32 [H, L, P] (sentinel-masked) -> int32 [H, P].

    P is padded to whole blocks with never-matching sentinels and the
    result sliced back, so any P works.
    """
    H, L, P = a.shape
    assert b.shape == (H, L, P) and L < 127
    tr, rows = block_rows(P)
    pad = rows * LANES - P
    if pad:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, pad)), constant_values=-1)
        b = jnp.pad(b, ((0, 0), (0, 0), (0, pad)), constant_values=-2)
    a = a.reshape(H, L, rows, LANES)
    b = b.reshape(H, L, rows, LANES)
    operand = pl.BlockSpec((H, L, tr, LANES), lambda r: (0, 0, r, 0))
    out = pl.pallas_call(
        _lcs_kernel,
        grid=(rows // tr,),
        in_specs=[operand, operand],
        out_specs=pl.BlockSpec((H, tr, LANES), lambda r: (0, r, 0)),
        out_shape=jax.ShapeDtypeStruct((H, rows, LANES), jnp.int32),
        interpret=interpret,
    )(a, b)
    return out.reshape(H, rows * LANES)[:, :P]


@functools.partial(jax.jit, static_argnames=("interpret",))
def lcs_pallas(
    a: jnp.ndarray, b: jnp.ndarray, *, interpret: bool = False
) -> jnp.ndarray:
    """a, b: int32 [B, L] (pre-padded, distinct sentinels) -> int32 [B].

    The row pairs are transposed to the lane-dense layout and scored by
    :func:`lcs_lanes`; any batch size works.
    """
    B, L = a.shape
    assert b.shape == (B, L)
    out = lcs_lanes(a.T[None], b.T[None], interpret=interpret)
    return out[0]
