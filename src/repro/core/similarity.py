"""Phase (iii): multi-level semantic trajectory similarity (Definitions 2,4,5).

``|M_h|`` is the length of the longest common subsequence (LCS) of the two
trajectories' level-h encodings — repetition-aware, unlike set-based prior
work (paper section IV.3).  ``MSS = sum_h beta_h * |M_h|``.

Two implementations of the batched LCS:

* ``lcs_ref``      — textbook row DP via nested ``lax.scan`` (the oracle;
                     O(La*Lb) sequential steps, used in tests only).
* ``lcs_wavefront``— anti-diagonal wavefront: 2L-1 vectorized steps keeping
                     two rolling diagonals.  This is the TPU-native rewrite
                     of the paper's CPU DP (see DESIGN.md) and the jnp
                     fallback for the Pallas kernel in kernels/lcs.

Padding convention: pad side A with PAD_CODE_A (-1) and side B with
PAD_CODE_B (-2); padded tails never match so LCS(full padded) == LCS(true
prefixes).  Callers gathering both sides from the same EncodedBatch must
re-pad one side (see ``repad``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.encoding import PAD_CODE_A, PAD_CODE_B


def repad(codes: jnp.ndarray, lengths: jnp.ndarray, pad_code: int) -> jnp.ndarray:
    """Set padded positions (>= length) of [..., L] codes to ``pad_code``."""
    L = codes.shape[-1]
    pos = jnp.arange(L, dtype=jnp.int32)
    mask = pos[None, :] < jnp.reshape(lengths, (-1, 1))
    mask = mask.reshape(lengths.shape + (L,))
    # broadcast mask over any intermediate dims (e.g. levels)
    while mask.ndim < codes.ndim:
        mask = mask[..., None, :]
    return jnp.where(mask, codes, pad_code)


def lcs_ref(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Oracle LCS, batched: a [B, La], b [B, Lb] -> int32 [B].

    Classic row-major DP; rows via lax.scan, columns via inner lax.scan.
    """
    B, La = a.shape
    Lb = b.shape[1]

    def row_step(prev_row, ai):  # prev_row [B, Lb+1], ai [B]
        def col_step(left, inputs):
            up, diag, bj = inputs  # each [B]
            match = (ai == bj) & (ai >= 0)
            val = jnp.where(match, diag + 1, jnp.maximum(up, left))
            return val, val

        ups = prev_row[:, 1:]      # dp[i-1, j]     j=1..Lb  -> [B, Lb]
        diags = prev_row[:, :-1]   # dp[i-1, j-1]
        _, cols = jax.lax.scan(
            col_step,
            jnp.zeros((B,), jnp.int32),
            (ups.T, diags.T, b.T),
        )
        new_row = jnp.concatenate([jnp.zeros((B, 1), jnp.int32), cols.T], axis=1)
        return new_row, None

    row0 = jnp.zeros((B, Lb + 1), jnp.int32)
    final, _ = jax.lax.scan(row_step, row0, a.T)
    return final[:, -1]


def wavefront_dtype_from_env() -> jnp.dtype:
    """Resolve the REPRO_LCS_DTYPE A/B probe at a *call boundary*.

    Must run in eager Python (a stage, an ops wrapper, a benchmark), never
    inside a jitted body: the dtype is a static jit argument downstream, so
    resolving it here keeps the env var out of every trace cache.
    """
    import os

    return jnp.int32 if os.environ.get("REPRO_LCS_DTYPE") == "int32" else jnp.int8


@functools.partial(jax.jit, static_argnames=("dtype",))
def lcs_wavefront(
    a: jnp.ndarray, b: jnp.ndarray, *, dtype: jnp.dtype = jnp.int8
) -> jnp.ndarray:
    """Anti-diagonal wavefront LCS, batched: a [B, La], b [B, Lb] -> int32 [B].

    dp[i, j] laid out along diagonals t = i + j; diagonal t stored as
    d_t[i] = dp[i, t - i] over the full i range [0, La] (out-of-range j
    entries are never read by valid cells — see DESIGN.md).  2 rolling
    diagonals, La + Lb - 1 steps of pure vector ops.

    The diagonals are carried in ``dtype`` — int8 by default (LCS values
    <= L < 127; §Perf anotherme/v2: the scan carry crosses fusion/HBM
    boundaries every step, so carry width sets the memory term).  ``dtype``
    is a static argument so the choice is part of the jit cache key;
    callers honouring the REPRO_LCS_DTYPE probe thread
    :func:`wavefront_dtype_from_env` in from eager code.
    """
    cdt = dtype
    B, La = a.shape
    Lb = b.shape[1]
    assert La < 127 and Lb < 127

    def step(carry, t):
        d_prev2, d_prev1 = carry  # [B, La+1] each: diagonals t-2, t-1
        i = jnp.arange(La + 1, dtype=jnp.int32)  # dp row index
        j = t - i
        # shifted views: x[i-1] with x[-1] := 0
        shift = lambda d: jnp.concatenate(
            [jnp.zeros((B, 1), cdt), d[:, :-1]], axis=1
        )
        up = d_prev1            # dp[i, j-1]  (diag t-1, same i)
        left = shift(d_prev1)   # dp[i-1, j]  (diag t-1, i-1)
        diag = shift(d_prev2)   # dp[i-1, j-1] (diag t-2, i-1)
        # match check: a[i-1] vs b[j-1]; clamp indices, mask validity
        ai = a[:, jnp.clip(i - 1, 0, La - 1)]
        bj = jnp.take_along_axis(
            b, jnp.broadcast_to(jnp.clip(j - 1, 0, Lb - 1)[None, :], (B, La + 1)),
            axis=1,
        )
        valid = (i >= 1) & (j >= 1) & (j <= Lb)
        match = (ai == bj) & valid[None, :]
        new = jnp.where(match, diag + jnp.ones((), cdt), jnp.maximum(up, left))
        new = jnp.where(valid[None, :], new, jnp.zeros((), cdt))
        return (d_prev1, new), None

    d0 = jnp.zeros((B, La + 1), cdt)
    (d_prev2, d_prev1), _ = jax.lax.scan(
        step, (d0, d0), jnp.arange(2, La + Lb + 1, dtype=jnp.int32)
    )
    # final diagonal t = La + Lb holds dp[La, Lb] at i = La
    return d_prev1[:, La].astype(jnp.int32)


def multi_level_lcs(
    codes_a: jnp.ndarray,
    len_a: jnp.ndarray,
    codes_b: jnp.ndarray,
    len_b: jnp.ndarray,
    *,
    impl=None,
) -> jnp.ndarray:
    """|M_h| for every level: [P, n_levels, L] x2 -> int32 [P, n_levels].

    Levels are folded into the batch dimension — the LCS recurrence is
    level-independent, so one batched kernel invocation covers all levels.
    """
    if impl is None:
        impl = lcs_wavefront
    P, H, L = codes_a.shape
    a = repad(codes_a, len_a, PAD_CODE_A).reshape(P * H, L)
    b = repad(codes_b, len_b, PAD_CODE_B).reshape(P * H, L)
    return impl(a, b).reshape(P, H)


def gather_windows(codes: jnp.ndarray, off: jnp.ndarray, window: int) -> jnp.ndarray:
    """Slice per-row windows out of gathered code rows.

    codes [P, H, L], off [P] window start offsets -> [P, H, W] with
    W = min(window, L).  Positions past ``L - 1`` clamp to the last column
    (garbage); callers mask by the window's valid length — for any valid
    position ``i < clip(len - off, 0, W)`` we have ``off + i < len <= L``,
    so the clamp never corrupts a valid entry.
    """
    L = codes.shape[-1]
    W = min(window, L)
    pos = off[:, None, None] + jnp.arange(W, dtype=jnp.int32)
    pos = jnp.clip(pos, 0, L - 1)
    return jnp.take_along_axis(
        codes, jnp.broadcast_to(pos, codes.shape[:-1] + (W,)), axis=-1
    )


def mss_scores(level_lcs: jnp.ndarray, betas: jnp.ndarray) -> jnp.ndarray:
    """MSS = sum_h beta_h * |M_h| (Definition 4). level_lcs [P, H] -> [P]."""
    return jnp.einsum("ph,h->p", level_lcs.astype(jnp.float32), betas)


def default_betas(n_levels: int) -> jnp.ndarray:
    """Paper default: equal weights 1/n (section V.1)."""
    return jnp.full((n_levels,), 1.0 / n_levels, dtype=jnp.float32)


def mss_upper_bound(len_a, len_b, betas_sum):
    """The free MSS upper bound: ``sum_h beta_h * min(len_a, len_b)``.

    Every level's LCS is at most ``min(len_a, len_b)`` (lengths are shared
    across levels), so ``MSS <= betas_sum * min(len_a, len_b)`` — computable
    from lengths alone, before any code row is touched.  Traceable on jnp
    arrays and exact on np arrays; float32 either way so the device pruning
    pass and the host capacity planner agree on the bound.
    """
    import numpy as np

    if isinstance(len_a, np.ndarray):
        return np.minimum(len_a, len_b).astype(np.float32) * np.float32(betas_sum)
    return jnp.minimum(len_a, len_b).astype(jnp.float32) * betas_sum


# Pruning keeps a pair when its upper bound clears ``tau - PRUNE_EPS``: the
# hair of slack only ever keeps extra pairs (which then get scored exactly),
# guarding against the bound and the float32 MSS rounding in opposite
# directions around an exact-threshold tie.
PRUNE_EPS = 1e-5


def lcs_impl(name: str, wavefront_dtype=None):
    """The scoring impl for an ``lcs_impl`` name, as :func:`score_indexed`
    takes it: a kernel dispatch mode (a string, see
    ``kernels/lcs/fused.FUSED_IMPL_MODES``), or a pairwise LCS callable
    ``(a [B, L], b [B, L]) -> [B]`` for "ref" and "wavefront".

    ``wavefront_dtype`` must be resolved eagerly by the caller
    (REPRO_LCS_DTYPE, the autotune table); None keeps the default.
    """
    from repro.kernels.lcs.fused import FUSED_IMPL_MODES

    if name in FUSED_IMPL_MODES:
        return FUSED_IMPL_MODES[name]
    if name == "ref":
        return lcs_ref
    if name != "wavefront":
        raise ValueError(f"unknown lcs_impl {name!r}")
    dt = jnp.int8 if wavefront_dtype is None else wavefront_dtype
    return functools.partial(lcs_wavefront, dtype=dt)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def score_chunk(H: int, L: int, *, lane_dense: bool) -> int:
    """Pairs per scoring chunk: the largest power of two (at least 1,024)
    whose score temporaries fit an eighth of one device's memory.

    Bytes per pair are bounded as if every gathered row were padded to
    whole 128-lane int32 tiles.  The row path holds ``[P*H, L]`` operands
    and their repadded copies (4 rows of H codes per pair); the lane-dense
    kernel path (kernels/lcs/fused.py) holds one ``[H*L]`` gathered row per
    side plus three dense ``[H, L, P]`` operand copies.  The result
    depends only on the shapes and the device, so a compiled shape always
    has the same chunk count.
    """
    from repro.core.compat import device_memory_bytes

    if lane_dense:
        per_pair = 2 * (_round_up(H * L, 128) + 3 * H * L) * 4
    else:
        per_pair = 4 * H * _round_up(L, 128) * 4
    fit = device_memory_bytes() // 8 // per_pair
    return 1 << max(10, fit.bit_length() - 1)


def _in_chunks(fn, chunk: int, *xs):
    """``fn`` over the leading axis of ``xs`` in static chunks of ``chunk``.

    ``fn`` maps ``[c]`` inputs to a ``[H, c]`` output, pairs on the minor
    axis (a small minor axis, ``[c, H]``, would be padded to whole TPU
    tiles).  Each chunk's output is written in place into one ``[H, P]``
    buffer, returned as ``[P, H]``.  The last chunk starts at
    ``P - chunk`` and rescores a few pairs of its predecessor, so no input
    is padded and no output is sliced.
    """
    P = xs[0].shape[0]
    if P <= chunk:
        return fn(*xs).T
    part = jax.eval_shape(fn, *(x[:chunk] for x in xs))

    def body(i, out):
        start = jnp.minimum(i * chunk, P - chunk)
        lvl = fn(*(jax.lax.dynamic_slice_in_dim(x, start, chunk) for x in xs))
        return jax.lax.dynamic_update_slice_in_dim(out, lvl, start, axis=1)

    out = jnp.zeros((part.shape[0], P), part.dtype)
    return jax.lax.fori_loop(0, -(-P // chunk), body, out).T


def score_indexed(table_a, len_a, table_b, len_b, left, right, betas, *,
                  impl, window: int | None = None, off_a=None, off_b=None):
    """(level_lcs [P, H], mss [P]) of the pairs ``(table_a[left],
    table_b[right])`` — the one scoring block every path goes through.

    table_* [N, H, L] code rows, len_* [N], left/right [P] row indices
    (valid: callers clamp PAD_ID), ``impl`` from :func:`lcs_impl`.  With
    ``window`` the pairs are (row, offset) window coordinates: each side
    scores ``rows[:, off : off + clip(len - off, 0, W)]``,
    ``W = min(window, L)``.

    Scoring runs in chunks of :func:`score_chunk` pairs, so any pair count
    fits the device; the MSS is taken once over the whole ``level_lcs``.
    The device work of each chunk is named ``score/gather`` and
    ``score/lcs`` (the kernel impls name theirs in kernels/lcs/fused.py).
    """
    from repro.core.compat import on_tpu
    from repro.kernels.lcs import fused

    H, L = table_a.shape[1], table_a.shape[2]
    if off_a is None:
        off_a = off_b = jnp.zeros_like(left)
    W = None if window is None else min(window, L)

    if isinstance(impl, str):
        lane_dense = impl != "ref" and (impl != "auto" or on_tpu())

        def chunk_lcs(ia, ib, oa, ob):
            if W is None:
                return fused.fused_score(table_a, len_a, table_b, len_b,
                                         ia, ib, betas, mode=impl)[0].T
            return fused.fused_windowed_score(
                table_a, len_a, table_b, len_b, ia, ib, oa, ob, betas,
                window=W, mode=impl,
            )[0].T
    else:
        lane_dense = False

        def chunk_lcs(ia, ib, oa, ob):
            with jax.named_scope("score/gather"):
                if W is None:
                    ops = (table_a[ia], len_a[ia], table_b[ib], len_b[ib])
                else:
                    ops = (gather_windows(table_a[ia], oa, W),
                           jnp.clip(len_a[ia] - oa, 0, W),
                           gather_windows(table_b[ib], ob, W),
                           jnp.clip(len_b[ib] - ob, 0, W))
            with jax.named_scope("score/lcs"):
                return multi_level_lcs(*ops, impl=impl).T

    chunk = score_chunk(H, L, lane_dense=lane_dense)
    lvl = _in_chunks(chunk_lcs, chunk, left, right, off_a, off_b)
    return lvl, mss_scores(lvl, betas)


@functools.partial(jax.jit, static_argnames=("impl_name", "wavefront_dtype"))
def score_pairs(
    codes: jnp.ndarray,
    lengths: jnp.ndarray,
    left: jnp.ndarray,
    right: jnp.ndarray,
    betas: jnp.ndarray,
    impl_name: str = "wavefront",
    wavefront_dtype: jnp.dtype | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Gather + score candidate pairs against the encoded table.

    codes [N, H, L], lengths [N], left/right [P] -> (level_lcs [P, H], mss [P]).
    Invalid slots (PAD_ID) are clamped to row 0; callers mask by pair validity.
    Any ``lcs_impl`` name works (see :func:`lcs_impl`); the fused names
    score on the Pallas kernel over lane-dense gathered operands
    (kernels/lcs/fused.py).
    """
    from repro.core.types import PAD_ID

    li = jnp.where(left == PAD_ID, 0, left)
    ri = jnp.where(right == PAD_ID, 0, right)
    return score_indexed(
        codes, lengths, codes, lengths, li, ri, betas,
        impl=lcs_impl(impl_name, wavefront_dtype),
    )


@functools.partial(
    jax.jit,
    static_argnames=("nw", "window", "stride", "impl_name", "wavefront_dtype"),
)
def score_windowed_pairs(
    codes: jnp.ndarray,
    lengths: jnp.ndarray,
    left: jnp.ndarray,
    right: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    nw: int,
    window: int,
    stride: int = 1,
    impl_name: str = "wavefront",
    wavefront_dtype: jnp.dtype | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Windowed ``score_pairs``: pair ids are WINDOW ids, not row ids.

    codes [N, H, L], lengths [N], left/right [P] global window ids
    (``traj = w // nw``, ``offset = (w % nw) * stride``) -> (level_lcs
    [P, H], mss [P]) of the windowed slices.  The fused impls mask the
    windows in place; the row impls gather the [P, H, W] windows and run
    the batched LCS over length-W rows (2W-1 wavefront steps instead of
    2L-1).
    """
    from repro.core.types import PAD_ID

    li = jnp.where(left == PAD_ID, 0, left)
    ri = jnp.where(right == PAD_ID, 0, right)
    return score_indexed(
        codes, lengths, codes, lengths, li // nw, ri // nw, betas,
        impl=lcs_impl(impl_name, wavefront_dtype), window=window,
        off_a=(li % nw).astype(jnp.int32) * stride,
        off_b=(ri % nw).astype(jnp.int32) * stride,
    )
