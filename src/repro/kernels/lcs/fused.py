"""Table-indexed scoring on the LCS kernel: table + pair ids -> (level_lcs, MSS).

The hot path of the pipeline is exact pair scoring: for every surviving
candidate pair (l, r), the LCS of the two trajectories' encodings at every
semantic level, beta-combined into the MSS (paper section IV.3).

The wrappers here take the resident code table(s) and the pair indices
directly and build the kernel's lane-dense operands in one XLA pass:

* **Lane-dense gather** — each pair's ``[H, L]`` rows are gathered as one
  flat ``[H*L]`` row, and the gathered block is transposed to
  ``[H, L, P]`` so pairs sit on the vector lanes (kernels/lcs/kernel.py).
  Gathering ``[P, H, L]`` instead would pad every pair's ``(H, L)`` minor
  tile to a full TPU tile, many times its logical size.
* **Repad and windowing in the same pass** — positions ``>= length``
  become the side sentinels (side A: -1, side B: -2, exactly
  ``similarity.repad``); the windowed variant keeps only
  ``[off, off + clip(len - off, 0, W))``.  Sentinels never match, so the
  masked full-row LCS IS the windowed LCS, and the windowed slices are
  never moved to the front of the row.
* **Level fusion** — all H levels of a pair run in one kernel call.

The MSS is ``similarity.mss_scores`` over the integer ``level_lcs``, the
same lowering every other ``lcs_impl`` uses, so scores are bit-identical
across impls.  Callers bound the pair count per call
(``similarity.score_indexed`` scores in chunks), which bounds the gathered
operands; nothing is held in SMEM, so no table or pair count is limited by
it.

Two tables are taken (``table_a``/``table_b``) so the same wrappers serve
both sharded score modes: "replicate" passes the all_gathered code table
twice with real pair indices, "shuffle" passes the two per-shard gathered
operand stacks with iota indices (the gather there already happened via
the owner hops).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.compat import on_tpu as _on_tpu
from repro.core.encoding import PAD_CODE_A, PAD_CODE_B
from repro.kernels.lcs.kernel import lcs_lanes

# the canonical lcs_impl-name -> dispatch-mode mapping for the names that
# score on the Pallas kernel; every registration point (stages,
# score_pairs, the sharded pipeline) reads THIS dict
FUSED_IMPL_MODES = {
    "fused": "auto",
    "fused-pallas": "pallas",
    "fused-interpret": "interpret",
}

_DISPATCH_MODES = ("auto", "pallas", "interpret", "ref")


def kernel_interpret(mode: str) -> bool:
    """Whether a forced kernel mode runs interpreted.

    "pallas" is the compiled kernel and refuses to run off the TPU rather
    than quietly falling back to the interpreter; "interpret" is the
    interpreter everywhere.
    """
    if mode == "interpret":
        return True
    if not _on_tpu():
        raise RuntimeError(
            f"LCS kernel mode {mode!r} compiles for the TPU and the default "
            "backend is not one; use the '-interpret' variant off the TPU"
        )
    return False


def _lane_operand(table, lengths, idx, pad_code, off=None, window=None):
    """Gather ``table[idx]`` as masked lane-dense operands [H, L, P]."""
    n, H, L = table.shape
    rows = table.reshape(n, H * L)[idx]          # [P, H*L]: one row per pair
    x = rows.T.reshape(H, L, idx.shape[0])       # pairs on the minor axis
    pos = jnp.arange(L, dtype=jnp.int32)[None, :, None]
    length = lengths[idx][None, None, :]
    if off is None:
        keep = pos < length
    else:
        o = off.astype(jnp.int32)[None, None, :]
        keep = (pos >= o) & (pos < o + jnp.clip(length - o, 0, window))
    return jnp.where(keep, x, pad_code)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def _gather_score(table_a, len_a, table_b, len_b, left, right, off_a, off_b,
                  betas, *, window, interpret):
    from repro.core.similarity import mss_scores

    L = table_a.shape[-1]
    assert L < 127 and table_b.shape[1:] == table_a.shape[1:]
    W = None if window is None else min(window, L)
    with jax.named_scope("score/gather"):
        a = _lane_operand(table_a, len_a, left, PAD_CODE_A, off_a, W)
        b = _lane_operand(table_b, len_b, right, PAD_CODE_B, off_b, W)
    with jax.named_scope("score/lcs"):
        lvl = lcs_lanes(a, b, interpret=interpret).T
    return lvl, mss_scores(lvl, betas)


def fused_gather_score(table_a, len_a, table_b, len_b, left, right, betas,
                       *, interpret: bool = False):
    """The raw kernel call: tables + pair indices -> (level_lcs, mss).

    table_a [Na, H, L] int32, len_a [Na] int32 (idem _b), left/right [P]
    int32 indices into the respective tables (pre-clamped: no PAD_ID), betas
    [H] float32 -> (level_lcs [P, H] int32, mss [P] float32).
    """
    return _gather_score(
        table_a, len_a, table_b, len_b, left, right, None, None, betas,
        window=None, interpret=interpret,
    )


def fused_windowed_gather_score(table_a, len_a, table_b, len_b, left, right,
                                off_a, off_b, betas, *, window: int,
                                interpret: bool = False):
    """The raw windowed kernel call: tables + (traj, offset) coordinates.

    Identical to :func:`fused_gather_score` except pairs carry per-side
    window offsets: left/right [P] are TRAJECTORY indices into the tables,
    off_a/off_b [P] the window start offsets, and the scored operand is
    the [H, W] slice ``rows[:, off : off + clip(len - off, 0, window)]``,
    masked in place.
    """
    return _gather_score(
        table_a, len_a, table_b, len_b, left, right, off_a, off_b, betas,
        window=window, interpret=interpret,
    )


def fused_score_ref(
    table_a, len_a, table_b, len_b, left, right, betas
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """jnp oracle for the fused kernel: the baseline gather-then-score path
    (``multi_level_lcs`` + ``mss_scores``), bit-identical by construction to
    ``score_pairs(..., impl_name="wavefront")``."""
    from repro.core.similarity import mss_scores, multi_level_lcs

    lvl = multi_level_lcs(
        table_a[left], len_a[left], table_b[right], len_b[right]
    )
    return lvl, mss_scores(lvl, betas)


def fused_score(
    table_a: jnp.ndarray,
    len_a: jnp.ndarray,
    table_b: jnp.ndarray,
    len_b: jnp.ndarray,
    left: jnp.ndarray,
    right: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    mode: str = "auto",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Dispatch wrapper mirroring kernels/lcs/ops.lcs:

      "auto"       the kernel on TPU, the jnp reference elsewhere (the
                   interpreter would be orders of magnitude slower than the
                   wavefront on CPU) — the production default.
      "pallas"     always the compiled kernel; refuses off the TPU.
      "interpret"  always the kernel with interpret=True, even on TPU.
      "ref"        always the jnp gather-then-score reference.
    """
    if mode not in _DISPATCH_MODES:
        raise ValueError(
            f"unknown fused dispatch mode {mode!r}; "
            f"valid: {list(_DISPATCH_MODES)}"
        )
    if mode == "ref" or (mode == "auto" and not _on_tpu()):
        return fused_score_ref(table_a, len_a, table_b, len_b, left, right, betas)
    return fused_gather_score(
        table_a, len_a, table_b, len_b, left, right, betas,
        interpret=mode != "auto" and kernel_interpret(mode),
    )


def fused_windowed_score_ref(
    table_a, len_a, table_b, len_b, left, right, off_a, off_b, betas,
    *, window: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """jnp oracle for the windowed kernel: gather the [P, H, W] window
    slices (``similarity.gather_windows``) and run the baseline
    gather-then-score path over length-W rows — bit-identical by
    construction to ``score_windowed_pairs(..., impl_name="wavefront")``."""
    from repro.core.similarity import (
        gather_windows, mss_scores, multi_level_lcs,
    )

    W = min(window, table_a.shape[-1])
    wla = jnp.clip(len_a[left] - off_a, 0, W)
    wlb = jnp.clip(len_b[right] - off_b, 0, W)
    lvl = multi_level_lcs(
        gather_windows(table_a[left], off_a, W), wla,
        gather_windows(table_b[right], off_b, W), wlb,
    )
    return lvl, mss_scores(lvl, betas)


def fused_windowed_score(
    table_a: jnp.ndarray,
    len_a: jnp.ndarray,
    table_b: jnp.ndarray,
    len_b: jnp.ndarray,
    left: jnp.ndarray,
    right: jnp.ndarray,
    off_a: jnp.ndarray,
    off_b: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    window: int,
    mode: str = "auto",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Windowed twin of :func:`fused_score`: same dispatch modes, pairs
    carry (traj, offset) coordinates."""
    if mode not in _DISPATCH_MODES:
        raise ValueError(
            f"unknown fused dispatch mode {mode!r}; "
            f"valid: {list(_DISPATCH_MODES)}"
        )
    if mode == "ref" or (mode == "auto" and not _on_tpu()):
        return fused_windowed_score_ref(
            table_a, len_a, table_b, len_b, left, right, off_a, off_b,
            betas, window=window,
        )
    return fused_windowed_gather_score(
        table_a, len_a, table_b, len_b, left, right, off_a, off_b, betas,
        window=window, interpret=mode != "auto" and kernel_interpret(mode),
    )
