"""Phase (ii) part 1: k-sequential shingling (paper Definition 3, Algorithm 1).

A k-sequential shingle is an order-preserving k-subsequence of the *type*
level codes of a trajectory.  The paper's Algorithm 1 is a triple nested loop
(k=3); on TPU we replace it with a static gather over the precomputed
C(L_max, k) index combinations followed by a base-Q integer pack, one vector
op per combination batch — O(N * C(L,k)) work with zero data-dependent
control flow.  Set semantics (distinct shingles per trajectory) are restored
with an in-row sort + duplicate masking, as the paper dedups shingles before
the self-join.

The packed shingle key is ``sum_i code_i * Q**(k-1-i)`` — a perfect hash of
the shingle (no collisions), which is what lets AnotherMe achieve 100%
accuracy where MinHash/BRP lose information.  We require Q**k < 2**31 and
fall back to a 2-word key above that (not needed for the paper's Q<=300,k=3).
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import PAD_KEY


# Hard budget on C(max_len, k): the combination table materializes
# eagerly into an unbounded lru_cache, so an oversized (max_len, k) would
# exhaust host memory before any shape error surfaced.  2M combos is
# ~24 MB at k=3 — far above the paper's C(10, 3) = 120 but small enough
# that the failure mode is a clear exception, not an OOM.
MAX_SHINGLE_COMBOS = 2_000_000


@functools.lru_cache(maxsize=None)
def shingle_indices(max_len: int, k: int) -> np.ndarray:
    """All C(max_len, k) strictly-increasing index k-tuples, int32 [S, k]."""
    from math import comb

    n_combos = comb(max_len, k) if max_len >= k >= 0 else 0
    if n_combos > MAX_SHINGLE_COMBOS:
        raise ValueError(
            f"C({max_len}, {k}) = {n_combos} shingle combinations exceeds "
            f"the budget of {MAX_SHINGLE_COMBOS}; shingling the full "
            "trajectory at this length would exhaust host memory.  Use the "
            "windowed subtrajectory mode instead — "
            "EngineConfig(subtraj_window=W) shingles C(W, k) combinations "
            "per sliding window."
        )
    combos = np.array(list(itertools.combinations(range(max_len), k)), dtype=np.int32)
    if combos.size == 0:
        combos = combos.reshape(0, k)
    return combos


def num_shingles(max_len: int, k: int) -> int:
    return shingle_indices(max_len, k).shape[0]


def expected_collision_rate(avg_len: float, k: int, num_types: int) -> float:
    """The paper's collision-rate model: C(L, k) / Q**k (section IV.2)."""
    from math import comb

    return comb(int(avg_len), k) / float(num_types) ** k


def pack_keys(codes: jnp.ndarray, num_types: int) -> jnp.ndarray:
    """Base-Q pack of [..., k] type codes into one int32 key."""
    k = codes.shape[-1]
    if num_types**k >= 2**31:
        raise ValueError(
            f"Q**k = {num_types}**{k} overflows int32; use a smaller k or Q "
            "(the paper uses Q<=300, k=3)."
        )
    key = jnp.zeros(codes.shape[:-1], dtype=jnp.int32)
    for i in range(k):
        key = key * num_types + codes[..., i]
    return key


@functools.partial(jax.jit, static_argnames=("k", "num_types", "dedup"))
def shingles_from_types(
    type_codes: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    k: int,
    num_types: int,
    dedup: bool = True,
) -> jnp.ndarray:
    """Distinct k-sequential shingle keys per trajectory.

    type_codes: int32 [N, L] (coarsest-level codes, padding may be negative)
    lengths:    int32 [N]
    returns:    int32 [N, S] ascending-sorted keys, PAD_KEY padded,
                S = C(L, k).
    """
    n, L = type_codes.shape
    idx = jnp.asarray(shingle_indices(L, k))  # [S, k]
    # gather: [N, S, k]
    gathered = type_codes[:, idx]
    # a combination is valid iff its last (largest) index < length
    valid = idx[:, -1][None, :] < lengths[:, None]  # [N, S]
    safe = jnp.where(valid[..., None], gathered, 0)
    keys = pack_keys(safe, num_types)
    keys = jnp.where(valid, keys, PAD_KEY)
    if dedup:
        # one operand: stability cannot show, and costs the TPU compiler
        keys = jnp.sort(keys, axis=-1, stable=False)
        dup = jnp.concatenate(
            [jnp.zeros((n, 1), dtype=bool), keys[:, 1:] == keys[:, :-1]], axis=1
        )
        keys = jnp.where(dup, PAD_KEY, keys)
        keys = jnp.sort(keys, axis=-1, stable=False)
    return keys


def shingles(encoded_codes: jnp.ndarray, lengths: jnp.ndarray, *, k: int,
             num_types: int, level: int = 0, dedup: bool = True) -> jnp.ndarray:
    """Convenience wrapper taking EncodedBatch.codes [N, n_levels, L]."""
    return shingles_from_types(
        encoded_codes[:, level, :], lengths, k=k, num_types=num_types, dedup=dedup
    )


def windowed_types(
    type_codes: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    window: int,
    stride: int = 1,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sliding-window view for the subtrajectory mode: [N, L] -> [N*nw, W].

    Window j of row i (j < nw, see :func:`repro.core.subtraj.num_windows`)
    starts at offset ``j * stride`` and holds
    ``clip(lengths[i] - j*stride, 0, W)`` valid positions; every window of
    row i becomes its own virtual row ``i * nw + j``, so downstream key
    machinery (``shingles_from_types``, MinHash, BRP) runs UNCHANGED over
    the windowed view — a window's keys are ``S = C(W, k)`` combinations
    instead of ``C(L, k)``.  Positions past a window's valid length gather
    clamped garbage; callers mask by the returned window lengths exactly
    as they mask full rows by ``lengths`` (both shingling and the hash
    backends already do).
    """
    from repro.core.subtraj import num_windows

    n, L = type_codes.shape
    W = min(window, L)
    nw = num_windows(L, window, stride)
    offs = jnp.arange(nw, dtype=jnp.int32) * stride           # [nw]
    pos = offs[:, None] + jnp.arange(W, dtype=jnp.int32)      # [nw, W]
    win = type_codes[:, jnp.clip(pos, 0, L - 1)]              # [N, nw, W]
    wlen = jnp.clip(lengths[:, None] - offs[None, :], 0, W)   # [N, nw]
    return win.reshape(n * nw, W), wlen.reshape(n * nw)
