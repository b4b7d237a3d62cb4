"""Public wrapper for the batched LCS kernel.

Dispatches a batch of sentinel-padded row pairs to the Pallas kernel
(kernels/lcs/kernel.py) or the jnp wavefront.  The wrapper is
shard-local-shape aware: it is traceable inside a shard_map program, where
the batch is the per-shard pair buffer; the kernel pads any remainder to
whole blocks with never-matching sentinels.

``mode`` selects the dispatch policy:

  "auto"       the Pallas kernel on TPU, the jnp wavefront elsewhere — the
               production default.
  "pallas"     always the compiled Pallas kernel; refuses off the TPU.
  "interpret"  always the Pallas kernel with interpret=True, even on TPU.
  "wavefront"  always the jnp anti-diagonal wavefront.

The kernel tiles pairs in whole ``[8, 128]`` vregs
(:func:`repro.kernels.lcs.kernel.block_rows`); there is no tile knob.
The engine's ``lcs_impl`` names do not come through here: they score by
table index through ``kernels/lcs/fused.py``, which lays the operands out
lane-dense directly.  This wrapper serves callers that hold row pairs.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.compat import on_tpu as _on_tpu
from repro.kernels.lcs.fused import kernel_interpret
from repro.kernels.lcs.kernel import lcs_pallas
from repro.core.similarity import lcs_wavefront, wavefront_dtype_from_env


def lcs(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    mode: str = "auto",
    wavefront_dtype: jnp.dtype | None = None,
) -> jnp.ndarray:
    """Batched LCS: int32 [B, L] x2 -> int32 [B].

    Inputs must be sentinel-padded (side A: -1, side B: -2) as produced by
    repro.core.similarity.repad.

    This wrapper is deliberately NOT jitted: it is pure dispatch (the kernel
    and the wavefront are jitted themselves), and it is the call boundary
    where the REPRO_LCS_DTYPE probe is resolved into the wavefront's static
    ``dtype`` argument (``wavefront_dtype=None`` -> read the env var here,
    never inside a trace).  A tuned dtype flows in the same way: the
    autotune table is resolved eagerly and passed as ``wavefront_dtype``.
    """
    if mode not in ("auto", "pallas", "interpret", "wavefront"):
        raise ValueError(
            f"unknown lcs dispatch mode {mode!r}; "
            "valid: ['auto', 'pallas', 'interpret', 'wavefront']"
        )
    B, L = a.shape
    assert b.shape == (B, L)
    if mode == "wavefront" or (mode == "auto" and not _on_tpu()):
        if wavefront_dtype is None:
            wavefront_dtype = wavefront_dtype_from_env()
        return lcs_wavefront(a, b, dtype=wavefront_dtype)
    interpret = mode != "auto" and kernel_interpret(mode)
    return lcs_pallas(a, b, interpret=interpret)
