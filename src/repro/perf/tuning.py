"""Cached autotune table for the LCS score stage.

The score stage's free parameter — the jnp wavefront's anti-diagonal
carry dtype (int8 rolling diagonals vs int32) — was guessed until now
(the Pallas kernel has no tile knob: it tiles pairs in whole [8, 128]
vregs).  This module stores measured winners in a small JSON table keyed per ``(P, H, L, backend)`` so the engine can look
them up instead, the same discipline REPOSE applies to its distributed
top-k search layout: tune once against the roofline harness, replay the
winner everywhere.

Three rules keep the table safe to consult from the hot path:

1. **Eager resolution only.**  Lookups happen at call boundaries (the
   engine building a runner, ``lcs_impl_fn`` closing over static args) —
   never inside a jitted trace — exactly like
   ``similarity.wavefront_dtype_from_env``.  A tuned value becomes a
   *static* kernel argument, so tuning can never introduce trace-time
   data dependence or steady-state recompiles (the runner cache keys on
   the resolved values).
2. **Bit-identical candidates only.**  Every candidate the sweep measures
   produces bit-identical scores by construction (int8 vs int32
   diagonals agree for L < 127, asserted at record time), so consulting
   the table can change throughput but never results.
3. **Environment pins win.**  An explicit ``REPRO_LCS_DTYPE`` pin
   overrides the tuned dtype — the reproducibility knob outranks the
   performance knob.

Keys quantize ``P`` (the pair-buffer size) to its ceiling power of two
because that is the granularity the capacity planner pads buffers to: two
workloads the planner maps to the same padded buffer get the same tuned
parameters.  Misses fall back to the nearest recorded ``P`` for the same
``(H, L, backend)`` (the winner varies slowly in P), then to ``None`` —
callers keep their current defaults on a total miss.

The table's one writer is ``benchmarks/roofline.py --tune``
(``PYTHONPATH=src:. python -m benchmarks.roofline --tune``).  The table
is invalidated wholesale when the schema, jax version, or backend it was
measured on changes — a stale table silently tuning a different machine is
worse than no table.
"""
from __future__ import annotations

import dataclasses
import json
import os
from pathlib import Path

import jax

from repro.core.compat import backend_name

SCHEMA = "repro-tuning/v2"

# default on-disk location; override with REPRO_TUNING_PATH
DEFAULT_PATH = Path(__file__).resolve().parents[3] / "TUNING.json"

_ENV_PATH = "REPRO_TUNING_PATH"

_DTYPES = ("int8", "int32")


def tuning_path() -> Path:
    """The table location: $REPRO_TUNING_PATH or <repo-root>/TUNING.json."""
    override = os.environ.get(_ENV_PATH)
    return Path(override) if override else DEFAULT_PATH


def quantize_pairs(pairs: int) -> int:
    """Ceiling power of two — the planner's buffer-padding granularity."""
    p = 1
    while p < max(1, pairs):
        p *= 2
    return p


@dataclasses.dataclass(frozen=True)
class LCSTuning:
    """Measured winner for one (P, H, L, backend) cell.

    ``wavefront_dtype``   "int8" | "int32" diagonal carry for the jnp
                          wavefront (overridden by REPRO_LCS_DTYPE).
    ``pairs_per_sec``     throughput of the winner when measured — carried
                          for the benchmark report, not consulted at
                          dispatch time.
    """

    wavefront_dtype: str
    pairs_per_sec: float = 0.0

    def __post_init__(self):
        if self.wavefront_dtype not in _DTYPES:
            raise ValueError(
                f"wavefront_dtype must be one of {_DTYPES}, "
                f"got {self.wavefront_dtype!r}"
            )


def _key(pairs: int, levels: int, length: int, backend: str) -> str:
    return f"P{quantize_pairs(pairs)}-H{levels}-L{length}-{backend}"


class TuningTable:
    """In-memory view of the JSON tuning table.

    Load with :meth:`load` (returns an EMPTY table on any mismatch —
    missing file, schema bump, different jax version or backend — so a
    stale table degrades to untuned defaults, never to wrong tiles),
    mutate with :meth:`record`, persist with :meth:`save`.
    """

    def __init__(self, entries: dict[str, LCSTuning] | None = None):
        self.entries: dict[str, LCSTuning] = dict(entries or {})

    # -- persistence ------------------------------------------------------

    @classmethod
    def load(cls, path: Path | str | None = None) -> "TuningTable":
        path = Path(path) if path else tuning_path()
        try:
            raw = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError):
            return cls()
        if (
            raw.get("schema") != SCHEMA
            or raw.get("jax_version") != jax.__version__
            or raw.get("backend") != backend_name()
        ):
            return cls()
        entries = {}
        for key, val in raw.get("entries", {}).items():
            try:
                entries[key] = LCSTuning(**val)
            except (TypeError, ValueError):
                return cls()  # corrupt cell -> whole table untrusted
        return cls(entries)

    def save(self, path: Path | None = None) -> Path:
        path = path or tuning_path()
        payload = {
            "schema": SCHEMA,
            "jax_version": jax.__version__,
            "backend": backend_name(),
            "entries": {
                key: dataclasses.asdict(t) for key, t in sorted(self.entries.items())
            },
        }
        path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
        return path

    # -- access -----------------------------------------------------------

    def record(
        self, pairs: int, levels: int, length: int, tuning: LCSTuning
    ) -> None:
        if length >= 127 and tuning.wavefront_dtype == "int8":
            # int8 diagonals saturate at 127; the sweep must never record a
            # dtype that could diverge from int32 results
            raise ValueError(f"int8 diagonals unsafe at L={length} (>= 127)")
        self.entries[_key(pairs, levels, length, backend_name())] = tuning

    def lookup(self, pairs: int, levels: int, length: int) -> LCSTuning | None:
        """Exact (quantized-P) hit, else nearest recorded P for the same
        (H, L, backend), else None (caller keeps its defaults)."""
        backend = backend_name()
        hit = self.entries.get(_key(pairs, levels, length, backend))
        if hit is not None:
            return hit
        want_p = quantize_pairs(pairs)
        suffix = f"-H{levels}-L{length}-{backend}"
        best, best_dist = None, None
        for key, t in self.entries.items():
            if not (key.startswith("P") and key.endswith(suffix)):
                continue
            have_p = int(key[1 : len(key) - len(suffix)].split("-")[0])
            dist = abs(have_p.bit_length() - want_p.bit_length())
            if best_dist is None or dist < best_dist:
                best, best_dist = t, dist
        return best


def resolve_wavefront_dtype(tuning: LCSTuning | None):
    """The dtype the wavefront should actually run with.

    Precedence: explicit REPRO_LCS_DTYPE env pin (reproducibility) >
    tuned dtype (performance) > the env-probe default.  Returns a jnp
    dtype, matching ``wavefront_dtype_from_env``.
    """
    import jax.numpy as jnp

    from repro.core.similarity import wavefront_dtype_from_env

    if os.environ.get("REPRO_LCS_DTYPE"):
        return wavefront_dtype_from_env()
    if tuning is not None:
        return jnp.int32 if tuning.wavefront_dtype == "int32" else jnp.int8
    return wavefront_dtype_from_env()
