"""Post-compile HLO analysis: collective bytes + roofline terms.

``cost_analysis()`` gives per-device FLOPs/bytes but no collective traffic;
we parse the post-SPMD HLO text and sum operand sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
(and ragged-all-to-all) op, per the assignment's roofline recipe.

Hardware constants (TPU v5e): 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link
ICI.
"""
from __future__ import annotations

import dataclasses
import re

PEAK_FLOPS = 197e12      # bf16 / chip
HBM_BW = 819e9           # bytes/s / chip
ICI_BW = 50e9            # bytes/s/link (per-chip injection, one link)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "ragged-all-to-all",
)

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPNAME_RE = re.compile(r"\b(" + "|".join(_COLLECTIVES) + r")(-start|-done)?\(")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _result_bytes(rhs: str) -> int:
    """Bytes of the result type(s) at the start of an HLO instruction RHS."""
    # result type is everything before the op name; shapes after the first
    # op-paren belong to operands in some dialects — cut at the first
    # lowercase-word+'(' that is NOT a dtype token.
    cut = len(rhs)
    m = re.search(r"[a-z][a-z0-9\-]*\(", rhs)
    if m:
        cut = m.start()
    total = 0
    for dt, dims in _SHAPE_RE.findall(rhs[:cut]):
        total += _shape_bytes(dt, dims)
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum of operand bytes per collective kind (per-device shard shapes).

    CPU-backend HLO references operands by name only, so we first build a
    name -> result-bytes symbol table, then resolve each collective's
    operand list against it.  Async pairs (-start/-done) count once.
    """
    table: dict[str, int] = {}
    coll_lines: list[tuple[str, str]] = []
    for line in hlo_text.splitlines():
        d = _DEF_RE.match(line)
        if not d:
            continue
        name, rhs = d.group(1), d.group(2)
        table[name] = _result_bytes(rhs)
        m = _OPNAME_RE.search(rhs)
        if m and m.group(2) != "-done":
            args = rhs[m.end():]
            # cut at the closing paren of the operand list (before attrs)
            depth = 1
            for i, ch in enumerate(args):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        args = args[:i]
                        break
            coll_lines.append((m.group(1), args))

    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for kind, args in coll_lines:
        total = 0
        for op in _OPERAND_RE.findall(args):
            total += table.get(op, 0)
        # inline-shaped operands (TPU-style HLO)
        for dt, dims in _SHAPE_RE.findall(args):
            total += _shape_bytes(dt, dims)
        out[kind] += total
        counts[kind] += 1
    return {"bytes": out, "counts": counts, "total_bytes": sum(out.values())}


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    hlo_flops: float            # per device
    hlo_bytes: float            # per device
    coll_bytes: float           # per device
    model_flops: float          # analytic 6*N*D (global)
    chips: int

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline lower bound: max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def mfu(self) -> float:
        """MODEL_FLOPS / (chips * peak * step_time) at the roofline bound."""
        if self.step_time_s == 0:
            return 0.0
        return self.model_flops / (self.chips * PEAK_FLOPS * self.step_time_s)

    @property
    def useful_flops_ratio(self) -> float:
        total_hlo = self.hlo_flops * self.chips
        return self.model_flops / total_hlo if total_hlo else 0.0

    def as_dict(self) -> dict:
        return {
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "hlo_flops_per_dev": self.hlo_flops,
            "hlo_bytes_per_dev": self.hlo_bytes,
            "coll_bytes_per_dev": self.coll_bytes,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "step_time_bound_s": self.step_time_s,
            "mfu_bound": self.mfu,
            "chips": self.chips,
        }


def memory_summary(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "peak_bytes_est": ma.argument_size_in_bytes
        + ma.output_size_in_bytes
        + ma.temp_size_in_bytes
        - ma.alias_size_in_bytes,
        "generated_code_bytes": ma.generated_code_size_in_bytes,
    }
