"""Roofline summary: reads experiments/dryrun.json (produced by
launch/dryrun.py) and emits the per-(arch x shape x mesh) table for
EXPERIMENTS.md §Roofline, plus a validation row comparing HLO flops against
the analytic 6*N*D model.

``python -m benchmarks.roofline --tune`` additionally runs the LCS
autotune sweep: for each (P, H, L) cell it measures every candidate
diagonal dtype of the score stage's jnp wavefront, asserts each candidate's LCS matrix is bit-identical to the untuned
default, and records the throughput winner into the
:mod:`repro.perf` tuning table (``TUNING.json`` or
``$REPRO_TUNING_PATH``).  The engine consults that table when
``ExecutionPlan(autotune=True)``.
"""
from __future__ import annotations

import argparse
import json
import pathlib

from benchmarks.common import Row


def load(path="experiments/dryrun.json"):
    p = pathlib.Path(path)
    if not p.exists():
        return []
    return json.loads(p.read_text())


def run(full: bool = False) -> list[Row]:
    rows = []
    recs = load()
    if not recs:
        return [Row("roofline/missing", 0.0,
                    "run: python -m repro.launch.dryrun first")]
    for r in recs:
        if r.get("status") != "ok":
            rows.append(Row(
                f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}", -1.0,
                str(r.get("status"))[:80],
            ))
            continue
        mem = r.get("memory", {})
        if "roofline" not in r:
            rows.append(Row(
                f"dryrun/{r['arch']}/{r['shape']}/{r['mesh']}",
                r.get("compile_s", 0) * 1e6,
                f"bytes_per_dev={mem.get('peak_bytes_est', 0):.3e}",
            ))
            continue
        rf = r["roofline"]
        rows.append(Row(
            f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}",
            rf["step_time_bound_s"] * 1e6,
            f"dom={rf['dominant']};compute={rf['compute_s']:.3g}s;"
            f"memory={rf['memory_s']:.3g}s;coll={rf['collective_s']:.3g}s;"
            f"mfu_bound={rf['mfu_bound']:.3f};"
            f"useful={rf['useful_flops_ratio']:.2f};"
            f"mem_per_dev={mem.get('peak_bytes_est', 0):.3e}",
        ))
    return rows


def summarize(path="experiments/dryrun.json"):
    """Human-readable table (used to draft EXPERIMENTS.md)."""
    recs = [r for r in load(path) if r.get("status") == "ok"]
    hdr = (f"{'arch':22s} {'shape':12s} {'mesh':8s} {'dom':10s} "
           f"{'compute_s':>10s} {'memory_s':>10s} {'coll_s':>10s} "
           f"{'MFU@bound':>9s} {'useful':>7s} {'mem/dev':>9s}")
    lines = [hdr, "-" * len(hdr)]
    for r in recs:
        rf = r.get("roofline")
        mem = r.get("memory", {}).get("peak_bytes_est", 0)
        if rf is None:
            lines.append(
                f"{r['arch']:22s} {r['shape']:12s} {r['mesh']:8s} "
                f"{'(multi-pod)':10s} {'-':>10s} {'-':>10s} {'-':>10s} "
                f"{'-':>9s} {'-':>7s} {mem/1e9:8.2f}G"
            )
            continue
        lines.append(
            f"{r['arch']:22s} {r['shape']:12s} {r['mesh']:8s} "
            f"{rf['dominant']:10s} {rf['compute_s']:10.4f} "
            f"{rf['memory_s']:10.4f} {rf['collective_s']:10.4f} "
            f"{rf['mfu_bound']:9.4f} {rf['useful_flops_ratio']:7.2f} "
            f"{mem/1e9:8.2f}G"
        )
    return "\n".join(lines)


def _tune_grid(smoke: bool):
    """(P, H, L) cells to tune.  Smoke covers the shapes the smoke bench
    and the parity tests hit; full adds the paper-scale cells."""
    if smoke:
        return [(1024, 3, 16), (4096, 3, 32)]
    return [
        (1024, 3, 16), (4096, 3, 16), (4096, 3, 32),
        (16384, 3, 32), (4096, 5, 32),
    ]


def tune(*, smoke=False, full=False, repeats=3, out_path=None):
    """Sweep the wavefront's diagonal dtype and persist the winners.

    For every grid cell the sweep builds one synthetic score-stage
    workload (same generator as bench_score), computes the untuned
    reference LCS matrix once, then measures every candidate
    ``wavefront_dtype``: int8 vs int32 anti-diagonal carries (int8 only
    where L < 127, where the two are bit-identical).

    Every candidate's output is asserted ``np.array_equal`` to the
    reference BEFORE it may win — the table can never hold a tuning
    that changes results.  Winners merge into the existing table (a
    stale table was already invalidated wholesale by ``load``).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.bench_score import _make_inputs, _time_call
    from repro.core.encoding import PAD_CODE_A, PAD_CODE_B
    from repro.core.similarity import repad
    from repro.kernels.lcs import ops as lcs_ops
    from repro.perf import LCSTuning, TuningTable, tuning_path

    path = pathlib.Path(out_path) if out_path else tuning_path()
    table = TuningTable.load(path)
    results = []
    for P, H, L in _tune_grid(smoke and not full):
        codes, lengths, left, right, betas = _make_inputs(P, H, L)
        a = repad(codes[left], lengths[left], PAD_CODE_A).reshape(P * H, L)
        b = repad(codes[right], lengths[right], PAD_CODE_B).reshape(P * H, L)
        ref = np.asarray(jax.jit(lcs_ops.lcs)(a, b))
        dtype_candidates = ("int8", "int32") if L < 127 else ("int32",)
        best = None
        for dt_name in dtype_candidates:
            dt = jnp.int8 if dt_name == "int8" else jnp.int32

            @jax.jit
            def call(a=a, b=b, dt=dt):
                return lcs_ops.lcs(a, b, mode="wavefront",
                                   wavefront_dtype=dt)

            got = np.asarray(call())
            if not np.array_equal(got, ref):
                raise AssertionError(
                    f"candidate dtype={dt_name} diverges from the untuned "
                    f"default at P={P} H={H} L={L} — refusing to record it"
                )
            wall = _time_call(call, repeats)
            pps = P / wall
            if best is None or pps > best[0]:
                best = (pps, dt_name)
        pps, dt_name = best
        winner = LCSTuning(wavefront_dtype=dt_name,
                           pairs_per_sec=round(pps, 1))
        table.record(P, H, L, winner)
        results.append((P, H, L, winner))
    table.save(path)
    return path, results


def _main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tune", action="store_true",
                    help="run the LCS autotune sweep and write the "
                         "tuning table")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny tune grid for CI (seconds, not minutes)")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale tune grid")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default=None,
                    help="tuning-table path (default: $REPRO_TUNING_PATH "
                         "or <repo>/TUNING.json)")
    args = ap.parse_args()
    if not args.tune:
        print(summarize())
        return
    path, results = tune(smoke=args.smoke, full=args.full,
                         repeats=args.repeats, out_path=args.out)
    for P, H, L, t in results:
        print(f"P={P:<6d} H={H} L={L:<3d} -> dtype={t.wavefront_dtype:<5s} "
              f"{t.pairs_per_sec:>12.0f} pairs/s")
    print(f"wrote {path}")


if __name__ == "__main__":
    _main()
