"""Roofline summary and LCS autotune sweep.

``python -m benchmarks.roofline`` prints the roofline table of the
records ``python -m repro.launch.dryrun`` appends to
``experiments/dryrun.json``.

``python -m benchmarks.roofline --tune`` runs the LCS autotune sweep:
for each (P, H, L) cell it measures every candidate diagonal dtype of the
score stage's jnp wavefront, asserts each candidate's LCS matrix is
bit-identical to the untuned default, and records the throughput winner
into the :mod:`repro.perf` tuning table (``TUNING.json`` or
``$REPRO_TUNING_PATH``).  The engine consults that table when
``ExecutionPlan(autotune=True)``.  Run both from the repo root with
``PYTHONPATH=src:.``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time


def load(path="experiments/dryrun.json"):
    p = pathlib.Path(path)
    if not p.exists():
        return []
    return json.loads(p.read_text())


def summarize(path="experiments/dryrun.json"):
    """Human-readable roofline table of the dry-run records."""
    recs = [r for r in load(path) if r.get("status") == "ok"]
    hdr = (f"{'arch':22s} {'shape':12s} {'mesh':8s} {'dom':10s} "
           f"{'compute_s':>10s} {'memory_s':>10s} {'coll_s':>10s} "
           f"{'mem/dev':>9s}")
    lines = [hdr, "-" * len(hdr)]
    for r in recs:
        rf = r["roofline"]
        mem = r.get("memory", {}).get("peak_bytes_est", 0)
        lines.append(
            f"{r['arch']:22s} {r['shape']:12s} {r['mesh']:8s} "
            f"{rf['dominant']:10s} {rf['compute_s']:10.4f} "
            f"{rf['memory_s']:10.4f} {rf['collective_s']:10.4f} "
            f"{mem/1e9:8.2f}G"
        )
    return "\n".join(lines)


def _make_inputs(P, H, L, *, seed=0):
    """A synthetic score-stage workload: code table + pair list.

    Lengths are skewed (heavy short head), matching real trajectory
    length distributions.
    """
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    N = max(256, P // 8)
    w = 1.0 / np.arange(1, L + 1)
    lengths = rng.choice(np.arange(1, L + 1), size=N, p=w / w.sum())
    lengths = lengths.astype(np.int32)
    codes = rng.integers(0, 30, size=(N, H, L)).astype(np.int32)
    pad = np.arange(L)[None, None, :] >= lengths[:, None, None]
    codes = np.where(pad, -1, codes)
    left = rng.integers(0, N, size=P).astype(np.int32)
    right = rng.integers(0, N, size=P).astype(np.int32)
    betas = np.full((H,), 1.0 / H, np.float32)
    return (jnp.asarray(codes), jnp.asarray(lengths), jnp.asarray(left),
            jnp.asarray(right), jnp.asarray(betas))


def _time_call(call, repeats):
    call().block_until_ready()  # compile + warm
    t0 = time.perf_counter()
    out = None
    for _ in range(repeats):
        out = call()
    out.block_until_ready()
    return (time.perf_counter() - t0) / repeats


def _tune_grid(smoke: bool):
    """(P, H, L) cells to tune.  Smoke covers the shapes the parity tests
    hit; full adds the paper-scale cells."""
    if smoke:
        return [(1024, 3, 16), (4096, 3, 32)]
    return [
        (1024, 3, 16), (4096, 3, 16), (4096, 3, 32),
        (16384, 3, 32), (4096, 5, 32),
    ]


def tune(*, smoke=False, full=False, repeats=3, out_path=None):
    """Sweep the wavefront's diagonal dtype and persist the winners.

    For every grid cell the sweep builds one synthetic score-stage
    workload (:func:`_make_inputs`), computes the untuned
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
