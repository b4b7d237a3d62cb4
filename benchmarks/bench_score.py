"""Score-stage benchmark: pairs/sec for wavefront vs pallas vs fused.

Measures the exact-similarity hot path (``score_pairs`` and its kernels)
in isolation over a grid of pair counts P, level counts H, sequence
lengths L, and MSS-prune rates, and writes a machine-readable
``BENCH_score.json`` so this and every later perf PR leaves a recorded
trajectory (ISSUE 3).  The tier-1 CI workflow runs ``--smoke`` and uploads
the JSON as an artifact per PR.

Implementations measured (dispatch recorded per row — on CPU the Pallas
kernels run under the interpreter and "fused" auto-dispatches to its jnp
reference, so CPU ratios document the harness, not the TPU win):

  wavefront    gather + repad + jnp anti-diagonal wavefront + mss_scores
               (the baseline ``score_pairs`` path)
  pallas       gather + repad + the blocked Pallas LCS kernel
  fused        the table-indexed kernel path: lane-dense gather from the
               resident table, in-place repad, level-fused LCS kernel
  fused+prune  MSS upper-bound prune (compaction included in the timing)
               then fused scoring of the survivors only; pairs/sec still
               counts ALL P pairs — the prune win shows up as throughput

JSON schema (``schema: bench_score/v2``)::

    {
      "schema": "bench_score/v2",
      "backend": "cpu" | "tpu" | ...,
      "jax_version": "...",
      "device_count": int,
      "smoke": bool,
      "rows": [
        {"impl": "fused", "dispatch": "kernel" | "interpret" | "ref"
                          | "wavefront",
         "P": int, "H": int, "L": int, "prune_rate": float,
         "tuned": false,
         "wavefront_dtype": "int8" | "int32" | null,
         "wall_s": float, "pairs_per_sec": float, "repeats": int}, ...
      ],
      "ratios": {"fused_vs_wavefront": {"P=4096,H=3,L=32": float, ...},
                 "pallas_vs_wavefront": {...}},
      "autotune": {   # tuned params vs library defaults, per tuned cell
        "cells": [{"P": ..., "H": ..., "L": ...,
                   "default": {"wavefront_dtype": "..."},
                   "tuned": {"wavefront_dtype": "..."},
                   "bit_identical": true, "tuned_vs_default": float}, ...]
      },
      "overlap": {    # shuffle-mode hop/score pipelining on vs off
        "skipped": str | null,   # single-device -> reason string
        "cells": [{"n_shards": ..., "cap_local": ..., "H": ..., "L": ...,
                   "pairs": ..., "overlap_chunks": ...,
                   "pairs_per_sec_nc1": float, "pairs_per_sec": float,
                   "overlap_vs_serial": float, "bit_identical": true,
                   "overflow": 0, "steady_state_recompiles": 0}, ...]
      }
    }

The ``autotune`` section compares the :mod:`repro.perf` table winners
(swept fresh by ``benchmarks.roofline.tune`` into a throwaway path)
against the library's built-in defaults — every tuned cell is asserted
bit-identical before its ratio is reported.  The only tuned parameter is
the jnp wavefront's diagonal dtype, and the default is already int8, so
the ratio sits near 1.0; the section's value is the end-to-end
sweep -> table -> lookup -> dispatch proof.

The ``overlap`` section measures the double-buffered owner-hop pipeline
(``overlap_chunks``) of the sharded shuffle score path against the serial
nc=1 program on the same inputs: score maps must match exactly, overflow
must be zero (exact per-chunk planning), and the trace counter must show
zero steady-state recompiles.  Needs >= 2 devices — run under ``run.sh``
(which fakes 8 host devices on CPU); skipped with a reason otherwise.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)
for p in (_REPO, os.path.join(_REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax
import jax.numpy as jnp
import numpy as np

IMPLS = ("wavefront", "pallas", "fused", "fused+prune")


def _make_inputs(P, H, L, *, n_rows=None, seed=0):
    """A synthetic score-stage workload: resident code table + pair list.

    Lengths are skewed (heavy short head) so prune rates are controllable
    via a quantile threshold, matching real trajectory length
    distributions.
    """
    rng = np.random.default_rng(seed)
    N = n_rows or max(256, P // 8)
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


def _tau_for_rate(lengths, left, right, betas, prune_rate):
    """The tau whose upper-bound prune drops ~prune_rate of the pairs."""
    if prune_rate <= 0.0:
        return None
    from repro.core.similarity import mss_upper_bound

    lengths, left, right = map(np.asarray, (lengths, left, right))
    ub = mss_upper_bound(
        lengths[left], lengths[right], float(np.asarray(betas).sum())
    )
    return float(np.quantile(ub, prune_rate))


def _build_call(impl, codes, lengths, left, right, betas, tau):
    """(callable returning mss, dispatch label) for one measured impl."""
    from repro.core.similarity import (
        PRUNE_EPS, mss_scores, mss_upper_bound, repad, score_pairs,
    )
    from repro.core.encoding import PAD_CODE_A, PAD_CODE_B
    from repro.kernels.lcs import ops as lcs_ops
    from repro.kernels.lcs.fused import fused_score

    from repro.core.compat import on_tpu as _on_tpu

    on_tpu = _on_tpu()
    P = left.shape[0]
    H, L = codes.shape[1], codes.shape[2]

    if impl == "wavefront":
        def call():
            _, mss = score_pairs(codes, lengths, left, right, betas,
                                 impl_name="wavefront")
            return mss

        return call, "wavefront"

    if impl == "pallas":
        @jax.jit
        def call():
            a = repad(codes[left], lengths[left], PAD_CODE_A)
            b = repad(codes[right], lengths[right], PAD_CODE_B)
            lv = lcs_ops.lcs(a.reshape(P * H, L), b.reshape(P * H, L),
                             mode="pallas" if on_tpu else "interpret",
                             ).reshape(P, H)
            return mss_scores(lv, betas)

        return call, ("kernel" if on_tpu else "interpret")

    if impl == "fused":
        @jax.jit
        def call():
            _, mss = fused_score(codes, lengths, codes, lengths, left, right,
                                 betas, mode="auto")
            return mss

        return call, ("kernel" if on_tpu else "ref")

    if impl == "fused+prune":
        t = 0.0 if tau is None else tau
        bsum = jnp.sum(betas)
        # host-planned post-prune capacity, as CapacityPlanner sizes it:
        # exact scoring then runs over the survivor buffer only
        ub_host = mss_upper_bound(
            np.asarray(lengths)[np.asarray(left)],
            np.asarray(lengths)[np.asarray(right)],
            float(np.asarray(betas).sum()),
        )
        cap = max(1, int((ub_host > np.float32(t - PRUNE_EPS)).sum()))

        @jax.jit
        def call():
            ub = mss_upper_bound(lengths[left], lengths[right], bsum)
            keep = ub > t - PRUNE_EPS
            order = jnp.argsort(jnp.logical_not(keep), stable=True)
            n_keep = jnp.minimum(jnp.sum(keep), cap)
            sl, sr = left[order][:cap], right[order][:cap]
            _, mss = fused_score(codes, lengths, codes, lengths, sl, sr,
                                 betas, mode="auto")
            return jnp.where(jnp.arange(cap) < n_keep, mss, -1.0)

        return call, ("kernel" if on_tpu else "ref")

    raise ValueError(f"unknown impl {impl!r}")


def _time_call(call, repeats):
    call().block_until_ready()  # compile + warm
    t0 = time.perf_counter()
    out = None
    for _ in range(repeats):
        out = call()
    out.block_until_ready()
    return (time.perf_counter() - t0) / repeats


def _default_dtype(impl):
    """The wavefront_dtype an UNTUNED row actually ran with (None: the
    row ran no jnp wavefront)."""
    from repro.core.similarity import wavefront_dtype_from_env

    if impl == "wavefront":
        return np.dtype(wavefront_dtype_from_env()).name
    return None


def run_grid(grid, *, repeats=3, impls=IMPLS):
    """Measure every (P, H, L, prune_rate) cell; returns the rows list."""
    rows = []
    for P, H, L, prune_rate in grid:
        codes, lengths, left, right, betas = _make_inputs(P, H, L)
        tau = _tau_for_rate(lengths, left, right, betas, prune_rate)
        for impl in impls:
            if impl == "fused+prune" and prune_rate <= 0.0:
                continue
            if impl != "fused+prune" and prune_rate > 0.0:
                continue  # prune rates only vary the fused+prune rows
            call, dispatch = _build_call(
                impl, codes, lengths, left, right, betas, tau
            )
            wall = _time_call(call, repeats)
            rows.append({
                "impl": impl, "dispatch": dispatch,
                "P": P, "H": H, "L": L, "prune_rate": prune_rate,
                "tuned": False,
                "wavefront_dtype": _default_dtype(impl),
                "wall_s": wall, "pairs_per_sec": P / wall,
                "repeats": repeats,
            })
    return rows


def _ratios(rows):
    base = {(r["P"], r["H"], r["L"]): r["pairs_per_sec"]
            for r in rows if r["impl"] == "wavefront"}
    out = {}
    for impl in ("pallas", "fused", "fused+prune"):
        rs = {}
        for r in rows:
            if r["impl"] != impl:
                continue
            key = (r["P"], r["H"], r["L"])
            if key not in base:
                continue
            tag = f"P={key[0]},H={key[1]},L={key[2]}"
            if impl == "fused+prune":
                tag += f",prune={r['prune_rate']}"
            rs[tag] = round(r["pairs_per_sec"] / base[key], 3)
        if rs:
            out[f"{impl.replace('+', '_')}_vs_wavefront"] = rs
    return out


def _grid(smoke, full):
    if smoke:
        return [(256, 3, 16, 0.0), (1024, 3, 16, 0.0), (1024, 3, 16, 0.7)]
    grid = []
    for P in (1024, 4096) + ((16384,) if full else ()):
        for L in (16, 32):
            grid.append((P, 3, L, 0.0))
            grid.append((P, 3, L, 0.5))
            grid.append((P, 3, L, 0.9))
    if full:
        grid.append((4096, 5, 32, 0.0))
    return grid


def _bench_autotune(*, repeats=2):
    """Tuned-vs-default section: sweep -> table -> lookup -> dispatch.

    Runs the real ``benchmarks.roofline.tune`` sweep into a throwaway
    table path, loads it back through :class:`repro.perf.TuningTable`,
    and re-measures each tuned cell against the library defaults.  Every
    tuned cell is asserted ``np.array_equal`` to the default's LCS matrix
    before its throughput ratio is reported — the committed benchmark is
    itself the bit-identity regression check.
    """
    import tempfile

    from benchmarks.roofline import _tune_grid, tune
    from repro.core.encoding import PAD_CODE_A, PAD_CODE_B
    from repro.core.similarity import repad
    from repro.kernels.lcs import ops as lcs_ops
    from repro.perf import TuningTable, resolve_wavefront_dtype

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "TUNING.json")
        tune(smoke=True, repeats=repeats, out_path=path)
        table = TuningTable.load(path)
        cells = []
        for P, H, L in _tune_grid(True):
            t = table.lookup(P, H, L)
            if t is None:
                continue
            codes, lengths, left, right, _ = _make_inputs(P, H, L)
            a = repad(codes[left], lengths[left], PAD_CODE_A)
            b = repad(codes[right], lengths[right], PAD_CODE_B)
            a, b = a.reshape(P * H, L), b.reshape(P * H, L)
            default = jax.jit(functools.partial(lcs_ops.lcs, mode="wavefront"))

            tuned_dt = resolve_wavefront_dtype(t)

            @jax.jit
            def tuned(a=a, b=b, dt=tuned_dt):
                return lcs_ops.lcs(a, b, mode="wavefront", wavefront_dtype=dt)

            ident = bool(np.array_equal(np.asarray(default(a, b)),
                                        np.asarray(tuned())))
            assert ident, f"tuned params diverge at P={P} H={H} L={L}"
            w_def = _time_call(lambda: default(a, b), repeats)
            w_tun = _time_call(tuned, repeats)
            cells.append({
                "P": P, "H": H, "L": L,
                "default": {"wavefront_dtype": _default_dtype("wavefront")},
                "tuned": {"wavefront_dtype": np.dtype(tuned_dt).name},
                "bit_identical": ident,
                "tuned_vs_default": round(w_def / w_tun, 3),
            })
    return {"cells": cells}


# overlap cells: (n_shards, cap_local, H, L, pairs, overlap_chunks) —
# L=32 with ~4-8k-pair sub-chunks is where the hop/score pipeline's cache
# blocking pays on CPU; on real meshes the win is hop/compute overlap
_OVERLAP_CELLS = (
    (2, 4096, 3, 32, 65536, 8),
    (4, 2048, 3, 32, 65536, 8),
)


def _bench_overlap(*, repeats=3, cells=_OVERLAP_CELLS):
    """Overlap-on vs overlap-off for the sharded shuffle score path.

    Builds the real :func:`repro.api.sharded.make_streaming_score_pipeline`
    (the hop+score program, no join) over a synthetic resident world and
    measures the identical delta-pair workload at ``overlap_chunks=1`` vs
    the cell's chunk count.  Per cell it asserts the (left, right) -> mss
    score map matches exactly (chunking only reorders output slots),
    overflow stays zero (exact per-chunk capacity planning) and the trace
    counter records zero steady-state recompiles after the first call.
    """
    from jax.sharding import Mesh

    from repro.api.sharded import (
        make_streaming_score_pipeline, plan_stream_capacities,
    )
    from repro.core.types import PAD_ID

    n_dev = jax.device_count()
    if n_dev < 2:
        return {
            "skipped": f"needs >= 2 devices, have {n_dev} "
                       "(run under ./run.sh to fake 8 host devices)",
            "cells": [],
        }

    def world(n_shards, cap_local, H, L, num_places=64, seed=0):
        rng = np.random.default_rng(seed)
        N = n_shards * cap_local
        w = 1.0 / np.arange(1, L + 1)
        lens = rng.choice(np.arange(1, L + 1), size=N, p=w / w.sum())
        places = np.full((N, L), -1, np.int32)
        for i in range(N):
            places[i, :lens[i]] = rng.integers(0, num_places, lens[i])
        g = np.arange(N)  # round-robin physical world layout
        phys = (g % n_shards) * cap_local + g // n_shards
        places_phys = np.empty_like(places)
        places_phys[phys] = places
        tables = rng.integers(0, 30, size=(H, num_places)).astype(np.int32)
        return places_phys, tables

    def pair_buffers(lo, hi, n_shards, pair_cap):
        # contiguous source chunks, front slots — the layout
        # plan_stream_capacities sizes the per-chunk hops for
        P = lo.shape[0]
        chunk = -(-P // n_shards)
        bl = np.full((n_shards * pair_cap,), PAD_ID, np.int32)
        br = np.full((n_shards * pair_cap,), PAD_ID, np.int32)
        for s in range(n_shards):
            a, b = s * chunk, min((s + 1) * chunk, P)
            bl[s * pair_cap: s * pair_cap + (b - a)] = lo[a:b]
            br[s * pair_cap: s * pair_cap + (b - a)] = hi[a:b]
        return bl, br

    out_cells = []
    for n_shards, cap_local, H, L, P, nc in cells:
        if n_dev < n_shards:
            continue
        rng = np.random.default_rng(1)
        N = n_shards * cap_local
        places, tables = world(n_shards, cap_local, H, L)
        lo = rng.integers(0, N, size=P).astype(np.int64)
        hi = rng.integers(0, N, size=P).astype(np.int64)
        betas = jnp.full((H,), 1.0 / H, jnp.float32)
        mesh = Mesh(np.array(jax.devices()[:n_shards]), ("ex",))
        res = {}
        for chunks in (1, nc):
            plan = plan_stream_capacities(
                lo, hi, n_shards, cap_local,
                score_mode="shuffle", overlap_chunks=chunks,
            )
            bl, br = pair_buffers(lo, hi, n_shards, plan.pair_cap)
            tc = [0]
            fn = make_streaming_score_pipeline(
                mesh, plan, betas=betas, score_mode="shuffle",
                lcs_impl="wavefront", trace_counter=tc,
            )
            args = (jnp.asarray(places), jnp.asarray(bl), jnp.asarray(br),
                    jnp.asarray(tables))
            r = fn(*args)
            jax.block_until_ready(r)
            traces_warm = tc[0]
            wall = _time_call(lambda: fn(*args)["mss"], repeats)
            r = fn(*args)
            ovf = int(np.asarray(r["overflow"]).sum())
            l = np.asarray(r["left"]).ravel()
            rr = np.asarray(r["right"]).ravel()
            m = np.asarray(r["mss"]).ravel()
            keep = l != PAD_ID
            smap = dict(zip(zip(l[keep].tolist(), rr[keep].tolist()),
                            m[keep].tolist()))
            res[chunks] = (wall, smap, ovf, tc[0] - traces_warm)
        w1, s1, o1, rc1 = res[1]
        wn, sn, on, rcn = res[nc]
        ident = s1 == sn
        assert ident, f"chunked scores diverge at {(n_shards, L, P, nc)}"
        out_cells.append({
            "n_shards": n_shards, "cap_local": cap_local, "H": H, "L": L,
            "pairs": P, "overlap_chunks": nc,
            "pairs_per_sec_nc1": round(P / w1, 1),
            "pairs_per_sec": round(P / wn, 1),
            "overlap_vs_serial": round(w1 / wn, 3),
            "bit_identical": ident,
            "overflow": on + o1,
            "steady_state_recompiles": rcn + rc1,
        })
    return {"skipped": None, "cells": out_cells}


def bench(*, smoke=False, full=False, repeats=None, out_path=None):
    repeats = repeats or (2 if smoke else 5)
    rows = run_grid(_grid(smoke, full), repeats=repeats)
    report = {
        "schema": "bench_score/v2",
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "device_count": jax.device_count(),
        "smoke": bool(smoke),
        "rows": rows,
        "ratios": _ratios(rows),
        "autotune": _bench_autotune(repeats=repeats),
        "overlap": _bench_overlap(repeats=max(repeats, 3)),
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2)
    return report


def run(full: bool = False):
    """benchmarks/run.py entry point: CSV rows + BENCH_score.json."""
    from benchmarks.common import Row

    report = bench(smoke=not full, full=full,
                   out_path=os.path.join(_REPO, "BENCH_score.json"))
    for r in report["rows"]:
        name = (f"bench_score/{r['impl']}/P{r['P']}_H{r['H']}_L{r['L']}"
                f"_prune{r['prune_rate']}")
        yield Row(name, r["wall_s"] * 1e6,
                  f"{r['pairs_per_sec']:.0f} pairs/s [{r['dispatch']}]")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid for CI (seconds, not minutes)")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale grid (adds P=16384, H=5)")
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--out", default="BENCH_score.json")
    args = ap.parse_args()
    report = bench(smoke=args.smoke, full=args.full, repeats=args.repeats,
                   out_path=args.out)
    print(f"# backend={report['backend']} jax={report['jax_version']}")
    for r in report["rows"]:
        print(f"{r['impl']:12s} P={r['P']:<6d} H={r['H']} L={r['L']:<3d} "
              f"prune={r['prune_rate']:.1f} [{r['dispatch']:9s}] "
              f"{r['pairs_per_sec']:>12.0f} pairs/s")
    for name, rs in report["ratios"].items():
        for tag, v in rs.items():
            print(f"# {name} {tag}: {v}x")
    for c in report["autotune"]["cells"]:
        print(f"# autotune P={c['P']},H={c['H']},L={c['L']}: "
              f"dtype={c['tuned']['wavefront_dtype']} "
              f"tuned_vs_default={c['tuned_vs_default']}x "
              f"bit_identical={c['bit_identical']}")
    ov = report["overlap"]
    if ov["skipped"]:
        print(f"# overlap: skipped ({ov['skipped']})")
    for c in ov["cells"]:
        print(f"# overlap sh={c['n_shards']} L={c['L']} P={c['pairs']} "
              f"nc={c['overlap_chunks']}: {c['overlap_vs_serial']}x "
              f"(ovf={c['overflow']}, "
              f"recompiles={c['steady_state_recompiles']})")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
