#!/usr/bin/env python3
"""Find the highest query rate the resident world sustains, once.

    python3 chipbench/sweep.py --workload v51-resident.serve --seed 5 \
        --seconds 20 --rates 2000,3000,4000,5000

Builds the cell's world once, then runs its open-loop window at each rate
and prints the latency percentiles and whether the queue grew (the mean
latency of the window's last tenth of queries against its first tenth).
The benchmark never searches for a rate: the cell's traffic file holds the
one chosen from this sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from chipbench import harness
    from chipbench.drivers import query

    bench = harness.benchmark(ROOT)
    w = harness.workload(bench, args.workload)
    jax, _ = harness.start_jax(w["chips"])
    cell = harness.Cell(args.workload, harness.config(w["config"]),
                        harness.traffic(w["traffic"]), args.seed,
                        args.seconds, False, harness.CompileCounter(jax))
    query.setup(cell)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic = dict(cell.traffic, rate_per_s=rate)
        cell.rec = {"attempted": 0, "failed": 0}
        query.arrivals(cell)
        loads = cell.counter.loads
        query.window(cell)
        lat = np.asarray(cell.rec["latency_ms"])
        tenth = max(lat.size // 10, 1)
        print(json.dumps({
            "rate_per_s": rate, "queries": int(lat.size),
            "failed": cell.rec["failed"],
            "p50_ms": harness.percentile(lat, 0.5),
            "p95_ms": harness.percentile(lat, 0.95),
            "p99_ms": harness.percentile(lat, 0.99),
            "first_tenth_ms": float(np.mean(lat[:tenth])),
            "last_tenth_ms": float(np.mean(lat[-tenth:])),
            "batch_ms_median": float(np.median(cell.rec["batch_ms"])),
            "queries_per_call": float(lat.size / len(cell.rec["batch_ms"])),
            "compiles": cell.counter.loads - loads}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
