#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the control on the chip.

    python3 chipbench/control.py --workload v51-batch.jobs \
        --seeds 11,12,13 --seconds 20 --variant control

Runs the cell once per seed in one process and prints one JSON line per
run with its checks.  ``--variant program`` runs the program as the
benchmark does (the lower readings); ``--variant control`` runs the
control, the program with its approximate MinHash candidate path
(``faults.control``), which has to read as not correct (the upper
readings).  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--variant", choices=("program", "control"),
                    default="control")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from chipbench import faults, harness

    bench = harness.benchmark(ROOT)
    factory = faults.control if args.variant == "control" else None
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        try:
            out = harness.run_cell(bench, args.workload, seed, args.seconds,
                                   False, system_factory=factory)
        except harness.NoChip as e:
            print(f"no result: {e}", file=sys.stderr)
            return 3
        print(json.dumps({"workload": args.workload, "variant": args.variant,
                          "seed": seed, "correct": out["correct"],
                          "checks": out["checks"],
                          "metrics": out["metrics"],
                          "run_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
