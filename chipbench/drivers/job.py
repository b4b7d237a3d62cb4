"""Closed loop of one-shot jobs: ``AnotherMeEngine.run`` back to back.

Each job is a fresh batch of the configuration's size drawn from the seed
under one forest.  Set-up runs one warm job of its own batch, so the
window compiles nothing.  The window starts jobs while it is open; it ends
when the last job it started has its similar pairs and communities on the
host, and every job it started counts.
"""
from __future__ import annotations

import numpy as np

from chipbench import data, reference
from chipbench.drivers.common import clock, exact_checks, failed_call, forest


def setup(cell) -> None:
    world = cell.cfg["world"]
    n = world["trajectories"]
    system = cell.build_system(forest(cell))
    system.run(*data.batch(world, n, cell.seed, data.WARM, 0))
    jobs = int(np.ceil(cell.seconds / cell.traffic["min_job_s"])) + 1
    cell.batches = [data.batch(world, n, cell.seed, data.BATCH, j)
                    for j in range(jobs)]


def window(cell) -> None:
    jobs, results = [], []
    traced_job = 1 if cell.seconds > 2 * cell.traffic["min_job_s"] else 0
    t0 = clock()
    end = t0
    for j, batch in enumerate(cell.batches):
        if clock() - t0 >= cell.seconds:
            break
        if j == traced_job:
            cell.trace_begin()
        cell.rec["attempted"] += 1
        ts = clock()
        try:
            with cell.span("job"):
                pairs, comms, stats = cell.system.run(*batch)
        except Exception as e:  # a failed job is counted, not fatal
            failed_call(cell, f"job {j}", e)
            results.append(None)
            continue
        end = clock()
        jobs.append({"wall_s": end - ts, "stats": stats})
        results.append((pairs, comms))
        if j == traced_job:
            cell.trace_end()
            cell.rec["traced_pairs"] = stats["num_candidates"]
    cell.rec["window_s"] = end - t0
    cell.rec["jobs"] = jobs
    cell.results = results


def check(cell) -> list:
    """One job drawn from the seed among those the window started."""
    n = len(cell.results)
    j = int(data.rng(cell.seed, data.SAMPLE).integers(n)) if n else 0
    got = cell.results[j] if n else None
    cell.results = None
    cell.free_system()
    if got is None:
        return [("answers_missing", 1, 0)]
    places, lengths = cell.batches[j]
    world = reference.World(cell.tables, cell.cfg["engine"], places, lengths,
                            np.arange(places.shape[0]))
    return [("answers_missing", 0, 0)] + exact_checks(cell, *got, world)
