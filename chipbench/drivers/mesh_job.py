"""Closed loop of one-shot jobs on the mesh: ``AnotherMeEngine.run`` with
the configuration's ``ExecutionPlan``, back to back.

The loop is the ``job`` driver's (a fresh batch a job, one warm job of its
own batch in set-up, every job the window started counts); only the
system differs, an engine sharded over the configuration's chips, and the
check, whose reference spreads the LCS over the devices
(``reference_blocked``), as the world is too large to score from the host
in a run's time.

Set-up has a time limit of its own (``SETUP_LIMIT_S``): past it the run
could not fit its window and its check inside the run's 360 s, so it
stops there, with the main thread's stack on stderr and exit code 1,
rather than run on until it is killed.  A program whose warm job cannot
run at this size (one that compiles for many minutes, say) thus fails in
set-up.  The check prints its own seconds to stderr.
"""
from __future__ import annotations

import os
import sys
import threading
import traceback

import numpy as np

from chipbench import data, system
from chipbench.drivers import job
from chipbench.drivers.common import clock, exact_checks
from chipbench.reference_blocked import BlockedWorld

# the run's 360 s less what follows set-up: the window (51 s and the last
# job, about 15 s) and the check (60-100 s on four v5e chips)
SETUP_LIMIT_S = 220


class MeshBatch(system.Batch):
    """One-shot jobs on an engine built with the configuration's plan."""

    def __init__(self, cfg: dict, forest: dict):
        api, _, _ = system.program()
        self.engine = api.AnotherMeEngine(
            system._forest(forest), api.EngineConfig(**cfg["engine"]),
            api.ExecutionPlan(**cfg["plan"]))


def _stop_after(seconds: float) -> threading.Timer:
    """A timer that ends the process with exit code 1 after ``seconds``,
    first printing where the main thread was."""
    main = threading.main_thread().ident

    def stop():
        print(f"mesh_job: set-up ran past its limit of {seconds} s",
              file=sys.stderr)
        frame = sys._current_frames().get(main)
        if frame is not None:
            traceback.print_stack(frame, file=sys.stderr)
        sys.stderr.flush()
        os._exit(1)

    timer = threading.Timer(seconds, stop)
    timer.daemon = True
    timer.start()
    return timer


def setup(cell) -> None:
    if cell._factory is None:
        cell._factory = MeshBatch
    print(f"mesh_job: set-up limit {SETUP_LIMIT_S} s", file=sys.stderr,
          flush=True)
    timer = _stop_after(SETUP_LIMIT_S)
    try:
        job.setup(cell)
    finally:
        timer.cancel()


window = job.window


def check(cell) -> list:
    """One job drawn from the seed among those the window started."""
    t0 = clock()
    try:
        return _check(cell)
    finally:
        print(f"mesh_job: check took {clock() - t0:.1f} s", file=sys.stderr,
              flush=True)


def _check(cell) -> list:
    n = len(cell.results)
    j = int(data.rng(cell.seed, data.SAMPLE).integers(n)) if n else 0
    got = cell.results[j] if n else None
    cell.results = None
    cell.free_system()
    if got is None:
        return [("answers_missing", 1, 0)]
    places, lengths = cell.batches[j]
    world = BlockedWorld(cell.tables, cell.cfg["engine"], places, lengths,
                         np.arange(places.shape[0]))
    return [("answers_missing", 0, 0)] + exact_checks(cell, *got, world)
