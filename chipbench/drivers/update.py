"""Closed loop of sliding-window updates: ``StreamingEngine.update`` back to
back on the full window.

Set-up fills the window and goes a few updates past it, so the retirement
path has run and every program the window runs is warm.  Each window
update then admits ``update_rows`` new rows and retires as many.  The
window starts updates while it is open and ends when the last one it
started returns; every update it started counts.  It holds at most
``max_updates``: set-up plus the window stay short of the engine's first
compaction (when the retired rows reach half the resident ones, 2 x
window updates in), whose programs set-up does not warm.
"""
from __future__ import annotations

from chipbench.drivers.common import (
    build_world, clock, exact_checks, failed_call, live_world, update_rows,
)


def setup(cell) -> None:
    build_world(cell, cell.traffic["warm_updates"])
    first = cell.updates
    cell.pending = [update_rows(cell, u)
                    for u in range(first, first + cell.traffic["max_updates"])]


def window(cell) -> None:
    system = cell.system
    update_ms, expire_s, rows = [], [], 0
    traced = cell.traffic["traced_updates"]
    t0 = clock()
    end = t0
    for u, (places, lengths) in enumerate(cell.pending):
        if clock() - t0 >= cell.seconds:
            break
        if u == traced[0]:
            cell.trace_begin()
        cell.rec["attempted"] += 1
        ts = clock()
        try:
            with cell.span("update"):
                stats = system.update(places, lengths)
        except Exception as e:  # a failed update is counted, not fatal
            failed_call(cell, f"update {cell.updates}", e)
            break
        end = clock()
        cell.updates += 1
        update_ms.append((end - ts) * 1e3)
        expire_s.append(stats["t_expire"])
        rows += places.shape[0]
        if u == traced[1]:
            cell.trace_end()
    cell.trace_end()
    cell.pending = None
    cell.rec.update(window_s=end - t0, rows=rows, update_ms=update_ms,
                    expire_s=expire_s)


def check(cell) -> list:
    """The world's similar pairs and communities after the window against
    the reference's over the live rows."""
    if cell.system.last is None:
        return [("answers_missing", 1, 0)]
    pairs, comms = cell.system.result()
    cell.free_system()
    return [("answers_missing", 0, 0)] + exact_checks(
        cell, pairs, comms, live_world(cell))
