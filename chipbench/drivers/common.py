"""Pieces the drivers share: the seeded forest, the resident world's
update stream, and the reference's view of what is live."""
from __future__ import annotations

import sys
import time

import numpy as np

from chipbench import data, reference

clock = time.perf_counter


def forest(cell) -> dict:
    """The configuration's forest from the seed; keeps its level tables."""
    f = data.forest(cell.cfg["world"], cell.seed)
    cell.tables = data.level_tables(f)
    return f


def update_rows(cell, u: int):
    """(places, lengths) of the u-th update of the resident world."""
    return data.batch(cell.cfg["world"], cell.cfg["update_rows"], cell.seed,
                      data.BATCH, u)


def build_world(cell, updates: int) -> None:
    """Ingest updates 0 .. updates-1 through the normal entry point."""
    cell.build_system(forest(cell))
    t0 = clock()
    for u in range(updates):
        cell.system.update(*update_rows(cell, u))
        if (u + 1) % 16 == 0 or u + 1 == updates:
            print(f"set-up: {u + 1} updates in {clock() - t0:.1f} s, "
                  f"{cell.counter.compiles} compiles, "
                  f"{cell.counter.cache_hits} cache loads", file=sys.stderr,
                  flush=True)
    cell.updates = updates


def live_world(cell) -> reference.World:
    """The reference's world: the rows of the last ``window`` updates, under
    their global ids (arrival order)."""
    rows = cell.cfg["update_rows"]
    first = max(cell.updates - cell.cfg["stream"]["window"], 0)
    parts = [update_rows(cell, u) for u in range(first, cell.updates)]
    places = np.concatenate([p for p, _ in parts])
    lengths = np.concatenate([n for _, n in parts])
    ids = np.arange(first * rows, cell.updates * rows)
    return reference.World(cell.tables, cell.cfg["engine"], places, lengths,
                           ids)


def exact_checks(cell, pairs: set, communities: set, world) -> list:
    """Similar pairs and communities against the reference's, as counts of
    the answers in one set and not the other."""
    want = world.similar_pairs()
    want_c = reference.communities(want, world.ids)
    lim = cell.traffic["limits"]
    return [("pairs_wrong", len(pairs ^ want), lim["pairs_wrong"]),
            ("communities_wrong", len(communities ^ want_c),
             lim["communities_wrong"])]


def failed_call(cell, what: str, err: BaseException) -> None:
    import sys
    import traceback

    cell.rec["failed"] += 1
    print(f"{what} failed: {err!r}", file=sys.stderr)
    traceback.print_exc(file=sys.stderr)
