"""Seeded inputs of the benchmark: the semantic forest and the trajectories.

A copy of the paper's section V.1 generator (trajectories of 5-10 places
drawn uniformly from 10,000 places, a stay repeated with probability 0.15,
a forest of types x classes x places).  It lives with the benchmark so that
the data cannot drift with the program under test; the program receives
only the arrays made here.  Everything is numpy and derives from
``(seed, stream, index)`` words, so any whole-number seed, 64-bit or larger,
gives the same inputs every time.
"""
from __future__ import annotations

import numpy as np

PAD_PLACE = -1

# independent random streams under one --seed
FOREST, BATCH, QUERY, ARRIVAL, SAMPLE, WARM = range(6)


def rng(seed: int, *words: int) -> np.random.Generator:
    """A generator keyed by the seed and a path of stream words (a negative
    seed is taken modulo 2**64)."""
    return np.random.default_rng([int(seed) % 2 ** 64, *map(int, words)])


def forest(world: dict, seed: int) -> dict:
    """A dense semantic forest: ``parents[l]`` maps a level-(l+1) id to its
    level-l parent, ``sizes[l]`` is the number of codes at level l
    (coarsest first).  Three levels: types, types x classes, places; every
    parent has at least one child."""
    if world["n_levels"] != 3:
        raise ValueError("the generator builds three-level forests only")
    r = rng(seed, FOREST)
    t = world["num_types"]
    sizes = [t, t * world["classes_per_type"], world["num_places"]]
    parents = []
    for lvl in range(2):
        child_n, parent_n = sizes[lvl + 1], sizes[lvl]
        p = r.integers(0, parent_n, size=child_n).astype(np.int32)
        p[:parent_n] = np.arange(parent_n, dtype=np.int32)
        r.shuffle(p)
        parents.append(p)
    return {"parents": parents, "sizes": sizes}


def level_tables(f: dict) -> np.ndarray:
    """int32 [levels, places]: the code of every place at every level."""
    maps = [np.arange(f["sizes"][-1], dtype=np.int32)]
    for lvl in range(len(f["sizes"]) - 2, -1, -1):
        maps.append(f["parents"][lvl][maps[-1]])
    return np.stack(maps[::-1])


def trajectories(world: dict, n: int, r: np.random.Generator):
    """(places int32 [n, max_len], lengths int32 [n]), PAD_PLACE-padded."""
    L = world["max_len"]
    lengths = r.integers(world["min_len"], L + 1, size=n).astype(np.int32)
    places = r.integers(0, world["num_places"], size=(n, L)).astype(np.int32)
    rep = r.random(size=(n, L)) < world["repeat_prob"]
    rep[:, 0] = False
    for j in range(1, L):
        places[:, j] = np.where(rep[:, j], places[:, j - 1], places[:, j])
    places[np.arange(L)[None, :] >= lengths[:, None]] = PAD_PLACE
    return places, lengths


def batch(world: dict, n: int, seed: int, *words: int):
    """The ``n`` trajectories of the stream named by ``words``."""
    return trajectories(world, n, rng(seed, *words))
