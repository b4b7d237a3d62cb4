"""The blocked reference of the mesh cell's check equals the plain
reference, pair for pair and community for community."""
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import data, reference  # noqa: E402
from chipbench.reference_blocked import (  # noqa: E402
    BlockedWorld, level_lcs, pairs_sharing_a_key,
)

WORLD = dict(num_places=80, num_types=8, classes_per_type=2, n_levels=3,
             min_len=5, max_len=10, repeat_prob=0.15)
ENGINE = dict(k=3, rho=2.0)


def test_blocked_world_equals_the_plain_reference(monkeypatch):
    import chipbench.reference_blocked as rb

    monkeypatch.setattr(rb, "BLOCK", 1 << 10)  # several blocks
    tables = data.level_tables(data.forest(WORLD, 5))
    places, lengths = data.batch(WORLD, 400, 5, data.BATCH, 0)
    ids = np.arange(places.shape[0])
    plain = reference.World(tables, ENGINE, places, lengths, ids)
    blocked = BlockedWorld(tables, ENGINE, places, lengths, ids)
    want, got = plain.similar_pairs(), blocked.similar_pairs()
    assert len(want) > 500
    assert got == want
    assert reference.communities(got, ids) == reference.communities(want, ids)


def test_level_lcs_equals_the_plain_recurrence():
    tables = data.level_tables(data.forest(WORLD, 6))
    places, lengths = data.batch(WORLD, 64, 6, data.BATCH, 0)
    codes = reference.codes(tables, places)
    a, b = np.triu_indices(64, 1)
    assert np.array_equal(level_lcs(codes, a, b),
                          reference.level_lcs(codes, a, codes, b))


def test_pairs_in_blocks_equal_the_plain_enumeration():
    tables = data.level_tables(data.forest(WORLD, 7))
    places, lengths = data.batch(WORLD, 300, 7, data.BATCH, 0)
    world = reference.World(tables, ENGINE, places, lengths,
                            np.arange(500, 800))
    want = reference.pairs_sharing_a_key(world.keys, world.ids)
    for threads in (1, 3, 8):
        got = pairs_sharing_a_key(world.keys, world.ids, threads=threads)
        assert np.array_equal(got, want), threads
    none = np.full((4, 3), -1, np.int64)
    assert pairs_sharing_a_key(none, np.arange(4)).shape == (0, 2)
