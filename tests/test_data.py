"""Data plane: synthetic generator stats and the GeoLife surrogate (incl.
the GPS round-trip through stay-point detection)."""
import numpy as np

from repro.core.types import PAD_PLACE
from repro.data.geolife import _stay_points, geolife_surrogate
from repro.data.synthetic import synthetic_setup, synthetic_trajectories


def test_synthetic_matches_paper_spec():
    batch, forest = synthetic_setup(500, seed=0)
    assert forest.sizes == (30, 300, 10_000)
    lengths = np.asarray(batch.lengths)
    assert lengths.min() >= 5 and lengths.max() <= 10
    places = np.asarray(batch.places)
    valid = places[places != PAD_PLACE]
    assert valid.min() >= 0 and valid.max() < 10_000


def test_synthetic_repetition():
    batch = synthetic_trajectories(500, repeat_prob=0.5, seed=1)
    places = np.asarray(batch.places)
    reps = 0
    for i in range(places.shape[0]):
        row = places[i][places[i] != PAD_PLACE]
        reps += int((row[1:] == row[:-1]).sum())
    assert reps > 100  # stay-duration repetition present


def test_stay_point_detector():
    # two dwells 1km apart with a fast transit between them
    t = []
    xy = []
    clock = 0.0
    for center in ((0.0, 0.0), (1000.0, 0.0)):
        for _ in range(8):
            xy.append([center[0] + np.random.default_rng(len(xy)).uniform(-30, 30),
                       center[1]])
            t.append(clock)
            clock += 300.0
        clock += 60.0
    sp = _stay_points(np.asarray(xy), np.asarray(t))
    assert sp.shape[0] == 2
    assert abs(sp[0][0]) < 100 and abs(sp[1][0] - 1000) < 100


def test_geolife_surrogate_shape():
    batch, forest = geolife_surrogate(num_users=20, num_traj=200, seed=0)
    assert batch.places.shape[0] == 200
    users = np.asarray(batch.user_id)
    assert users.max() < 20
    # behavioural recurrence: home appears at start and end
    places = np.asarray(batch.places)
    lengths = np.asarray(batch.lengths)
    same = sum(
        places[i, 0] == places[i, lengths[i] - 1] for i in range(200)
    )
    assert same > 150


def test_geolife_gps_roundtrip():
    batch, forest = geolife_surrogate(num_users=5, num_traj=64, seed=1, fast=False)
    lengths = np.asarray(batch.lengths)
    assert (lengths > 0).all()
