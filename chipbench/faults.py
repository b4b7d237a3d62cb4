"""Broken systems and the control, for showing that ``correct`` can fail.

``control`` is the program with its approximate candidate path switched on
(MinHash LSH, ``backend="minhash"``): it breaks the configurations' stated
guarantee of exact results, and the check has to call it not correct.  The
faults break the timed path where it produces its answers:

* ``stale``:   a call returns the state or answer of the call before it;
* ``half``:    a call works on the first half of its batch only;
* ``altered``: one answer is changed where it is produced.

(The cells run on one chip, so a fault in the exchange between chips has
no place here.)  The tests run each against the harness on the CPU; the
control also runs on the chip at each cell's own size (``control.py``).
"""
from __future__ import annotations

import numpy as np

from chipbench import system


def control(cfg: dict, forest: dict):
    return system.build(dict(cfg, engine=dict(cfg["engine"],
                                              backend="minhash")), forest)


def _alter_pairs(pairs: set) -> set:
    if not pairs:
        return {(0, 1)}
    a, b = min(pairs)
    return (pairs - {(a, b)}) | {(a, b + 1)}


class _Wrap:
    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        return getattr(self.inner, name)


class StaleBatch(_Wrap):
    def run(self, places, lengths):
        out = self.inner.run(places, lengths)
        prev, self.prev = getattr(self, "prev", out), out
        return prev


class HalfBatch(_Wrap):
    def run(self, places, lengths):
        h = places.shape[0] // 2
        return self.inner.run(places[:h], lengths[:h])


class AlteredBatch(_Wrap):
    def run(self, places, lengths):
        pairs, comms, stats = self.inner.run(places, lengths)
        return _alter_pairs(pairs), comms, stats


class StaleResident(_Wrap):
    """Updates leave the world as it was; queries answer the call before."""

    def update(self, places, lengths):
        if self.inner.last is None:
            return self.inner.update(places, lengths)
        return self.inner.last.stats

    def query(self, places, lengths):
        out = self.inner.query(places, lengths)
        prev, self.prev = getattr(self, "prev", out), out
        return prev


class HalfResident(_Wrap):
    def update(self, places, lengths):
        h = places.shape[0] // 2
        return self.inner.update(places[:h], lengths[:h])

    def query(self, places, lengths):
        n = int((lengths > 0).sum())
        ids, mss, stats = self.inner.query(places, lengths)
        ids, mss = ids.copy(), mss.copy()
        ids[n // 2:n], mss[n // 2:n] = np.iinfo(np.int32).max, -1.0
        return ids, mss, stats


class AlteredResident(_Wrap):
    def result(self):
        pairs, comms = self.inner.result()
        return _alter_pairs(pairs), comms

    def query(self, places, lengths):
        ids, mss, stats = self.inner.query(places, lengths)
        ids = ids.copy()
        hit = np.argwhere(ids != np.iinfo(np.int32).max)
        if hit.size:
            q, c = hit[0]
            ids[q, c] += 1
        return ids, mss, stats


FAULTS = {
    "batch": {"stale": StaleBatch, "half": HalfBatch,
              "altered": AlteredBatch},
    "resident": {"stale": StaleResident, "half": HalfResident,
                 "altered": AlteredResident},
}


def faulty(fault: str):
    """A system factory whose system has ``fault``."""
    def build(cfg, forest):
        return FAULTS[cfg["system"]][fault](system.build(cfg, forest))

    return build
