"""Percent of the slots the all_to_all rounds of a job shipped that carried
a row (``stats["exchange"]``: rows carried and slots shipped by the key
route, the dedup route and the two owner hops, counted in the mesh), mean
over the window's jobs."""
from chipbench.metrics_util import job_mean


def _fill(stats):
    rounds = stats.get("exchange")
    if not rounds:
        return None
    rows = sum(r for r, _ in rounds.values())
    slots = sum(s for _, s in rounds.values())
    return 100.0 * rows / slots if slots else None


def read(rec):
    return job_mean(rec, lambda s, wall: _fill(s))
