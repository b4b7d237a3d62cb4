"""Arithmetic the metric readers share."""
from __future__ import annotations


def job_mean(rec, value):
    """Mean of ``value(stats, wall_s)`` over the window's jobs."""
    jobs = rec.get("jobs")
    if not jobs:
        return None
    vals = [value(j["stats"], j["wall_s"]) for j in jobs]
    if any(v is None for v in vals):
        return None
    return sum(vals) / len(vals)


def idle_share(rec):
    """Percent of the traced window with no device operation running."""
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
