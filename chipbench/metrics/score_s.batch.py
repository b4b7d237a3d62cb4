"""Mean seconds of a job's score phase (``t_score``: the LCS kernel over
every candidate pair, ending in a device sync), over the window's jobs."""
from chipbench.metrics_util import job_mean


def read(rec):
    return job_mean(rec, lambda s, wall: s.get("t_score"))
