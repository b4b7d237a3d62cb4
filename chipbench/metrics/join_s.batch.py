"""Mean seconds of a job's join phase (``t_join``: SSH sort-merge join and
dedup, ending in a device sync), over the window's jobs."""
from chipbench.metrics_util import job_mean


def read(rec):
    return job_mean(rec, lambda s, wall: s.get("t_join"))
