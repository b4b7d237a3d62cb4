"""Mean seconds of a sharded job's communities phase (``t_communities``:
connected components over the similar pairs, and the groups built on the
host), over the window's jobs."""
from chipbench.metrics_util import job_mean


def read(rec):
    return job_mean(rec, lambda s, wall: s.get("t_communities"))
