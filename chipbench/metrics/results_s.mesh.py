"""Mean seconds of a sharded job's results phase (``t_results``: the
resting buffers of every chip copied to the host and the similar-pair set
built there), over the window's jobs."""
from chipbench.metrics_util import job_mean


def read(rec):
    return job_mean(rec, lambda s, wall: s.get("t_results"))
