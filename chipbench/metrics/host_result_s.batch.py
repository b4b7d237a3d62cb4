"""Mean seconds of a job outside encode, keys, join and score: building
the similar set on the host and the communities phase."""
from chipbench.metrics_util import job_mean

TIMED = ("t_encode", "t_keys", "t_join", "t_score")


def read(rec):
    return job_mean(rec, lambda s, wall: wall - sum(s.get(k, 0.0)
                                                    for k in TIMED))
