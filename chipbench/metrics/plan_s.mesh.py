"""Mean seconds of a sharded job's plan phase (``t_plan``: the key shuffle
program, whose in-mesh counts plan the join and score, and the plan on the
host), over the window's jobs."""
from chipbench.metrics_util import job_mean


def read(rec):
    return job_mean(rec, lambda s, wall: s.get("t_plan"))
