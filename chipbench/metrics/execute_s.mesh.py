"""Mean seconds of a sharded job's execute phase (``t_execute``: the join
and score programs on the mesh, ending in a device sync), over the
window's jobs."""
from chipbench.metrics_util import job_mean


def read(rec):
    return job_mean(rec, lambda s, wall: s.get("t_execute"))
