"""Mean seconds a sharded job spends tracing, lowering and compiling
programs (``compile_s``, counted by the program into the phase that
compiled), over the window's jobs."""
from chipbench.metrics_util import job_mean


def read(rec):
    return job_mean(rec, lambda s, wall: s.get("compile_s"))
