"""Share of the traced job, in percent, in which no operation ran on a
device, the mean of the chips."""
from chipbench.metrics_util import idle_share


def read(rec):
    return idle_share(rec)
