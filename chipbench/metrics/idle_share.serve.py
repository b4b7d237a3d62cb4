"""Share of the traced window, in percent, in which no operation ran on
the device."""
from chipbench.metrics_util import idle_share


def read(rec):
    return idle_share(rec)
