"""95th percentile, nearest rank, of the window's update calls in
milliseconds, by the host clock around each call."""
from chipbench.harness import percentile


def read(rec):
    ms = rec.get("update_ms")
    return percentile(ms, 0.95) if ms else None
