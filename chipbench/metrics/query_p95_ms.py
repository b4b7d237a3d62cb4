"""95th percentile, nearest rank, of every query due in the window, from
the time it was due to the time its result was on the host; a query that
never came back counts as infinitely late."""
from chipbench.harness import percentile


def read(rec):
    lat = rec.get("latency_ms")
    return percentile(lat, 0.95) if lat is not None and len(lat) else None
