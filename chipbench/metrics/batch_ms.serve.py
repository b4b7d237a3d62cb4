"""Median milliseconds of one ``QueryEngine.query`` call, by the host
clock around the call (its result is on the host when it returns)."""
import numpy as np


def read(rec):
    ms = rec.get("batch_ms")
    return float(np.median(ms)) if ms else None
