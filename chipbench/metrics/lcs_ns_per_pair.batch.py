"""Device nanoseconds of the LCS kernel per candidate pair, over the traced
job: the kernel's events in the trace over the job's candidate pairs."""


def read(rec):
    tr, pairs = rec.get("trace"), rec.get("traced_pairs")
    if not tr or not pairs or not tr["kernel_ns"]:
        return None
    return tr["kernel_ns"] / pairs
