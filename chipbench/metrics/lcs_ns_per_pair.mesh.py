"""Device nanoseconds of the LCS kernel per candidate pair on one chip,
over the traced job: the kernel's events in the trace (per chip, the mean
of the chips) over the job's candidate pairs per chip."""


def read(rec):
    tr, pairs, jobs = rec.get("trace"), rec.get("traced_pairs"), \
        rec.get("jobs")
    if not tr or not pairs or not tr["kernel_ns"] or not jobs:
        return None
    n = jobs[0]["stats"].get("n_shards")
    return tr["kernel_ns"] / (pairs / n) if n else None
