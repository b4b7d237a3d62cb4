"""Window seconds per one-shot job: from the window's start to the end of
the last job it started, over the jobs it started."""


def read(rec):
    jobs = rec.get("jobs")
    return rec["window_s"] / len(jobs) if jobs else None
