"""Candidate (query, world row) pairs the probe found, per query served
(``QueryResult.stats["candidates"]``)."""


def read(rec):
    n = rec.get("queries_served")
    return rec["candidates"] / n if n else None
