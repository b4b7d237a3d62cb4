"""Rows of the updates the window completed, over the window's seconds."""


def read(rec):
    if not rec.get("update_ms"):
        return None
    return rec["rows"] / rec["window_s"]
