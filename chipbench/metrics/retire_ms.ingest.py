"""Mean milliseconds of an update's retirement phase (``t_expire``: the
rows whose window closed leave the slabs, the accumulated pairs, the
similar set and the communities), over the window's updates."""


def read(rec):
    ex = rec.get("expire_s")
    return 1e3 * sum(ex) / len(ex) if ex else None
