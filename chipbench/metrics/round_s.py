"""round_s: the window over the coupled rounds it completed (host clock)."""


def read(rec):
    n = rec.counters.get("units", 0)
    return rec.window_s / n if n else None
