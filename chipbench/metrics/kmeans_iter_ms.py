"""kmeans_iter_ms: the window over the map-reduce iterations completed."""


def read(rec):
    n = rec.counters.get("kmeans_iters", 0)
    return 1e3 * rec.window_s / n if n else None
