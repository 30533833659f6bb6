"""kmeans_init_ms: device time per fit of the initial draw of centroids
(the ``jit__shuffle`` program), from the trace."""
from chipbench import readers


def read(rec):
    fits = rec.counters.get("units", 0)
    if rec.trace is None or not fits:
        return None
    s = readers.program_s(rec, readers.KMEANS_INIT_PROGRAM)
    return 1e3 * s / fits if s > 0 else None
