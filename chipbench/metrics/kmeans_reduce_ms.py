"""kmeans_reduce_ms: device busy time per iteration outside the
assignment kernel and the fit's initial draw (the relayout of the points
for the kernel, one-hot sums, psum, centroid update), from the trace."""
from chipbench import readers


def read(rec):
    t = readers.kernel_s(rec, readers.is_kmeans_kernel)
    n = rec.counters.get("kmeans_iters", 0)
    if t is None or not n:
        return None
    init = readers.program_s(rec, readers.KMEANS_INIT_PROGRAM)
    return 1e3 * (rec.trace.busy_s - t - init) / n
