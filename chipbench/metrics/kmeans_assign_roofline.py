"""kmeans_assign_roofline: the least time the chip needs for the
assignment passes of the window (FLOPs and bytes of the unpadded shapes,
chipbench.counts) over the kernel's device time in the trace, in %."""
from chipbench import counts, readers


def read(rec):
    t = readers.kernel_s(rec, readers.is_kmeans_kernel)
    c = rec.counters
    if t is None or not c.get("kmeans_iters"):
        return None
    need = counts.kmeans_assign(c["kmeans_points"], c["kmeans_k"],
                                c["kmeans_d"])
    least = counts.roofline_s(need["flops"], need["bytes"], rec.peak)["s"]
    return 100.0 * least * c["kmeans_iters"] / t
