"""kmeans_mfu: an iteration's required FLOPs x iterations/s over the
bf16 peak, in %."""
from chipbench import readers


def read(rec):
    return readers.share_of_peak_pct(rec, readers.kmeans_flops(rec))
