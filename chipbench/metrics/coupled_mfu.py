"""coupled_mfu: (model FLOPs of the window's training tokens + K-Means
required FLOPs) over (window x chips x bf16 peak), in %."""
from chipbench import readers


def read(rec):
    return readers.share_of_peak_pct(
        rec, readers.train_flops(rec) + readers.kmeans_flops(rec))
