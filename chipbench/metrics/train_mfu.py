"""train_mfu: model FLOPs per token x tokens/s over chips x bf16 peak, in %."""
from chipbench import readers


def read(rec):
    return readers.share_of_peak_pct(rec, readers.train_flops(rec))
