"""moe_train_mfu: FLOPs the window's training tokens need (every token's
matmuls and attention, plus the held experts' per pair they computed, from
the program's ``moe_pairs``) over window x chips x bf16 peak, in %."""
from chipbench import counts_mla_moe, readers


def read(rec):
    c = rec.counters
    if not c.get("train_tokens") or "moe_pairs" not in c:
        return None
    flops = counts_mla_moe.train_flops(
        rec.config, int(rec.traffic["train"]["seq"]), c["train_tokens"],
        c["moe_pairs"])
    return readers.share_of_peak_pct(rec, flops)
