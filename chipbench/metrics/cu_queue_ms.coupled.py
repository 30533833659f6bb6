"""cu_queue_ms.coupled: mean pending -> running time of the window's
Compute-Units (their own state stamps)."""
from chipbench import readers


def read(rec):
    return readers.mean_ms(rec.counters.get("cu_overheads", ()))
