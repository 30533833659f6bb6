"""device_idle.train-ep: 1 - device busy / traced window, in % (trace;
the mean over the chips)."""
from chipbench import readers


def read(rec):
    return readers.idle_pct(rec)
