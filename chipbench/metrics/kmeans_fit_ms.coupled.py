"""kmeans_fit_ms.coupled: analyze-stage body time per round (host clock)."""
from chipbench import readers


def read(rec):
    return readers.mean_ms(readers.in_window(rec, "stage:analyze"))
