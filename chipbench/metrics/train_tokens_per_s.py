"""train_tokens_per_s: training tokens completed over the window."""


def read(rec):
    t = rec.counters.get("train_tokens", 0)
    return t / rec.window_s if t and rec.window_s else None
