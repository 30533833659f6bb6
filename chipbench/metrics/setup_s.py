"""setup_s: process start to the first timed unit of work (host clock)."""


def read(rec):
    return rec.setup_s
