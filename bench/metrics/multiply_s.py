"""Wall seconds of the window per multiply, each ended by
``block_until_ready``."""


def read(rec):
    n = rec.counters.get("multiplies")
    return rec.window_s / n if n else None
