"""Wall seconds of the window per converged purification."""


def read(rec):
    n = rec.counters.get("purifications")
    return rec.window_s / n if n else None
