"""Newton-Schulz sweeps per purification (``SignIterStats.iterations``),
the mean over the window's purifications."""


def read(rec):
    s = rec.counters.get("sweeps")
    return sum(s) / len(s) if s else None
