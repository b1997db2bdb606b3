"""Mean block occupancy of X over every sweep of the window's
purifications (``SignIterStats.occupancy_trace``), in %."""


def read(rec):
    o = rec.counters.get("x_occupancy")
    return 100.0 * sum(o) / len(o) if o else None
