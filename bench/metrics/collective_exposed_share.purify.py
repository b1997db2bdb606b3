"""Share of the traced window in which a collective ran on a device and
no other op did, averaged over the cell's chips, in %."""


def read(rec):
    t = rec.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return 100.0 * t.exposed_collective_s() / t.window_s
