"""Device seconds per purification in the local stage: dot and
convolution ops, fusions around them and custom-call kernels, from the
trace, averaged over the cell's chips."""


def read(rec):
    t, n = rec.trace, rec.counters.get("purifications")
    if t is None or not t.devices or not n:
        return None
    s = t.class_s("local")
    return s / n if s > 0 else None
