"""Peak device memory in use, GB (1e9 bytes), on the fullest of the
cell's chips at the end of the window (``memory_stats``)."""


def read(rec):
    return rec.memory_peak_bytes / 1e9 if rec.memory_peak_bytes else None
