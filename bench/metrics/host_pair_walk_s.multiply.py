"""Host seconds per multiply in the walk of the (i, k, j) pair cube, the
``spgemm.pair_walk`` spans inside each ``spgemm.multiply`` span of the
window, the mean per multiply."""
from benchlib.spans import descendants, window_spans


def read(rec):
    n = rec.counters.get("multiplies")
    got = window_spans(rec, "spgemm.multiply", n)
    if got is None:
        return None
    calls, recs = got
    walks = descendants(recs, calls, "spgemm.pair_walk")
    return sum(w.seconds for w in walks) / n
