"""Host seconds per sweep in ``signiter.dispatch`` (fetching the sweep
program and enqueueing the sweep), over the window's ``signiter.chain``
spans: the mean per sweep."""
from benchlib.spans import descendants, window_spans


def read(rec):
    n = len(rec.counters.get("sweeps", ()))
    got = window_spans(rec, "signiter.chain", n)
    if got is None:
        return None
    chains, recs = got
    sweeps = sum(c.counts.get("sweeps", 0) for c in chains)
    if not sweeps:
        return None
    calls = descendants(recs, chains, "signiter.dispatch")
    return sum(d.seconds for d in calls) / sweeps
