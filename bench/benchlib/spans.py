"""The program's own host spans (``repro.obs``), as the metric readers
find them after a run.

No program code runs between the end of the window and the readers (the
check and the release use only the references), so the window's units
of work are the last ``n`` top-level spans of their kind, ``n`` being the
units the run counted.  A run whose trace holds no device (the CPU runs
of the tests) measured nothing on the chip, and its spans are not read,
as the device readers read nothing there.  A program without the span
module, a ring that dropped records, or fewer than ``n`` such spans give
None too.
"""
from __future__ import annotations


def window_spans(rec, name: str, n: int):
    """``(spans, records)``: the last ``n`` top-level spans ``name`` of
    the run ``rec`` and every record of the ring; None where they cannot
    be told."""
    if rec.trace is None or not rec.trace.devices:
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    if not n or obs.dropped():
        return None
    recs = obs.records()
    top = [r for r in recs if r.name == name and r.parent is None]
    if len(top) < n:
        return None
    return top[-n:], recs


def descendants(recs, roots, name: str) -> list:
    """The records ``name`` that lie inside one of the spans ``roots``."""
    parent = {r.id: r.parent for r in recs}
    ids = {r.id for r in roots}

    def inside(r) -> bool:
        p = r.parent
        while p is not None:
            if p in ids:
                return True
            p = parent.get(p)
        return False

    return [r for r in recs if r.name == name and inside(r)]
