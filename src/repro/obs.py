"""Host spans of the program: named, timed, nested regions kept in one
bounded in-memory ring.

    with span("spgemm.multiply") as s:
        ...
        s.counts["sweeps"] = 20

A span records its name, its start and end (``time.perf_counter_ns``),
the span that was open around it on the same thread, and a dict of
counts that the code inside may add to before the span closes.  It also
opens a ``jax.profiler.TraceAnnotation`` of the same name, so under a
profiler the span sits on the device trace's own clock.  The ring is
always on and costs a few microseconds per span; it keeps the last
``RING_SIZE`` closed spans, in the order they closed (children before
their parent), and counts the ones it had to drop.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import NamedTuple

from jax.profiler import TraceAnnotation

RING_SIZE = 1 << 14


class Record(NamedTuple):
    """One closed span.  ``parent`` is the ``id`` of the span that was
    open around it on its thread, None for a top-level span."""

    id: int
    name: str
    start_ns: int
    end_ns: int
    parent: int | None
    counts: dict

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


_ring: deque[Record] = deque(maxlen=RING_SIZE)
_ids = itertools.count(1)
_lock = threading.Lock()
_closed = 0  # spans ever closed; the ring holds the last RING_SIZE
_open = threading.local()  # .stack: the thread's open spans, innermost last


class span:
    """Context manager of one span; ``counts`` start as the keyword
    arguments and may be added to until the span closes."""

    __slots__ = ("name", "counts", "id", "parent", "start_ns", "_ann")

    def __init__(self, name: str, **counts):
        self.name = name
        self.counts = counts

    def __enter__(self) -> span:
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self.parent = stack[-1].id if stack else None
        self.id = next(_ids)
        stack.append(self)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end_ns = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        stack = _open.stack
        # an exception may have skipped the exit of spans opened inside
        # this one: close the stack down to and including this span
        while stack and stack.pop() is not self:
            pass
        global _closed
        with _lock:
            _ring.append(Record(self.id, self.name, self.start_ns, end_ns,
                                self.parent, self.counts))
            _closed += 1


def records() -> list[Record]:
    """The spans in the ring, oldest first by closing time."""
    with _lock:
        return list(_ring)


def dropped() -> int:
    """Spans that closed and are no longer in the ring."""
    with _lock:
        return _closed - len(_ring)
