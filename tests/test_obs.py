"""The span ring (``repro.obs``): nesting, counts, the bound and its drop
count, and spans left by an exception."""
from collections import deque

import pytest

from repro import obs


@pytest.fixture
def ring(monkeypatch):
    """An empty ring of four records for the test."""
    monkeypatch.setattr(obs, "_ring", deque(maxlen=4))
    monkeypatch.setattr(obs, "_closed", 0)
    return obs


def test_nesting_parents_and_counts(ring):
    with ring.span("outer", a=1) as outer:
        with ring.span("inner") as inner:
            inner.counts["b"] = 2
        outer.counts["c"] = 3
    with ring.span("next"):
        pass
    got = {r.name: r for r in ring.records()}
    assert [r.name for r in ring.records()] == ["inner", "outer", "next"]
    assert got["inner"].parent == got["outer"].id
    assert got["outer"].parent is None and got["next"].parent is None
    assert got["outer"].counts == {"a": 1, "c": 3}
    assert got["inner"].counts == {"b": 2}
    assert got["outer"].start_ns <= got["inner"].start_ns
    assert got["inner"].end_ns <= got["outer"].end_ns
    assert got["outer"].seconds >= got["inner"].seconds >= 0.0
    assert ring.dropped() == 0


def test_ring_is_bounded_and_counts_drops(ring):
    for i in range(7):
        with ring.span("s", i=i):
            pass
    assert [r.counts["i"] for r in ring.records()] == [3, 4, 5, 6]
    assert ring.dropped() == 3


def test_span_left_by_an_exception_is_recorded(ring):
    with pytest.raises(KeyError):
        with ring.span("outer"):
            with ring.span("inner"):
                raise KeyError("x")
    assert [r.name for r in ring.records()] == ["inner", "outer"]
    # no open parent is left behind: the next span is top-level
    with ring.span("after"):
        pass
    assert ring.records()[-1].parent is None


def test_inner_span_never_closed_leaves_no_open_parent(ring):
    outer = ring.span("outer")
    outer.__enter__()
    ring.span("lost").__enter__()  # its exit never runs
    outer.__exit__(None, None, None)
    with ring.span("after"):
        pass
    assert [r.name for r in ring.records()] == ["outer", "after"]
    assert ring.records()[-1].parent is None
