"""The readers of the program's own spans and counts (``repro.obs``): on a
synthetic run whose ring holds warm-up spans ahead of the window, and
with nothing to read."""
from collections import deque

import pytest

from benchlib.harness import RunRecord
from benchlib.peaks import DevicePeaks
from benchlib.spec import load_module
from benchlib.trace import DeviceOp, TraceSummary
from conftest import BENCH


def _reader(name):
    return load_module(f"{BENCH}/metrics/{name}.py",
                       "test_metric_" + name.replace(".", "_"))


class _Ring:
    """Writes closed spans into the ring, each with a given duration."""

    def __init__(self, obs):
        self.obs, self.ids = obs, iter(range(1, 10**6))

    def add(self, name, ns=1, parent=None, **counts):
        rec = self.obs.Record(next(self.ids), name, 0, ns, parent, counts)
        self.obs._ring.append(rec)
        self.obs._closed += 1
        return rec.id

    def chain(self, sweeps, present, computed, dispatch_ns):
        me = next(self.ids)
        for _ in range(sweeps):
            self.add("signiter.dispatch", dispatch_ns, parent=me)
        self.add("signiter.sync", 7, parent=me)
        self.obs._ring.append(self.obs.Record(
            me, "signiter.chain", 0, 10**9, None,
            dict(sweeps=sweeps, host_syncs=1, products_present=present,
                 products_computed=computed, block_flops=2 * 23 ** 3)))
        self.obs._closed += 1


@pytest.fixture
def ring(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "_ring", deque(maxlen=obs.RING_SIZE))
    monkeypatch.setattr(obs, "_closed", 0)
    return _Ring(obs)


def _traced(rec, chips=1):
    """``rec`` with a device trace (one local op of 0.5 s per chip in a
    1 s window) and the chip's peaks, as a ``--trace 1`` run on a chip
    has."""
    ops = [DeviceOp("dot.1", "dot", "", 0.0, 0.5)]
    rec.chips = chips
    rec.trace = TraceSummary(window=(0.0, 1.0), devices={
        f"/device:TPU:{i}": ops for i in range(chips)})
    rec.peaks = DevicePeaks(flops=1e9, hbm_bw=1.0, hbm_bytes=1.0, source="")
    return rec


def _purify_run(ring):
    """A warm-up chain (4 sweeps), then a window of two purifications."""
    ring.chain(4, present=10**9, computed=10**9, dispatch_ns=10**9)
    ring.chain(20, present=300, computed=4000, dispatch_ns=2000)
    ring.chain(20, present=100, computed=4000, dispatch_ns=4000)
    return _traced(RunRecord(workload="h2o_purify_1chip", chips=1, seed=1,
                             counters={"sweeps": [20, 20]}))


def test_useful_product_share(ring):
    rec = _purify_run(ring)
    assert _reader("useful_product_share.purify").read(rec) == pytest.approx(
        100.0 * 400 / 8000)


def test_sweep_dispatch_s(ring):
    rec = _purify_run(ring)
    assert _reader("sweep_dispatch_s.purify").read(rec) == pytest.approx(
        (20 * 2000 + 20 * 4000) * 1e-9 / 40)


def test_local_mm_roofline_purify(ring):
    rec = _traced(_purify_run(ring), chips=2)
    want = 100.0 * 400 * 2 * 23 ** 3 / (2 * 0.5 * 1e9)
    assert _reader("local_mm_roofline.purify").read(rec) == pytest.approx(want)
    rec.peaks = None
    assert _reader("local_mm_roofline.purify").read(rec) is None


def test_host_pair_walk_s(ring):
    def multiply(walks):
        me = next(ring.ids)
        for ns in walks:
            ring.add("spgemm.pair_walk", ns, parent=me)
        dispatch = next(ring.ids)
        ring.add("spgemm.pair_walk", 5, parent=dispatch)  # nested deeper
        ring.obs._ring.append(ring.obs.Record(
            dispatch, "spgemm.dispatch", 0, 9, me, {}))
        ring.obs._ring.append(ring.obs.Record(
            me, "spgemm.multiply", 0, 10**9, None, {}))
        ring.obs._closed += 2

    multiply([10**9])  # warm-up
    ring.add("spgemm.pair_walk", 10**9)  # outside any multiply
    multiply([100, 200])
    multiply([300])
    rec = _traced(RunRecord(workload="dense_multiply_1chip", chips=1,
                            seed=1, counters={"multiplies": 2}))
    got = _reader("host_pair_walk_s.multiply").read(rec)
    assert got == pytest.approx((100 + 200 + 5 + 300 + 5) * 1e-9 / 2)


@pytest.mark.parametrize("name,counters", [
    ("useful_product_share.purify", {"sweeps": [20]}),
    ("local_mm_roofline.purify", {"sweeps": [20]}),
    ("sweep_dispatch_s.purify", {"sweeps": [20]}),
    ("host_pair_walk_s.multiply", {"multiplies": 1}),
])
def test_nothing_to_read(ring, name, counters):
    rec = _traced(RunRecord(workload="w", chips=1, seed=1,
                            counters=counters))
    reader = _reader(name)
    assert reader.read(rec) is None  # no records
    if "sweeps" in counters:
        ring.chain(20, present=1, computed=2, dispatch_ns=1)
    else:
        ring.add("spgemm.multiply")
    assert reader.read(rec) is not None
    # no units counted
    assert reader.read(_traced(RunRecord(workload="w", chips=1,
                                         seed=1))) is None
    # no device in the trace: the run measured nothing on a chip
    rec.trace = TraceSummary(window=(0.0, 1.0))
    assert reader.read(rec) is None
    rec.trace = None
    assert reader.read(rec) is None
    rec = _traced(rec)
    ring.obs._closed += 1  # a record dropped from the ring
    assert reader.read(rec) is None


def test_traced_runs_report_the_program_metrics(bench_run, monkeypatch):
    """A ``--trace 1`` run reads the program's spans and counts: the
    purification cell its product share and dispatch seconds, the
    multiply cell its pair-walk seconds, within the host call.  The CPU
    trace has no device plane, so one is added to what the reduction
    returns, as a chip's trace has."""
    from benchlib import trace

    reduce_xplane = trace.reduce_xplane

    def with_a_device(path):
        t = reduce_xplane(path)
        t.devices["/device:TPU:0"] = [DeviceOp("dot.1", "dot", "",
                                                *t.window)]
        return t

    monkeypatch.setattr(trace, "reduce_xplane", with_a_device)
    line = bench_run.run("h2o_purify_1chip", trace=1)
    m = line["metrics"]
    assert 0.0 < m["useful_product_share.purify"]["value"] <= 100.0
    assert m["sweep_dispatch_s.purify"]["value"] > 0.0
    line = bench_run.run("dense_multiply_1chip", trace=1)
    m = line["metrics"]
    assert 0.0 < m["host_pair_walk_s.multiply"]["value"] <= m[
        "host_call_s.multiply"]["value"]
