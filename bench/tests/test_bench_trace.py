"""The trace reduction: interval algebra, op classes, and the whole
reduction on small traces recorded on a TPU v5e (``data/``)."""
import glob
import os

import pytest

from benchlib.trace import (DeviceOp, TraceSummary, length, op_class,
                            parse_op, reduce_xplane, subtract, union)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_subtract():
    u = union([(3, 5), (0, 1), (4, 6), (6, 7), (9, 9)])
    assert u == [(0, 1), (3, 7)]
    assert length(u) == 5
    assert subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert subtract([(0, 2), (5, 8)], [(1, 6)]) == [(0, 1), (6, 8)]
    assert subtract([(0, 2)], []) == [(0, 2)]


@pytest.mark.parametrize("text,cls", [
    ("%multiply_reduce_fusion = (f32[16,16]{0,1:T(8,128)S(1)}, f32[16,16,23,23]"
     "{3,2,0,1:T(8,128)S(1)}) fusion(f32[16,16,23,23,1]{3,1,2,0,4:T(8,128)S(1)}"
     " %bitcast.18), kind=kOutput, calls=%fused_computation.16", "local"),
    ("%convolution.1 = f32[128,128]{1,0} convolution(f32[128,128]{1,0} %a, "
     "f32[128,128]{1,0} %b), dim_labels=bf_io->bf", "local"),
    ("%custom-call.2 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %x), "
     'custom_call_target="tpu_custom_call"', "local"),
    ("%all-reduce.4 = f32[3]{0} all-reduce(f32[3]{0} %p), to_apply=%add",
     "collective"),
    ("%collective-permute-start = (f32[16,16,23,23]{3,1,2,0:T(8,128)}) "
     "collective-permute-start(f32[16,16,23,23]{3,1,2,0} %x)", "collective"),
    ("%fusion.17 = f32[16,16]{1,0:T(8,128)} fusion(f32[16,16]{1,0} %a), "
     "kind=kLoop, calls=%fused_computation.5", "other"),
    ("%copy.46 = f32[16,16,23,23]{3,2,1,0} copy(f32[16,16,23,23]{3,1,2,0} %x)",
     "other"),
])
def test_op_class(text, cls):
    name, opcode, kind = parse_op(text)
    assert name and opcode
    assert op_class(opcode, kind) == cls


def test_summary_on_synthetic_ops():
    ops = [DeviceOp("dot.1", "dot", "", 0.0, 2.0),
           DeviceOp("fusion.1", "fusion", "kLoop", 1.0, 3.0),
           DeviceOp("all-reduce.1", "all-reduce", "", 2.5, 4.0),
           DeviceOp("fusion.2", "fusion", "kLoop", 6.0, 7.0)]
    s = TraceSummary(window=(0.0, 8.0), devices={"/device:TPU:0": ops},
                     spans=[("bench.window", 0.0, 8.0),
                            ("bench.wait", 3.5, 6.5)])
    assert s.busy_s() == pytest.approx(5.0)
    assert s.class_s("local") == pytest.approx(2.0)
    assert s.class_s("collective") == pytest.approx(1.5)
    assert s.exposed_collective_s() == pytest.approx(1.0)  # 3.0 .. 4.0
    gaps = dict(s.idle_gaps())
    assert gaps == pytest.approx({"bench.wait": 2.0, "bench.window": 1.0})
    assert s.top_ops(1)[0][0] == "dot.1"


def _traces():
    return sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))


@pytest.mark.parametrize("path", _traces(), ids=os.path.basename)
def test_reduction_of_a_recorded_trace(path):
    s = reduce_xplane(path)
    assert s.devices, "no TPU plane"
    assert 0 < s.busy_s() <= s.window_s
    local, coll = s.class_s("local"), s.class_s("collective")
    other = s.class_s("other")
    assert local > 0
    assert local + coll + other >= s.busy_s() * (1 - 1e-9)
    assert 0 <= s.exposed_collective_s() <= coll + 1e-12
    if len(s.devices) > 1:
        assert coll > 0
    idle = sum(v for _, v in s.idle_gaps(100))
    assert idle == pytest.approx(s.window_s - s.busy_s(), rel=1e-6, abs=1e-9)
    assert all(name.startswith("bench.") for name, _ in s.idle_gaps())


def test_recorded_traces_are_present():
    assert len(_traces()) >= 1
