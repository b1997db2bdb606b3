"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

- busy: the union of the intervals in which an operation runs on a
  device (the ``XLA Ops`` line of each ``/device:TPU:n`` plane), clipped
  to the traced window, the host span ``bench.window``;
- op classes, from the HLO instruction text that names each op event
  (``%name = shape opcode(...), kind=...``): ``local`` (convolution, dot
  and custom-call ops, such as Pallas' ``tpu_custom_call``, and the
  ``kOutput`` fusions that XLA builds around a convolution or dot),
  ``collective`` (all-reduce, all-gather, reduce-scatter, all-to-all,
  collective-permute, send, recv, their start and done halves) and
  ``other``;
- exposed collective time: collective intervals less the union of every
  other op's intervals on the same device;
- idle gaps: the complement of busy in the window, each gap named by the
  innermost host span ``bench.*`` around its midpoint.

Every per-device quantity is averaged over the devices in the trace.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"

_COLLECTIVE = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "send", "recv")
_LOCAL = ("convolution", "dot", "custom-call")
_HLO = re.compile(r"%(?P<name>[^\s=]+) = .*?\s(?P<opcode>[a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=(k[A-Za-z]+)")


def parse_op(text: str) -> tuple[str, str, str]:
    """``(name, opcode, fusion kind)`` of an op event's HLO text; a text
    that is not an HLO instruction is its own name, with no opcode."""
    m = _HLO.match(text)
    if not m:
        return text, "", ""
    kind = _KIND.search(text)
    return m.group("name"), m.group("opcode"), kind.group(1) if kind else ""


def op_class(opcode: str, kind: str = "") -> str:
    """``collective``, ``local`` or ``other`` for one device op."""
    base = opcode.removesuffix("-start").removesuffix("-done")
    if base in _COLLECTIVE:
        return "collective"
    if base in _LOCAL or (opcode == "fusion" and kind == "kOutput"):
        return "local"
    return "other"


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint union of (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """The parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


@dataclass
class DeviceOp:
    name: str
    opcode: str
    kind: str
    start: float  # seconds, on the trace's clock
    end: float

    @property
    def cls(self) -> str:
        return op_class(self.opcode, self.kind)

    @property
    def label(self) -> str:
        """The HLO name, with the opcode where the name does not start
        with it, and the fusion kind."""
        parts = [self.name]
        if self.opcode and not self.name.startswith(self.opcode):
            parts.append(self.opcode)
        if self.kind:
            parts.append(self.kind)
        return " ".join(parts)


@dataclass
class TraceSummary:
    window: tuple[float, float]
    devices: dict = field(default_factory=dict)  # plane name -> [DeviceOp]
    spans: list = field(default_factory=list)  # (name, start, end)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _per_device(self, fn) -> float:
        if not self.devices:
            return 0.0
        return sum(fn(ops) for ops in self.devices.values()) / len(self.devices)

    def _clipped(self, ops, cls=None):
        return union(clip([(o.start, o.end) for o in ops
                           if cls is None or o.cls in cls], *self.window))

    def busy_s(self) -> float:
        return self._per_device(lambda ops: length(self._clipped(ops)))

    def class_s(self, cls: str) -> float:
        """Device seconds of ops of class ``cls`` in the window."""
        return self._per_device(
            lambda ops: length(self._clipped(ops, (cls,))))

    def exposed_collective_s(self) -> float:
        def exposed(ops):
            coll = self._clipped(ops, ("collective",))
            busy_other = self._clipped(ops, ("local", "other"))
            return length(subtract(coll, busy_other))
        return self._per_device(exposed)

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` ops with the most device time, per device."""
        tot: dict[str, float] = defaultdict(float)
        for ops in self.devices.values():
            for o in ops:
                s, e = max(o.start, self.window[0]), min(o.end, self.window[1])
                if e > s:
                    tot[o.label] += (e - s) / len(self.devices)
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """Idle device time in the window, summed by the innermost host
        span around each gap, per device; the ``n`` largest."""
        spans = sorted(self.spans, key=lambda s: s[2] - s[1])
        tot: dict[str, float] = defaultdict(float)
        for ops in self.devices.values():
            gaps = subtract([self.window], self._clipped(ops))
            for s, e in gaps:
                mid = 0.5 * (s + e)
                name = next((sp[0] for sp in spans if sp[1] <= mid <= sp[2]),
                            "outside bench spans")
                tot[name] += (e - s) / len(self.devices)
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def reduce_xplane(path: str) -> TraceSummary:
    """Read one ``.xplane.pb`` into a :class:`TraceSummary`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list[DeviceOp]] = {}
    spans = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(DeviceOp(*parse_op(ev.name), ev.start_ns * 1e-9,
                                        ev.end_ns * 1e-9))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      ev.end_ns * 1e-9))
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    _, w0, w1 = max(windows, key=lambda s: s[2] - s[1])
    return TraceSummary(window=(w0, w1), devices=devices, spans=spans)
