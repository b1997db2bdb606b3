"""The local stage's share of its roofline in the multiply cells, in %:
the useful work (2 bs^3 FLOP per block product whose A and B blocks are
both present, counted from the masks by ``benchlib.work``, the same
whichever backend runs it) over the device seconds of the local-stage
ops times the chip's bf16 peak.  The compute bound applies: a product of
two N x N float32 matrices does 2 N^3 FLOP on about 12 N^2 bytes, far
above the v5e's 240 FLOP per HBM byte (197 TFLOP/s over 819 GB/s) at
N = 16,384."""


def read(rec):
    t, n = rec.trace, rec.counters.get("multiplies")
    flops = rec.counters.get("useful_flops_per_step")
    if t is None or not t.devices or not n or not flops or rec.peaks is None:
        return None
    s = t.class_s("local")
    if s <= 0:
        return None
    return 100.0 * flops * n / (s * rec.peaks.flops)
