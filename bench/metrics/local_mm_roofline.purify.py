"""The local stage's share of its roofline in the purification cells,
in %: the useful work (2 bs^3 FLOP per block product whose A and B
blocks are both present, counted by the program on each
``signiter.chain`` span of the window) over the device seconds of the
local-stage ops summed over the cell's chips times the chip's bf16
peak.  The compute bound applies, as in ``local_mm_roofline.multiply``."""
from benchlib.spans import window_spans


def read(rec):
    if rec.peaks is None:
        return None
    n = len(rec.counters.get("sweeps", ()))
    got = window_spans(rec, "signiter.chain", n)
    if got is None:
        return None
    chains, _ = got
    flops = sum(c.counts.get("products_present", 0)
                * c.counts.get("block_flops", 0) for c in chains)
    s = rec.trace.class_s("local") * rec.chips
    if not flops or s <= 0:
        return None
    return 100.0 * flops / (s * rec.peaks.flops)
