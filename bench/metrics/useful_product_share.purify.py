"""Share of the block products that the fused sweep's local stage
multiplies whose A and B blocks are both present, over the window's
purifications, in %: the program's own counts on each ``signiter.chain``
span (``products_present`` over ``products_computed``, summed over the
mesh)."""
from benchlib.spans import window_spans


def read(rec):
    n = len(rec.counters.get("sweeps", ()))
    got = window_spans(rec, "signiter.chain", n)
    if got is None:
        return None
    chains, _ = got
    computed = sum(c.counts.get("products_computed", 0) for c in chains)
    if not computed:
        return None
    present = sum(c.counts.get("products_present", 0) for c in chains)
    return 100.0 * present / computed
