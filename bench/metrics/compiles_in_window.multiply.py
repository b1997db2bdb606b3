"""Backend compiles (persistent-cache loads included) inside the
window, a count; warm-up should leave none."""


def read(rec):
    return rec.compiles_in_window
