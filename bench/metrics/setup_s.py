"""Set-up seconds: from the start of the process to the start of the
window (making the inputs, loading or compiling the programs, warm-up)."""


def read(rec):
    return rec.setup_s
