"""Host seconds inside ``engine.multiply`` until it returns, before the
wait for the device, the mean per multiply."""


def read(rec):
    s = rec.counters.get("host_call_s")
    return sum(s) / len(s) if s else None
