"""The benchmark's own library: the spec and name lookups, the closed
loop, the yardstick (peaks, work counts, trace reduction, compile clock)
and the result line.  Nothing here is specific to one configuration,
traffic mix or metric; those live in files of their own, found by name."""
