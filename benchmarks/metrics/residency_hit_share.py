"""Engine residency: of the window's lookups of staged arrays, the share
the device already held (``/debug/memory`` ``counters.hits`` over ``hits +
misses``, after the window less before)."""


def read(ctx):
    after = ctx["after"]["memory"]["counters"]
    before = ctx["before"]["memory"]["counters"]
    hits = after["hits"] - before["hits"]
    misses = after["misses"] - before["misses"]
    if hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
