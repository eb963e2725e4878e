"""Executor-cache hits over hits + misses, all caches, window deltas of
the registry counters ``cache.<id>.hits`` and ``cache.<id>.misses``, %."""


def read(window):
    hits = window.counter_sum("cache.", ".hits")
    misses = window.counter_sum("cache.", ".misses")
    if hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
