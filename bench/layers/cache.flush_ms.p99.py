"""Host milliseconds per ``Cluster.tick()`` spent in the caches' flushes
and pushes: the program's ``cache.flush`` phase seconds (one phase per
cache a tick) over the window's ticks (``engine.tick.n``)."""


def read(window):
    ticks = window.counters.get("engine.tick.n", 0.0)
    if ticks <= 0 or "cache.flush.s" not in window.counters:
        return None
    return 1e3 * window.counters["cache.flush.s"] / ticks
