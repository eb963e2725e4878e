"""Host milliseconds per ``Cluster.tick()``, mean over the window: the
program's ``engine.tick`` phase (registry counters ``engine.tick.s`` /
``.n``, window deltas)."""


def read(window):
    n = window.counters.get("engine.tick.n", 0.0)
    if n <= 0:
        return None
    return 1e3 * window.counters["engine.tick.s"] / n
