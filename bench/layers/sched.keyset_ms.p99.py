"""Host milliseconds per ``Cluster.tick()`` spent republishing the
caches' key sets and refreshing the scheduler's index: the program's
``sched.keyset`` and ``sched.index`` phase seconds over the window's
ticks (``engine.tick.n``)."""


def read(window):
    ticks = window.counters.get("engine.tick.n", 0.0)
    c = window.counters
    if ticks <= 0 or "sched.keyset.s" not in c or "sched.index.s" not in c:
        return None
    return 1e3 * (c["sched.keyset.s"] + c["sched.index.s"]) / ticks
