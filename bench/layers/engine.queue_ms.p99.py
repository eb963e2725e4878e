"""Host milliseconds a call waited from ``call_dag_async`` to the
dispatch of its run's first trigger, mean over the window's calls
(registry counters ``engine.queue.s`` / ``.n``, window deltas)."""


def read(window):
    n = window.counters.get("engine.queue.n", 0.0)
    if n <= 0:
        return None
    return 1e3 * window.counters["engine.queue.s"] / n
