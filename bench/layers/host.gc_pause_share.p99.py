"""Share of the window that Python's collector held the process,
every generation (registry gauge ``host.gc.pause_s``, window delta, over
the window's seconds), %."""


def read(window):
    paused = window.counters.get("host.gc.pause_s")
    if paused is None or window.seconds <= 0:
        return None
    return 100.0 * paused / window.seconds
