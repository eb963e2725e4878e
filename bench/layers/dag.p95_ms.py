"""95th percentile of the host-clock latency of every DAG call due in the
window, from when it was due to its run ending, ms."""

from bench.harness.common import quantile


def read(window):
    if not window.latency_ms:
        return None
    return quantile(window.latency_ms, 0.95)
