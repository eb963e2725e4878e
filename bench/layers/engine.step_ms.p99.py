"""Host milliseconds per ``Cluster.step()`` call, mean over the window."""


def read(window):
    if not window.step_s:
        return None
    return 1e3 * sum(window.step_s) / len(window.step_s)
