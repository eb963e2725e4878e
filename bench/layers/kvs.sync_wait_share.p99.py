"""Share of the window the host spent blocked in the KVS tier's
device->host copies (registry gauge ``kvs.device_sync_s``, window
delta, over the window's seconds), %."""


def read(window):
    blocked = window.counters.get("kvs.device_sync_s")
    if blocked is None or window.seconds <= 0:
        return None
    return 100.0 * blocked / window.seconds
