"""Host-device syncs of the KVS tier (``AnnaKVS.transfer_stats()``
``device_syncs``, window delta) per DAG call completed."""


def read(window):
    calls = window.work.get("calls_completed", 0.0)
    if calls <= 0:
        return None
    return window.work["device_syncs"] / calls
