"""The store driver at a small size on the CPU, sound and broken."""

import pytest

from bench.harness import store
from bench.tests._small import assert_sound, run_small, small_cell


@pytest.fixture
def device_tier(monkeypatch):
    """The device slab tier, as every benchmark run has it."""
    import repro.core.arena as arena

    monkeypatch.setattr(arena, "_DEVICE_TIER_CACHE", True)


def test_ycsb_a_open_loop_is_correct(device_tier):
    line = run_small(small_cell("ycsb-a.open"))
    assert_sound(line)
    assert set(line["metrics"]) == {"dag_p99_ms", "setup_s"}
    assert line["attempted"] == 60 and line["failed"] == 0


def test_ycsb_a_traced_run_reads_its_layers(device_tier):
    line = run_small(small_cell("ycsb-a.open"), traced=True)
    assert_sound(line)
    assert {"engine.step_ms.p99", "cache.hit_share.p99",
            "kvs.syncs_per_call.p99", "device.idle_share.p99",
            "dag.p95_ms"} <= set(
                line["metrics"])
    # no peaks for a CPU: no roofline is reported from a rehearsal
    assert "slab_roofline.p99" not in line["metrics"]
    assert line["device"]["busy_s"] > 0
    assert 0 < line["device"]["window_s"]


def test_a_read_altered_where_it_is_produced_is_caught(device_tier,
                                                        monkeypatch):
    monkeypatch.setattr(store, "ycsb_read", lambda record: record + 1.0)
    line = run_small(small_cell("ycsb-a.open"))
    assert not line["correct"]
    assert line["checks"]["read_mismatch"][0] > 0


def test_an_update_acknowledged_but_not_applied_is_caught(device_tier,
                                                           monkeypatch):
    from repro.core.cache import ExecutorCache

    monkeypatch.setattr(ExecutorCache, "write",
                        lambda self, key, value, clock=None: value)
    line = run_small(small_cell("ycsb-a.open"))
    assert not line["correct"]
    assert line["checks"]["merged_mismatch"][0] > 0


def test_the_control_breaking_replication_is_caught(device_tier,
                                                     monkeypatch):
    import bench.control as control

    monkeypatch.setattr(*control.fault_patch("drop_gossip"))
    line = run_small(small_cell("ycsb-a.open"))
    assert not line["correct"]
    assert line["checks"]["replica_mismatch"][0] > 0


def test_put_planes_load_stores_what_put_many_stores(device_tier):
    cfg = small_cell("ycsb-a.open").config
    n, floats = 2048, int(cfg["record_floats"])
    rows = store.loaded_records(5, n, floats)
    dumps = []
    for how in ("put_planes", "put_many"):
        cluster = store.build_cluster(cfg, 5)
        store.load(cluster, rows, how)
        keys = [store.key_name(i) for i in range(n)]
        per_node = {}
        for node_id, node in sorted(cluster.kvs.nodes.items()):
            b = node.engine.export_planes(keys).to_host()
            per_node[node_id] = sorted(
                (k, ts, r.tobytes())
                for k, r, ts in store._entries(b))
        dumps.append(per_node)
    assert dumps[0] == dumps[1]
    assert sum(len(v) for v in dumps[0].values()) == 2 * n
