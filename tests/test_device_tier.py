"""Device-resident slab tier: equivalence, deletion, sync discipline.

The device tier keeps arena slabs as donated jax arrays; every path must
stay bit-identical to the host-numpy tier, deleted keys must not be
resurrected out of still-live donated buffers, ``LatticeArena``
materialization must cross the host boundary exactly once per call, and
the steady-state gossip / warmed-read planes must cross it ZERO times
(counter-asserted AND enforced with a d2h transfer guard — the
device-tier twin of the zero-object asserts in test_planes).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.arena import (
    MergeEngine,
    NodeRegistry,
    PlaneBuffer,
    oracle_lww_fold,
)
from repro.core.kvs import AnnaKVS
from repro.core.lattices import LWWLattice

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lat(rng, node_pool, D=16):
    return LWWLattice(
        (int(rng.integers(0, 5)),  # small clocks: frequent ties
         node_pool[int(rng.integers(0, len(node_pool)))]),
        rng.normal(size=(D,)).astype(np.float32))


def _materialized(engine, keys):
    engine.arena.clear_memo()
    return {k: engine.get(k) for k in keys}


def _assert_same_state(host, device, keys):
    got_h = _materialized(host, keys)
    got_d = _materialized(device, keys)
    for key in keys:
        h, d = got_h[key], got_d[key]
        if h is None or d is None:
            assert h is None and d is None, key
            continue
        assert h.timestamp == d.timestamp, (key, h.timestamp, d.timestamp)
        np.testing.assert_array_equal(np.asarray(h.value),
                                      np.asarray(d.value))


def test_device_tier_bit_identical_to_host_under_random_traffic():
    """Twin engines (host slab / device slab) fed the same randomized
    merge + gossip + dup-key + delete traffic converge to bit-identical
    state — including registry remaps (late node ids that re-sort the
    intern table) and slab growth past the initial capacity."""
    rng = np.random.default_rng(7)
    registry_h, registry_d = NodeRegistry(), NodeRegistry()
    host = MergeEngine(registry_h, device=False)
    dev = MergeEngine(registry_d, device=True)
    assert dev.device and not host.device
    keys = [f"k{i}" for i in range(37)]  # > initial cap: forces slab_grow
    # round 0 pool sorts AFTER round 2's ids: ensure() mid-stream remaps
    pools = [["n5", "n9"], ["n1", "n7"], ["a0", "zz"]]
    for round_i in range(3):
        node_pool = pools[round_i]
        items = [(k, _lat(rng, node_pool)) for k in keys
                 if rng.random() < 0.7]
        for eng in (host, dev):
            eng.merge_batch(list(items))
        # gossip with duplicate keys (two queued rounds drain together)
        dup_items = [(k, _lat(rng, node_pool)) for k in keys[:11]]
        dup_items += [(k, _lat(rng, node_pool)) for k in keys[:5]]
        for eng in (host, dev):
            buf = PlaneBuffer()
            for k, v in dup_items:
                buf.add(k, v)
            eng.ingest_planes(buf.drain())
        victim = keys[round_i]
        for eng in (host, dev):
            assert eng.delete(victim)
        _assert_same_state(host, dev, keys)
    # plane export round-trips bit-identical off the device slab too
    alive = [k for k in keys if k in dev.arena]
    back = MergeEngine(registry_d, device=False)
    back.ingest_planes(dev.export_planes(alive).to_host())
    _assert_same_state(back, dev, alive)


def test_kvs_delete_does_not_resurrect_from_device_buffers():
    """Deleted keys stay deleted on the device tier: neither still-live
    donated slab buffers nor queued (device-resident) gossip rows may
    bring the value back on later ticks/reads."""
    kvs = AnnaKVS(num_nodes=3, replication=2, device_tier=True)
    rng = np.random.default_rng(3)
    keys = [f"d{i}" for i in range(12)]
    for k in keys:
        kvs.put(k, _lat(rng, ["w1", "w2"]))
    kvs.tick()
    # a fresh write is still in replica inboxes when the delete lands
    kvs.put("d3", _lat(rng, ["w1"]))
    kvs.delete("d3")
    for _ in range(3):
        kvs.tick()
    assert kvs.get("d3") is None
    assert kvs.get_merged("d3") is None
    batch = kvs.get_merged_many(keys)
    got = {k: v for k, v in batch.iter_entries()}
    assert "d3" not in got
    # the dropped row's bytes live on in the donated buffer until
    # overwritten — new keys must not alias or expose them
    kvs.put("fresh", _lat(rng, ["w2"]))
    kvs.tick()
    assert kvs.get_merged("d3") is None
    survivors = [k for k in keys if k != "d3"]
    merged = kvs.get_merged_many_values(survivors)
    assert all(merged[k] is not None for k in survivors)


def test_materialize_syncs_exactly_once_per_call():
    """``LatticeArena.get`` on a device slab pulls the row in exactly ONE
    host transfer; the memo makes repeat reads free until the row (or
    layout) changes."""
    eng = MergeEngine(NodeRegistry(), device=True)
    rng = np.random.default_rng(11)
    keys = [f"m{i}" for i in range(6)]
    eng.merge_batch([(k, _lat(rng, ["a", "b"])) for k in keys])
    arena = eng.arena
    for k in keys:
        before = arena.device_syncs
        first = arena.get(k)
        assert arena.device_syncs == before + 1, k
        again = arena.get(k)  # memo hit: no second transfer
        assert again is first
        assert arena.device_syncs == before + 1, k
    arena.clear_memo()
    before = arena.device_syncs
    arena.get(keys[0])
    assert arena.device_syncs == before + 1
    assert arena.d2h_bytes > 0


def test_steady_state_device_gossip_zero_host_syncs():
    """Engine-to-engine gossip on the device tier (export -> inbox ->
    ingest) crosses the host boundary ZERO times once warmed: counters
    stay flat and a device-to-host transfer guard proves no hidden
    ``__array__`` syncs either."""
    jax = pytest.importorskip("jax")
    rng = np.random.default_rng(5)
    registry = NodeRegistry()
    src = MergeEngine(registry, device=True)
    dst = MergeEngine(registry, device=True)
    keys = [f"g{i}" for i in range(24)]
    for eng in (src, dst):
        eng.merge_batch([(k, _lat(rng, ["w1", "w2", "w3"])) for k in keys])

    def deliver():
        buf = PlaneBuffer()
        buf.add_batch(src.export_planes(keys))
        dst.ingest_planes(buf.drain())

    deliver()  # warm: rows allocated, launches compiled
    counters = lambda: (src.h2d_bytes, src.d2h_bytes, src.device_syncs,
                        dst.h2d_bytes, dst.d2h_bytes, dst.device_syncs)
    before = counters()
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(4):
            deliver()
    assert counters() == before
    assert dst.plane_object_fallbacks == 0
    # and the traffic really merged: winners == per-key folds
    for k in keys[:5]:
        want = oracle_lww_fold([dst.get(k), src.get(k)])
        got = dst.get(k)
        assert got.timestamp == want.timestamp


def test_warmed_batched_reads_zero_host_syncs():
    """Warmed ``get_merged_many`` on the device tier re-executes its
    cached plan as fused on-device launches: zero host syncs, enforced
    by counters and a d2h transfer guard; winners stay bit-identical to
    the per-key read-repair fold."""
    jax = pytest.importorskip("jax")
    kvs = AnnaKVS(num_nodes=3, replication=2, device_tier=True)
    rng = np.random.default_rng(9)
    keys = [f"r{i}" for i in range(20)]
    for k in keys:
        for owner in kvs._owners(k):
            kvs.nodes[owner].engine.merge_one(k, _lat(rng, ["w1", "w2"]))
    batch = kvs.get_merged_many(keys)  # warm: plan cached, jit compiled
    batch.block_until_ready()
    before = kvs.transfer_stats()
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(4):
            kvs.get_merged_many(keys).block_until_ready()
    assert kvs.transfer_stats() == before
    # correctness (materializes, so outside the guard)
    got = {k: v for k, v in kvs.get_merged_many(keys).iter_entries()}
    for k in keys:
        want = kvs.get_merged(k)
        assert got[k].timestamp == want.timestamp, k
        np.testing.assert_array_equal(np.asarray(got[k].value),
                                      np.asarray(want.value))
    # content writes re-use the cached plan (layout unchanged) and the
    # next read sees the new winner
    kvs.put(keys[0], LWWLattice((10 ** 6, "w9"),
                                np.full((16,), 42.0, np.float32)))
    kvs.tick()
    plans_before = len(kvs._read_plans)
    fresh = {k: v for k, v in kvs.get_merged_many(keys).iter_entries()}
    assert len(kvs._read_plans) == plans_before
    assert fresh[keys[0]].timestamp == (10 ** 6, "w9")


_DEVICE_SHARDED_WORLD = r"""
import numpy as np
import jax

assert jax.local_device_count() == 4, jax.devices()

from repro.core.arena import device_tier_default
from repro.core.kvs import AnnaKVS
from repro.core.lattices import LWWLattice
from repro.launch.sharding import kvs_slab_sharding
from repro.kernels import ops

assert device_tier_default()  # REPRO_DEVICE_TIER=1 in the env

kvs = AnnaKVS(num_nodes=3, replication=3)
assert kvs.device_tier
rng = np.random.default_rng(0)
node_pool = ["anna-0", "anna-1", "anna-10", "zz"]
oracle = {}
for round_i in range(3):
    for k in range(24):
        key = f"g{k}"
        clock = int(rng.integers(0, 3))
        node = node_pool[int(rng.integers(0, len(node_pool)))]
        seed = np.random.default_rng(abs(hash((clock, node, k))) % 2**32)
        val = seed.normal(size=(16,)).astype(np.float32)
        val[::5] = -0.0  # the sign of zero must survive sharded gathers
        lat = LWWLattice((clock, node), val)
        kvs.put(key, lat)
        cur = oracle.get(key)
        oracle[key] = lat if cur is None else cur.merge(lat)
    kvs.tick(defer_prob=0.3)
for _ in range(3):
    kvs.tick()

# slab planes are K-sharded over the 4-device "kvs" mesh
mesh = ops.merge_mesh()
assert mesh is not None and mesh.size == 4
slab = next(iter(kvs.nodes.values())).engine.arena._slabs
slab = next(iter(slab.values()))
want_sharding = kvs_slab_sharding(mesh, slab.cap)
assert want_sharding is not None
assert slab.vals.sharding.is_equivalent_to(want_sharding, slab.vals.ndim)

for node in kvs.nodes.values():
    for key, want in oracle.items():
        got = node.store[key]
        assert got.timestamp == want.timestamp, (key, got.timestamp)
        np.testing.assert_array_equal(np.asarray(got.value).view(np.uint32),
                                      want.value.view(np.uint32))

# batched read-repair over sharded device slabs == per-key oracle
batch = kvs.get_merged_many(list(oracle))
for key, got in batch.iter_entries():
    want = oracle[key]
    assert got.timestamp == want.timestamp, (key, got.timestamp)
    np.testing.assert_array_equal(np.asarray(got.value).view(np.uint32),
                                  want.value.view(np.uint32))

print("DEVICE-SHARDED-OK")
"""


def test_device_slabs_shard_across_4_devices():
    """The device tier under a 4-device host platform: slab planes carry
    the "kvs" mesh sharding and every path stays bit-identical to the
    per-key oracle (jax fixes its device count at backend init, so the
    sharded world runs in a subprocess)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["REPRO_DEVICE_TIER"] = "1"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run(
        [sys.executable, "-c", _DEVICE_SHARDED_WORLD],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "DEVICE-SHARDED-OK" in proc.stdout
