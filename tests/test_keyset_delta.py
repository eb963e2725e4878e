"""Key-set deltas: membership watches on the merge engine keep the KVS
push index and the scheduler's per-executor key sets equal to a full
republish of every cache's key set, tick after tick."""

import random
from collections import defaultdict

import numpy as np
import pytest

from repro.core import (
    AnnaKVS,
    Cluster,
    LamportClock,
    LWWLattice,
    MergeEngine,
    SetLattice,
)
from repro.core import arena as arena_mod
from repro.core.arena import PlaneBatch, PlaneBuffer, PlaneGroup


def _tensor(rng: random.Random, n: int) -> np.ndarray:
    return np.full(n, rng.randint(0, 99), np.float32)


def _lww_value(rng: random.Random):
    """A value that lands in the (8,) slab, the (16,) slab or, opaque,
    in the fallback: rewriting a key moves it between all three."""
    kind = rng.random()
    if kind < 0.6:
        return _tensor(rng, 8)
    if kind < 0.85:
        return _tensor(rng, 16)
    return f"opaque-{rng.randint(0, 99)}"


# ---------------------------------------------------------------------------
# the engine's watch, path by path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("device", [False, True], ids=["host", "device"])
@pytest.mark.parametrize("seed", [0, 1])
def test_engine_watch_tracks_membership(device, seed):
    """Every path that adds or drops a key — point merges, batched
    merges, plane ingests with and without duplicate keys, fallback
    writes, moves between slabs and to and from the fallback, deletes
    and a tier migration — leaves a drained delta that turns the last
    snapshot into the engine's key set."""
    rng = random.Random(seed)
    clk = LamportClock("w")
    eng = MergeEngine(device=device)
    watch = eng.watch_keys()
    snapshot: set = set()
    keys = [f"k{i}" for i in range(24)]
    for step in range(40):
        op = rng.randrange(7)
        if op == 0:
            key = rng.choice(keys)
            eng.merge_one(key, LWWLattice(clk.tick(), _lww_value(rng)))
        elif op == 1:
            eng.merge_batch([
                (k, LWWLattice(clk.tick(), _tensor(rng, 8)))
                for k in rng.sample(keys, 6)])
        elif op == 2:
            buf = PlaneBuffer()
            picked = rng.sample(keys, 5)
            for k in picked + picked[:2]:  # duplicates: the multi path
                buf.add(k, LWWLattice(clk.tick(), _tensor(rng, 8)))
            eng.ingest_planes(buf.drain())
        elif op == 3:
            buf = PlaneBuffer()
            for k in rng.sample(keys, 5):
                buf.add(k, LWWLattice(clk.tick(), _tensor(rng, 8)))
            eng.ingest_planes(buf.drain())
        elif op == 4:
            key = f"s{rng.randrange(4)}"
            eng.merge_one(key, SetLattice.of([rng.randrange(9)]))
        elif op == 5:
            held = sorted(eng.view)
            if held:
                del eng.view[rng.choice(held)]
        else:
            eng.delete(rng.choice(keys))
        if step == 20:
            eng.migrate_device(not eng.device)
        if rng.random() < 0.4:
            added, removed = watch.drain()
            assert not added & removed
            snapshot -= removed
            snapshot |= added
            assert snapshot == set(eng.view)
    added, removed = watch.drain()
    assert (snapshot - removed) | added == set(eng.view)
    eng.unwatch_keys(watch)
    assert eng.arena._watches == []


def test_tier_migration_and_slab_moves_are_not_membership_changes():
    clk = LamportClock("w")
    rng = random.Random(3)
    eng = MergeEngine(device=False)
    eng.merge_one("a", LWWLattice(clk.tick(), _tensor(rng, 8)))
    watch = eng.watch_keys()
    eng.merge_one("a", LWWLattice(clk.tick(), _tensor(rng, 16)))
    eng.migrate_device(True)
    assert watch.drain() == (set(), set())
    eng.merge_one("b", LWWLattice(clk.tick(), _tensor(rng, 8)))
    assert watch.drain() == ({"b"}, set())


def test_unwatched_engines_record_nothing(monkeypatch):
    """Storage nodes and the reader register no watch: a put_planes
    load, a put_many and a batched read never reach the watch code."""

    def refuse(*_):
        raise AssertionError("an unwatched engine noted a key")

    monkeypatch.setattr(arena_mod, "_note_added", refuse)
    monkeypatch.setattr(arena_mod, "_note_removed", refuse)
    kvs = AnnaKVS(num_nodes=3, replication=2)
    n = 64
    keys = [f"rec{i}" for i in range(n)]
    batch = PlaneBatch(["loader"])
    batch.groups[((8,), "float32")] = PlaneGroup(
        (8,), np.dtype(np.float32), keys,
        np.arange(n * 8, dtype=np.float32).reshape(n, 8),
        np.zeros((n, 1), np.int32), np.zeros((n, 1), np.int32))
    kvs.put_planes(batch, sync=True)
    clk = LamportClock("w")
    kvs.put_many([(f"new{i}", LWWLattice(clk.tick(), np.ones(8, np.float32)))
                  for i in range(16)])
    kvs.tick()
    kvs.delete("rec0")
    assert len(kvs.get_merged_many(keys[1:] + ["new3"])) == n
    engines = [node.engine for node in kvs.nodes.values()] + [kvs.reader]
    assert all(e.arena._watches == [] for e in engines)


# ---------------------------------------------------------------------------
# the cluster: KVS push index and scheduler key sets after every tick
# ---------------------------------------------------------------------------


def _assert_matches_full_republish(c: Cluster) -> None:
    expected = defaultdict(set)
    for cache_id, cache in c.caches.items():
        for key in set(cache.data):
            expected[key].add(cache_id)
    assert dict(c.kvs._cache_index) == dict(expected)
    for eid, ex in c.executors.items():
        assert c.scheduler.executor_keysets[eid] == set(ex.cache.data), eid


def _random_batch(c: Cluster, rng: random.Random, clk: LamportClock,
                  keys) -> None:
    for _ in range(rng.randint(1, 5)):
        live = [cache for cache in c.caches.values() if cache.alive]
        cache = rng.choice(live) if live else None
        op = rng.randrange(8)
        if op == 0 and cache is not None:  # batched miss fill
            cache.read_many(rng.sample(keys, 6) + ["absent"])
        elif op == 1 and cache is not None:  # write-back, any slab/fallback
            cache.write(rng.choice(keys),
                        LWWLattice(clk.tick(), _lww_value(rng)))
        elif op == 2:  # a KVS write: pushes to every subscribed cache
            c.kvs.put(rng.choice(keys),
                      LWWLattice(clk.tick(), _lww_value(rng)), sync=True)
        elif op == 3 and cache is not None:  # a non-LWW lattice
            cache.write(f"set{rng.randrange(4)}",
                        SetLattice.of([rng.randrange(9)]))
        elif op == 4 and cache is not None:  # scalar miss path
            cache.read(rng.choice(keys))
        elif op == 5 and cache is not None:  # a local delete
            held = sorted(cache.data)
            if held:
                del cache.data[rng.choice(held)]
        elif op == 6 and live:
            c.fail_vm(rng.choice(live).cache_id[len("cache-"):])
        elif op == 7:
            dead = [x for x in c.caches.values() if not x.alive]
            if dead:
                c.recover_vm(rng.choice(dead).cache_id[len("cache-"):],
                             warm_keys=rng.sample(keys, 3))


@pytest.mark.parametrize("seed,device", [
    (0, False), (1, False), (2, False), (3, False), (11, True)])
def test_keysets_equal_full_republish_after_every_tick(seed, device,
                                                       monkeypatch):
    monkeypatch.setattr(arena_mod, "_DEVICE_TIER_CACHE", device)
    rng = random.Random(seed)
    clk = LamportClock("client")
    c = Cluster(n_vms=2, executors_per_vm=2, n_kvs_nodes=3, seed=seed)
    assert c.kvs.device_tier == device
    keys = [f"k{i}" for i in range(20)]
    for key in keys[:16]:
        c.kvs.put(key, LWWLattice(clk.tick(), _tensor(rng, 8)), sync=True)
    for n_tick in range(30):
        if n_tick == 3:
            c.add_vm(2)  # the autoscaler's path: a cache seen late
        _random_batch(c, rng, clk, keys)
        c.tick(defer_prob=0.3 if n_tick % 4 == 0 else 0.0)
        _assert_matches_full_republish(c)


def test_delta_counter_counts_changed_keys_only():
    clk = LamportClock("client")
    c = Cluster(n_vms=2, executors_per_vm=2, n_kvs_nodes=2, seed=0)
    keys = [f"k{i}" for i in range(12)]
    for key in keys:
        c.kvs.put(key, LWWLattice(clk.tick(), np.ones(8, np.float32)),
                  sync=True)
    cache = c.caches["cache-vm-0"]
    cache.read_many(keys[:4])
    c.tick()
    snap = c.telemetry()
    # each cache publishes in full once, the scheduler seeds each once
    assert snap["sched.keyset.full"] == 2 * len(c.caches)
    base = snap["sched.keyset.delta_keys"]
    cache.read_many(keys[:4])  # hits only
    c.tick()
    assert c.telemetry()["sched.keyset.delta_keys"] == base
    k = 5
    cache.read_many(keys[4:4 + k])  # k fresh misses
    c.tick()
    snap = c.telemetry()
    assert snap["sched.keyset.delta_keys"] == base + k
    assert snap["sched.keyset.full"] == 2 * len(c.caches)
    assert snap["sched.keyset.n"] == 3 * len(c.caches)
