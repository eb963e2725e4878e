"""Store cells: YCSB operations as Cloudburst DAG calls on the device-tier KVS.

One DAG call is one function doing one operation on one record:
``ycsb_read`` takes the record as a KVS reference (the engine fuses the
wave's reads into one batched fetch per cache) and returns it;
``ycsb_update`` writes a fresh record through the executor's cache and
returns the write's LWW timestamp, which acknowledges it.  The window
drives ``Cluster.call_dag_async`` and ``Cluster.step()``, with one
``Cluster.tick()`` (cache flushes, gossip, pushes) after every turn.

Correctness is checked against ``lww.py`` once the window has closed:
what every read returned, and every acknowledged write read back merged
and from each of its replicas.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Cluster, CloudburstReference
from repro.core.arena import PlaneBatch, PlaneGroup

from . import flops, loops, lww, traffic
from .common import Outcome, Stopwatch, quantile
from .window import Window, delta, numeric_snapshot

LOAD_NODE = "load"  # writer id of the loaded records, clock 0: any update wins


def key_name(i: int) -> str:
    return f"user{i:08d}"


def ycsb_read(record):
    """YCSB read: the engine resolves the reference through the cache."""
    return record


def ycsb_update(cloudburst, key, record):
    """YCSB update of the whole record; the timestamp acknowledges it."""
    return cloudburst.put(key, record).timestamp


@functools.partial(jax.jit, static_argnums=(1, 2))
def _records(key, n: int, floats: int):
    return jax.random.normal(key, (n, floats), jnp.float32)


def loaded_records(seed: int, n: int, floats: int) -> jax.Array:
    """Every loaded record, made on the device from the seed."""
    word = int(traffic.rng_for(seed, "records").integers(0, 2**31))
    return _records(jax.random.PRNGKey(word), n, floats)


def build_cluster(cfg: Dict[str, Any], seed: int) -> Cluster:
    if cfg["mode"] != "lww":
        raise ValueError(f"store driver runs LWW only, not {cfg['mode']!r}")
    cluster = Cluster(n_vms=int(cfg["vms"]),
                      executors_per_vm=int(cfg["executors_per_vm"]),
                      n_kvs_nodes=int(cfg["nodes"]),
                      replication=int(cfg["replication"]), mode="lww",
                      seed=seed & 0x7FFFFFFF)
    if not cluster.kvs.device_tier:
        raise RuntimeError("the device slab tier is off")
    cluster.register(ycsb_read, "ycsb_read")
    cluster.register(ycsb_update, "ycsb_update")
    cluster.register_dag("ycsb_read", ["ycsb_read"])
    cluster.register_dag("ycsb_update", ["ycsb_update"])
    return cluster


def load(cluster: Cluster, records: jax.Array, how: str) -> None:
    """Every record at clock 0 by ``LOAD_NODE``, on all its replicas."""
    n, floats = records.shape
    keys = [key_name(i) for i in range(n)]
    if how == "put_planes":
        batch = PlaneBatch([LOAD_NODE])
        batch.groups[((floats,), "float32")] = PlaneGroup(
            (floats,), np.dtype(np.float32), keys, records,
            np.zeros((n, 1), np.int32), np.zeros((n, 1), np.int32))
        cluster.kvs.put_planes(batch, sync=True)
    elif how == "put_many":
        from repro.core import LWWLattice

        host = np.asarray(records)
        cluster.kvs.put_many(
            [(k, LWWLattice((0, LOAD_NODE), host[i]))
             for i, k in enumerate(keys)], sync=True)
    else:
        raise ValueError(f"unknown load path {how!r}")
    cluster.kvs.reset_transfer_stats()


def prefill_caches(cluster: Cluster, seed: int, mix: Dict[str, Any],
                   n_records: int, per_cache: int) -> None:
    """Fill every executor cache with ``per_cache`` distinct records
    drawn from the mix's key distribution, as a cache that has served
    this traffic for a long time holds them.  One batched warm per cache
    (``ExecutorCache.warm_plane``); it also sizes the cache's slabs, so
    that they do not grow (and compile for a new size) in the window."""
    for cache_id, cache in sorted(cluster.caches.items()):
        draw = traffic.key_sampler(traffic.fixed_rng(f"prefill/{cache_id}"),
                                   mix["keys"], n_records)
        seen: Dict[int, None] = {}
        while len(seen) < per_cache:
            for i in draw(4 * per_cache):
                seen.setdefault(int(i))
        keys = [key_name(i) for i in list(seen)[:per_cache]]
        resident = cache.warm_plane(keys)
        if len(resident) != per_cache:
            raise RuntimeError(f"cache {cache_id}: {len(resident)} of "
                               f"{per_cache} prefilled keys resident")
    cluster.tick()


def warm_wave_shapes(cluster: Cluster, seed: int, mix: Dict[str, Any],
                     n_records: int, floats: int, largest: int,
                     backlog: int = 0) -> List:
    """Send waves of 1, 2, ... ``largest`` calls of the mix at once, twice
    over, then waves of ``largest`` + 2, + 4, ... ``backlog`` calls once,
    each drained before the next; returns them as [(ops, loop)].  The
    slab programs compile per row count, and a wave's row counts (misses
    fetched, rows flushed and gossiped per node) follow its size; so the
    window finds its programs already built, also for the wave that a
    stall of the engine leaves behind it, up to ``backlog`` calls."""
    rng = traffic.rng_for(seed, "shape-waves")
    sizes = traffic.fixed_rng("shape-waves")
    waves = 2 * list(range(1, largest + 1)) + list(
        range(largest + 2, backlog + 1, 2))
    done = []
    for size in waves:
        ops = Ops.draw(rng, sizes, mix, size, n_records, floats)
        loop = loops.Loop(cluster, np.zeros(size),
                          _submitter(cluster, ops), False)
        loops.run_open(loop, 0.0)
        cluster.tick()
        done.append((ops, loop))
    return done


@dataclasses.dataclass
class Ops:
    """What each call of a loop does: read or update which record, with
    which fresh record."""

    op: np.ndarray  # "read" | "update"
    key: np.ndarray  # record index
    rows: np.ndarray  # (n_updates, floats) fresh records, by update rank

    @classmethod
    def draw(cls, rng, sizes, mix: Dict[str, Any], n: int, n_records: int,
             floats: int) -> "Ops":
        """``sizes`` draws the keys, the same for every seed; ``rng`` their
        order, the order of the ops and the records written."""
        ops = traffic.op_sequence(rng, mix["ops"], n)
        keys = traffic.seeded_order(
            rng, traffic.key_sampler(sizes, mix["keys"], n_records)(n))
        n_up = int((ops == "update").sum())
        rows = rng.standard_normal((n_up, floats), dtype=np.float32)
        return cls(op=ops, key=keys, rows=rows)

    @property
    def update_rank(self) -> np.ndarray:
        return np.cumsum(self.op == "update") - 1


def make_loop(cluster: Cluster, seed: int, stream: str, mix: Dict[str, Any],
              seconds: float, n_records: int, floats: int, traced: bool):
    """The calls of one stretch of traffic, ready to drive: the same
    calls at the same gaps for every seed, in the seed's order."""
    rng, sizes = traffic.rng_for(seed, stream), traffic.fixed_rng(stream)
    due = traffic.open_arrivals(rng, mix["arrivals"], seconds, sizes)
    ops = Ops.draw(rng, sizes, mix, len(due), n_records, floats)
    return ops, loops.Loop(cluster, due, _submitter(cluster, ops), traced)


def _submitter(cluster: Cluster, ops: Ops):
    rank = ops.update_rank

    def submit(i: int):
        key = key_name(int(ops.key[i]))
        if ops.op[i] == "read":
            return cluster.call_dag_async(
                "ycsb_read", {"ycsb_read": (CloudburstReference(key),)})
        return cluster.call_dag_async(
            "ycsb_update", {"ycsb_update": (key, ops.rows[rank[i]])})

    return submit


def run(cell, seed: int, seconds: float, traced: bool, trace_ctx,
        setup_clock: Stopwatch, log, peak_fn) -> Outcome:
    """Set up, warm up, measure one window, check it."""
    cfg, mix = cell.config, cell.traffic
    n_records = int(cfg["records"])
    floats = int(cfg["record_floats"])
    phases: Dict[str, float] = {}

    t = time.perf_counter()
    cluster = build_cluster(cfg, seed)
    records = loaded_records(seed, n_records, floats)
    records.block_until_ready()
    phases["records_s"] = time.perf_counter() - t
    t = time.perf_counter()
    load(cluster, records, cfg["load"])
    del records
    phases["load_s"] = time.perf_counter() - t
    t = time.perf_counter()
    prefill_caches(cluster, seed, mix, n_records,
                   int(cfg["cache_prefill_keys"]))
    phases["cache_prefill_s"] = time.perf_counter() - t

    t = time.perf_counter()
    waves = warm_wave_shapes(cluster, seed, mix, n_records, floats,
                             int(cell.params["shape_waves"]),
                             int(cell.params.get("backlog_waves", 0)))
    phases["shape_waves_s"] = time.perf_counter() - t
    t = time.perf_counter()
    warm_s = float(mix["warmup_s"])
    warm_ops, warm = make_loop(cluster, seed, "warmup", mix, warm_s,
                               n_records, floats, False)
    loops.run_open(warm, warm_s)
    for _ in range(4):
        cluster.tick()
    phases["warmup_s"] = time.perf_counter() - t

    ops, loop = make_loop(cluster, seed, "window", mix, seconds, n_records,
                          floats, traced)
    before = numeric_snapshot(cluster.metrics)
    xfer0 = cluster.kvs.transfer_stats()["device_syncs"]
    setup_s = setup_clock()
    log(f"set-up {setup_s:.3f} s: " + ", ".join(
        f"{k} {v:.3f}" for k, v in phases.items()))
    with trace_ctx():
        loops.run_open(loop, seconds)
    after = numeric_snapshot(cluster.metrics)
    syncs = cluster.kvs.transfer_stats()["device_syncs"] - xfer0
    for _ in range(4):  # flush every cache, then gossip to every replica
        cluster.tick()
    peak = peak_fn()

    ok = loop.ok
    attempted = int(loop.submitted.sum())
    completed = int(ok.sum())
    in_window = int((loop.done <= seconds).sum())
    lat_ms = loop.latencies_ms()
    e2e: Dict[str, float] = {"setup_s": setup_s}
    if lat_ms:
        e2e["dag_p99_ms"] = quantile(lat_ms, 0.99)
    n_reads = int(((ops.op == "read") & ok).sum())
    n_updates = int(((ops.op == "update") & ok).sum())
    work = {
        "calls_completed": float(completed),
        "calls_in_window": float(in_window),
        "device_syncs": float(syncs),
        "slab_bytes": float((n_reads + n_updates * int(cfg["replication"]))
                            * flops.slab_row_bytes(floats)),
    }
    window = Window(seconds=seconds, chips=cell.chips, peaks={},
                    counters=delta(after, before), step_s=loop.step_s,
                    work=work, latency_ms=lat_ms)
    if lat_ms:
        log(f"window: {completed}/{attempted} calls, p50 "
            f"{quantile(lat_ms, 0.5):.3f} ms, p95 {quantile(lat_ms, 0.95):.3f}"
            f" ms, p99 {quantile(lat_ms, 0.99):.3f} ms, mean "
            f"{sum(lat_ms) / len(lat_ms):.3f} ms, {len(loop.step_s)} turns, {in_window / seconds:.1f} "
            f"calls/s; longest turn {1e3 * max(loop.turn_s):.1f} ms, "
            f"{sum(t > 0.5 for t in loop.turn_s)} turns over 0.5 s; calls "
            f"sent up to {1e3 * loop.late_s:.1f} ms after they were due")
    checks = check(cluster, waves + [(warm_ops, warm), (ops, loop)], seed,
                   cfg, log)
    checks.insert(0, ["lost_calls", attempted - completed, 0])
    correct = all(value <= limit for _n, value, limit in checks)
    return Outcome(correct=correct, attempted=attempted,
                   failed=attempted - completed, end_to_end=e2e,
                   checks=checks, window=window, memory_peak_bytes=peak)


def check(cluster: Cluster, stretches, seed: int, cfg: Dict[str, Any],
          log) -> List[List[Any]]:
    """Compare the window's reads, and every acknowledged write of the
    run (warm-up included), with the plain per-key LWW fold of everything
    this run wrote.  ``stretches`` is [(ops, loop)], warm-up first; the
    last one is the window."""
    kvs = cluster.kvs
    floats = int(cfg["record_floats"])
    touched = np.unique(np.concatenate(
        [ops.key[loop.submitted] for ops, loop in stretches]))
    loaded = np.asarray(loaded_records(seed, int(cfg["records"]), floats
                                       )[jnp.asarray(touched)])
    history = lww.History()
    for j, i in enumerate(touched):
        history.add(int(i), (0, LOAD_NODE), loaded[j], -np.inf)
    for n, (ops, loop) in enumerate(stretches):
        window = n == len(stretches) - 1
        rank = ops.update_rank
        for i in np.flatnonzero((ops.op == "update") & loop.ok):
            clock, node = loop.value[i]
            history.add(int(ops.key[i]), (int(clock), str(node)),
                        ops.rows[rank[i]],
                        float(loop.due[i]) if window else -np.inf)
    restarts = int(cluster.telemetry().get("engine.run_restarts", 0))

    ops, loop = stretches[-1]
    reads = np.flatnonzero((ops.op == "read") & loop.ok)
    read_bad = sum(not history.could_read(int(ops.key[i]), loop.value[i],
                                          float(loop.done[i]))
                   for i in reads)

    written = sorted(history.written_keys())
    names = [key_name(i) for i in written]
    expect = {key_name(i): history.winner(i) for i in written}
    merged = kvs.get_merged_many(names).to_host()
    merged_bad = len(names) - _count_matching(merged, expect)
    held: Dict[str, int] = {k: 0 for k in names}
    replica_bad = 0
    for node in kvs.nodes.values():
        batch = node.engine.export_planes(names).to_host()
        for key, row, ts in _entries(batch):
            held[key] += 1
            if not lww.same(expect[key], ts, row):
                replica_bad += 1
        replica_bad += len(batch.sidecar)
    replica_bad += sum(1 for k in names
                       if held[k] != int(cfg["replication"]))
    log(f"checked {len(reads)} reads, {len(names)} written keys merged and "
        f"{sum(held.values())} replica copies")
    return [["read_mismatch", int(read_bad), 0],
            ["merged_mismatch", int(merged_bad), 0],
            ["replica_mismatch", int(replica_bad), 0],
            ["run_restarts", restarts, 0]]


def _entries(batch):
    node_ids = list(batch.node_ids)
    for pg in batch.groups.values():
        for j, key in enumerate(pg.keys):
            yield key, pg.vals[j], (int(pg.clocks[j, 0]),
                                    node_ids[int(pg.node_idx[j, 0])])


def _count_matching(batch, expect) -> int:
    seen = set()
    good = 0
    for key, row, ts in _entries(batch):
        if key in expect and key not in seen:
            seen.add(key)
            good += lww.same(expect[key], ts, row)
    return good
