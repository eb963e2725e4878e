"""Observability plane: registry, shims, tracing, telemetry-driven §4.4.

Covers the obs subsystem end to end: metric primitives and the registry
snapshot/reset story; the counter shims that keep the legacy attribute
APIs working; per-engine transfer stats + reset; the KVS-snapshot-driven
``MonitoringEngine.decide``; span-tree correctness on a diamond DAG
(parent/child edges match the topology, root duration equals the run's
virtual-clock latency, Chrome export round-trips); and the instrumentation
cost contract — tracing disabled changes nothing, tracing at 1% sampling
stays under 5% overhead.
"""

from __future__ import annotations

import json
import math
import time

import numpy as np
import pytest

from repro.core import CloudburstReference, Cluster
from repro.core.autoscaler import MonitorConfig, MonitoringEngine
from repro.core.kvs import AnnaKVS
from repro.core.netsim import NetworkProfile
from repro.obs import Histogram, MetricsRegistry, Tracer


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_snapshot_reset():
    m = MetricsRegistry()
    c = m.counter("a.count")
    c.inc()
    c.inc(4)
    m.gauge("a.gauge").set(2.5)
    backing = {"v": 7}
    m.register_callback("a.cb", lambda: backing["v"],
                        reset_fn=lambda: backing.update(v=0))
    snap = m.snapshot()
    assert snap["a.count"] == 5
    assert snap["a.gauge"] == 2.5
    assert snap["a.cb"] == 7
    # get-or-create returns the same object; type clashes are errors
    assert m.counter("a.count") is c
    with pytest.raises(TypeError):
        m.gauge("a.count")
    m.reset()
    snap = m.snapshot()
    assert snap["a.count"] == 0 and snap["a.gauge"] == 0.0
    assert snap["a.cb"] == 0  # reset hook ran
    m.unregister_prefix("a.")
    assert m.names() == []


def test_histogram_streaming_quantiles():
    h = Histogram("lat")
    values = [0.001 * (i + 1) for i in range(100)]  # 1ms .. 100ms
    for v in values:
        h.observe(v)
    r = h.read()
    assert r["count"] == 100
    assert r["min"] == pytest.approx(0.001)
    assert r["max"] == pytest.approx(0.100)
    assert r["mean"] == pytest.approx(sum(values) / 100)
    # log-bucketed: quantiles land within one bucket width (~19%) of exact
    for q, exact in ((50, 0.0505), (95, 0.0955), (99, 0.0995)):
        got = r[f"p{q}"]
        assert exact / Histogram.GROWTH <= got <= exact * Histogram.GROWTH
    # quantiles never leave the observed range
    assert r["min"] <= r["p50"] <= r["p95"] <= r["p99"] <= r["max"]
    h.observe(0.0)  # zero bucket
    assert h.read()["min"] == 0.0
    h.reset()
    assert h.read() == {"count": 0}
    assert math.isnan(h.quantile(0.5))


def test_counter_shims_keep_legacy_attribute_api():
    c = Cluster(n_vms=1, executors_per_vm=1, n_kvs_nodes=2, seed=0,
                tracer=Tracer(enabled=False))
    c.register(lambda x: x + 1, "inc")
    c.register_dag("d", ["inc"])
    c.call_dag("d", {"inc": (1,)})
    # legacy attribute reads still work, backed by the registry
    assert c.engine_turns >= 1
    snap = c.telemetry()
    assert snap["engine.turns"] == c.engine_turns
    assert snap["engine.runs_submitted"] == 1
    assert snap["engine.runs_completed"] == 1
    assert snap["engine.run_latency_s.count"] == 1
    # attribute writes pass through to the registry too
    c.engine_turns = 0
    assert c.telemetry()["engine.turns"] == 0
    cache = next(iter(c.caches.values()))
    cache.hits += 3
    assert c.telemetry()[f"cache.{cache.cache_id}.hits"] == cache.hits
    # one consistent reset story
    c.reset_telemetry()
    assert c.telemetry()["engine.runs_submitted"] == 0
    assert cache.hits == 0


# ---------------------------------------------------------------------------
# transfer stats (per-engine breakdown + reset)
# ---------------------------------------------------------------------------

def test_transfer_stats_per_engine_breakdown_and_reset():
    kvs = AnnaKVS(num_nodes=3, replication=2)
    stats = kvs.transfer_stats()
    per = stats["per_engine"]
    assert set(per) == set(kvs.nodes) | {"reader"}
    assert stats["h2d_bytes"] == stats["d2h_bytes"] == 0
    # bump one node's counters directly (the host-numpy path never
    # transfers): totals must sum the per-engine entries
    node_id = next(iter(kvs.nodes))
    xfer = kvs.nodes[node_id].engine.arena._xfer
    xfer.h2d_bytes += 128
    xfer.device_syncs += 2
    kvs.reader.arena._xfer.d2h_bytes += 64
    stats = kvs.transfer_stats()
    assert stats["h2d_bytes"] == 128
    assert stats["d2h_bytes"] == 64
    assert stats["device_syncs"] == 2
    assert stats["per_engine"][node_id]["h2d_bytes"] == 128
    assert stats["per_engine"]["reader"]["d2h_bytes"] == 64
    # the registry sees the same totals through its callback gauges
    assert kvs.metrics.snapshot()["kvs.h2d_bytes"] == 128
    kvs.reset_transfer_stats()
    stats = kvs.transfer_stats()
    assert stats["h2d_bytes"] == stats["d2h_bytes"] == 0
    assert stats["device_syncs"] == 0
    assert all(v == 0 for e in stats["per_engine"].values()
               for v in e.values())


# ---------------------------------------------------------------------------
# telemetry-driven MonitoringEngine (§4.4)
# ---------------------------------------------------------------------------

def _publish_snapshot(mon, t, util, arrivals, completions, boots=0):
    mon.publish("time", t)
    mon.publish("avg_util", util)
    mon.publish("arrivals", arrivals)
    mon.publish("completions", completions)
    mon.publish("pending_boots", boots)


def test_decide_consumes_only_kvs_snapshots():
    kvs = AnnaKVS(num_nodes=2, replication=1)
    mon = MonitoringEngine(kvs, MonitorConfig(executors_per_node=3))
    # first decision: no rate window yet -> no replica action
    _publish_snapshot(mon, 0.0, 0.9, 0.0, 0.0)
    up, down, delta = mon.decide()
    assert up and not down and delta == 0
    # 5s later: 600 arrivals vs 100 completions -> 120 vs 20 req/s
    _publish_snapshot(mon, 5.0, 0.9, 600.0, 100.0)
    up, down, delta = mon.decide()
    assert up and not down and delta == 3
    # pending boots suppress further scale-up; low util scales down,
    # and a collapsed arrival rate sheds a replica
    _publish_snapshot(mon, 10.0, 0.1, 601.0, 700.0, boots=4)
    up, down, delta = mon.decide()
    assert not up and down and delta == -1


def test_cluster_publish_telemetry_drives_decide():
    c = Cluster(n_vms=1, executors_per_vm=2, n_kvs_nodes=2, seed=0,
                tracer=Tracer(enabled=False))
    c.register(lambda x: x * 2, "dbl")
    c.register_dag("d", ["dbl"])
    mon = MonitoringEngine(c.kvs, MonitorConfig(executors_per_node=3))
    c.publish_telemetry(now=0.0)
    mon.decide()  # seed the rate window from the live snapshot
    for i in range(6):
        c.call_dag("d", {"dbl": (i,)})
    # tiny utilization window -> executors look saturated; the arrival
    # counter moved while completions kept pace
    c.publish_telemetry(now=1.0, window=1e-9)
    up, down, delta = mon.decide()
    assert up  # avg_util == 1.0 from the live snapshot, no hand-fed float
    assert mon.read("arrivals") == 6
    assert mon.read("completions") == 6
    assert mon.read("cache_hit_rate") is not None
    assert mon.read("run_latency_p99") > 0


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------

def _diamond_cluster(tracer, profile=None):
    kw = {} if profile is None else {"profile": profile}
    c = Cluster(n_vms=2, executors_per_vm=2, n_kvs_nodes=2, seed=1,
                tracer=tracer, **kw)
    c.put("k1", np.ones(16, np.float32))
    c.put("k2", np.ones(16, np.float32))

    def a(x1, x2):
        return float(np.sum(np.asarray(x1)) + np.sum(np.asarray(x2)))

    c.register(a, "a")
    c.register(lambda v: v + 1, "b")
    c.register(lambda v: v * 2, "c")
    c.register(lambda vb, vc: (vb, vc), "d")
    c.register_dag("diamond", ["a", "b", "c", "d"],
                   edges=[("a", "b"), ("a", "c"), ("b", "d"), ("c", "d")])
    return c


def test_diamond_span_tree_matches_topology_and_latency():
    tr = Tracer(enabled=True)
    c = _diamond_cluster(tr)
    res = c.call_dag(
        "diamond",
        {"a": (CloudburstReference("k1"), CloudburstReference("k2"))},
        store_in_kvs="resp",
    )
    spans = tr.spans
    root = next(s for s in spans if s.name == "dag.diamond")
    # root span duration IS the run's virtual-clock latency
    assert root.t1 - root.t0 == pytest.approx(res.latency, abs=1e-12)
    invokes = {s.name.split(".", 1)[1]: s
               for s in spans if s.name.startswith("invoke.")}
    assert set(invokes) == {"a", "b", "c", "d"}
    # structural parent: every invoke hangs off the run's root span
    assert all(s.parent == root.sid for s in invokes.values())
    # DAG-topology edges ride the deps attr, matching the diamond
    assert invokes["a"].attrs["deps"] == []
    assert invokes["b"].attrs["deps"] == ["a"]
    assert invokes["c"].attrs["deps"] == ["a"]
    assert sorted(invokes["d"].attrs["deps"]) == ["b", "c"]
    # every invoke window sits inside the run window, on the run's clock
    for s in invokes.values():
        assert root.t0 <= s.t0 <= s.t1 <= root.t1
        assert s.tid == root.tid
    # invoke windows follow the topology order on the virtual clock
    assert invokes["a"].t1 <= min(invokes["b"].t0, invokes["c"].t0)
    assert max(invokes["b"].t1, invokes["c"].t1) <= invokes["d"].t0
    # all four layers appear: engine / scheduler / cache / kvs
    cats = {s.cat for s in spans}
    assert {"engine", "scheduler", "cache", "kvs"} <= cats
    # the read-set warm shows up as cache -> kvs nesting under the run
    cache_spans = [s for s in spans if s.cat == "cache"]
    assert cache_spans and cache_spans[0].parent == root.sid
    kvs_reads = [s for s in spans if s.name == "get_merged_many"]
    assert kvs_reads and kvs_reads[0].parent == cache_spans[0].sid
    # the response write is attributed to the kvs layer
    assert any(s.name == "response_put" for s in spans)


def test_trace_exports_round_trip():
    tr = Tracer(enabled=True)
    c = _diamond_cluster(tr)
    c.call_dag("diamond",
               {"a": (CloudburstReference("k1"), CloudburstReference("k2"))})
    # JSONL: one valid object per line, same span count
    lines = tr.export_jsonl().strip().splitlines()
    assert len(lines) == len(tr.spans)
    recs = [json.loads(line) for line in lines]
    assert all(rec["dur"] >= 0 for rec in recs)
    # Chrome trace_event: round-trips json, complete events + thread names
    doc = json.loads(json.dumps(tr.export_chrome()))
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    metas = [e for e in events if e["ph"] == "M"]
    assert len(xs) == len(tr.spans)
    assert {e["args"]["name"] for e in metas} >= {"run-1"}
    assert all(isinstance(e["tid"], int) and e["dur"] >= 0 for e in xs)
    assert len({e["cat"] for e in xs}) >= 4


def test_tracing_never_perturbs_execution():
    # virtual latency folds in REAL measured compute, so it is never
    # bit-identical across runs; what tracing must not perturb is the
    # deterministic machinery: results, scheduling counters, and the
    # network model's rng draw sequence
    runs = {}
    for name, tracer in (("off", Tracer(enabled=False)),
                         ("on", Tracer(enabled=True))):
        profile = NetworkProfile(seed=7)
        c = _diamond_cluster(tracer, profile=profile)
        res = c.call_dag(
            "diamond",
            {"a": (CloudburstReference("k1"), CloudburstReference("k2"))})
        snap = c.telemetry()
        runs[name] = (res.value, c.engine_turns,
                      snap["engine.fused_prefetch_batches"],
                      snap["engine.runs_completed"],
                      profile.rng.getstate())
    assert runs["on"] == runs["off"]


def test_run_sampling_is_deterministic_every_nth():
    tr = Tracer(enabled=True, sample=0.25)
    c = Cluster(n_vms=1, executors_per_vm=1, n_kvs_nodes=2, seed=0,
                tracer=tr)
    c.register(lambda x: x, "id")
    c.register_dag("d", ["id"])
    for i in range(8):
        c.call_dag("d", {"id": (i,)})
    roots = [s for s in tr.spans if s.name == "dag.d"]
    assert len(roots) == 2  # runs 1 and 5 of 8 at 1-in-4 sampling
    assert [s.tid for s in roots] == ["run-1", "run-5"]
    # unsampled runs contributed no spans at all
    assert all(s.tid in ("run-1", "run-5", "engine") for s in tr.spans)


def test_tracer_from_env(monkeypatch):
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    assert not Tracer.from_env().enabled
    monkeypatch.setenv("REPRO_TRACE", "1")
    monkeypatch.setenv("REPRO_TRACE_SAMPLE", "0.01")
    tr = Tracer.from_env()
    assert tr.enabled and tr.sample == 0.01 and tr._every == 100


# ---------------------------------------------------------------------------
# overhead contract
# ---------------------------------------------------------------------------

def _serve_once(tracer, n_requests=48, in_flight=8, seed=3):
    c = Cluster(n_vms=2, executors_per_vm=2, n_kvs_nodes=2, seed=seed,
                tracer=tracer)
    for i in range(n_requests):
        c.put(f"x-{i}", np.ones(64, np.float32))
        c.put(f"y-{i}", np.ones(64, np.float32))

    def fn(xa, xb):
        return float(np.sum(np.asarray(xa)) - np.sum(np.asarray(xb)))

    c.register(fn, "fn")
    c.register_dag("d", ["fn"])
    pending, submitted = [], 0
    t0 = time.perf_counter()
    while submitted < n_requests or pending:
        while submitted < n_requests and len(pending) < in_flight:
            pending.append(c.call_dag_async("d", {"fn": (
                CloudburstReference(f"x-{submitted}"),
                CloudburstReference(f"y-{submitted}"))}))
            submitted += 1
        c.step()
        pending = [f for f in pending if not f.done()]
    return time.perf_counter() - t0


def test_sampled_tracing_overhead_under_5_percent():
    # interleaved min-of-N: the floor is the honest per-config cost and
    # shields the comparison from background-load noise
    off = [_serve_once(Tracer(enabled=False)) for _ in range(2)]
    on = []
    for _ in range(5):
        off.append(_serve_once(Tracer(enabled=False)))
        on.append(_serve_once(Tracer(enabled=True, sample=0.01)))
    floor_off, floor_on = min(off), min(on)
    # < 5% relative (plus a small absolute guard for timer jitter)
    assert floor_on <= floor_off * 1.05 + 2e-3, (floor_off, floor_on)


def test_disabled_tracer_records_nothing_on_hot_paths():
    tr = Tracer(enabled=False)
    c = _diamond_cluster(tr)
    c.call_dag("diamond",
               {"a": (CloudburstReference("k1"), CloudburstReference("k2"))})
    assert tr.spans == [] and tr.dropped == 0


# ---------------------------------------------------------------------------
# phases, host signals and the counters beside the work
# ---------------------------------------------------------------------------

_TURN_PHASES = ("engine.schedule", "engine.prefetch", "engine.invoke",
                "engine.finalize")
_TICK_PHASES = ("kvs.gossip", "cache.flush", "sched.keyset", "sched.index")


def test_phases_count_turns_and_ticks_and_nest():
    c = Cluster(n_vms=2, executors_per_vm=2, n_kvs_nodes=2, seed=0)
    c.register(lambda x: float(np.sum(np.asarray(x))), "a")
    c.register(lambda v: v + 1.0, "b")
    c.register_dag("ab", ["a", "b"], edges=[("a", "b")])
    for i in range(6):
        c.put(f"k{i}", np.full(16, i, np.float32))
    c.reset_telemetry()
    turns = ticks = 0
    futures = []
    for wave in range(3):
        futures += [c.call_dag_async("ab", {"a": (
            CloudburstReference(f"k{(wave + j) % 6}"),)}) for j in range(4)]
        while any(not f.done() for f in futures):
            turns += c.step() > 0
            c.tick()
            ticks += 1
    assert c.step() == 0  # a turn with no triggers opens no phase
    snap = c.telemetry()
    assert snap["engine.step.n"] == turns == snap["engine.turns"]
    assert snap["engine.tick.n"] == ticks
    assert snap["cache.flush.n"] == ticks * len(c.caches)
    assert snap["sched.keyset.n"] == ticks * len(c.caches)
    for parent, children in (("engine.step", _TURN_PHASES),
                             ("engine.tick", _TICK_PHASES)):
        for child in children:
            assert 0 < snap[f"{child}.s"] <= snap[f"{parent}.s"]
        assert sum(snap[f"{ch}.s"] for ch in children) <= snap[f"{parent}.s"]
    # routing runs inside the cache flushes and the response writes only
    assert 0 < snap["kvs.route.s"] <= (snap["cache.flush.s"]
                                       + snap["engine.finalize.s"])
    # one queue wait per run (first dispatch), one wall span per completion
    assert snap["engine.queue.n"] == snap["engine.runs_submitted"] == 12
    assert snap["engine.run_wall.n"] == snap["engine.runs_completed"] == 12
    assert 0 < snap["engine.queue.s"] < snap["engine.run_wall.s"]


def test_phase_spans_nest_on_the_engine_timeline():
    tr = Tracer(enabled=True)
    c = _diamond_cluster(tr)
    c.call_dag("diamond",
               {"a": (CloudburstReference("k1"), CloudburstReference("k2"))})
    steps = {s.sid: s for s in tr.spans if s.name == "engine.step"}
    assert len(steps) == 3  # a, {b, c}, d: one turn each
    assert all(s.tid == "engine" and s.cat == "engine" for s in steps.values())
    assert [s.attrs["turn"] for s in steps.values()] == [1, 2, 3]
    children = [s for s in tr.spans if s.name in _TURN_PHASES]
    assert len(children) == 4 * len(steps)
    for s in children:
        parent = steps[s.parent]
        assert parent.t0 <= s.t0 <= s.t1 <= parent.t1
    # a disabled tracer still counts every phase, and records no span
    off = Tracer(enabled=False)
    c = _diamond_cluster(off)
    c.call_dag("diamond",
               {"a": (CloudburstReference("k1"), CloudburstReference("k2"))})
    assert off.spans == [] and c.telemetry()["engine.step.n"] == 3


def test_collector_hook_is_installed_once_and_counts_pauses():
    import gc

    from repro.obs import host as obs_host

    for _ in range(3):
        Cluster(n_vms=1, executors_per_vm=1, n_kvs_nodes=1)
    assert sum(cb is obs_host._on_gc for cb in gc.callbacks) == 1
    c = Cluster(n_vms=1, executors_per_vm=1, n_kvs_nodes=1)
    was_enabled = gc.isenabled()
    gc.disable()  # only the collection below runs between the reads
    try:
        before = c.telemetry()
        gc.collect(2)
        after = c.telemetry()
    finally:
        if was_enabled:
            gc.enable()
    assert (after["host.gc.collections.gen2"]
            - before["host.gc.collections.gen2"]) == 1
    assert after["host.gc.pause_s"] > before["host.gc.pause_s"]
    assert after["host.gc.gen2.pause_s"] > before["host.gc.gen2.pause_s"]
    assert after["host.gc.collections.gen0"] == before[
        "host.gc.collections.gen0"]


def test_jit_listener_counts_backend_compiles():
    import jax
    import jax.numpy as jnp

    from repro.obs import host as obs_host

    obs_host.install()
    before = obs_host.snapshot()
    width = 7 + before["host.jit.compiles"] % 5  # a shape not built before
    jax.jit(lambda x: x * 3 + 1)(jnp.ones((width, 3))).block_until_ready()
    after = obs_host.snapshot()
    assert after["host.jit.compiles"] > before["host.jit.compiles"]
    assert after["host.jit.compile_s"] > before["host.jit.compile_s"]


def test_read_plan_memo_counts_one_miss_then_hits():
    from repro.core.lattices import LWWLattice

    kvs = AnnaKVS(num_nodes=3, replication=2)
    keys = [f"k{i}" for i in range(5)]
    for i, key in enumerate(keys):
        kvs.put(key, LWWLattice((1, "w"), np.full(8, i, np.float32)),
                sync=True)
    for _ in range(3):
        kvs.get_merged_many(keys)
    snap = kvs.metrics.snapshot()
    assert snap["kvs.read_plan.misses"] == 1
    assert snap["kvs.read_plan.hits"] == 2
    # a standalone KVS counts its own routing phase
    assert snap["kvs.route.n"] == len(keys)


def test_device_sync_seconds_sit_beside_the_sync_count():
    from repro.core.lattices import LWWLattice

    kvs = AnnaKVS(num_nodes=2, replication=2, device_tier=True)
    keys = [f"k{i}" for i in range(4)]
    kvs.put_many([(k, LWWLattice((1, "w"), np.full(128, i, np.float32)))
                  for i, k in enumerate(keys)], sync=True)
    kvs.reset_transfer_stats()
    batch = kvs.get_merged_many(keys)
    assert all(pg.is_device() for pg in batch.groups.values())
    batch.to_host(kvs.reader.arena._xfer)
    stats = kvs.transfer_stats()
    assert stats["device_syncs"] == len(batch.groups) >= 1
    assert stats["device_sync_s"] > 0
    assert kvs.metrics.snapshot()["kvs.device_sync_s"] == stats[
        "device_sync_s"]
    kvs.reset_transfer_stats()
    assert kvs.transfer_stats()["device_sync_s"] == 0
