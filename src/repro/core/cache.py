"""Executor-colocated mutable cache (paper §4.2) + bolt-on causal cut (§5.3).

One cache process per VM.  Executors talk to the cache over IPC; the cache
talks to Anna.  Semantics reproduced:

* **write-back**: updates are applied locally, acknowledged, and flushed to
  the KVS asynchronously (``tick``);
* **miss path**: reads of absent keys fetch from the KVS;
* **keyset publishing**: the cache periodically publishes its key set; Anna
  pushes updates for those keys (lattice-merged on arrival);
* **repeatable-read snapshots**: on first read within a DAG the cache pins a
  snapshot version for the DAG's lifetime; downstream caches may fetch it;
* **causal-cut maintenance** (bolt-on causal consistency [10]): a causal
  version only becomes visible once the cache holds every dependency at a
  dominating-or-concurrent vector clock; otherwise the update is buffered.
"""

from __future__ import annotations

import weakref
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .arena import KeyWatch, MergeEngine, vc_dominates_or_concurrent_batch
from .faultnet import KVSUnavailableError
from .kvs import AnnaKVS
from .lattices import CausalLattice, Lattice, LWWLattice
from .netsim import NetworkProfile, VirtualClock, DEFAULT_PROFILE
from ..obs import counter_shim


class CacheFailure(RuntimeError):
    """Raised when a (failed) cache is asked for data — triggers DAG restart."""


class ExecutorCache:
    def __init__(
        self,
        cache_id: str,
        kvs: AnnaKVS,
        profile: NetworkProfile = DEFAULT_PROFILE,
        device: Optional[bool] = None,
    ):
        self.cache_id = cache_id
        self.kvs = kvs
        self.profile = profile
        # arena-backed local store: tensor-valued LWW entries live in
        # contiguous rows and merge through the batched kernels; the
        # registry is shared with the KVS so node ranks are comparable.
        # The cache rides the tier's device-resident slab mode: Cloudburst
        # colocates caches with compute, so a device KVS means the cache's
        # hot rows live on the accelerator too (override via ``device``).
        self.engine = MergeEngine(
            kvs.registry,
            device=kvs.device_tier if device is None else device)
        self.data = self.engine.view
        self.pending_flush: List[Tuple[str, Lattice]] = []
        # (dag_id, key) -> pinned lattice version
        self.snapshots: Dict[Tuple[str, str], Lattice] = {}
        self.pending_causal: List[Tuple[str, CausalLattice]] = []
        self.alive = True
        # hit/miss telemetry lives in the tier's shared registry;
        # the counter_shim properties below keep the legacy attribute
        # API (``cache.hits``, ``cache.batched_misses`` asserts).
        # batched_misses counts misses filled by a batched read_many
        # fetch (one get_merged_many round trip, packed ingest).
        m = kvs.metrics
        self._m_hits = m.counter(f"cache.{cache_id}.hits")
        self._m_misses = m.counter(f"cache.{cache_id}.misses")
        self._m_batched_misses = m.counter(f"cache.{cache_id}.batched_misses")
        # key-set publishing: the first publish (and the first after a
        # recovery) sends the whole set, every later one the delta this
        # watch collected since
        self._keywatch: Optional[KeyWatch] = None
        self._m_keyset_delta = m.counter("sched.keyset.delta_keys")
        self._m_keyset_full = m.counter("sched.keyset.full")
        # weakref: the registry outlives removed caches and must not pin
        # them (their arena subscriptions would never be pruned)
        wself = weakref.ref(self)
        m.register_callback(
            f"cache.{cache_id}.keys",
            lambda: len(c.data) if (c := wself()) is not None else 0)

    hits = counter_shim("_m_hits")
    misses = counter_shim("_m_misses")
    batched_misses = counter_shim("_m_batched_misses")

    # -- basic data path ----------------------------------------------------
    def _check_alive(self):
        if not self.alive:
            raise CacheFailure(self.cache_id)

    def read(self, key: str, clock: Optional[VirtualClock] = None) -> Optional[Lattice]:
        """Local read; on miss, fetch from the KVS and insert."""
        self._check_alive()
        if clock is not None:
            clock.advance(self.profile.sample(self.profile.ipc))
        val = self.data.get(key)
        if val is not None:
            self.hits += 1
            return val
        self.misses += 1
        val = self.kvs.get(key, clock=clock)
        if val is not None:
            self.insert(key, val)
        return val

    def read_many(
        self,
        keys: Sequence[str],
        clock: Optional[VirtualClock] = None,
        clocks: Optional[Sequence[VirtualClock]] = None,
        mover_kind: Optional[str] = None,
    ) -> Set[str]:
        """Batched local read / miss fill — the DAG read-set warm path.

        ONE IPC advance covers the whole set (the executor ships the
        batch as a single cache call); misses are collected and fetched
        from the KVS as ONE :meth:`AnnaKVS.get_merged_many` round trip —
        the warm path trades the scalar miss path's any-replica
        staleness for a single batched read-repair — and the packed
        results land in the cache's arena via ``ingest_planes``, so no
        per-key lattice objects are constructed.  Causal sidecar values
        still route through the cut-maintaining :meth:`insert` (an
        uncovered causal update stays buffered, exactly as on the push
        path).  Returns the requested keys now resident, so callers can
        distinguish warmed keys from ones the KVS does not hold.

        ``clocks`` is the cross-request form: when the cluster engine
        fuses SEVERAL in-flight requests' read sets into one call, every
        waiting request's clock is charged the SAME batched cost (one
        IPC sample + one batched KVS fetch) — the whole point of sharing
        the launch.  Passing a single ``clock`` is the per-request path
        and draws exactly the samples it always did.
        """
        self._check_alive()
        all_clocks = (list(clocks) if clocks is not None
                      else ([] if clock is None else [clock]))
        if all_clocks:
            ipc = self.profile.sample(self.profile.ipc)
            for c in all_clocks:
                c.advance(ipc)
        primary = all_clocks[0] if all_clocks else None
        uniq = list(dict.fromkeys(keys))
        misses = [k for k in uniq if k not in self.data]
        self.hits += len(uniq) - len(misses)
        if misses:
            self.misses += len(misses)
            self.batched_misses += len(misses)
            t_fetch = primary.now if primary is not None else 0.0
            with self.kvs.tracer.span(
                    "cache", "read_many", clock=primary,
                    cache=self.cache_id, n_keys=len(uniq),
                    n_misses=len(misses)):
                # graceful degradation under the failure plane: keys
                # with no reachable replica are skipped (they stay
                # non-resident and the caller sees them missing from
                # the returned set) instead of failing the whole wave;
                # the KVS counts them in kvs.degraded_reads
                batch = self.kvs.get_merged_many(misses, clock=primary,
                                                 on_unavailable="skip")
            if primary is not None:
                for c in all_clocks[1:]:
                    c.advance(primary.now - t_fetch)
            if batch:
                if mover_kind is not None:
                    self.kvs.mover.record(mover_kind, batch)
                for key, value in batch.sidecar:
                    if isinstance(value, CausalLattice):
                        self.insert(key, value)  # causal cut stays per-key
                    else:
                        self.engine.merge_one(key, value)
                self.engine.ingest_planes(batch, include_sidecar=False)
        return {k for k in uniq if k in self.data}

    def warm_plane(self, keys: Sequence[str],
                   clock: Optional[VirtualClock] = None) -> Set[str]:
        """Recovery warm-up: refill the cache for ``keys`` as packed
        plane motion (one batched fetch + one ``ingest_planes`` scatter
        per slab group), accounted as ``planecp.warm`` on the bulk
        state-motion ledger.  Returns the keys now resident."""
        return self.read_many(keys, clock=clock, mover_kind="warm")

    def read_local(self, key: str) -> Optional[Lattice]:
        self._check_alive()
        return self.data.get(key)

    def write(self, key: str, value: Lattice, clock: Optional[VirtualClock] = None) -> Lattice:
        """Write-back: merge locally, ack, flush to KVS asynchronously."""
        self._check_alive()
        if clock is not None:
            clock.advance(self.profile.sample(self.profile.ipc))
        merged = self.insert(key, value)
        self.pending_flush.append((key, value))
        return merged

    def insert(self, key: str, value: Lattice) -> Lattice:
        """Merge a value into the cache, honoring causal-cut maintenance."""
        if isinstance(value, CausalLattice):
            if not self._deps_covered(value):
                # Buffer until the cut can be maintained (bolt-on write buffer)
                self.pending_causal.append((key, value))
                return self.data.get(key, value)
        return self.engine.merge_one(key, value)

    def _deps_covered(self, value: CausalLattice, depth: int = 8,
                      prefetched: Optional[Dict[str, Optional[Lattice]]] = None,
                      ) -> bool:
        """Causal cut check: every dependency present at >= its clock.

        The dominance comparisons for already-held dependencies are
        batched through ``ops.vc_join_classify`` (one densified (K, N)
        launch for all of this update's deps); the deps the batch cannot
        cover are then fetched as ONE ``get_merged_many`` round trip per
        closure level (``prefetched`` memoizes fetches — including
        negative results — across the level's deps and across callers
        that share a dict, e.g. the ``tick`` retry loop).  Dependencies
        are installed *transitively* through the same check — a dep
        fetched from the KVS only lands in the cache once its own
        dependency closure is covered (bolt-on's causal-cut invariant);
        otherwise the whole update stays buffered.
        """
        deps = [
            (dep_key, dep_vc)
            for version in value.versions
            for dep_key, dep_vc in version.dependencies
        ]
        if not deps:
            return True
        covered = [False] * len(deps)
        held_pairs, held_idx = [], []
        for i, (dep_key, dep_vc) in enumerate(deps):
            held = self.data.get(dep_key)
            if isinstance(held, CausalLattice):
                held_pairs.append((held.joined_clock(), dep_vc))
                held_idx.append(i)
        if held_pairs:
            flags = vc_dominates_or_concurrent_batch(held_pairs)
            for i, ok in zip(held_idx, flags):
                covered[i] = bool(ok)
        if depth > 0:
            need = list(dict.fromkeys(
                deps[i][0] for i in range(len(deps))
                if not covered[i]
                and (prefetched is None or deps[i][0] not in prefetched)
            ))
            if need:
                if prefetched is None:
                    prefetched = {}
                try:
                    prefetched.update(self.kvs.get_merged_many_values(need))
                except KVSUnavailableError:
                    # causal NEVER degrades: with deps unreachable the
                    # update just stays buffered until replicas return
                    return False
        for i, (dep_key, dep_vc) in enumerate(deps):
            if not covered[i] and not self._ensure_dep(dep_key, dep_vc, depth,
                                                       prefetched):
                return False
        return True

    def _ensure_dep(self, dep_key: str, dep_vc, depth: int,
                    prefetched: Optional[Dict[str, Optional[Lattice]]] = None,
                    ) -> bool:
        # single-pair checks stay pure Python: a K=1 kernel dispatch costs
        # more than the dict comparison it would replace (the batched
        # classifier earns its keep in _deps_covered, where K = #deps)
        held = self.data.get(dep_key)
        if isinstance(held, CausalLattice) and held.dominates_or_concurrent(dep_vc):
            return True
        if depth <= 0:
            return False
        if prefetched is not None and dep_key in prefetched:
            fetched = prefetched[dep_key]  # batched closure fetch
        else:
            try:
                fetched = self.kvs.get_merged(dep_key)
            except KVSUnavailableError:
                return False  # dep unreachable: stay buffered (block)
        if not isinstance(fetched, CausalLattice):
            return False
        merged = (fetched if not isinstance(held, CausalLattice)
                  else held.merge(fetched))
        if not merged.dominates_or_concurrent(dep_vc):
            return False
        if not self._deps_covered(merged, depth - 1, prefetched):
            return False
        # through the engine, never a raw view assignment: cache
        # bookkeeping (arena routing, telemetry) must see every write
        self.engine.merge_one(dep_key, merged)
        return True

    # -- repeatable-read snapshot support (paper §5.3) ------------------------
    def pin_snapshot(self, dag_id: str, key: str, value: Lattice) -> None:
        self.snapshots[(dag_id, key)] = value

    def get_snapshot(self, dag_id: str, key: str) -> Optional[Lattice]:
        self._check_alive()
        return self.snapshots.get((dag_id, key))

    def evict_dag(self, dag_id: str) -> None:
        """Sink-notifies-upstream completion: drop the DAG's snapshots."""
        for k in [k for k in self.snapshots if k[0] == dag_id]:
            del self.snapshots[k]

    # -- background work -------------------------------------------------------
    def tick(self, clock: Optional[VirtualClock] = None,
             defer_prob: float = 0.0) -> None:
        """Flush pending writes, receive KVS pushes, retry buffered causal.

        ``defer_prob`` randomly postpones individual flushes/pushes to the
        next tick — continuous, out-of-order background propagation, which
        lattice merges make safe (ACI) but which creates the per-key
        staleness skew behind the paper's Table 2 / Retwis anomalies.
        """
        if not self.alive:
            return
        rng = self.kvs.rng
        still: List[Tuple[str, Lattice]] = []
        flush_now: List[Tuple[str, Lattice]] = []
        for key, value in self.pending_flush:
            if defer_prob > 0 and rng.random() < defer_prob:
                still.append((key, value))
            else:
                flush_now.append((key, value))
        if flush_now:
            # async: no session latency; one batched coordinator merge
            # per storage node instead of per-key puts.  pending_flush is
            # only trimmed after the batch lands: a no-live-replica error
            # leaves every write queued for retry after recovery (merge
            # idempotence makes re-flushing already-applied items safe).
            try:
                self.kvs.put_many(flush_now, clock=None)
            except KVSUnavailableError:
                # failure-plane quorum loss: keep the whole batch queued
                # and retry next tick once replicas heartbeat back
                still = flush_now + still
        self.pending_flush = still
        # KVS pushes arrive as a packed PlaneBatch; deferral is row-
        # granular inside the KVS queue.  Packed rows ingest as one
        # launch per payload group (no per-key objects); the sidecar is
        # handled here because causal values must route through the
        # causal-cut check, not a blind merge.
        pushes = self.kvs.drain_cache_pushes(self.cache_id, rng, defer_prob)
        if pushes:
            for key, value in pushes.sidecar:
                if isinstance(value, CausalLattice):
                    self.insert(key, value)  # causal-cut check stays per-key
                else:
                    self.engine.merge_one(key, value)
            self.engine.ingest_planes(pushes, include_sidecar=False)
        still_pending: List[Tuple[str, CausalLattice]] = []
        # one shared fetch memo for the whole retry round: each closure
        # level batches its uncovered deps through get_merged_many, and
        # a dep fetched for one buffered update is not refetched for the
        # next (the KVS cannot change mid-tick)
        prefetched: Dict[str, Optional[Lattice]] = {}
        for key, value in self.pending_causal:
            if self._deps_covered(value, prefetched=prefetched):
                self.engine.merge_one(key, value)
            else:
                still_pending.append((key, value))
        self.pending_causal = still_pending

    def publish_keyset(self) -> None:
        """Publish the key set to the KVS: in full the first time, then
        only the keys added and removed since the last publish."""
        if self._keywatch is None:
            self._keywatch = self.engine.watch_keys()
            self.kvs.publish_keyset(self.cache_id, set(self.data))
            self._m_keyset_full.inc()
            return
        added, removed = self._keywatch.drain()
        if added or removed:
            self.kvs.update_keyset(self.cache_id, added, removed)
            self._m_keyset_delta.inc(len(added) + len(removed))

    # -- failure ------------------------------------------------------------------
    def fail(self) -> None:
        self.alive = False

    def recover(self) -> None:
        self.alive = True
        if self._keywatch is not None:
            # the next publish is a full one
            self.engine.unwatch_keys(self._keywatch)
            self._keywatch = None
        self.data.clear()
        self.snapshots.clear()
        self.pending_flush.clear()
        self.pending_causal.clear()
        # A recovered cache restarts empty: retract the stale keyset
        # subscriptions published before the failure and drop pushes that
        # queued while failed — otherwise the KVS keeps pushing updates
        # for keys this cache no longer holds.
        self.kvs.publish_keyset(self.cache_id, set())
        self.kvs.drop_cache_pushes(self.cache_id)

    def stats(self) -> Dict[str, int]:
        return {
            "keys": len(self.data),
            "hits": self.hits,
            "misses": self.misses,
            "pinned": len(self.snapshots),
        }
