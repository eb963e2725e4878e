"""Anna-style autoscaling key-value store (paper §2.2, §4).

Key properties reproduced from Anna [86, 87]:

* every stored value is a :class:`~repro.core.lattices.Lattice`; replica
  convergence is by lattice merge (ACI), never by coordination;
* consistent-hash ring with virtual nodes; per-key replication factor
  (default ``k``) with *selective replication* for hot keys;
* **asynchronous multi-master replication**: a ``put`` is applied at the
  coordinator replica immediately and propagated to the other replicas via
  gossip on ``tick()`` — this is what makes stale reads (and hence the
  anomalies of Table 2) possible, exactly as in the real system;
* cached-keyset index: executor caches publish the set of keys they hold;
  Anna pushes key updates to the caches that subscribe to them (§4.2);
* storage-node elasticity: nodes can join/leave; ownership moves with the
  ring and data is handed off by merge;
* k-fault tolerance: reads fall back to surviving replicas; writes to a
  failed node are queued as hinted handoff and delivered on recovery.
"""

from __future__ import annotations

import bisect
import hashlib
import random
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .arena import (
    MergeEngine,
    NodeRegistry,
    PlaneBatch,
    PlaneBuffer,
    device_tier_default,
    try_reduce_lww,
)
from .faultnet import FailurePlane, KVSUnavailableError, RetryPolicy
from .lattices import Lattice
from .netsim import NetworkProfile, VirtualClock, DEFAULT_PROFILE
from .remesh import PlaneMover
from ..obs import MetricsRegistry, Tracer, counter_shim

# the host<->device transfer ledger of every engine (``transfer_stats``),
# each also a registry callback ``kvs.<field>``
_XFER_FIELDS = ("h2d_bytes", "d2h_bytes", "device_syncs", "device_sync_s")


def _hash(s: str) -> int:
    return int.from_bytes(hashlib.md5(s.encode()).digest()[:8], "big")


class StorageNode:
    """One Anna storage node: an arena-backed lattice map + gossip inbox.

    Tensor-valued LWW payloads live in the node's :class:`MergeEngine`
    arena (contiguous (K, D) value rows with (K, 1) Lamport planes);
    ``store`` is the dict-like view over arena + fallback, so callers
    keep ordinary mapping semantics.
    """

    def __init__(self, node_id: str, registry: Optional[NodeRegistry] = None,
                 device: Optional[bool] = None):
        self.node_id = node_id
        self.engine = MergeEngine(registry, device=device)
        self.store = self.engine.view
        self.inbox = PlaneBuffer()  # pending gossip, packed on the wire
        self.alive = True
        self.puts = 0
        self.gets = 0

    def merge_in(self, key: str, value: Lattice) -> Lattice:
        return self.engine.merge_one(key, value)

    def drain_inbox(self, rng: Optional[random.Random] = None,
                    defer_prob: float = 0.0) -> int:
        """Apply pending gossip; each queued row may defer to the next round.

        Out-of-order delivery is safe *because* values are lattices: merge
        is ACI, so replicas converge regardless of interleaving (§2.2).
        The inbox is a :class:`PlaneBuffer`: arena-eligible traffic
        arrives packed and is applied as one ``ops.lww_merge_many``
        launch per payload group via ``ingest_planes`` — no per-key
        lattice objects on the gossip path; the sidecar (opaque/non-LWW
        values) keeps exact per-key merges.
        """
        batch = self.inbox.split(rng, defer_prob)
        if not batch:
            return 0
        return self.engine.ingest_planes(batch)


class AnnaKVS:
    """The storage tier.  All methods optionally account virtual latency."""

    VNODES = 16

    def __init__(
        self,
        num_nodes: int = 4,
        replication: int = 2,
        profile: NetworkProfile = DEFAULT_PROFILE,
        sync_replication: bool = False,
        device_tier: Optional[bool] = None,
        metrics: Optional[MetricsRegistry] = None,
        tracer: Optional[Tracer] = None,
    ):
        self.profile = profile
        self.replication = replication
        self.sync_replication = sync_replication
        # observability plane: a Cluster passes its shared registry and
        # tracer; a standalone KVS gets its own registry and a disabled
        # tracer counting its phases there (spans only record under a
        # traced DAG run)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = (tracer if tracer is not None
                       else Tracer()).bind(self.metrics)
        # device-resident slab tier: arena planes live as donated jax
        # arrays on every storage node (None → REPRO_DEVICE_TIER env)
        self.device_tier = (device_tier_default() if device_tier is None
                            else bool(device_tier))
        self.rng = random.Random(profile.seed if hasattr(profile, "seed") else 0)
        # one node-id intern table for the whole tier, so arena node ranks
        # are comparable across storage nodes and executor caches
        self.registry = NodeRegistry()
        # the tier's read-reduction engine: batched R-replica read-repair
        # (get_merged_many) reduces through it; its arena stays empty —
        # it exists for the kernel façade + read-plane telemetry
        # (reader.plane_reads counts keys answered without objects)
        self.reader = MergeEngine(self.registry, device=self.device_tier)
        # read-plan memo for get_merged_many: a hot read set with stable
        # placement + arena layouts re-executes its cached reduce plan,
        # skipping the per-key ring walk and candidate-index build
        # (row CONTENTS re-gather at execute, so writes never stale it)
        self._read_plans: Dict[Tuple[str, ...], Tuple[tuple, object]] = {}
        self._placement_epoch = 0
        self.nodes: Dict[str, StorageNode] = {}
        self._ring: List[Tuple[int, str]] = []  # (hash, node_id), sorted
        self._key_replication: Dict[str, int] = {}  # selective replication
        # memoized ring placement: every data-path op consults _owners,
        # and md5 + ring walk per key dominates batched reads otherwise.
        # Invalidated whenever placement inputs change (membership,
        # per-key replication).  Entries are shared lists: never mutated.
        self._owners_cache: Dict[str, List[str]] = {}
        # cached-keyset index (paper §4.2): key -> caches that hold it,
        # and its reverse, cache -> keys it subscribed
        self._cache_index: Dict[str, Set[str]] = defaultdict(set)
        self._cache_keys: Dict[str, Set[str]] = {}
        self._cache_pushes: Dict[str, PlaneBuffer] = defaultdict(PlaneBuffer)
        self._hints: Dict[str, PlaneBuffer] = defaultdict(PlaneBuffer)
        # failure plane (off by default: every data-path hook is a single
        # ``is not None`` check until enable_failure_plane() is called)
        self.failure_plane: Optional[FailurePlane] = None
        self.faultnet = None
        self.detector = None
        self.retry = RetryPolicy()
        # bulk state-motion ledger: checkpoint save/restore, membership
        # handoff, anti-entropy repair, warm-up and tier migration all
        # account their packed transfers here (planecp.* counters/spans)
        self.mover = PlaneMover(self.metrics, self.tracer)
        self._m_retries = self.metrics.counter("kvs.retries")
        self._m_backoff = self.metrics.counter("kvs.backoff_s")
        self._m_degraded = self.metrics.counter("kvs.degraded_reads")
        # get_merged_many's read-plan memo: a hit re-executes a cached
        # plan, a miss walks the ring and builds one
        self._m_plan_hits = self.metrics.counter("kvs.read_plan.hits")
        self._m_plan_misses = self.metrics.counter("kvs.read_plan.misses")
        # pull-based telemetry: the plane counters mutate inside kernel
        # launch paths, so the registry reads them lazily at snapshot —
        # zero added cost on the hot planes
        self.metrics.register_callback(
            "kvs.reader.plane_reads", lambda: self.reader.plane_reads)
        self.metrics.register_callback(
            "kvs.reader.plane_keys", lambda: self.reader.plane_keys)
        self.metrics.register_callback(
            "kvs.reader.plane_object_fallbacks",
            lambda: self.reader.plane_object_fallbacks)
        for field in _XFER_FIELDS:
            self.metrics.register_callback(
                f"kvs.{field}",
                lambda f=field: self.transfer_stats()[f],
                reset_fn=self.reset_transfer_stats)
        for i in range(num_nodes):
            self.add_node(f"anna-{i}")

    retries = counter_shim("_m_retries")
    backoff_s = counter_shim("_m_backoff")
    degraded_reads = counter_shim("_m_degraded")

    # -- failure plane (channel faults + heartbeat detection + retry) --------
    def enable_failure_plane(
        self,
        clock: Optional[VirtualClock] = None,
        retry: Optional[RetryPolicy] = None,
        heartbeat_interval: float = 0.05,
        suspicion_multiplier: float = 3.0,
        seed: Optional[int] = None,
    ) -> FailurePlane:
        """Switch the tier from oracle liveness to the failure plane:
        every replication channel (gossip, hints, cache pushes,
        membership handoff) routes through a :class:`FaultNetwork`, and
        liveness becomes heartbeat suspicion on the plane's virtual
        clock — routing never consults ``node.alive`` directly again;
        a dead-but-trusted node is discovered by data-path probe
        timeouts charged to the caller's clock."""
        if self.failure_plane is not None:
            return self.failure_plane
        rng = random.Random(
            (self.profile.seed if hasattr(self.profile, "seed") else 0)
            if seed is None else seed)
        plane = FailurePlane(
            clock or VirtualClock(), self._resolve_channel, rng=rng,
            metrics=self.metrics, retry=retry,
            heartbeat_interval=heartbeat_interval,
            suspicion_multiplier=suspicion_multiplier)
        self.failure_plane = plane
        self.faultnet = plane.network
        self.detector = plane.detector
        self.retry = plane.retry
        for node_id in self.nodes:
            self._register_node_endpoint(node_id)
        return plane

    def _resolve_channel(self, kind: str, dst):
        """Delivery-time destination lookup for the fault network (never
        hand out buffer references early: push buffers are popped when
        empty, and membership churn swaps node objects)."""
        if kind in ("gossip", "handoff"):
            node = self.nodes.get(dst)
            return node.inbox if node is not None else None
        if kind == "hint":
            return self._hints[dst]
        if kind == "push":
            return self._cache_pushes[dst]
        return None

    def _register_node_endpoint(self, node_id: str) -> None:
        self.detector.register(
            node_id,
            lambda nid=node_id: (n := self.nodes.get(nid)) is not None
            and n.alive,
            on_rejoin=lambda nid=node_id: self._on_node_rejoin(nid))

    def _on_node_rejoin(self, node_id: str) -> None:
        """A suspected node heartbeat back: flush its hinted handoffs
        (through the fault network, so a still-partitioned path holds
        them) and let reads route to it again."""
        hints = self._hints.pop(node_id, None)
        if hints is not None and node_id in self.nodes:
            self.faultnet.deliver("handoff", None, node_id,
                                  batch=hints.drain())

    def _reachable(self, node_id: str, node: StorageNode) -> bool:
        """Routing predicate: oracle liveness without the failure plane,
        heartbeat trust with it (a dead-but-trusted node stays a routing
        target until a probe timeout or missed heartbeat suspects it)."""
        if self.detector is None:
            return node.alive
        return node.alive and self.detector.trusts(node_id)

    def _probe_owners(self, owner_ids, clock: Optional[VirtualClock],
                      op: str) -> None:
        """Detector-mode data-path probe: a trusted-but-dead owner means
        the op's request to it times out — charge the timeout plus a
        capped exponential backoff to the caller's virtual clock, report
        the suspicion, and retry (the retry re-routes around the now
        suspected replica)."""
        if self.detector is None:
            return
        tr = self.tracer
        for attempt in range(self.retry.max_attempts):
            stale = [o for o in owner_ids
                     if self.detector.trusts(o)
                     and (n := self.nodes.get(o)) is not None
                     and not n.alive]
            if not stale:
                return
            for o in stale:
                self.detector.report_timeout(o)
            back = self.retry.backoff(attempt)
            self._m_retries.inc(len(stale))
            self._m_backoff.inc(self.retry.op_timeout + back)
            if clock is not None:
                t0 = clock.now
                clock.advance(self.retry.op_timeout + back)
                if tr.enabled and tr.cur is not None:
                    tr.add_complete(
                        "kvs", f"retry:{op}", t0, clock.now,
                        tid=tr.cur.tid, parent=tr.cur, attempt=attempt,
                        suspects=list(stale))

    def anti_entropy(self) -> int:
        """One full repair round: every alive node re-exports its owned
        keys to the co-owners, one packed plane batch per (src, dst)
        pair.  This is the convergence backstop after chaos — a dropped
        gossip plane is otherwise lost forever (there is no background
        read-repair on idle keys) — and what makes ``heal_all()``'s
        bit-identical-replicas assertion well-defined.  Merge makes the
        re-export idempotent; returns the number of key-copies shipped."""
        shipped = 0
        for node in self.nodes.values():
            if not node.alive:
                continue
            by_dst: Dict[str, List[str]] = defaultdict(list)
            for key in node.store:
                for owner in self._owners(key):
                    if owner != node.node_id:
                        by_dst[owner].append(key)
            for dst, keys in by_dst.items():
                self._enqueue_handoff(dst, node.engine.export_planes(keys),
                                      kind="repair")
                shipped += len(keys)
        return shipped

    # -- membership -----------------------------------------------------------
    def _enqueue_handoff(self, owner: str, batch: PlaneBatch,
                         kind: str = "remesh") -> None:
        """Route a membership-change handoff batch to ``owner``, through
        the same dead-owner hinting as ``_route_put``: data handed to a
        failed node must wait in ``_hints`` (delivered on recovery), not
        rot in a dead inbox.  ``kind`` tags the move on the bulk-motion
        ledger (``planecp.remesh`` for ring handoff, ``planecp.repair``
        for anti-entropy re-replication)."""
        if not batch:
            return
        self.mover.record(kind, batch)
        node = self.nodes.get(owner)
        if node is not None and self._reachable(owner, node):
            if self.faultnet is not None:
                self.faultnet.deliver("handoff", None, owner, batch=batch)
            else:
                node.inbox.add_batch(batch)
        else:
            if self.faultnet is not None:
                self.faultnet.deliver("hint", None, owner, batch=batch)
            else:
                self._hints[owner].add_batch(batch)

    def add_node(self, node_id: str) -> None:
        assert node_id not in self.nodes
        self._owners_cache.clear()  # ring placement changes
        self._placement_epoch += 1
        node = StorageNode(node_id, self.registry, device=self.device_tier)
        self.nodes[node_id] = node
        pre = f"kvs.node.{node_id}."
        self.metrics.register_callback(
            pre + "puts", lambda n=node: n.puts,
            reset_fn=lambda n=node: setattr(n, "puts", 0))
        self.metrics.register_callback(
            pre + "gets", lambda n=node: n.gets,
            reset_fn=lambda n=node: setattr(n, "gets", 0))
        self.metrics.register_callback(
            pre + "keys", lambda n=node: len(n.store))
        self.metrics.register_callback(
            pre + "plane_keys", lambda n=node: n.engine.plane_keys)
        self.metrics.register_callback(
            pre + "materializations",
            lambda n=node: n.engine.arena.materializations)
        if self.detector is not None:
            self._register_node_endpoint(node_id)
        for v in range(self.VNODES):
            bisect.insort(self._ring, (_hash(f"{node_id}#{v}"), node_id))
        # New owner: existing replicas re-gossip their keys so ownership
        # converges (merge makes this idempotent / safe).  The handoff is
        # one packed export per source node, not per-key objects.
        for other in list(self.nodes.values()):
            if other.node_id == node_id:
                continue
            owned = [k for k in other.store if node_id in self._owners(k)]
            if owned:
                self._enqueue_handoff(node_id, other.engine.export_planes(owned))

    def remove_node(self, node_id: str) -> None:
        node = self.nodes.pop(node_id)
        self.metrics.unregister_prefix(f"kvs.node.{node_id}.")
        if self.detector is not None:
            self.detector.unregister(node_id)
        self._owners_cache.clear()  # ring placement changes
        self._placement_epoch += 1
        self._ring = [(h, n) for (h, n) in self._ring if n != node_id]
        # hand off data to the new owners by merge: group the departing
        # node's keys per new owner, one packed export per owner
        by_owner: Dict[str, List[str]] = defaultdict(list)
        for key in node.store:
            for owner in self._owners(key):
                by_owner[owner].append(key)
        for owner, keys in by_owner.items():
            self._enqueue_handoff(owner, node.engine.export_planes(keys))

    def fail_node(self, node_id: str) -> None:
        self.nodes[node_id].alive = False
        self._placement_epoch += 1

    def recover_node(self, node_id: str) -> None:
        node = self.nodes[node_id]
        node.alive = True
        self._placement_epoch += 1
        if self.detector is not None:
            # no instant knowledge: the node stays suspected (and hinted
            # to) until its next heartbeat round, whose rejoin callback
            # flushes the hints through the fault network
            return
        hints = self._hints.pop(node_id, None)
        if hints is not None:
            node.inbox.add_batch(hints.drain())

    # -- ring routing -----------------------------------------------------------
    def _owners(self, key: str) -> List[str]:
        owners = self._owners_cache.get(key)
        if owners is not None:
            return owners
        if not self._ring:
            return []
        k = self._key_replication.get(key, self.replication)
        k = min(k, len(self.nodes))
        h = _hash(key)
        idx = bisect.bisect_left(self._ring, (h, ""))
        owners = []
        i = idx
        while len(owners) < k and len(owners) < len(self.nodes):
            _, node_id = self._ring[i % len(self._ring)]
            if node_id not in owners:
                owners.append(node_id)
            i += 1
        self._owners_cache[key] = owners
        return owners

    def set_replication(self, key: str, k: int) -> None:
        """Selective replication for hot keys (Anna [87])."""
        self.set_replication_many((key,), k)

    def set_replication_many(self, keys: Sequence[str], k: int) -> None:
        """Batched selective replication — the checkpoint path bumps a
        whole snapshot's shard keys in one call.  No-ops (an unchanged
        factor) cost a dict probe and do NOT bump the placement epoch,
        so idempotent re-saves never invalidate cached read plans."""
        changed = False
        for key in keys:
            if self._key_replication.get(key) == k:
                continue
            self._key_replication[key] = k
            self._owners_cache.pop(key, None)
            changed = True
        if changed:
            self._placement_epoch += 1

    # -- data path --------------------------------------------------------------
    def _route_put(
        self, key: str, value: Lattice, sync: bool,
        clock: Optional[VirtualClock],
    ) -> Tuple[List[str], List[str]]:
        """Shared per-key put routing: (merge targets, gossip targets).

        Appends hinted handoffs for dead owners and the cache-index
        pushes (paper §4.2); raises when no live replica exists.  Both
        ``put`` and ``put_many`` route through here so the per-key and
        batched planes cannot drift.
        """
        owners = self._owners(key)
        if clock is not None:
            clock.advance(
                self.profile.sample(self.profile.kvs_op, value.byte_size())
            )
        if self.detector is not None:
            # a trusted-but-dead owner means this put's request to it
            # times out: charge the probe + backoff, suspect it, retry
            self._probe_owners(owners, clock, "put")
        merge_targets: List[str] = []
        gossip_targets: List[str] = []
        hint_targets: List[str] = []
        for owner in owners:
            node = self.nodes[owner]
            if not self._reachable(owner, node):
                # dead (oracle) or suspected (detector): hinted handoff,
                # delivered when the owner recovers / heartbeats back
                hint_targets.append(owner)
                continue
            if not merge_targets or sync:
                merge_targets.append(owner)
                node.puts += 1
            else:
                gossip_targets.append(owner)  # async gossip
        if not merge_targets:
            # NO side effects on the unavailable path: a put that raises
            # is UNACKED and must not resurface later via a hint flush
            # (the chaos convergence oracle only replays acked writes)
            if self.detector is not None:
                raise KVSUnavailableError([key], op="put")
            raise RuntimeError(f"no live replica for {key}")
        for owner in hint_targets:
            if self.faultnet is not None:
                self.faultnet.deliver("hint", None, owner,
                                      key=key, value=value)
            else:
                self._hints[owner].add(key, value)
        # push-based cache invalidation/update (paper §4.2)
        if self.faultnet is None:
            for cache_id in self._cache_index.get(key, ()):
                self._cache_pushes[cache_id].add(key, value)
        else:
            for cache_id in self._cache_index.get(key, ()):
                self.faultnet.deliver("push", merge_targets[0], cache_id,
                                      key=key, value=value)
        return merge_targets, gossip_targets

    def put(
        self,
        key: str,
        value: Lattice,
        clock: Optional[VirtualClock] = None,
        sync: Optional[bool] = None,
    ) -> Lattice:
        """``sync=True`` writes all replicas before acking (client puts
        block for durability); the default async path acks after the
        coordinator and gossips the rest (cache flush path)."""
        sync = self.sync_replication if sync is None else sync
        with self.tracer.phase("kvs.route"):
            merge_targets, gossip_targets = self._route_put(
                key, value, sync, clock)
        merged: Optional[Lattice] = None
        for owner in merge_targets:
            merged = self.nodes[owner].merge_in(key, value)
        if self.faultnet is None:
            for owner in gossip_targets:
                self.nodes[owner].inbox.add(key, value)  # packed at enqueue
        else:
            for owner in gossip_targets:
                self.faultnet.deliver("gossip", merge_targets[0], owner,
                                      key=key, value=value)
        return merged

    def put_many(
        self,
        items: List[Tuple[str, Lattice]],
        clock: Optional[VirtualClock] = None,
        sync: Optional[bool] = None,
    ) -> int:
        """Batched multi-key put — the cache write-back flush path.

        Per-key routing is ``_route_put``, identical to ``put``; the
        coordinator-side merges are coalesced per storage node and
        applied through the node's ``MergeEngine.merge_batch``, so
        tensor-valued flushes become one ``ops.lww_merge_many`` launch
        per (node, payload group).  On a no-live-replica error the
        earlier items' coordinator merges still apply (matching the
        sequential ``put`` loop they replace).
        """
        sync = self.sync_replication if sync is None else sync
        tr = self.tracer
        sp = None
        if tr.enabled and tr.cur is not None:
            sp = tr.start("kvs", "put_many", clock=clock or tr.cur.clock,
                          tid=tr.cur.tid, parent=tr.cur, n_items=len(items))
        coord_batches: Dict[str, List[Tuple[str, Lattice]]] = defaultdict(list)
        unrouted: Optional[RuntimeError] = None
        with tr.phase("kvs.route"):
            for key, value in items:
                try:
                    merge_targets, gossip_targets = self._route_put(
                        key, value, sync, clock)
                except RuntimeError as e:
                    unrouted = e
                    break
                for owner in merge_targets:
                    coord_batches[owner].append((key, value))
                if self.faultnet is None:
                    for owner in gossip_targets:
                        self.nodes[owner].inbox.add(key, value)
                else:
                    for owner in gossip_targets:
                        self.faultnet.deliver("gossip", merge_targets[0],
                                              owner, key=key, value=value)
        for owner, batch in coord_batches.items():
            self.nodes[owner].engine.merge_batch(batch)
        if unrouted is not None:
            raise unrouted
        if sp is not None:
            tr.finish(sp)
        return len(items)

    def put_planes(
        self,
        batch: PlaneBatch,
        clock: Optional[VirtualClock] = None,
        sync: Optional[bool] = None,
    ) -> int:
        """Whole-:class:`PlaneBatch` put — the bulk save / state-motion
        write path.

        Per-key routing semantics are identical to :meth:`put` (first
        reachable owner merges, the rest gossip — or all merge under
        ``sync`` — dead/suspected owners get hinted handoff, subscribed
        caches get pushes), but the movement is plane-shaped end to end:
        the batch splits into one packed sub-batch per destination
        channel (row ``take`` per slab group, sidecar partitioned
        alongside), coordinator merges apply through
        ``MergeEngine.ingest_planes`` (one fused launch per slab group)
        and the virtual clock advances ONCE, sized by total payload
        bytes.  Zero per-key lattice objects for packed traffic.

        Availability is checked FIRST: when any key has no reachable
        owner the whole batch raises with NO side effects — an unacked
        bulk save must never resurface later through a hint flush (the
        chaos convergence oracle replays acked writes only), and a
        checkpoint is all-or-nothing anyway (the commit marker is only
        written after this returns).
        """
        sync = self.sync_replication if sync is None else sync
        tr = self.tracer
        sp = None
        if tr.enabled and tr.cur is not None:
            sp = tr.start("kvs", "put_planes", clock=clock or tr.cur.clock,
                          tid=tr.cur.tid, parent=tr.cur, n_keys=len(batch))
        keys = batch.keys()
        ukeys = list(dict.fromkeys(keys))
        if clock is not None:
            clock.advance(
                self.profile.sample(self.profile.kvs_op, batch.byte_size()))
        if self.detector is not None:
            # one probe/retry round for the whole batch (batched puts
            # pay batched timeouts, exactly like get_merged_many)
            involved = list(dict.fromkeys(
                o for key in ukeys for o in self._owners(key)))
            self._probe_owners(involved, clock, "put_planes")
        # -- route first, deliver after: NO side effects before the
        # whole batch is known to be storable
        plans: Dict[str, Tuple[List[str], List[str], List[str]]] = {}
        unavailable: List[str] = []
        for key in ukeys:
            merge_t: List[str] = []
            gossip_t: List[str] = []
            hint_t: List[str] = []
            for owner in self._owners(key):
                node = self.nodes[owner]
                if not self._reachable(owner, node):
                    hint_t.append(owner)
                    continue
                if not merge_t or sync:
                    merge_t.append(owner)
                else:
                    gossip_t.append(owner)
            if not merge_t:
                unavailable.append(key)
            plans[key] = (merge_t, gossip_t, hint_t)
        if unavailable:
            if self.detector is not None:
                raise KVSUnavailableError(unavailable, op="put_planes")
            raise RuntimeError(f"no live replica for {unavailable[0]}")
        # -- split into per-destination sub-batches: (channel, dst, src)
        # -> row indices per group + sidecar slice.  src matters to the
        # fault network (partitions are per endpoint pair), so gossip
        # and pushes key on the coordinating replica like _route_put.
        _Dest = Tuple[str, str, Optional[str]]
        dest_rows: Dict[_Dest, Dict] = defaultdict(lambda: defaultdict(list))
        dest_side: Dict[_Dest, List[Tuple[str, Lattice]]] = defaultdict(list)

        def fan_out(key: str, sink) -> None:
            merge_t, gossip_t, hint_t = plans[key]
            src = merge_t[0]
            for owner in merge_t:
                sink(("merge", owner, None))
            for owner in gossip_t:
                sink(("gossip", owner, src))
            for owner in hint_t:
                sink(("hint", owner, None))
            for cache_id in self._cache_index.get(key, ()):
                sink(("push", cache_id, src))

        for group, pg in batch.groups.items():
            for i, key in enumerate(pg.keys):
                fan_out(key, lambda d, g=group, i=i:
                        dest_rows[d][g].append(i))
        for key, value in batch.sidecar:
            fan_out(key, lambda d, kv=(key, value): dest_side[d].append(kv))

        def sub_batch(dest: _Dest) -> PlaneBatch:
            sub = PlaneBatch(batch.node_ids)
            for group, idx in dest_rows.get(dest, {}).items():
                pg = batch.groups[group]
                # full-coverage destinations reuse the group's planes
                # (read-only everywhere downstream): zero copies on the
                # common all-replicas / single-coordinator layout
                sub.groups[group] = (pg if len(idx) == len(pg)
                                     else pg.take(idx))
            sub.sidecar = list(dest_side.get(dest, ()))
            return sub

        for dest in list(dest_rows) + [d for d in dest_side
                                       if d not in dest_rows]:
            channel, target, src = dest
            sub = sub_batch(dest)
            if not sub:
                continue
            if channel == "merge":
                node = self.nodes[target]
                node.engine.ingest_planes(sub)
                node.puts += len(sub)
            elif channel == "gossip":
                if self.faultnet is not None:
                    self.faultnet.deliver("gossip", src, target, batch=sub)
                else:
                    self.nodes[target].inbox.add_batch(sub)
            elif channel == "hint":
                if self.faultnet is not None:
                    self.faultnet.deliver("hint", None, target, batch=sub)
                else:
                    self._hints[target].add_batch(sub)
            else:  # push-based cache update (paper §4.2), plane-shaped
                if self.faultnet is not None:
                    self.faultnet.deliver("push", src, target, batch=sub)
                else:
                    self._cache_pushes[target].add_batch(sub)
        if sp is not None:
            tr.finish(sp, bytes=batch.byte_size())
        return len(keys)

    def get(
        self,
        key: str,
        clock: Optional[VirtualClock] = None,
        prefer: Optional[str] = None,
    ) -> Optional[Lattice]:
        """Anna any-replica read — intentionally stale-prone.

        The request routes to ONE replica (random live owner, or
        ``prefer`` first) and that replica's answer is authoritative:
        the clock is charged and the value returned after the FIRST
        alive replica, *even when that replica holds nothing while
        another replica already has the value* (async replication lag).
        This is Anna's semantics, not a bug — it is the source of the
        stale reads behind the paper's Table-2 anomalies; callers that
        need freshness use :meth:`get_merged` (read-repair).  Dead
        replicas are skipped; ``None`` only means "no live replica
        answered with a value from its local store".
        """
        owners = self._owners(key)
        if not owners:
            return None
        if self.detector is not None:
            self._probe_owners(owners, clock, "get")
        # Anna routes to ANY replica: reads may be stale under async
        # replication — the source of Table 2's anomalies.
        if prefer is None:
            order = list(owners)
            self.rng.shuffle(order)
        else:
            order = sorted(owners, key=lambda o: o != prefer)
        for owner in order:
            node = self.nodes[owner]
            if not self._reachable(owner, node):
                continue
            node.gets += 1
            val = node.store.get(key)
            if clock is not None:
                size = val.byte_size() if val is not None else 0
                clock.advance(self.profile.sample(self.profile.kvs_op, size))
            return val
        return None

    def _merge_replicas(self, key: str) -> Optional[Lattice]:
        """Per-key read-repair fold (no clock accounting): merge the key
        across all reachable replicas, in owner order, dead (oracle) or
        suspected (detector) replicas skipped.  Both ``get_merged`` and
        the leftover path of ``get_merged_many`` route through here so
        scalar and batched reads cannot drift."""
        replicas: List[Lattice] = []
        for owner in self._owners(key):
            node = self.nodes[owner]
            if not self._reachable(owner, node):
                continue
            val = node.store.get(key)
            if val is not None:
                replicas.append(val)
        result = try_reduce_lww(replicas)
        if result is None:
            for val in replicas:
                result = val if result is None else result.merge(val)
        return result

    def get_merged(self, key: str, clock: Optional[VirtualClock] = None,
                   allow_partial: bool = True) -> Optional[Lattice]:
        """Read-repair style read: merge across all reachable replicas.

        Tensor-valued LWW replicas reduce as one batched R-replica
        ``ops.lww_merge_many`` launch; other lattice types fold
        ``Lattice.merge`` per replica as before.

        Under the failure plane: unreachable (suspected) owners are
        probed/retried with backoff first; if some owners stay
        unreachable the merge is *partial* — served anyway when
        ``allow_partial`` (counted in ``kvs.degraded_reads``), raised as
        :class:`KVSUnavailableError` when the caller's consistency
        level cannot tolerate missing replicas (dsc/causal block rather
        than degrade) or when NO owner is reachable at all.
        """
        if self.detector is not None:
            owners = self._owners(key)
            self._probe_owners(owners, clock, "get_merged")
            unreachable = [o for o in owners
                           if not self._reachable(o, self.nodes[o])]
            if unreachable:
                if len(unreachable) == len(owners) or not allow_partial:
                    raise KVSUnavailableError([key], op="get_merged")
                self._m_degraded.inc()
        result = self._merge_replicas(key)
        if clock is not None:
            size = result.byte_size() if result is not None else 0
            clock.advance(self.profile.sample(self.profile.kvs_op, size))
        return result

    # -- the read plane (batched multi-key reads) ---------------------------------
    def get_many(
        self,
        keys: Sequence[str],
        clock: Optional[VirtualClock] = None,
        prefer: Optional[str] = None,
    ) -> PlaneBatch:
        """Batched any-replica read: per key, the SAME replica choice as
        :meth:`get` (random live owner, or ``prefer`` first) — including
        its intentional staleness: the chosen replica is authoritative
        even when it holds nothing while another replica has the value,
        so such keys are simply absent from the result.  Arena rows
        travel packed (no per-key lattice objects); fallback-held values
        ride the sidecar as existing object references.  The virtual
        clock advances ONCE for the whole batch, sized by total payload
        bytes.
        """
        tr = self.tracer
        sp = None
        if tr.enabled and tr.cur is not None:
            sp = tr.start("kvs", "get_many", clock=clock or tr.cur.clock,
                          tid=tr.cur.tid, parent=tr.cur, n_keys=len(keys))
        ukeys = list(dict.fromkeys(keys))
        if self.detector is not None:
            # one probe/retry round for the whole batch: every involved
            # owner that turns out dead is suspected once, the backoff
            # charged once (batched reads pay batched timeouts)
            involved = list(dict.fromkeys(
                o for key in ukeys for o in self._owners(key)))
            self._probe_owners(involved, clock, "get_many")
        chosen: List[Tuple[str, StorageNode]] = []
        degraded = 0
        for key in ukeys:
            owners = self._owners(key)
            if not owners:
                continue
            if prefer is None:
                order = list(owners)
                self.rng.shuffle(order)
            else:
                order = sorted(owners, key=lambda o: o != prefer)
            hit = False
            for owner in order:
                node = self.nodes[owner]
                if not self._reachable(owner, node):
                    continue
                node.gets += 1
                chosen.append((key, node))
                hit = True
                break
            if not hit and self.detector is not None:
                degraded += 1  # no reachable replica: key absent, the
                # cache falls back to its local copy
        if degraded:
            self._m_degraded.inc(degraded)
        batch, leftover = self.reader.reduce_replica_planes(
            [(key, (node.engine,)) for key, node in chosen])
        by_key = dict(chosen)
        for key in leftover:  # fallback-held at the chosen replica
            val = by_key[key].engine.fallback.get(key)
            if val is not None:
                batch.sidecar.append((key, val))
        if clock is not None:
            clock.advance(
                self.profile.sample(self.profile.kvs_op, batch.byte_size()))
        if sp is not None:
            tr.finish(sp, bytes=batch.byte_size())
        return batch

    def get_merged_many(
        self,
        keys: Sequence[str],
        clock: Optional[VirtualClock] = None,
        allow_partial: bool = True,
        on_unavailable: str = "raise",
    ) -> PlaneBatch:
        """Batched read-repair over a whole key list (the read plane).

        Per key the semantics are identical to :meth:`get_merged` —
        merge across all live replicas in owner order, dead replicas
        skipped — but tensor-valued LWW keys reduce as ONE
        ``ops.lww_merge_many`` launch per slab group through
        ``MergeEngine.reduce_replica_planes`` ((R, K, D) candidate
        stack), winners travel as packed planes (zero per-key lattice
        objects), and the clock advances ONCE for the batch, sized by
        total payload bytes.  Keys held nowhere are absent from the
        result; non-arena lattices (opaque, causal, Set/Map, 64-bit
        exact-path payloads) fold per key exactly as before and ride
        the sidecar.

        A hot read set re-executes a cached reduce plan: the per-key
        ring walk and candidate-index build are skipped whenever the
        placement epoch and every engine's ``layout_version`` are
        unchanged since the plan was built (row contents re-gather at
        execute, so steady-state writes never invalidate it — on the
        device tier a warmed read is one fused gather-reduce launch
        per slab group with zero host syncs).
        """
        tr = self.tracer
        sp = None
        if tr.enabled and tr.cur is not None:
            sp = tr.start("kvs", "get_merged_many",
                          clock=clock or tr.cur.clock, tid=tr.cur.tid,
                          parent=tr.cur, n_keys=len(keys))
        ukeys = tuple(dict.fromkeys(keys))
        if self.detector is not None:
            involved = list(dict.fromkeys(
                o for key in ukeys for o in self._owners(key)))
            self._probe_owners(involved, clock, "get_merged_many")
            # reachability per key: fully-unreachable keys either raise
            # (the caller cannot degrade) or are skipped (the cache
            # serves its freshest local copy); partially-reachable keys
            # serve a degraded merge over the replicas that answered
            if not all(self._reachable(nid, n)
                       for nid, n in self.nodes.items()):
                unavailable: List[str] = []
                partial = 0
                for key in ukeys:
                    owners = self._owners(key)
                    down = [o for o in owners
                            if not self._reachable(o, self.nodes[o])]
                    if not down:
                        continue
                    if len(down) == len(owners) or not allow_partial:
                        unavailable.append(key)
                    else:
                        partial += 1
                if unavailable:
                    if on_unavailable == "raise" or not allow_partial:
                        raise KVSUnavailableError(
                            unavailable, op="get_merged_many")
                    ukeys = tuple(k for k in ukeys if k not in
                                  set(unavailable))
                    partial += len(unavailable)
                if partial:
                    self._m_degraded.inc(partial)
        sig = (self._placement_epoch,
               tuple((nid, self._reachable(nid, node),
                      node.engine.layout_version)
                     for nid, node in self.nodes.items()))
        cached = self._read_plans.get(ukeys)
        if cached is not None and cached[0] == sig:
            plan = cached[1]
            self._m_plan_hits.inc()
        else:
            self._m_plan_misses.inc()
            live = {nid: node.engine for nid, node in self.nodes.items()
                    if self._reachable(nid, node)}
            keyed = [
                (key, [live[o] for o in self._owners(key) if o in live])
                for key in ukeys
            ]
            plan = self.reader.plan_replica_reduce(keyed)
            if len(self._read_plans) >= 32:  # bound the memo: drop oldest
                self._read_plans.pop(next(iter(self._read_plans)))
            self._read_plans[ukeys] = (sig, plan)
        batch, leftover = self.reader.execute_reduce_plan(plan)
        for key in leftover:
            merged = self._merge_replicas(key)
            if merged is not None:
                batch.sidecar.append((key, merged))
        if clock is not None:
            clock.advance(
                self.profile.sample(self.profile.kvs_op, batch.byte_size()))
        if sp is not None:
            tr.finish(sp, bytes=batch.byte_size())
        return batch

    def get_merged_many_values(
        self,
        keys: Sequence[str],
        clock: Optional[VirtualClock] = None,
    ) -> Dict[str, Optional[Lattice]]:
        """Materializing convenience over :meth:`get_merged_many`:
        key -> merged lattice, with ``None`` recorded for keys held
        nowhere (so callers can cache negative results).  Packed winners
        materialize one object per key here — arena-backed consumers
        (the executor cache) ingest the batch form instead.
        """
        batch = self.get_merged_many(keys, clock=clock)
        out: Dict[str, Optional[Lattice]] = {
            key: None for key in dict.fromkeys(keys)
        }
        for key, lat in batch.iter_entries():
            out[key] = lat
        return out

    def delete(self, key: str) -> None:
        """Remove a key everywhere, including in-flight copies: gossip
        inboxes, hinted handoffs and pending cache pushes would otherwise
        resurrect the value on the next tick/recovery.  In-flight copies
        live in packed PlaneBuffers; purge drops the key's rows (and any
        sidecar entries) in place."""
        for node in self.nodes.values():
            node.store.pop(key, None)
            node.inbox.purge(key)
        for hints in self._hints.values():
            hints.purge(key)
        for pushes in self._cache_pushes.values():
            pushes.purge(key)

    # -- cache keyset index (paper §4.2) -----------------------------------------
    def publish_keyset(self, cache_id: str, keys: Set[str]) -> None:
        """Replace the cache's whole subscription with ``keys``: work in
        the sizes of its old and new key sets, not of the index."""
        old = self._cache_keys.get(cache_id, set())
        keys = set(keys)
        self.update_keyset(cache_id, keys - old, old - keys)

    def update_keyset(self, cache_id: str, added: Set[str],
                      removed: Set[str]) -> None:
        """Apply a membership delta to the cache's subscription; keys
        whose subscriber set empties are pruned, so the index does not
        leak dead entries."""
        index = self._cache_index
        for key in removed:
            caches = index.get(key)
            if caches is not None:
                caches.discard(cache_id)
                if not caches:
                    del index[key]
        for key in added:
            index[key].add(cache_id)
        mine = self._cache_keys.setdefault(cache_id, set())
        mine -= removed
        mine |= added
        if not mine:
            del self._cache_keys[cache_id]

    def drain_cache_pushes(
        self,
        cache_id: str,
        rng: Optional[random.Random] = None,
        defer_prob: float = 0.0,
    ) -> PlaneBatch:
        """Pop pending pushes for a cache as a packed :class:`PlaneBatch`.

        With ``defer_prob`` each queued row/sidecar entry independently
        stays behind for the next tick (the cache's out-of-order delivery
        knob) — deferral happens plane-native, no requeue round-trip.
        """
        buf = self._cache_pushes.get(cache_id)
        if buf is None:
            return PlaneBatch()
        batch = buf.split(rng, defer_prob)
        if not buf:
            self._cache_pushes.pop(cache_id, None)
        return batch

    def drop_cache_pushes(self, cache_id: str) -> None:
        """Discard queued pushes (cache recovery: a recovered cache is
        empty and must not receive pushes for keys it no longer holds)."""
        self._cache_pushes.pop(cache_id, None)

    def defer_cache_push(self, cache_id: str, key: str, value: Lattice) -> None:
        """Requeue a pushed update for the cache's next tick (public API —
        caches must not reach into the push queues directly)."""
        self._cache_pushes[cache_id].add(key, value)

    def caches_holding(self, key: str) -> Set[str]:
        return set(self._cache_index.get(key, ()))

    # -- gossip / background ------------------------------------------------------
    def tick(self, defer_prob: float = 0.0) -> int:
        """Deliver pending replica gossip; returns #messages applied.

        With the failure plane enabled each tick is one background
        round: the plane clock advances by a heartbeat interval (due
        delayed planes release, one heartbeat sweep runs), the reorder
        pool flushes shuffled, and hinted handoffs for nodes that are
        back in trust drain through the fault network."""
        if self.failure_plane is not None:
            self.failure_plane.advance(self.detector.interval)
            self.faultnet.flush_tick()
            if self._hints:
                for owner in [o for o in self._hints
                              if (n := self.nodes.get(o)) is not None
                              and self._reachable(o, n)]:
                    buf = self._hints.pop(owner)
                    self.faultnet.deliver("handoff", None, owner,
                                          batch=buf.drain())
        return sum(n.drain_inbox(self.rng, defer_prob)
                   for n in self.nodes.values() if n.alive)

    # -- introspection --------------------------------------------------------------
    def stats(self) -> Dict[str, Dict[str, int]]:
        return {
            nid: {"keys": len(n.store), "puts": n.puts, "gets": n.gets}
            for nid, n in self.nodes.items()
        }

    def transfer_stats(self) -> Dict[str, object]:
        """Host↔device transfer telemetry across the tier.

        Summed totals at the top level (all zeros on the host-numpy
        path; on the device tier, steady-state gossip and warmed batched
        reads must keep ``device_syncs`` flat), plus a ``per_engine``
        breakdown keyed by storage-node id and ``"reader"`` (the
        R-replica read-reduction engine) so regressions localize to the
        engine that caused them.  :meth:`reset_transfer_stats` windows
        measurements without rebuilding the tier."""
        engines = {nid: n.engine for nid, n in self.nodes.items()}
        engines["reader"] = self.reader
        per_engine = {
            name: {field: getattr(engine, field) for field in _XFER_FIELDS}
            for name, engine in engines.items()
        }
        out: Dict[str, object] = {
            field: sum(stats[field] for stats in per_engine.values())
            for field in _XFER_FIELDS
        }
        out["per_engine"] = per_engine
        return out

    def reset_transfer_stats(self) -> None:
        """Zero the transfer counters on every engine in the tier."""
        for n in self.nodes.values():
            n.engine.reset_transfer_stats()
        self.reader.reset_transfer_stats()

    def total_keys(self) -> int:
        keys: Set[str] = set()
        for n in self.nodes.values():
            keys |= set(n.store)
        return len(keys)
