"""Unified failure plane: channel faults, heartbeat detection, retry.

Cloudburst's fault story (paper §4.5) rests on Anna's hinted handoff for
k-1 replica tolerance plus idempotent whole-DAG restart.  Until this
module the repo only exercised that with oracle kill switches: flipping
``alive`` flags the runtime observed instantly.  Real serverless
coordination (FaaSKeeper, 2203.14859) has no failure oracle — it lives
on timeouts and suspicion.  This module supplies the three missing
layers:

* ``FaultNetwork`` — an interposition layer over every replication
  channel (gossip inboxes, hints, cache pushes, membership handoff)
  that can drop, delay (on the virtual clock), duplicate, reorder, and
  bidirectionally partition traffic at ``PlaneBatch`` granularity.
  Delivery targets are resolved at *delivery time* through a resolver
  callback, never by holding buffer references (the KVS pops empty
  push buffers, so a stored reference would go stale).
* ``FailureDetector`` — per-endpoint heartbeats on the virtual clock
  with a suspicion threshold.  A suspected-but-alive endpoint (false
  positive) is harmless by construction: reads route around it, writes
  hint to it, and it rejoins on its next successful heartbeat.  Steady
  state touches only per-endpoint floats — no per-key objects.
* ``RetryPolicy`` — capped exponential backoff for KVS client ops,
  charged to the caller's ``VirtualClock``.

Everything here is a no-op until ``AnnaKVS.enable_failure_plane`` /
``Cluster.enable_failure_plane`` is called: the data-plane hooks are a
single ``is not None`` check when disabled (counter-asserted in
``tests/test_failure_plane.py``).

This module deliberately imports nothing from ``kvs``/``cache``/
``runtime`` — they import from it.
"""

from __future__ import annotations

import heapq
import random
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Set

from ..obs import MetricsRegistry, counter_shim
from .netsim import VirtualClock

__all__ = [
    "KVSUnavailableError",
    "RetryPolicy",
    "ChannelFault",
    "FaultNetwork",
    "FailureDetector",
    "FailurePlane",
    "CHANNEL_KINDS",
]

# every replication channel the KVS moves planes over
CHANNEL_KINDS = ("gossip", "hint", "push", "handoff", "heartbeat")


class KVSUnavailableError(RuntimeError):
    """No reachable replica quorum for the given keys (detector mode).

    Raised only when a failure detector is wired: with the oracle
    liveness model the KVS keeps its historical plain ``RuntimeError``.
    The runtime treats this as an infrastructure fault (retry the
    attempt), not a user error.
    """

    def __init__(self, keys, op: str = "op"):
        self.keys = list(keys)
        self.op = op
        head = ", ".join(map(str, self.keys[:4]))
        more = "..." if len(self.keys) > 4 else ""
        super().__init__(
            f"kvs unavailable for {op}: no reachable replica for "
            f"[{head}{more}]")


@dataclass(frozen=True)
class RetryPolicy:
    """Capped exponential backoff, charged to the op's VirtualClock."""

    op_timeout: float = 0.05      # virtual seconds before a probe fails
    base_backoff: float = 0.01
    max_backoff: float = 0.25
    multiplier: float = 2.0
    max_attempts: int = 3

    def backoff(self, attempt: int) -> float:
        """Backoff before retry ``attempt`` (0-based first retry)."""
        return min(self.max_backoff,
                   self.base_backoff * (self.multiplier ** attempt))


@dataclass
class ChannelFault:
    """One fault rule on the interposed channels.

    ``action`` ∈ {drop, delay, duplicate, reorder}; ``kind``/``src``/
    ``dst`` filter which traffic it applies to (``None`` = wildcard);
    ``p`` is the per-delivery firing probability; ``delay`` is the
    virtual-clock hold for ``delay`` actions.
    """

    action: str
    kind: Optional[str] = None
    src: Optional[str] = None
    dst: Optional[str] = None
    p: float = 1.0
    delay: float = 0.0

    def matches(self, kind: str, src, dst) -> bool:
        if self.kind is not None and self.kind != kind:
            return False
        if self.src is not None and src is not None and self.src != src:
            return False
        if self.dst is not None and self.dst != dst:
            return False
        return True


class FaultNetwork:
    """Interposition layer over the KVS replication channels.

    ``resolve(kind, dst)`` must return the destination ``PlaneBuffer``
    (or ``None`` if the destination no longer exists).  All delivery —
    immediate, delayed, held by a partition — funnels through
    ``_deliver_now`` so the resolver is consulted at the moment the
    plane lands, never earlier.
    """

    def __init__(self, clock: VirtualClock, rng: random.Random,
                 resolve: Callable[[str, Any], Any],
                 metrics: Optional[MetricsRegistry] = None):
        self.clock = clock
        self.rng = rng
        self.resolve = resolve
        self.metrics = metrics or MetricsRegistry()
        self.rules: List[ChannelFault] = []
        # bidirectional partitions: frozenset pairs of endpoint ids;
        # ("*", x) isolates x from everyone
        self.partitions: Set[frozenset] = set()
        # delayed planes: (release_at, seq, kind, src, dst, key, value, batch)
        self._delayed: List[tuple] = []
        # planes held behind a partition, delivered on heal
        self._held: List[tuple] = []
        # planes held for reordering, flushed shuffled each tick
        self._reorder: List[tuple] = []
        self._seq = 0

        m = self.metrics
        self._m_dropped = m.counter("faultnet.dropped_planes")
        self._m_delayed = m.counter("faultnet.delayed_planes")
        self._m_duplicated = m.counter("faultnet.duplicated_planes")
        self._m_reordered = m.counter("faultnet.reordered_planes")
        self._m_partitioned = m.counter("faultnet.partitioned_planes")

    dropped_planes = counter_shim("_m_dropped")
    delayed_planes = counter_shim("_m_delayed")
    duplicated_planes = counter_shim("_m_duplicated")
    reordered_planes = counter_shim("_m_reordered")
    partitioned_planes = counter_shim("_m_partitioned")

    # -- fault management -------------------------------------------------

    def add_fault(self, fault: ChannelFault) -> ChannelFault:
        if fault.action not in ("drop", "delay", "duplicate", "reorder"):
            raise ValueError(fault.action)
        self.rules.append(fault)
        return fault

    def remove_fault(self, fault: ChannelFault) -> None:
        if fault in self.rules:
            self.rules.remove(fault)

    def partition(self, a, b) -> None:
        """Bidirectionally partition endpoints ``a`` and ``b``."""
        self.partitions.add(frozenset((a, b)))

    def isolate(self, endpoint) -> None:
        """Partition ``endpoint`` from every other endpoint."""
        self.partitions.add(frozenset(("*", endpoint)))

    def heal_partition(self, a, b) -> None:
        self.partitions.discard(frozenset((a, b)))
        self._release_held()

    def heal_isolation(self, endpoint) -> None:
        self.partitions.discard(frozenset(("*", endpoint)))
        self._release_held()

    def blocked(self, src, dst) -> bool:
        """Is the (src, dst) path cut by a partition?  ``None`` src
        (e.g. a client-coordinated hint with no single origin) is only
        blocked by the dst's isolation."""
        if not self.partitions:
            return False
        parts = self.partitions
        if frozenset(("*", dst)) in parts:
            return True
        if src is None:
            return False
        if frozenset(("*", src)) in parts:
            return True
        return frozenset((src, dst)) in parts if src != dst else False

    # -- delivery ---------------------------------------------------------

    def deliver(self, kind: str, src, dst, key=None, value=None,
                batch=None) -> None:
        """Route one plane (a (key, value) pair or whole PlaneBatch)
        through the fault rules toward ``resolve(kind, dst)``."""
        if self.blocked(src, dst):
            self._m_partitioned.inc()
            self._held.append((kind, src, dst, key, value, batch))
            return
        for rule in self.rules:
            if not rule.matches(kind, src, dst):
                continue
            if rule.p < 1.0 and self.rng.random() >= rule.p:
                continue
            if rule.action == "drop":
                self._m_dropped.inc()
                return
            if rule.action == "delay":
                self._m_delayed.inc()
                self._seq += 1
                heapq.heappush(self._delayed,
                               (self.clock.now + rule.delay, self._seq,
                                kind, src, dst, key, value, batch))
                return
            if rule.action == "duplicate":
                # back-to-back same-tick duplicates: the second copy
                # merges against an identical winner (equal timestamp
                # and vector clock), which lattice idempotence absorbs
                # without perturbing anomaly accounting
                self._m_duplicated.inc()
                self._deliver_now(kind, dst, key, value, batch)
                self._deliver_now(kind, dst, key, value, batch)
                return
            if rule.action == "reorder":
                self._m_reordered.inc()
                self._reorder.append((kind, src, dst, key, value, batch))
                return
        self._deliver_now(kind, dst, key, value, batch)

    def _deliver_now(self, kind: str, dst, key, value, batch) -> None:
        buf = self.resolve(kind, dst)
        if buf is None:
            return  # destination left the cluster; plane is moot
        if batch is not None:
            buf.add_batch(batch)
        else:
            buf.add(key, value)

    def _release_held(self) -> None:
        """Re-attempt delivery of held planes whose path healed."""
        held, self._held = self._held, []
        for (kind, src, dst, key, value, batch) in held:
            if self.blocked(src, dst):
                self._held.append((kind, src, dst, key, value, batch))
            else:
                self._deliver_now(kind, dst, key, value, batch)

    def release_due(self) -> int:
        """Deliver delayed planes whose virtual release time arrived."""
        n = 0
        while self._delayed and self._delayed[0][0] <= self.clock.now:
            (_, _, kind, src, dst, key, value, batch) = heapq.heappop(
                self._delayed)
            if self.blocked(src, dst):
                self._m_partitioned.inc()
                self._held.append((kind, src, dst, key, value, batch))
            else:
                self._deliver_now(kind, dst, key, value, batch)
            n += 1
        return n

    def flush_tick(self) -> None:
        """Flush the reorder pool in shuffled order (one gossip tick's
        worth of out-of-order delivery)."""
        if not self._reorder:
            return
        pool, self._reorder = self._reorder, []
        self.rng.shuffle(pool)
        for (kind, src, dst, key, value, batch) in pool:
            if self.blocked(src, dst):
                self._m_partitioned.inc()
                self._held.append((kind, src, dst, key, value, batch))
            else:
                self._deliver_now(kind, dst, key, value, batch)

    def heal_all(self) -> None:
        """Clear every rule and partition and flush all in-flight
        planes so convergence assertions are well-defined."""
        self.rules.clear()
        self.partitions.clear()
        pool, self._reorder = self._reorder, []
        self.rng.shuffle(pool)
        for (kind, _src, dst, key, value, batch) in pool:
            self._deliver_now(kind, dst, key, value, batch)
        while self._delayed:
            (_, _, kind, _src, dst, key, value, batch) = heapq.heappop(
                self._delayed)
            self._deliver_now(kind, dst, key, value, batch)
        held, self._held = self._held, []
        for (kind, _src, dst, key, value, batch) in held:
            self._deliver_now(kind, dst, key, value, batch)

    @property
    def in_flight(self) -> int:
        return len(self._delayed) + len(self._held) + len(self._reorder)


class FailureDetector:
    """Heartbeat + suspicion-threshold failure detection on the
    virtual clock (FaaSKeeper-style: no perfect failure oracle).

    Endpoints register with an ``alive_fn`` ground-truth probe (used
    ONLY to emit heartbeats and classify false suspicions — routing
    decisions never consult it) and an optional ``on_rejoin`` callback
    fired when a previously suspected endpoint heartbeats again.
    """

    def __init__(self, clock: VirtualClock, network: FaultNetwork,
                 interval: float = 0.05, suspicion_multiplier: float = 3.0,
                 metrics: Optional[MetricsRegistry] = None):
        self.clock = clock
        self.network = network
        self.interval = interval
        self.threshold = interval * suspicion_multiplier
        self.metrics = metrics or MetricsRegistry()
        self._alive_fn: Dict[Any, Callable[[], bool]] = {}
        self._on_rejoin: Dict[Any, Callable[[], None]] = {}
        self.last_heard: Dict[Any, float] = {}
        self.suspected: Set[Any] = set()
        self._next_poll = clock.now

        m = self.metrics
        self._m_susp = m.counter("detector.suspicions")
        self._m_false = m.counter("detector.false_suspicions")
        self._m_rejoin = m.counter("detector.rejoins")
        self._m_beats = m.counter("detector.heartbeats")

    suspicions = counter_shim("_m_susp")
    false_suspicions = counter_shim("_m_false")
    rejoins = counter_shim("_m_rejoin")
    heartbeats = counter_shim("_m_beats")

    def register(self, endpoint, alive_fn: Callable[[], bool],
                 on_rejoin: Optional[Callable[[], None]] = None) -> None:
        self._alive_fn[endpoint] = alive_fn
        if on_rejoin is not None:
            self._on_rejoin[endpoint] = on_rejoin
        self.last_heard[endpoint] = self.clock.now

    def unregister(self, endpoint) -> None:
        self._alive_fn.pop(endpoint, None)
        self._on_rejoin.pop(endpoint, None)
        self.last_heard.pop(endpoint, None)
        self.suspected.discard(endpoint)

    def trusts(self, endpoint) -> bool:
        """Routing predicate: unknown endpoints are trusted (they get
        probed and suspected on timeout), suspected ones are not."""
        return endpoint not in self.suspected

    def report_timeout(self, endpoint) -> None:
        """A data-path probe of ``endpoint`` timed out: suspect it
        immediately rather than waiting for the heartbeat sweep."""
        if endpoint not in self._alive_fn or endpoint in self.suspected:
            return
        self.suspected.add(endpoint)
        self._m_susp.inc()
        if self._alive_fn[endpoint]():
            self._m_false.inc()

    def _heartbeat_blocked(self, endpoint) -> bool:
        """Is this endpoint's heartbeat lost to a partition or a
        heartbeat-channel fault rule?"""
        net = self.network
        if net.blocked(endpoint, "detector"):
            return True
        for rule in net.rules:
            if rule.action != "drop":
                continue
            if not rule.matches("heartbeat", endpoint, "detector"):
                continue
            if rule.p >= 1.0 or net.rng.random() < rule.p:
                return True
        return False

    def poll(self) -> None:
        """One heartbeat round if due.  Steady state touches only the
        per-endpoint float in ``last_heard`` — no per-key objects."""
        now = self.clock.now
        if now < self._next_poll:
            return
        self._next_poll = now + self.interval  # no catch-up storm
        for endpoint, alive_fn in self._alive_fn.items():
            if alive_fn() and not self._heartbeat_blocked(endpoint):
                self.last_heard[endpoint] = now
                self._m_beats.inc()
                if endpoint in self.suspected:
                    self.suspected.discard(endpoint)
                    self._m_rejoin.inc()
                    cb = self._on_rejoin.get(endpoint)
                    if cb is not None:
                        cb()
            elif (endpoint not in self.suspected
                  and now - self.last_heard[endpoint] > self.threshold):
                self.suspected.add(endpoint)
                self._m_susp.inc()
                if alive_fn():
                    self._m_false.inc()


class FailurePlane:
    """Bundles the shared clock, fault network, detector and retry
    policy; the KVS/cluster own one of these when chaos is enabled."""

    def __init__(self, clock: VirtualClock,
                 resolve: Callable[[str, Any], Any],
                 rng: Optional[random.Random] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 retry: Optional[RetryPolicy] = None,
                 heartbeat_interval: float = 0.05,
                 suspicion_multiplier: float = 3.0):
        self.clock = clock
        self.metrics = metrics or MetricsRegistry()
        self.network = FaultNetwork(clock, rng or random.Random(0),
                                    resolve, metrics=self.metrics)
        self.detector = FailureDetector(
            clock, self.network, interval=heartbeat_interval,
            suspicion_multiplier=suspicion_multiplier, metrics=self.metrics)
        self.retry = retry or RetryPolicy()

    def advance(self, dt: float) -> None:
        """Advance the failure plane's virtual clock: release due
        delayed planes and run a heartbeat round if one is due."""
        if dt > 0:
            self.clock.advance(dt)
        self.network.release_due()
        self.detector.poll()

    def heal_all(self) -> None:
        """Flush all channel faults and force a heartbeat round so
        live-but-suspected endpoints rejoin immediately."""
        self.network.heal_all()
        self.detector._next_poll = self.clock.now
        self.detector.poll()
