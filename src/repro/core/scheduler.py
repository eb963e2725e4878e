"""Function schedulers (paper §4.3): mechanisms + locality/load heuristics.

Mechanisms: function registration (stored in Anna + a shared registered-
function list), DAG registration (verify functions, pick executors to cache
each function), per-request executor selection, schedule broadcast.

Policy (the paper's default heuristics, pluggable):
* prefer the executor with the most KVS-reference arguments already cached
  (via the scheduler-local cached-key index built from published keysets);
* avoid executors above 70% utilization — backpressure makes hot data/
  functions replicate onto fresh executors (§4.3 "Scheduling Policy");
* otherwise pick uniformly at random.
"""

from __future__ import annotations

import random
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

from .arena import KeyWatch
from .cache import ExecutorCache
from .dag import Dag
from .executor import CloudburstReference, Executor
from .kvs import AnnaKVS
from .lattices import LamportClock, LWWLattice, SetLattice
from .netsim import NetworkProfile, VirtualClock, DEFAULT_PROFILE

OVERLOAD_THRESHOLD = 0.70
FUNCS_KEY = "__cloudburst_registered_functions"


class SchedulingPolicy:
    """Pluggable policy interface (paper: 'pluggable policies')."""

    def pick(
        self,
        scheduler: "Scheduler",
        fn_name: str,
        args: Sequence,
        candidates: List[str],
    ) -> str:  # pragma: no cover - interface
        raise NotImplementedError


class LocalityPolicy(SchedulingPolicy):
    """The paper's default: data locality first, then load, then random."""

    def pick(self, scheduler, fn_name, args, candidates):
        ref_keys = [a.key for a in args if isinstance(a, CloudburstReference)]
        not_overloaded = [
            e for e in candidates if scheduler.utilization.get(e, 0.0) <= OVERLOAD_THRESHOLD
        ] or candidates
        if ref_keys:
            best, best_score = None, -1
            for e in not_overloaded:
                cached = scheduler.executor_keysets.get(e, set())
                score = sum(1 for k in ref_keys if k in cached)
                if score > best_score:
                    best, best_score = e, score
            if best is not None and best_score > 0:
                return best
        return scheduler.rng.choice(not_overloaded)


class RandomPolicy(SchedulingPolicy):
    def pick(self, scheduler, fn_name, args, candidates):
        return scheduler.rng.choice(candidates)


class Scheduler:
    def __init__(
        self,
        scheduler_id: str,
        kvs: AnnaKVS,
        executors: Dict[str, Executor],
        profile: NetworkProfile = DEFAULT_PROFILE,
        policy: Optional[SchedulingPolicy] = None,
        seed: int = 0,
        pin_replicas: int = 2,
        tracer=None,
    ):
        self.scheduler_id = scheduler_id
        self.kvs = kvs
        self.executors = executors
        self.profile = profile
        # shares the deployment's tracer (the KVS carries it) so batched
        # scheduling waves show up as scheduler-layer spans
        self.tracer = tracer if tracer is not None else kvs.tracer
        self.policy = policy or LocalityPolicy()
        self.rng = random.Random(seed)
        self.pin_replicas = pin_replicas
        self.lamport = LamportClock(scheduler_id)
        # scheduler-local indexes (paper: each scheduler constructs a local
        # index tracking the keys stored by each cache)
        self.executor_keysets: Dict[str, Set[str]] = defaultdict(set)
        # cache -> (watch, key set as of the last refresh): the
        # executors of one VM share their cache's set (read-only)
        self._cache_keysets: Dict[ExecutorCache,
                                  Tuple[KeyWatch, Set[str]]] = {}
        self._m_keyset_full = kvs.metrics.counter("sched.keyset.full")
        self.utilization: Dict[str, float] = {}
        self.function_locations: Dict[str, List[str]] = defaultdict(list)
        self.dags: Dict[str, Dag] = {}
        self.call_counts: Dict[str, int] = defaultdict(int)
        # names registered THROUGH this scheduler: a local fast path for
        # submit-time validation (the KVS set stays authoritative for
        # functions registered by other schedulers)
        self.local_functions: Set[str] = set()

    # -- registration mechanisms ---------------------------------------------------
    def register_function(self, name: str, fn: Callable) -> None:
        """Store the function in Anna + update the registered-function set."""
        self.kvs.put(f"__func_{name}", LWWLattice(self.lamport.tick(), fn))
        cur = self.kvs.get_merged(FUNCS_KEY) or SetLattice()
        self.kvs.put(FUNCS_KEY, cur.merge(SetLattice.of([name])))
        self.local_functions.add(name)

    def registered_functions(self) -> Set[str]:
        lat = self.kvs.get_merged(FUNCS_KEY)
        return set(lat.reveal()) if lat is not None else set()

    def load_function(self, name: str) -> Callable:
        lat = self.kvs.get_merged(f"__func_{name}")
        if lat is None:
            raise KeyError(f"function {name!r} not registered")
        return lat.reveal()

    def register_dag(self, dag: Dag) -> None:
        registered = self.registered_functions()
        missing = [f for f in dag.functions if f not in registered]
        if missing:
            raise KeyError(f"DAG {dag.name}: unregistered functions {missing}")
        # pick executors to cache each function (deserialize-and-pin, §4.1)
        for fn_name in dag.functions:
            fn = self.load_function(fn_name)
            replicas = min(self.pin_replicas, len(self.executors))
            alive = [e for e in self.executors.values() if e.alive]
            for executor in self.rng.sample(alive, min(replicas, len(alive))):
                executor.pin_function(fn_name, fn)
                self.function_locations[fn_name].append(executor.executor_id)
        # DAG topologies are the scheduler's only persistent metadata (§4.3)
        self.kvs.put(f"__dag_{dag.name}", LWWLattice(self.lamport.tick(), dag))
        self.dags[dag.name] = dag

    # -- index maintenance -------------------------------------------------------------
    def refresh_index(self, window_seconds: float = 1.0) -> None:
        """Pull cached keysets + executor metrics (published via the KVS).

        Each cache's set is copied once, the first time it is seen; later
        refreshes apply the keys it added and removed since."""
        seen: Dict[ExecutorCache, Set[str]] = {}
        for eid, ex in self.executors.items():
            keys = seen.get(ex.cache)
            if keys is None:
                keys = seen[ex.cache] = self._cache_keyset(ex.cache)
            self.executor_keysets[eid] = keys
            self.utilization[eid] = ex.utilization(window_seconds)
        for cache in self._cache_keysets.keys() - seen.keys():
            watch, _ = self._cache_keysets.pop(cache)
            cache.engine.unwatch_keys(watch)

    def _cache_keyset(self, cache: ExecutorCache) -> Set[str]:
        entry = self._cache_keysets.get(cache)
        if entry is None:
            watch = cache.engine.watch_keys()
            keys = set(cache.data)
            self._cache_keysets[cache] = (watch, keys)
            self._m_keyset_full.inc()
            return keys
        watch, keys = entry
        added, removed = watch.drain()
        keys -= removed
        keys |= added
        return keys

    # -- per-request scheduling -----------------------------------------------------------
    def _schedulable(self, executor: Executor) -> bool:
        """Liveness as the scheduler KNOWS it.  With the failure plane
        enabled the ground-truth ``alive`` flag is off-limits: placement
        consults the heartbeat detector's suspicion list instead, so a
        freshly-dead-but-still-trusted executor CAN be picked — the
        invocation then times out, the engine reports the timeout, and
        the retry routes around it (no instant-knowledge oracle)."""
        det = self.kvs.detector
        if det is not None and executor.vm_id in det.last_heard:
            return det.trusts(executor.vm_id)
        return executor.alive

    def pick_executor(
        self,
        fn_name: str,
        args: Sequence,
        exclude: Optional[Set[str]] = None,
    ) -> str:
        exclude = exclude or set()
        candidates = [
            e
            for e in self.function_locations.get(fn_name, [])
            if e not in exclude and self._schedulable(self.executors[e])
        ]
        if not candidates:
            # cold function: any live executor can pull + deserialize it
            candidates = [
                e for e, ex in self.executors.items()
                if self._schedulable(ex) and e not in exclude
            ]
        if not candidates:
            raise RuntimeError("no live executors")
        self.call_counts[fn_name] += 1
        return self.policy.pick(self, fn_name, args, candidates)

    def schedule_ready(
        self,
        triggers: Sequence[Tuple[str, Sequence, Optional[Set[str]]]],
    ) -> List[str]:
        """Batched scheduling entry point for the cluster engine.

        ``triggers`` is one engine turn's worth of ready functions across
        ALL in-flight DAGs: ``(fn_name, args, exclude)`` tuples in
        submission order.  Placement is per-trigger :meth:`pick_executor`
        (same policy, same rng draw sequence — a single in-flight DAG
        reproduces the sequential scheduler's picks exactly); what is
        batched is the entry point itself: one scheduler hop serves the
        whole wave instead of one per function.
        """
        with self.tracer.span("scheduler", "schedule_ready",
                              n_triggers=len(triggers)):
            return [
                self.pick_executor(fn_name, args, exclude=exclude)
                for fn_name, args, exclude in triggers
            ]

    def schedule_dag(
        self,
        dag: Dag,
        args_by_fn: Dict[str, Sequence],
        exclude: Optional[Set[str]] = None,
    ) -> Dict[str, str]:
        """Create the schedule broadcast to all participating executors."""
        schedule: Dict[str, str] = {}
        for fn_name in dag.topo_order():
            schedule[fn_name] = self.pick_executor(
                fn_name, args_by_fn.get(fn_name, ()), exclude=exclude
            )
        return schedule

    # -- autoscaler hooks ---------------------------------------------------------------
    def add_executor(self, executor: Executor) -> None:
        self.executors[executor.executor_id] = executor

    def remove_executor(self, executor_id: str) -> None:
        self.executors.pop(executor_id, None)
        for locs in self.function_locations.values():
            if executor_id in locs:
                locs.remove(executor_id)

    def pin_function_replica(self, fn_name: str, executor_id: str) -> None:
        fn = self.load_function(fn_name)
        self.executors[executor_id].pin_function(fn_name, fn)
        if executor_id not in self.function_locations[fn_name]:
            self.function_locations[fn_name].append(executor_id)

    def unpin_function_replica(self, fn_name: str, executor_id: str) -> None:
        self.executors[executor_id].unpin_function(fn_name)
        if executor_id in self.function_locations[fn_name]:
            self.function_locations[fn_name].remove(executor_id)
