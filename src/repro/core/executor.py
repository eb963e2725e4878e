"""Function executors (paper §4.1) + the user-facing system API (Table 1).

Each executor is a long-running worker pinned to a VM; several executors
share the VM's cache process.  Before each invocation the executor resolves
KVS-reference arguments through the session's consistency protocol, builds
the Cloudburst user library (get/put/delete/send/recv/get_id), runs the
function, and reports metrics.
"""

from __future__ import annotations

import dataclasses
import inspect
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

from .cache import ExecutorCache
from .consistency import AnomalyTracker, ProtocolClient, SessionContext
from .lattices import LamportClock, Lattice
from .netsim import NetworkProfile, VirtualClock, DEFAULT_PROFILE


class ExecutorFailure(RuntimeError):
    """The executor's VM died mid-invocation (fault-injection hook)."""


@dataclasses.dataclass
class CloudburstReference:
    """A function argument resolved from the KVS at invocation time (§3)."""

    key: str
    deserialize: bool = True


class UserLibrary:
    """The API handed to user functions (paper Table 1)."""

    def __init__(self, executor: "Executor", protocol: ProtocolClient, invocation_id: str):
        self._executor = executor
        self._protocol = protocol
        self._invocation_id = invocation_id

    def get(self, key: str) -> Any:
        return self._protocol.get(key)

    def get_many(self, keys: List[str]) -> List[Any]:
        """Batched multi-get: one ``ExecutorCache.read_many`` warm (ONE
        ``get_merged_many`` launch for all misses), then per-key session
        resolution as cache hits.  Same semantics as a ``get`` loop,
        minus the per-key scalar round trips."""
        return self._protocol.get_many(keys)

    def put_many(self, pairs: List[Tuple[str, Any]]) -> List[Lattice]:
        """Batched multi-put: per-key session write semantics; the
        writes leave the cache as ONE batched flush on the next tick.
        Returns the written lattices (each carries its version)."""
        return self._protocol.put_many(pairs)

    def put(self, key: str, value: Any) -> Lattice:
        return self._protocol.put(key, value)

    def delete(self, key: str) -> None:
        self._executor.cache.kvs.delete(key)

    def send(self, recv_id: str, msg: Any) -> None:
        self._executor.send_message(recv_id, msg, self._protocol.clock)

    def recv(self) -> List[Any]:
        return self._executor.drain_messages()

    def get_id(self) -> str:
        return self._invocation_id

    @property
    def vm_id(self) -> str:
        """The VM this invocation runs on.  Functions that memoize
        VM-local state (e.g. device-resident model params fetched once
        per VM) key their memo on this."""
        return self._executor.vm_id


class Executor:
    """One executor process.  ``vm_id`` groups executors sharing a cache."""

    def __init__(
        self,
        executor_id: str,
        cache: ExecutorCache,
        vm_id: str,
        profile: NetworkProfile = DEFAULT_PROFILE,
        registry: Optional[Dict[str, "Executor"]] = None,
    ):
        self.executor_id = executor_id
        self.vm_id = vm_id
        self.cache = cache
        self.profile = profile
        self.registry = registry if registry is not None else {}
        self.lamport = LamportClock(executor_id)
        self.pinned: Dict[str, Callable] = {}
        self.inbox: List[Any] = []
        self.alive = True
        self.slow_factor = 1.0  # straggler injection
        # metrics (paper §4.1: executors publish these to the KVS)
        self.invocations = 0
        self.busy_seconds = 0.0
        self.recent_latencies: List[float] = []
        self._invocation_seq = 0

    # -- function management ----------------------------------------------------
    def pin_function(self, name: str, fn: Callable) -> None:
        """Deserialize-and-cache a DAG function at this executor (§4.1)."""
        self.pinned[name] = fn

    def unpin_function(self, name: str) -> None:
        self.pinned.pop(name, None)

    def has_function(self, name: str) -> bool:
        return name in self.pinned

    # -- messaging (Table 1) -------------------------------------------------------
    def send_message(self, recv_id: str, msg: Any, clock: Optional[VirtualClock]) -> None:
        target = self.registry.get(recv_id)
        if clock is not None:
            clock.advance(self.profile.sample(self.profile.tcp, 64))
        if target is not None and target.alive:
            target.inbox.append(msg)

    def drain_messages(self) -> List[Any]:
        out, self.inbox = self.inbox, []
        return out

    # -- invocation ------------------------------------------------------------------
    def invoke(
        self,
        fn_name: str,
        args: Tuple[Any, ...],
        session: SessionContext,
        caches: Dict[str, ExecutorCache],
        clock: Optional[VirtualClock] = None,
        tracker: Optional[AnomalyTracker] = None,
        fn: Optional[Callable] = None,
        prefetch: bool = True,
    ) -> Any:
        if not self.alive:
            raise ExecutorFailure(self.executor_id)
        func = fn if fn is not None else self.pinned.get(fn_name)
        if func is None:
            raise KeyError(f"function {fn_name!r} not pinned at {self.executor_id}")
        userlib, resolved = self.resolve_invocation(
            fn_name, args, session, caches, clock=clock, tracker=tracker,
            prefetch=prefetch,
        )
        t0 = time.perf_counter()
        if _wants_userlib(func):
            result = func(userlib, *resolved)
        else:
            result = func(*resolved)
        elapsed = (time.perf_counter() - t0) * self.slow_factor
        if clock is not None:
            clock.advance(elapsed)
        self.record_invocation(elapsed)
        return result

    def resolve_invocation(
        self,
        fn_name: str,
        args: Tuple[Any, ...],
        session: SessionContext,
        caches: Dict[str, ExecutorCache],
        clock: Optional[VirtualClock] = None,
        tracker: Optional[AnomalyTracker] = None,
        prefetch: bool = True,
    ) -> Tuple[UserLibrary, List[Any]]:
        """Everything :meth:`invoke` does BEFORE user code runs: build the
        per-invocation session protocol + user library and resolve the
        KVS-reference arguments.  Split out so the engine can resolve a
        whole wave of same-function invocations, then dispatch user code
        ONCE for the group (cross-request model batching)."""
        if not self.alive:
            raise ExecutorFailure(self.executor_id)
        self._invocation_seq += 1
        invocation_id = f"{self.executor_id}:{fn_name}:{self._invocation_seq}"
        protocol = ProtocolClient(
            cache=self.cache,
            caches=caches,
            session=session,
            node_id=self.executor_id,
            lamport=self.lamport,
            clock=clock,
            profile=self.profile,
            tracker=tracker,
        )
        # The function's declared read set (its KVS-reference args — the
        # keys the scheduler used for locality placement): warm the cache
        # with ONE batched read-repair fetch, then resolve per key as
        # cache hits.
        if prefetch:
            protocol.warm_read_set(
                [a.key for a in args if isinstance(a, CloudburstReference)])
        resolved: List[Any] = []
        for a in args:
            if isinstance(a, CloudburstReference):
                resolved.append(protocol.get(a.key))
            else:
                resolved.append(a)
        return UserLibrary(self, protocol, invocation_id), resolved

    def record_invocation(self, elapsed: float) -> None:
        """Fold one finished invocation into the executor's published
        metrics (§4.1) — shared by :meth:`invoke` and the engine's
        batched group dispatch."""
        self.invocations += 1
        self.busy_seconds += elapsed
        self.recent_latencies.append(elapsed)
        if len(self.recent_latencies) > 256:
            del self.recent_latencies[:128]

    # -- metrics / fault hooks ------------------------------------------------------
    def utilization(self, window_seconds: float) -> float:
        if window_seconds <= 0:
            return 0.0
        return min(1.0, self.busy_seconds / window_seconds)

    def fail(self) -> None:
        self.alive = False
        self.cache.fail()

    def recover(self) -> None:
        self.alive = True
        self.cache.recover()


_WANTS_USERLIB_MEMO: "weakref.WeakKeyDictionary[Callable, bool]" = (
    weakref.WeakKeyDictionary()
)


def _wants_userlib(fn: Callable) -> bool:
    # memoized per function object: signature inspection costs ~40us and
    # executors invoke the same pinned functions for their whole lifetime
    try:
        cached = _WANTS_USERLIB_MEMO.get(fn)
    except TypeError:  # unhashable/unweakrefable callable
        cached = None
    if cached is not None:
        return cached
    try:
        params = list(inspect.signature(fn).parameters)
        wants = bool(params) and params[0] in ("cloudburst", "userlib", "cb")
    except (TypeError, ValueError):
        wants = False
    try:
        _WANTS_USERLIB_MEMO[fn] = wants
    except TypeError:
        pass
    return wants
