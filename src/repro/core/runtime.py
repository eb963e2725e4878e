"""Cluster wiring + the event-driven DAG execution engine (paper §4).

``Cluster`` builds the whole deployment: Anna storage nodes, VMs (one cache
per VM, several executor processes per VM — the paper uses 3 executor cores
+ 1 cache core per c5.2xlarge), schedulers, and the monitoring engine.

Execution is futures-first, matching the paper's asynchronous client API
(§3, Fig. 2 lines 11-12): :meth:`Cluster.call_async` /
:meth:`Cluster.call_dag_async` enqueue an invocation and immediately return
a KVS-backed :class:`CloudburstFuture` (response key + ``done()`` /
``get(timeout=...)``).  Each in-flight request is a :class:`DagRun` state
machine (pending/ready/completed functions, per-attempt schedules,
restart-on-failure per §4.5, straggler speculation); many runs progress
concurrently, driven by :meth:`Cluster.step`:

* every engine turn batch-schedules ALL ready triggers across ALL in-flight
  DAGs through one :meth:`Scheduler.schedule_ready` call;
* the in-flight functions' read-set prefetches are fused into ONE
  ``ExecutorCache.read_many`` (→ one ``AnnaKVS.get_merged_many`` launch)
  per cache per turn — cross-request plane batching;
* response-key writes of runs completing in the same turn flush as ONE
  ``AnnaKVS.put_many`` batch;
* cache flush ticks (:meth:`Cluster.tick`) carry many DAGs' write-backs in
  one ``PlaneBatch`` per channel.

``call`` / ``call_dag`` are thin synchronous wrappers: submit a run and
drive ``step()`` until it resolves.  For linear DAGs (every wave a
single function — all the paper workloads) a solo ``call_dag``
reproduces the sequential executor bit-for-bit: same values, retries,
speculation, scheduling-rng draw order, per-invocation warm rule and
latency accounting (Table-2 anomaly counts verified identical).  DAGs
with parallel branches keep the same values/warm rule per function, but
the wave structure schedules sibling branches before invoking them, so
latency-model draws interleave differently than the old depth-first
walk.  Single-function ``call`` keeps its values/retries but rides the
engine's uniform DAG hop model (256-byte scheduler hops + cold-pin
charge), so its modeled latencies shift by a few hundred microseconds
versus the old bespoke two-hop path.

Fault tolerance (paper §4.5): if an executor/cache fails mid-DAG, the whole
DAG is re-executed after a configurable timeout (idempotence is the user's
concern, exactly as in AWS Lambda).  Beyond-paper: straggler speculation —
if a function runs beyond a p99-based budget, it is duplicated on a second
executor and the faster result wins.
"""

from __future__ import annotations

import dataclasses
import random
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

from .cache import CacheFailure, ExecutorCache
from .consistency import (
    AnomalyTracker,
    DagRestart,
    SessionContext,
    session_prefetch_keys,
)
from .dag import Dag
from .executor import CloudburstReference, Executor, ExecutorFailure
from .faultnet import FailurePlane, KVSUnavailableError, RetryPolicy
from .kvs import AnnaKVS
from .lattices import LamportClock, Lattice, LWWLattice, encapsulate
from .netsim import NetworkProfile, VirtualClock
from .scheduler import Scheduler, SchedulingPolicy
from ..obs import MetricsRegistry, Tracer, counter_shim
from ..obs import host as obs_host
from ..obs.trace import Span


@dataclasses.dataclass
class DagResult:
    value: Any
    latency: float  # virtual seconds, end-to-end
    schedule: Dict[str, str]
    retries: int = 0
    speculated: int = 0


# ---------------------------------------------------------------------------
# Per-request state machine
# ---------------------------------------------------------------------------

RUN_RUNNING = "running"
RUN_DONE = "done"
RUN_FAILED = "failed"


@dataclasses.dataclass
class DagRun:
    """One in-flight DAG invocation: the engine's unit of concurrency.

    Tracks the function state machine for the CURRENT attempt (functions
    whose upstreams are all complete sit in ``ready``; ``waiting`` counts
    unfinished upstreams; ``results`` holds completed outputs) plus the
    per-attempt schedule and the across-attempt restart bookkeeping
    (``attempt``, ``exclude``) of §4.5.  The virtual clock is per-run:
    concurrent runs own independent timelines, exactly like concurrent
    client requests against a real deployment.
    """

    run_id: str
    dag: Dag
    args_by_fn: Dict[str, Sequence]
    mode: str
    clock: VirtualClock
    response_key: Optional[str] = None
    t0: float = 0.0
    # host wall clock (perf_counter) at submit, and whether a trigger of
    # the run has been dispatched yet: the engine.queue / engine.run_wall
    # counters measure from here
    wall0: float = 0.0
    dispatched: bool = False
    # -- per-attempt state --------------------------------------------------
    session: Optional[SessionContext] = None
    schedule: Dict[str, str] = dataclasses.field(default_factory=dict)
    results: Dict[str, Any] = dataclasses.field(default_factory=dict)
    ready: List[str] = dataclasses.field(default_factory=list)
    waiting: Dict[str, int] = dataclasses.field(default_factory=dict)
    attempt: int = 0
    exclude: Set[str] = dataclasses.field(default_factory=set)
    speculated: int = 0
    # -- lifecycle ----------------------------------------------------------
    state: str = RUN_RUNNING
    value: Any = None
    error: Optional[BaseException] = None
    # root trace span when this run is sampled (None otherwise); opened
    # at submit on the run's virtual clock, closed at finalize so its
    # duration IS the run's reported end-to-end latency
    span: Optional[Span] = None
    # user-code exception (not infra): surfaced as-is, never retried
    user_failed: bool = False
    result: Optional[DagResult] = None

    def reset_attempt(self) -> None:
        """Seed the function state machine for a (re)started attempt."""
        self.schedule = {}
        self.results = {}
        # per-attempt, like the pre-engine executor: DagResult reports
        # only the successful attempt's speculation count
        self.speculated = 0
        self.waiting = {
            fn: len(self.dag.upstream(fn)) for fn in self.dag.functions
        }
        # sources release in topo order so single-run turns replay the
        # sequential executor's within-DAG function order exactly
        self.ready = [fn for fn in self.dag.topo_order()
                      if self.waiting[fn] == 0]

    def complete_fn(self, fn: str, result: Any) -> None:
        self.results[fn] = result
        for down in self.dag.downstream(fn):
            self.waiting[down] -= 1
            if self.waiting[down] == 0:
                self.ready.append(down)

    @property
    def finished(self) -> bool:
        return self.state != RUN_RUNNING


class CloudburstFuture:
    """Result stored in the KVS; retrieved on ``get()`` (Fig. 2 lines 11-12).

    ``call_async`` / ``call_dag_async`` return one of these immediately:
    the invocation's sink value lands at ``key`` when the run completes.
    ``get`` drives the cluster engine (``step``, falling back to ``tick``
    for background progress) while waiting; ``timeout`` (wall-clock
    seconds) bounds the wait — a failed or garbage-collected DAG whose
    response key never arrives raises :class:`TimeoutError` instead of
    busy-looping forever.
    """

    def __init__(
        self,
        key: str,
        cluster: "Cluster",
        clock: Optional[VirtualClock] = None,
        run: Optional[DagRun] = None,
    ):
        self.key = key
        self._cluster = cluster
        self._clock = clock
        self.run = run

    def done(self) -> bool:
        """Non-blocking completion probe (no engine driving, no latency)."""
        if self.run is not None:
            return self.run.finished
        # key EXISTENCE, not value: a stored None still counts as done
        try:
            return self._cluster.kvs.get_merged(self.key) is not None
        except KVSUnavailableError:
            # replicas unreachable right now: indistinguishable from
            # "not written yet" — report not-done, never raise
            return False

    def result(self) -> DagResult:
        """Full :class:`DagResult` (latency/schedule/retries); blocks via
        :meth:`get` until the run resolves."""
        if self.run is None:
            raise ValueError("future is not bound to an in-flight run")
        self.get()
        assert self.run.result is not None
        return self.run.result

    def get(self, timeout: Optional[float] = None) -> Any:
        deadline = (None if timeout is None
                    else time.monotonic() + max(0.0, timeout))
        while True:
            if self.run is not None:
                # bound future: the run's state is authoritative.  The
                # KVS key is deliberately NOT polled while the run is in
                # flight — a user-supplied ``store_in_kvs`` key may hold
                # an EARLIER invocation's value, which must not be
                # returned as this run's result (and polling would pay a
                # read-repair fetch per engine turn for nothing).
                if self.run.state == RUN_FAILED:
                    if self.run.user_failed:
                        raise self.run.error  # user-code error, as-is
                    raise RuntimeError(
                        f"DAG {self.run.dag.name} failed after "
                        f"{self.run.attempt} retries"
                    ) from self.run.error
                if self.run.state == RUN_DONE:
                    return self.run.value
            else:
                # existence probe, not value probe: a key legitimately
                # storing None must resolve to None, not spin forever
                try:
                    lat = self._cluster.kvs.get_merged(self.key,
                                                       clock=self._clock)
                except KVSUnavailableError:
                    lat = None  # unreachable == not arrived yet; keep waiting
                if lat is not None:
                    return lat.reveal()
            if deadline is not None and time.monotonic() >= deadline:
                raise TimeoutError(
                    f"result key {self.key!r} did not arrive within "
                    f"{timeout}s (failed or garbage-collected DAG?)"
                )
            if self._cluster.step() == 0:
                # engine idle: the key can only arrive via background
                # progress (an unflushed cache write-back, gossip)
                self._cluster.tick()


class Cluster:
    def __init__(
        self,
        n_vms: int = 3,
        executors_per_vm: int = 3,
        n_kvs_nodes: int = 4,
        replication: int = 2,
        mode: str = "lww",
        profile: Optional[NetworkProfile] = None,
        seed: int = 0,
        scheduler_policy: Optional[SchedulingPolicy] = None,
        dag_timeout: float = 5.0,
        max_retries: int = 3,
        straggler_speculation: bool = False,
        tick_jitter: float = 0.0,
        read_prefetch: bool = True,
        metrics: Optional[MetricsRegistry] = None,
        tracer: Optional[Tracer] = None,
    ):
        self.profile = profile or NetworkProfile(seed=seed)
        self.rng = random.Random(seed)
        self.mode = mode
        self.dag_timeout = dag_timeout
        self.max_retries = max_retries
        self.straggler_speculation = straggler_speculation
        self.tick_jitter = tick_jitter
        # DAG read-set prefetch: executors warm their cache with one
        # batched read-repair fetch of a function's reference keys before
        # user code runs (off => per-key scalar miss path, for A/B runs)
        self.read_prefetch = read_prefetch
        # one observability plane per deployment: the registry and tracer
        # are shared with the KVS tier, every cache and the scheduler
        # (env default: REPRO_TRACE / REPRO_TRACE_SAMPLE)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = (tracer if tracer is not None
                       else Tracer.from_env()).bind(self.metrics)
        obs_host.register(self.metrics)
        self.kvs = AnnaKVS(
            num_nodes=n_kvs_nodes, replication=replication,
            profile=self.profile, metrics=self.metrics, tracer=self.tracer,
        )
        self.caches: Dict[str, ExecutorCache] = {}
        self.executors: Dict[str, Executor] = {}
        self._vm_count = 0
        for _ in range(n_vms):
            self.add_vm(executors_per_vm)
        self.scheduler = Scheduler(
            "sched-0",
            self.kvs,
            self.executors,
            profile=self.profile,
            policy=scheduler_policy,
            seed=seed,
        )
        self.client_clock = LamportClock("client")
        # chaos-hardened failure plane (off by default: zero overhead).
        # Enabled via enable_failure_plane(); shared with the KVS tier.
        self.failure_plane: Optional[FailurePlane] = None
        self.tracker: Optional[AnomalyTracker] = None
        self._dag_seq = 0
        self._run_seq = 0
        self._runs: Dict[str, DagRun] = {}  # in flight, submission-ordered
        self._fn_latency_stats: Dict[str, List[float]] = {}
        # engine telemetry: read-set warm launch accounting.  Both the
        # per-request warms (single-run groups) and the cross-request
        # fused fetches count here — cross-request batching shows up as
        # FEWER batches per request, which is what the serving
        # benchmarks compare against the scalar hop count.  The counters
        # live in the shared registry; the counter_shim properties below
        # keep the legacy attribute API (``cluster.engine_turns`` etc.).
        m = self.metrics
        self._m_turns = m.counter("engine.turns")
        self._m_fused_batches = m.counter("engine.fused_prefetch_batches")
        self._m_fused_keys = m.counter("engine.fused_prefetch_keys")
        self._m_response_puts = m.counter("engine.batched_response_puts")
        self._m_submitted = m.counter("engine.runs_submitted")
        self._m_completed = m.counter("engine.runs_completed")
        self._m_failed = m.counter("engine.runs_failed")
        self._m_restarts = m.counter("engine.run_restarts")
        self._m_run_latency = m.histogram("engine.run_latency_s")
        # host wall seconds from submit to the first dispatch, and from
        # submit to finalize (completed runs)
        self._m_queue_s = m.counter("engine.queue.s")
        self._m_queue_n = m.counter("engine.queue.n")
        self._m_run_wall_s = m.counter("engine.run_wall.s")
        self._m_run_wall_n = m.counter("engine.run_wall.n")
        # cross-request model batching: waves of same-function triggers
        # dispatched through the pinned callable's ``batch_call`` hook
        self._m_batched_invokes = m.counter("engine.batched_invokes")
        self._m_batched_invoke_requests = m.counter(
            "engine.batched_invoke_requests")
        m.register_callback("engine.in_flight", lambda: len(self._runs))
        # run_id -> warm cost charged by _fused_prefetch this turn,
        # folded back into the invocation window by _invoke_trigger
        self._warm_charged: Dict[str, float] = {}

    # legacy engine counters, registry-backed (benches/tests assert on
    # these attribute names; writes pass through to the Counter objects)
    engine_turns = counter_shim("_m_turns")
    fused_prefetch_batches = counter_shim("_m_fused_batches")
    fused_prefetch_keys = counter_shim("_m_fused_keys")
    batched_response_puts = counter_shim("_m_response_puts")
    batched_invokes = counter_shim("_m_batched_invokes")
    batched_invoke_requests = counter_shim("_m_batched_invoke_requests")

    # -- failure plane ------------------------------------------------------------
    def enable_failure_plane(
        self,
        retry: Optional[RetryPolicy] = None,
        heartbeat_interval: float = 0.05,
        suspicion_multiplier: float = 3.0,
    ) -> FailurePlane:
        """Switch the deployment from oracle liveness to heartbeat-based
        failure detection, and interpose the fault network on every
        replication channel.  Idempotent.  VM endpoints heartbeat to the
        same detector as the KVS nodes, so the scheduler routes around
        suspected VMs instead of consulting ground-truth ``alive`` flags.
        """
        plane = self.kvs.enable_failure_plane(
            retry=retry,
            heartbeat_interval=heartbeat_interval,
            suspicion_multiplier=suspicion_multiplier,
        )
        self.failure_plane = plane
        for vm_id in sorted({ex.vm_id for ex in self.executors.values()}):
            self._register_vm_endpoint(vm_id)
        return plane

    def _register_vm_endpoint(self, vm_id: str) -> None:
        det = self.kvs.detector
        if det is None or vm_id in det.last_heard:
            return
        det.register(
            vm_id,
            lambda v=vm_id: any(
                ex.alive for ex in self.executors.values() if ex.vm_id == v
            ),
        )

    # -- elasticity ---------------------------------------------------------------
    def add_vm(self, executors_per_vm: int = 3) -> List[str]:
        vm_id = f"vm-{self._vm_count}"
        self._vm_count += 1
        cache = ExecutorCache(f"cache-{vm_id}", self.kvs, profile=self.profile)
        self.caches[cache.cache_id] = cache
        ids = []
        for t in range(executors_per_vm):
            eid = f"{vm_id}/exec-{t}"
            ex = Executor(eid, cache, vm_id, profile=self.profile, registry=None)
            ex.registry = {}  # filled by _refresh_registry
            self.executors[eid] = ex
            ids.append(eid)
        self._refresh_registry()
        if hasattr(self, "scheduler"):
            for eid in ids:
                self.scheduler.add_executor(self.executors[eid])
        if getattr(self, "kvs", None) is not None and self.kvs.detector is not None:
            self._register_vm_endpoint(vm_id)
        return ids

    def remove_vm(self, vm_id: str) -> None:
        for eid in [e for e, ex in self.executors.items() if ex.vm_id == vm_id]:
            self.scheduler.remove_executor(eid)
            del self.executors[eid]
        self.caches.pop(f"cache-{vm_id}", None)
        self.metrics.unregister_prefix(f"cache.cache-{vm_id}.")
        if self.kvs.detector is not None:
            self.kvs.detector.unregister(vm_id)
        self._refresh_registry()

    def _refresh_registry(self) -> None:
        registry = {eid: ex for eid, ex in self.executors.items()}
        for ex in self.executors.values():
            ex.registry = registry

    # -- client API (used by client.py) ----------------------------------------------
    def register(self, fn: Callable, name: str) -> None:
        self.scheduler.register_function(name, fn)

    def register_dag(
        self,
        name: str,
        functions: Sequence[str],
        edges: Optional[Sequence[Tuple[str, str]]] = None,
    ) -> Dag:
        dag = (
            Dag.linear(name, functions)
            if edges is None
            else Dag(name, list(functions), list(edges))
        )
        self.scheduler.register_dag(dag)
        return dag

    def _client_lattice(self, value: Any) -> Lattice:
        """Client-side LWW encapsulation, shared by the scalar put path
        and the engine's batched response flush."""
        return value if isinstance(value, Lattice) else LWWLattice(
            self.client_clock.tick(), value
        )

    def put(self, key: str, value: Any, clock: Optional[VirtualClock] = None) -> None:
        # client puts block until all replicas ack (read-your-writes for
        # the issuing client); executor cache flushes stay async
        self.kvs.put(key, self._client_lattice(value), clock=clock, sync=True)

    def get(self, key: str, clock: Optional[VirtualClock] = None) -> Any:
        lat = self.kvs.get_merged(key, clock=clock)
        return None if lat is None else lat.reveal()

    # -- futures-first invocation API (paper §3, Fig. 2) ------------------------------
    @property
    def in_flight(self) -> int:
        """Number of DAG runs currently in flight in the engine."""
        return len(self._runs)

    def call_async(
        self,
        fn_name: str,
        *args: Any,
        clock: Optional[VirtualClock] = None,
        mode: Optional[str] = None,
    ) -> CloudburstFuture:
        """Enqueue a single-function invocation; returns immediately.

        The function runs as an ephemeral one-node DAG through the engine
        (so it shares restart-on-failure, speculation and the per-turn
        batched paths); the result lands at the future's KVS key.
        """
        self._require_function(fn_name)
        key = f"__async_result_{fn_name}_{self._run_seq + 1}"
        run = self._submit(
            Dag(f"call.{fn_name}", [fn_name]), {fn_name: tuple(args)},
            clock=clock, mode=mode, response_key=key,
        )
        return CloudburstFuture(key, self, run=run)

    def call_dag_async(
        self,
        dag_name: str,
        args_by_fn: Optional[Dict[str, Sequence]] = None,
        clock: Optional[VirtualClock] = None,
        mode: Optional[str] = None,
        store_in_kvs: Optional[str] = None,
    ) -> CloudburstFuture:
        """Enqueue a DAG invocation; returns a KVS-backed future immediately.

        Many calls may be in flight at once — drive them with
        :meth:`step` (or just ``future.get()``), and the engine batches
        their scheduling, read-set prefetches and response writes per
        turn.  ``store_in_kvs`` overrides the auto-generated response key.
        """
        key = store_in_kvs or f"__dag_result_{dag_name}_{self._run_seq + 1}"
        run = self._submit(
            self.scheduler.dags[dag_name], args_by_fn,
            clock=clock, mode=mode, response_key=key,
        )
        return CloudburstFuture(key, self, run=run)

    # -- single-function call (paper §4.3 "single function execution") ----------------
    def call(
        self,
        fn_name: str,
        *args: Any,
        clock: Optional[VirtualClock] = None,
        mode: Optional[str] = None,
    ) -> Tuple[Any, float]:
        """Synchronous single-function call: submit + drive to completion."""
        self._require_function(fn_name)
        run = self._submit(
            Dag(f"call.{fn_name}", [fn_name]), {fn_name: tuple(args)},
            clock=clock, mode=mode, response_key=None,
        )
        result = self._drive(run)
        return result.value, result.latency

    # -- DAG call with restart-on-failure (paper §4.5) ---------------------------------
    def call_dag(
        self,
        dag_name: str,
        args_by_fn: Optional[Dict[str, Sequence]] = None,
        clock: Optional[VirtualClock] = None,
        mode: Optional[str] = None,
        store_in_kvs: Optional[str] = None,
    ) -> DagResult:
        """Synchronous wrapper over the engine: drive ``step()`` until the
        run resolves.  With no other runs in flight this degenerates to
        the sequential executor (one ready function per turn, same
        scheduling-rng draw order, same per-hop latency accounting)."""
        run = self._submit(
            self.scheduler.dags[dag_name], args_by_fn,
            clock=clock, mode=mode, response_key=store_in_kvs,
        )
        return self._drive(run)

    # -- engine internals ---------------------------------------------------------
    def _require_function(self, fn_name: str) -> None:
        """Fail-fast at submit time: an unregistered function must error
        in the offending call (as the pre-engine path did), never inside
        ``step()`` where it would poison the other in-flight runs'
        already-drained triggers."""
        sched = self.scheduler
        if fn_name in sched.local_functions:
            return
        if fn_name in sched.registered_functions():  # cross-client KVS set
            sched.local_functions.add(fn_name)
            return
        raise KeyError(f"function {fn_name!r} not registered")

    def _submit(
        self,
        dag: Dag,
        args_by_fn: Optional[Dict[str, Sequence]],
        clock: Optional[VirtualClock],
        mode: Optional[str],
        response_key: Optional[str],
    ) -> DagRun:
        self._run_seq += 1
        run = DagRun(
            run_id=f"run-{self._run_seq}",
            dag=dag,
            args_by_fn=dict(args_by_fn or {}),
            mode=mode or self.mode,
            clock=clock or VirtualClock(),
            response_key=response_key,
        )
        run.t0 = run.clock.now
        run.wall0 = time.perf_counter()
        if self.tracer.sample_run():
            # root span on the run's own virtual timeline: closed at
            # finalize, so duration == DagResult.latency exactly
            run.span = self.tracer.start(
                "engine", f"dag.{dag.name}", t=run.t0, clock=run.clock,
                tid=run.run_id, run_id=run.run_id,
            )
        self._m_submitted.inc()
        self._begin_attempt(run, first=True)
        self._runs[run.run_id] = run
        return run

    def _begin_attempt(self, run: DagRun, first: bool = False) -> None:
        """Start a (re)execution attempt: fresh session, client->scheduler
        hop, function state machine reset (§4.5 whole-DAG re-execution)."""
        if not first:
            run.attempt += 1
            self._m_restarts.inc()
        self._dag_seq += 1
        run.session = SessionContext(
            dag_id=f"{run.dag.name}-{self._dag_seq}", mode=run.mode
        )
        run.clock.advance(self.profile.sample(self.profile.tcp, 256))
        run.reset_attempt()

    def _drive(self, run: DagRun) -> DagResult:
        while run.state == RUN_RUNNING:
            if self.step() == 0:
                # unreachable in normal operation: invocation is
                # synchronous inside step(), so an unfinished run always
                # has ready triggers — guard against a looping caller
                raise RuntimeError(
                    f"engine stalled with run {run.run_id} unfinished")
        if run.state == RUN_FAILED:
            if run.user_failed:
                raise run.error  # pre-engine semantics: user errors as-is
            raise RuntimeError(
                f"DAG {run.dag.name} failed after {self.max_retries} retries"
            ) from run.error
        assert run.result is not None
        return run.result

    def step(self) -> int:
        """One engine turn; returns the number of triggers processed.

        1. collect every ready function across all in-flight runs;
        2. batch-schedule them (ONE ``Scheduler.schedule_ready`` call);
        3. per trigger: downstream-trigger hop + cold function pin;
        4. fuse the triggers' read-set prefetches per cache — one
           ``read_many`` (one ``get_merged_many`` launch) per cache per
           turn, every waiting run charged the same batched cost;
        5. invoke (synchronously), with per-function straggler
           speculation; failures restart their run (§4.5) without
           disturbing the other in-flight runs.  Same-function triggers
           landing on one cache whose pinned callable has a
           ``batch_call`` hook dispatch as ONE user-code call
           (cross-request model batching);
        6. finalize runs whose functions all completed — response keys
           flush as ONE batched ``put_many``.
        """
        triggers: List[Tuple[DagRun, str, Tuple[Any, ...], int]] = []
        for run in list(self._runs.values()):
            if run.state != RUN_RUNNING:
                continue
            ready, run.ready = run.ready, []
            for fn in ready:
                upstream = [run.results[u] for u in run.dag.upstream(fn)]
                args = tuple(upstream) + tuple(run.args_by_fn.get(fn, ()))
                triggers.append((run, fn, args, run.attempt))
        if not triggers:
            return 0
        self.engine_turns += 1
        tr = self.tracer
        # the turn's phases count on every turn; the engine.step span is
        # also recorded on the tracer's WALL timeline (a turn serves many
        # runs, so no single virtual clock applies) when at least one
        # sampled run participates, and is the active context so
        # cross-run infrastructure spans (batched scheduling, fused plane
        # launches) attach under it
        sampled = tr.enabled and any(r.span is not None
                                     for r, _f, _a, _t in triggers)
        with tr.phase("engine.step", record=sampled,
                      turn=self.engine_turns, n_triggers=len(triggers)):
            with tr.phase("engine.schedule"):
                plans = self._schedule(triggers)
            if self.read_prefetch:
                with tr.phase("engine.prefetch"):
                    self._fused_prefetch(plans)
            with tr.phase("engine.invoke"):
                self._invoke_wave(plans)
            with tr.phase("engine.finalize"):
                self._finalize_completed()
        return len(triggers)

    def _schedule(
        self, triggers: List[Tuple[DagRun, str, Tuple[Any, ...], int]],
    ) -> List[Tuple[DagRun, str, Tuple[Any, ...], str, int]]:
        """Pick an executor for every trigger of the wave (ONE batched
        ``schedule_ready`` call), charge the trigger hop and cold pins;
        returns the dispatch plans."""
        tr = self.tracer
        # batched scheduling: one entry point call for the whole wave.
        # If it raises (a trigger with no schedulable executor, a buggy
        # custom policy), fall back to per-trigger picks so ONLY the
        # offending runs fail — exclude sets are per-run, so one run's
        # unschedulable trigger must not kill the healthy wave.
        trigger_specs = [(fn, run.args_by_fn.get(fn, ()), run.exclude)
                         for run, fn, _args, _att in triggers]
        try:
            picks: List[Optional[str]] = list(
                self.scheduler.schedule_ready(trigger_specs))
        except Exception:
            picks = []
            for (run, fn, _args, attempt), spec in zip(triggers,
                                                       trigger_specs):
                try:
                    picks.append(self.scheduler.pick_executor(
                        spec[0], spec[1], exclude=spec[2]))
                except Exception as e:
                    picks.append(None)
                    if run.state == RUN_RUNNING and run.attempt == attempt:
                        self._fail_user(run, e)  # propagate as-is, no retry
        plans: List[Tuple[DagRun, str, Tuple[Any, ...], str, int]] = []
        for (run, fn, args, attempt), eid in zip(triggers, picks):
            if eid is None:
                continue
            run.schedule[fn] = eid
            executor = self.executors[eid]
            t_dispatch = run.clock.now
            # executor->executor trigger carries session metadata (§5.3)
            meta_bytes = run.session.metadata_bytes() + 256
            run.clock.advance(self.profile.sample(self.profile.tcp, meta_bytes))
            if not executor.has_function(fn):
                # cold executor: pull + deserialize the function from Anna
                try:
                    executor.pin_function(fn, self.scheduler.load_function(fn))
                except Exception as e:  # function vanished from the KVS
                    self._fail_user(run, e)
                    continue
                run.clock.advance(self.profile.sample(self.profile.kvs_op, 1024))
            plans.append((run, fn, args, eid, attempt))
            if not run.dispatched:
                run.dispatched = True
                self._m_queue_s.inc(time.perf_counter() - run.wall0)
                self._m_queue_n.inc()
            if run.span is not None:
                # trigger-hop + cold-pin window on the run's timeline
                tr.add_complete("scheduler", f"dispatch.{fn}", t_dispatch,
                                run.clock.now, tid=run.run_id,
                                parent=run.span, executor=eid)
        return plans

    def _invoke_wave(
        self, plans: Sequence[Tuple[DagRun, str, Tuple[Any, ...], str, int]]
    ) -> None:
        """Invoke the wave's plans.  Cross-request model batching: a
        wave's same-function triggers landing on the SAME cache (VM)
        whose pinned callable exposes ``batch_call`` dispatch as ONE
        user-code call — the continuous-batching serving path.  Batched
        groups go first, then the leftover singles in original plan
        order, so a wave with nothing batchable replays the sequential
        invocation (and rng draw) order exactly."""
        groups: Dict[Tuple[str, str], List[
            Tuple[DagRun, str, Tuple[Any, ...], str, int]]] = {}
        for plan in plans:
            _run, fn, _args, eid, _att = plan
            func = self.executors[eid].pinned.get(fn)
            if callable(getattr(func, "batch_call", None)):
                key = (fn, self.executors[eid].cache.cache_id)
                groups.setdefault(key, []).append(plan)
        batched_ids: Set[int] = set()
        for group in groups.values():
            if len(group) < 2:
                continue
            batched_ids.update(id(p) for p in group)
            self._invoke_batched(group)
        for plan in plans:
            if id(plan) in batched_ids:
                continue
            run, fn, args, eid, attempt = plan
            # skip triggers whose run restarted/failed earlier this turn
            if run.state != RUN_RUNNING or run.attempt != attempt:
                continue
            self._invoke_trigger(run, fn, args, eid)

    def _fused_prefetch(
        self, plans: Sequence[Tuple[DagRun, str, Tuple[Any, ...], str, int]]
    ) -> None:
        """Fuse the wave's read-set prefetches into one batched
        ``read_many`` per cache.

        Each function's read set is its KVS-reference args filtered by
        the session protocol (``session_prefetch_keys``: dsrr-pinned keys
        skipped).  A cache serving a single function this turn keeps the
        per-invocation warm rule (only batch when the read set has >= 2
        keys, preserving the scalar miss path's any-replica semantics);
        a cache serving SEVERAL functions fuses ALL their keys — even
        single-key read sets — into one read-repair fetch, the
        cross-request batching this engine exists for.  Every run waiting
        on the fused fetch is charged the same batched virtual cost.
        """
        by_cache: Dict[str, List[Tuple[DagRun, List[str], int]]] = {}
        for run, fn, args, eid, attempt in plans:
            keys = session_prefetch_keys(
                run.session,
                [a.key for a in args if isinstance(a, CloudburstReference)],
            )
            if not keys:
                continue
            cache_id = self.executors[eid].cache.cache_id
            by_cache.setdefault(cache_id, []).append((run, keys, attempt))
        for cache_id, group in by_cache.items():
            cache = self.caches.get(cache_id)
            if cache is None:
                continue
            # drop entries whose run failed or restarted while an
            # earlier cache group of THIS turn was processed — a dead
            # attempt must not have keys fetched or its clock charged
            group = [(run, keys, att) for run, keys, att in group
                     if run.state == RUN_RUNNING and run.attempt == att]
            if not group:
                continue
            if len({id(run) for run, _keys, _att in group}) == 1:
                # every trigger belongs to ONE run: keep the pre-engine
                # per-invocation warm rule exactly — each function's read
                # set warms on its own, and only when it has >= 2 keys
                # (the scalar miss path keeps its any-replica semantics).
                # Fusing here would change what a solo sync call_dag
                # observes; cross-REQUEST fusion below is the new power.
                for run, keys, attempt in group:
                    if (len(keys) < 2 or run.state != RUN_RUNNING
                            or run.attempt != attempt):
                        continue
                    t_warm = run.clock.now
                    try:
                        # parent the cache/KVS spans under the owning
                        # run (no-op for unsampled runs)
                        with self.tracer.use(run.span):
                            cache.read_many(keys, clocks=[run.clock])
                        self.fused_prefetch_batches += 1
                        self.fused_prefetch_keys += len(keys)
                        self._warm_charged[run.run_id] = (
                            self._warm_charged.get(run.run_id, 0.0)
                            + run.clock.now - t_warm)
                    except (CacheFailure, KVSUnavailableError) as e:
                        self._fail_attempt(run, e)
                continue
            fused = list(dict.fromkeys(
                k for _run, keys, _att in group for k in keys))
            # dedup by CLOCK identity, not run identity: two runs
            # sharing one VirtualClock (public ``clock=`` parameter)
            # must be charged the batched cost once, not twice
            seen: Dict[int, VirtualClock] = {}
            for run, _keys, _att in group:
                seen.setdefault(id(run.clock), run.clock)
            clocks = list(seen.values())
            t_warms = {run.run_id: run.clock.now for run, _k, _a in group}
            try:
                cache.read_many(fused, clocks=clocks)
                self.fused_prefetch_batches += 1
                self.fused_prefetch_keys += len(fused)
                for run, _keys, _att in group:
                    self._warm_charged[run.run_id] = (
                        self._warm_charged.get(run.run_id, 0.0)
                        + run.clock.now - t_warms[run.run_id])
            except (CacheFailure, KVSUnavailableError) as e:
                # fail only runs still on the attempt that planned this
                # fetch: a run already restarted by an earlier group this
                # turn must not burn a second retry for the same turn
                for run, _keys, attempt in group:
                    if run.state == RUN_RUNNING and run.attempt == attempt:
                        self._fail_attempt(run, e)

    def _invoke_batched(
        self,
        group: Sequence[Tuple[DagRun, str, Tuple[Any, ...], str, int]],
    ) -> None:
        """Dispatch a wave's same-function, same-cache triggers as ONE
        user-code call through the pinned callable's ``batch_call``.

        Each trigger still gets its own session protocol / user library
        / reference resolution (``Executor.resolve_invocation``) and its
        own clock and metric accounting; only the model call itself is
        shared.  The group's wall time, scaled by each executor's
        ``slow_factor``, is charged to every participating run — the
        batch runs once for everyone.  A user-code exception fails every
        run in the group (the batch was one call); infra failures during
        resolution fail only the affected run.  Straggler speculation is
        skipped: duplicating a batch would re-run the whole group.
        """
        live = [p for p in group
                if p[0].state == RUN_RUNNING and p[0].attempt == p[4]]
        if not live:
            return
        if len(live) == 1:
            run, fn, args, eid, _att = live[0]
            self._invoke_trigger(run, fn, args, eid)
            return
        fn = live[0][1]
        func = self.executors[live[0][3]].pinned.get(fn)
        tr = self.tracer
        entries: List[Tuple[DagRun, Executor, Any, List[Any], float, Any]] = []
        for run, _fn, args, eid, _att in live:
            executor = self.executors[eid]
            # fold the fused-prefetch warm back into the invocation
            # window, exactly like _invoke_trigger
            warm = self._warm_charged.pop(run.run_id, 0.0)
            t_before = run.clock.now - warm
            inv_span = None
            if run.span is not None:
                inv_span = tr.start(
                    "engine", f"invoke.{fn}", t=t_before, clock=run.clock,
                    tid=run.run_id, parent=run.span, executor=eid,
                    deps=list(run.dag.upstream(fn)), batched=True,
                )
            try:
                with tr.use(inv_span):
                    userlib, resolved = executor.resolve_invocation(
                        fn, args, run.session, self.caches, clock=run.clock,
                        tracker=self.tracker, prefetch=False,
                    )
            except (DagRestart, ExecutorFailure, CacheFailure,
                    KVSUnavailableError) as e:
                if inv_span is not None:
                    tr.finish(inv_span, error=type(e).__name__)
                self._fail_attempt(run, e)
                continue
            except Exception as e:
                if inv_span is not None:
                    tr.finish(inv_span, error=type(e).__name__)
                self._fail_user(run, e)
                continue
            entries.append((run, executor, userlib, resolved, t_before,
                            inv_span))
        if not entries:
            return
        t0 = time.perf_counter()
        try:
            results = func.batch_call(
                [e[2] for e in entries], [tuple(e[3]) for e in entries])
            if len(results) != len(entries):
                raise ValueError(
                    f"batch_call for {fn!r} returned {len(results)} results "
                    f"for {len(entries)} invocations")
        except (DagRestart, ExecutorFailure, CacheFailure,
                KVSUnavailableError) as e:
            for run, _ex, _ul, _res, _tb, inv_span in entries:
                if inv_span is not None:
                    tr.finish(inv_span, error=type(e).__name__)
                if run.state == RUN_RUNNING:
                    self._fail_attempt(run, e)
            return
        except Exception as e:
            # user-code error: the batch was ONE call, so every
            # participating run fails with the original exception
            for run, _ex, _ul, _res, _tb, inv_span in entries:
                if inv_span is not None:
                    tr.finish(inv_span, error=type(e).__name__)
                if run.state == RUN_RUNNING:
                    self._fail_user(run, e)
            return
        wall = time.perf_counter() - t0
        self._m_batched_invokes.inc()
        self._m_batched_invoke_requests.inc(len(entries))
        for (run, executor, _ul, _res, t_before, inv_span), result in zip(
                entries, results):
            elapsed = wall * executor.slow_factor
            run.clock.advance(elapsed)
            executor.record_invocation(elapsed)
            if inv_span is not None:
                tr.finish(inv_span)
            self._record_latency(fn, run.clock.now - t_before)
            run.complete_fn(fn, result)

    def _invoke_trigger(
        self, run: DagRun, fn: str, args: Tuple[Any, ...], eid: str
    ) -> None:
        executor = self.executors[eid]
        tr = self.tracer
        # the pre-engine executor charged the read-set warm INSIDE the
        # invocation window (invoke ran warm_read_set itself); the
        # engine warmed earlier in the turn, so fold that cost back in —
        # straggler stats and the speculation trigger stay equivalent
        warm = self._warm_charged.pop(run.run_id, 0.0)
        t_before = run.clock.now - warm
        inv_span = None
        if run.span is not None:
            # DAG-topology edges ride the span: ``deps`` names the
            # upstream functions whose invoke spans feed this one
            inv_span = tr.start(
                "engine", f"invoke.{fn}", t=t_before, clock=run.clock,
                tid=run.run_id, parent=run.span, executor=eid,
                deps=list(run.dag.upstream(fn)),
            )
        try:
            # prefetch=False: the engine already fused this trigger's
            # read-set warm into the per-cache batch (or skipped it,
            # exactly as the per-invocation warm rule would)
            with tr.use(inv_span):
                result = executor.invoke(
                    fn, args, run.session, self.caches, clock=run.clock,
                    tracker=self.tracker, prefetch=False,
                )
        except (DagRestart, ExecutorFailure, CacheFailure,
                KVSUnavailableError) as e:
            if inv_span is not None:
                tr.finish(inv_span, error=type(e).__name__)
            self._fail_attempt(run, e)
            return
        except Exception as e:
            # user-code error: deterministic, so no §4.5 retry — fail
            # THIS run and surface the original exception through its
            # future / sync wrapper.  It must not escape step(): the
            # other in-flight runs' triggers still need invoking.
            if inv_span is not None:
                tr.finish(inv_span, error=type(e).__name__)
            self._fail_user(run, e)
            return
        elapsed = run.clock.now - t_before
        budget = self._straggler_budget(fn)
        if (
            self.straggler_speculation
            and budget is not None
            and elapsed > budget
        ):
            # speculative re-execution on another executor; faster wins.
            # A failure here is contained exactly like a primary-invoke
            # failure: §4.5 whole-DAG restart, not an escaped exception
            # that would abort the other in-flight runs' drive.
            alt = self._pick_alternate(fn, eid)
            if alt is not None:
                spec_clock = VirtualClock(t_before)
                try:
                    alt_result = alt.invoke(
                        fn, args, run.session, self.caches, clock=spec_clock,
                        tracker=self.tracker, prefetch=self.read_prefetch,
                    )
                except (DagRestart, ExecutorFailure, CacheFailure,
                        KVSUnavailableError) as e:
                    if inv_span is not None:
                        tr.finish(inv_span, error=type(e).__name__)
                    self._fail_attempt(run, e)
                    return
                except Exception as e:
                    # user-code error on the speculative copy (§4.5:
                    # idempotence is the user's concern): fail this run
                    # as-is, exactly like the primary-invoke path
                    if inv_span is not None:
                        tr.finish(inv_span, error=type(e).__name__)
                    self._fail_user(run, e)
                    return
                run.speculated += 1
                if spec_clock.now < run.clock.now:
                    run.clock.now = spec_clock.now
                    result = alt_result
        if inv_span is not None:
            # closed AFTER a possible speculation fold-back, so the span
            # covers exactly the latency the run was charged
            tr.finish(inv_span)
        self._record_latency(fn, elapsed)
        run.complete_fn(fn, result)

    def _fail_user(self, run: DagRun, err: BaseException) -> None:
        """User-visible, non-retryable failure (user-code error, missing
        function, unschedulable trigger): surfaced as-is through the
        run's future / sync wrapper; never disturbs other runs."""
        run.error = err
        run.user_failed = True
        run.state = RUN_FAILED
        self._m_failed.inc()
        if run.span is not None:
            self.tracer.finish(run.span, t=run.clock.now, status="failed")
            run.span = None
        self._runs.pop(run.run_id, None)
        self._warm_charged.pop(run.run_id, None)

    def _fail_attempt(self, run: DagRun, err: BaseException) -> None:
        """§4.5: configurable timeout, then whole-DAG re-execution on a
        schedule excluding the executors observed dead — or permanent
        failure once the retry budget is spent."""
        run.error = err
        self._warm_charged.pop(run.run_id, None)
        run.clock.advance(self.dag_timeout)
        run.exclude |= {
            eid
            for eid in run.schedule.values()
            if eid not in self.executors or not self.executors[eid].alive
        }
        det = self.kvs.detector
        if det is not None:
            # an attempt failure is an OBSERVED timeout on the executors
            # it was scheduled on — feed the dead ones to the failure
            # detector so subsequent scheduling routes around their VM
            # without waiting for the heartbeat sweep
            for eid in set(run.schedule.values()):
                ex = self.executors.get(eid)
                if ex is not None and not ex.alive and ex.vm_id in det.last_heard:
                    det.report_timeout(ex.vm_id)
        if run.attempt >= self.max_retries:
            run.state = RUN_FAILED
            self._m_failed.inc()
            if run.span is not None:
                self.tracer.finish(run.span, t=run.clock.now,
                                   status="failed")
                run.span = None
            self._runs.pop(run.run_id, None)
        else:
            self._begin_attempt(run)

    def _finalize_completed(self) -> None:
        """Complete runs whose every function produced a result.

        The sink value is computed per run; response-key writes for ALL
        runs completing this turn land as ONE batched ``kvs.put_many``
        (sync: futures read the key immediately via read-repair), each
        run charged its own payload's virtual put cost.  A single
        completion keeps the scalar client-put path bit-for-bit."""
        completed = [
            run for run in self._runs.values()
            if run.state == RUN_RUNNING
            and len(run.results) == len(run.dag.functions)
        ]
        if not completed:
            return
        responses: List[Tuple[DagRun, Lattice]] = []
        unfinalized: set = set()
        for run in completed:
            sinks = run.dag.sinks()
            run.value = (
                run.results[sinks[0]] if len(sinks) == 1
                else [run.results[s] for s in sinks]
            )
            if run.response_key is not None:
                if len(completed) == 1:
                    t_resp = run.clock.now
                    try:
                        self.put(run.response_key, run.value, clock=run.clock)
                    except KVSUnavailableError as e:
                        # response replicas unreachable: the attempt is not
                        # acked — retry the whole DAG (§4.5 idempotence
                        # makes the re-put safe)
                        self._fail_attempt(run, e)
                        unfinalized.add(run.run_id)
                        continue
                    if run.span is not None:
                        self.tracer.add_complete(
                            "kvs", "response_put", t_resp, run.clock.now,
                            tid=run.run_id, parent=run.span)
                else:
                    responses.append((run, self._client_lattice(run.value)))
        if responses:
            try:
                self.kvs.put_many(
                    [(run.response_key, lat) for run, lat in responses],
                    clock=None, sync=True,
                )
            except KVSUnavailableError as e:
                # some response key had no reachable replica; puts before
                # the failing key may have landed, but restarting every
                # run in the batch is safe (re-puts merge idempotently)
                for run, _lat in responses:
                    if run.state == RUN_RUNNING:
                        self._fail_attempt(run, e)
                    unfinalized.add(run.run_id)
                responses = []
            else:
                self.batched_response_puts += 1
                for run, lat in responses:
                    t_resp = run.clock.now
                    run.clock.advance(
                        self.profile.sample(self.profile.kvs_op,
                                            lat.byte_size()))
                    if run.span is not None:
                        self.tracer.add_complete(
                            "kvs", "response_put", t_resp, run.clock.now,
                            tid=run.run_id, parent=run.span, batched=True)
        for run in completed:
            if run.run_id in unfinalized:
                continue
            run.clock.advance(self.profile.sample(self.profile.tcp, 256))
            if self.tracker is not None:
                self.tracker.finish_dag(run.session.dag_id)
            self._evict_snapshots(run.session)
            run.state = RUN_DONE
            run.result = DagResult(
                run.value, run.clock.now - run.t0, dict(run.schedule),
                retries=run.attempt, speculated=run.speculated,
            )
            self._m_completed.inc()
            self._m_run_latency.observe(run.result.latency)
            self._m_run_wall_s.inc(time.perf_counter() - run.wall0)
            self._m_run_wall_n.inc()
            if run.span is not None:
                # root closes at the SAME virtual instant the latency is
                # computed from: span.duration == DagResult.latency
                self.tracer.finish(run.span, t=run.clock.now, status="done",
                                   retries=run.attempt)
                run.span = None
            self._runs.pop(run.run_id, None)

    def _evict_snapshots(self, session: SessionContext) -> None:
        for cache in self.caches.values():
            cache.evict_dag(session.dag_id)

    # -- straggler mitigation helpers -----------------------------------------------
    def _record_latency(self, fn_name: str, seconds: float) -> None:
        hist = self._fn_latency_stats.setdefault(fn_name, [])
        hist.append(seconds)
        if len(hist) > 512:
            del hist[:256]

    def _straggler_budget(self, fn_name: str) -> Optional[float]:
        hist = self._fn_latency_stats.get(fn_name)
        if not hist or len(hist) < 16:
            return None
        s = sorted(hist)
        p99 = s[min(len(s) - 1, int(0.99 * len(s)))]
        return max(p99 * 2.0, 1e-4)

    def _vm_trusted(self, vm_id: str) -> bool:
        det = self.kvs.detector
        return det is None or det.trusts(vm_id)

    def _pick_alternate(self, fn_name: str, exclude: str) -> Optional[Executor]:
        cands = [
            self.executors[e]
            for e in self.scheduler.function_locations.get(fn_name, [])
            if e != exclude and self.executors[e].alive
            and self._vm_trusted(self.executors[e].vm_id)
        ]
        if not cands:
            cands = [
                ex
                for eid, ex in self.executors.items()
                if eid != exclude and ex.alive and self._vm_trusted(ex.vm_id)
            ]
            for ex in cands:
                if not ex.has_function(fn_name):
                    ex.pin_function(fn_name, self.scheduler.load_function(fn_name))
        return self.rng.choice(cands) if cands else None

    # -- observability (§4.4 substrate) ------------------------------------------------
    def telemetry(self) -> Dict[str, Any]:
        """One consistent snapshot of the deployment's registry: engine
        counters + run-latency quantiles, per-cache hit/miss, per-node
        KVS traffic, and the plane/transfer telemetry (pulled lazily
        from the arenas)."""
        return self.metrics.snapshot()

    def reset_telemetry(self) -> None:
        """Zero counters/histograms and the tier's transfer stats so
        benches/tests can window measurements on a live deployment."""
        self.metrics.reset()
        self.kvs.reset_transfer_stats()

    def publish_telemetry(self, now: Optional[float] = None,
                          window: float = 1.0,
                          pending_boots: int = 0) -> None:
        """Publish the registry snapshot through the KVS as the
        ``__metrics_*`` keys the §4.4 monitoring engine consumes.

        ``MonitoringEngine.decide()`` reads ONLY these keys: utilization
        and cache hit rate directly, arrival/completion rates derived
        from the cumulative counters between successive publishes.
        ``now`` names the publishing timeline (a driving harness's
        virtual time); defaults to the tracer's wall clock.
        """
        if now is None:
            now = self.tracer.wall()
        utils = [ex.utilization(window) for ex in self.executors.values()]
        snap = self.metrics.snapshot()
        hits = sum(v for k, v in snap.items()
                   if k.startswith("cache.") and k.endswith(".hits"))
        misses = sum(v for k, v in snap.items()
                     if k.startswith("cache.") and k.endswith(".misses")
                     and not k.endswith(".batched_misses"))
        values = {
            "time": now,
            "avg_util": sum(utils) / len(utils) if utils else 0.0,
            "arrivals": snap.get("engine.runs_submitted", 0),
            "completions": snap.get("engine.runs_completed", 0),
            "in_flight": snap.get("engine.in_flight", 0),
            "pending_boots": pending_boots,
            "cache_hit_rate": hits / (hits + misses) if hits + misses else 0.0,
            "run_latency_p50": snap.get("engine.run_latency_s.p50", 0.0),
            "run_latency_p99": snap.get("engine.run_latency_s.p99", 0.0),
        }
        for key, value in values.items():
            self.kvs.put(f"__metrics_{key}", self._client_lattice(value),
                         sync=True)

    # -- background work ("periodically" in the paper) -------------------------------
    def tick(self, defer_prob: Optional[float] = None) -> None:
        # replica gossip delivers first: writes flushed in THIS tick reach
        # the other replicas only on the NEXT tick (async replication lag);
        # with tick_jitter > 0 individual items defer randomly, modeling
        # continuous out-of-order background propagation (legal because
        # merges are ACI) — the staleness skew behind Table 2's anomalies.
        # With many DAGs in flight, one cache flush carries ALL their
        # pending write-backs in one put_many / PlaneBatch.
        p = self.tick_jitter if defer_prob is None else defer_prob
        tr = self.tracer
        with tr.phase("engine.tick"):
            with tr.phase("kvs.gossip"):
                self.kvs.tick(p)
            for cache in self.caches.values():
                with tr.phase("cache.flush"):
                    cache.tick(defer_prob=p)
            for cache in self.caches.values():
                with tr.phase("sched.keyset"):
                    cache.publish_keyset()
            with tr.phase("sched.index"):
                self.scheduler.refresh_index()

    # -- fault injection -----------------------------------------------------------------
    def fail_vm(self, vm_id: str) -> None:
        for ex in self.executors.values():
            if ex.vm_id == vm_id:
                ex.alive = False
        cache = self.caches.get(f"cache-{vm_id}")
        if cache is not None:
            cache.fail()

    def recover_vm(self, vm_id: str,
                   warm_keys: Optional[Sequence[str]] = None) -> None:
        """Bring a VM back: recover its cache and executors; with
        ``warm_keys`` the fresh (empty) cache is refilled through the
        bulk plane path (``ExecutorCache.warm_plane`` — one packed
        fetch, ``planecp.warm`` on the obs plane) instead of faulting
        keys back one miss at a time."""
        cache = self.caches.get(f"cache-{vm_id}")
        if cache is not None:
            cache.recover()
            if warm_keys:
                cache.warm_plane(warm_keys)
        for ex in self.executors.values():
            if ex.vm_id == vm_id:
                ex.alive = True
