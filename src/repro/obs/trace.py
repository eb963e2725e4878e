"""Per-DAG-run span tracing over the engine's virtual clocks.

A :class:`Span` is one timed region on one timeline.  Per-run spans
(the DAG root, per-function dispatch/invoke, response puts) carry the
run's :class:`~repro.core.netsim.VirtualClock`, so latency attribution
matches the netsim cost model exactly: the root span's duration IS the
run's reported end-to-end latency, and each child covers precisely the
clock advances charged inside it.  Cross-run spans (engine turns,
batched scheduler calls, fused plane launches serving several runs)
have no single virtual timeline and record on the tracer's wall clock
instead; every span says which timeline it is on via ``tid``.

Recording discipline — built for near-zero disabled cost on the hot
planes:

* the tracer is **off** unless enabled (``REPRO_TRACE=1`` or an
  explicit :class:`Tracer`); a disabled tracer's :meth:`span` is one
  attribute check returning a shared no-op context manager;
* runs are **sampled** (``REPRO_TRACE_SAMPLE``, default 1.0) with a
  deterministic every-Nth rule, so tests can predict exactly which runs
  trace;
* instrumented *infrastructure* calls (cache reads, KVS plane launches,
  scheduler waves) record only when a traced context is active
  (``tracer.cur``), so unsampled traffic never allocates a span.

Export: :meth:`Tracer.export_jsonl` (one span per line) and
:meth:`Tracer.export_chrome` (Chrome ``trace_event`` JSON — load the
file in chrome://tracing or https://ui.perfetto.dev; each ``tid`` row
is one timeline: the engine's wall track plus one track per traced
run).

Phases (:meth:`Tracer.phase`) are the always-on wall-clock spans at
the layer boundaries (``engine.step``, ``engine.tick``, ``kvs.route``,
...).  Each adds its host seconds and a count to the registry counters
``<name>.s`` / ``<name>.n``, and while a ``jax.profiler`` session is
capturing it records a ``cb.<name>`` annotation into the same trace as
the device ops, on the same clock.  Phases nest strictly, so a layer's
self time is its phase minus its children's.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

from .metrics import MetricsRegistry

__all__ = ["NULL_TRACER", "Span", "Tracer", "profiler_annotation"]

_ANNOTATION: Any = None  # jax.profiler.TraceAnnotation; False sans jax


def profiler_annotation():
    """``jax.profiler.TraceAnnotation``, or None where JAX is absent
    (imported on first use, so ``repro.obs`` imports without JAX).  Its
    ``is_enabled()`` says whether a profiler session is capturing."""
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = False
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION or None


class Span:
    """One timed region: half-open until :meth:`Tracer.finish` stamps
    ``t1``.  ``parent`` is the structural parent span id (nesting);
    DAG-topology edges ride ``attrs`` (the invoke spans carry a
    ``deps`` list naming their upstream functions)."""

    __slots__ = ("sid", "parent", "cat", "name", "tid", "t0", "t1",
                 "clock", "attrs")

    def __init__(self, sid: int, parent: Optional[int], cat: str, name: str,
                 tid: str, t0: float, clock=None,
                 attrs: Optional[Dict[str, Any]] = None):
        self.sid = sid
        self.parent = parent
        self.cat = cat
        self.name = name
        self.tid = tid
        self.t0 = t0
        self.t1: Optional[float] = None
        self.clock = clock
        self.attrs = attrs or {}

    @property
    def duration(self) -> float:
        return (self.t1 - self.t0) if self.t1 is not None else 0.0

    def to_dict(self) -> Dict[str, Any]:
        return {
            "sid": self.sid,
            "parent": self.parent,
            "cat": self.cat,
            "name": self.name,
            "tid": self.tid,
            "t0": self.t0,
            "t1": self.t1,
            "dur": self.duration,
            "attrs": self.attrs,
        }


class _NoopCM:
    """Shared do-nothing context manager: the disabled/unsampled path."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _NoopCM()


class _SpanCM:
    __slots__ = ("tr", "span", "prev")

    def __init__(self, tr: "Tracer", span: Span):
        self.tr = tr
        self.span = span
        self.prev = None

    def __enter__(self) -> Span:
        self.prev = self.tr.cur
        self.tr.cur = self.span
        return self.span

    def __exit__(self, *exc):
        self.tr.finish(self.span)
        self.tr.cur = self.prev
        return False


class _UseCM:
    """Set ``tracer.cur`` to an already-open span for a region (no
    open/close): how the engine parents infrastructure spans under the
    right run/turn."""

    __slots__ = ("tr", "span", "prev")

    def __init__(self, tr: "Tracer", span: Span):
        self.tr = tr
        self.span = span
        self.prev = None

    def __enter__(self) -> Span:
        self.prev = self.tr.cur
        self.tr.cur = self.span
        return self.span

    def __exit__(self, *exc):
        self.tr.cur = self.prev
        return False


class _Phase:
    """A named phase's registry counters and profiler label."""

    __slots__ = ("name", "cat", "label", "s", "n")

    def __init__(self, name: str, metrics: MetricsRegistry):
        self.name = name
        self.cat = name.split(".", 1)[0]
        self.label = f"cb.{name}"
        self.s = metrics.counter(f"{name}.s")
        self.n = metrics.counter(f"{name}.n")


class _PhaseCM:
    __slots__ = ("tr", "ph", "record", "attrs", "t0", "ann", "span",
                 "prev")

    def __init__(self, tr: "Tracer", ph: _Phase, record: bool,
                 attrs: Dict[str, Any]):
        self.tr = tr
        self.ph = ph
        self.record = record
        self.attrs = attrs
        self.ann = None
        self.span: Optional[Span] = None

    def __enter__(self) -> Optional[Span]:
        ann = profiler_annotation()
        if ann is not None and ann.is_enabled():
            self.ann = ann(self.ph.label)
            self.ann.__enter__()
        if self.record:
            tr = self.tr
            self.prev = tr.cur
            self.span = tr.start(self.ph.cat, self.ph.name, tid="engine",
                                 parent=tr.cur, **self.attrs)
            tr.cur = self.span
        self.t0 = time.perf_counter()
        return self.span

    def __exit__(self, *exc):
        ph = self.ph
        ph.s.value += time.perf_counter() - self.t0
        ph.n.value += 1
        if self.span is not None:
            self.tr.finish(self.span)
            self.tr.cur = self.prev
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        return False


class Tracer:
    """Span recorder; one per deployment (the cluster shares it with
    the KVS, the scheduler and every cache)."""

    def __init__(self, enabled: bool = False, sample: float = 1.0,
                 max_spans: int = 200_000):
        self.enabled = bool(enabled)
        self.sample = float(sample)
        # deterministic every-Nth run sampling (test-predictable; no
        # rng draws that could perturb the engine's seeded streams)
        self._every = max(1, int(round(1.0 / self.sample))) \
            if self.sample > 0 else 0
        self._seq = 0
        self._next_sid = 0
        self.spans: List[Span] = []
        self.dropped = 0
        self.max_spans = max_spans
        # active traced context: infrastructure spans attach here (and
        # record nothing when it is None)
        self.cur: Optional[Span] = None
        self._t0_wall = time.perf_counter()
        # where phases count: the deployment's registry once bound
        self.metrics = MetricsRegistry()
        self._phases: Dict[str, _Phase] = {}

    def bind(self, metrics: MetricsRegistry) -> "Tracer":
        """Count phases into ``metrics``, the deployment's shared
        registry."""
        if metrics is not self.metrics:
            self.metrics = metrics
            self._phases = {}
        return self

    @classmethod
    def from_env(cls) -> "Tracer":
        """``REPRO_TRACE=1`` enables; ``REPRO_TRACE_SAMPLE`` sets the
        run sampling rate (default 1.0 — trace every run)."""
        enabled = os.environ.get("REPRO_TRACE", "0") not in ("", "0")
        sample = float(os.environ.get("REPRO_TRACE_SAMPLE", "1.0"))
        return cls(enabled=enabled, sample=sample)

    # -- timelines ---------------------------------------------------------
    def wall(self) -> float:
        """The tracer's wall timeline (seconds since construction) —
        used by cross-run spans that have no single virtual clock."""
        return time.perf_counter() - self._t0_wall

    def sample_run(self) -> bool:
        """Deterministic per-run sampling decision (every Nth run)."""
        if not self.enabled or self._every == 0:
            return False
        self._seq += 1
        return (self._seq - 1) % self._every == 0

    # -- recording ---------------------------------------------------------
    def start(self, cat: str, name: str, t: Optional[float] = None,
              clock=None, tid: str = "main", parent: Optional[Span] = None,
              **attrs: Any) -> Span:
        """Open a span explicitly (closed later via :meth:`finish`)."""
        if t is None:
            t = clock.now if clock is not None else self.wall()
        self._next_sid += 1
        return Span(self._next_sid, parent.sid if parent else None,
                    cat, name, tid, t, clock=clock, attrs=attrs)

    def finish(self, span: Span, t: Optional[float] = None,
               **attrs: Any) -> None:
        if t is None:
            t = span.clock.now if span.clock is not None else self.wall()
        span.t1 = t
        if attrs:
            span.attrs.update(attrs)
        self._record(span)

    def add_complete(self, cat: str, name: str, t0: float, t1: float,
                     tid: str, parent: Optional[Span] = None,
                     **attrs: Any) -> None:
        """Record an already-timed region in one call (the engine's
        per-trigger dispatch / response-put windows)."""
        self._next_sid += 1
        span = Span(self._next_sid, parent.sid if parent else None,
                    cat, name, tid, t0, attrs=attrs)
        span.t1 = t1
        self._record(span)

    def span(self, cat: str, name: str, clock=None, tid: Optional[str] = None,
             **attrs: Any):
        """Context manager for an *infrastructure* span: records only
        under an active traced context (``self.cur``), as a child of it,
        inheriting its timeline unless ``clock``/``tid`` say otherwise.
        Disabled or unsampled traffic gets the shared no-op manager —
        near-zero cost on the hot planes."""
        cur = self.cur
        if not self.enabled or cur is None:
            return _NOOP
        if clock is None:
            clock = cur.clock
        if tid is None:
            tid = cur.tid
        sp = self.start(cat, name, clock=clock, tid=tid, parent=cur, **attrs)
        return _SpanCM(self, sp)

    def phase(self, name: str, record: Optional[bool] = None,
              **attrs: Any) -> _PhaseCM:
        """Context manager for one always-on wall-clock phase: adds its
        host seconds and a count to ``<name>.s`` / ``<name>.n`` in the
        bound registry and opens a ``cb.<name>`` profiler annotation
        while a profiler session captures.  It also records a
        :class:`Span` on the ``"engine"`` wall timeline, as the active
        context for the spans inside it, when the tracer is enabled and
        ``record`` says so (default: a traced context on the wall
        timeline is active, so per-run virtual timelines stay pure)."""
        ph = self._phases.get(name)
        if ph is None:
            ph = self._phases[name] = _Phase(name, self.metrics)
        if record is None:
            record = self.cur is not None and self.cur.clock is None
        return _PhaseCM(self, ph, self.enabled and record, attrs)

    def use(self, span: Optional[Span]):
        """Parent subsequent infrastructure spans under ``span`` for the
        region (no open/close of ``span`` itself)."""
        if span is None:
            return _NOOP
        return _UseCM(self, span)

    def _record(self, span: Span) -> None:
        if len(self.spans) >= self.max_spans:
            self.dropped += 1
            return
        self.spans.append(span)

    def clear(self) -> None:
        self.spans = []
        self.dropped = 0

    # -- export ------------------------------------------------------------
    def export_jsonl(self, path: Optional[str] = None) -> str:
        """One JSON object per span, submission order."""
        text = "\n".join(json.dumps(s.to_dict()) for s in self.spans)
        if text:
            text += "\n"
        if path is not None:
            with open(path, "w") as f:
                f.write(text)
        return text

    def export_chrome(self, path: Optional[str] = None) -> Dict[str, Any]:
        """Chrome ``trace_event`` format for chrome://tracing.

        Each span becomes one complete ("ph": "X") event; timelines map
        to integer ``tid`` rows with thread-name metadata so the runs
        render as labeled tracks.  Timestamps are microseconds (virtual
        for per-run tracks, wall for the engine track).
        """
        tids: Dict[str, int] = {}
        events: List[Dict[str, Any]] = []
        for s in self.spans:
            tid = tids.setdefault(s.tid, len(tids) + 1)
            t1 = s.t1 if s.t1 is not None else s.t0
            events.append({
                "ph": "X",
                "pid": 1,
                "tid": tid,
                "cat": s.cat,
                "name": s.name,
                "ts": s.t0 * 1e6,
                "dur": max(t1 - s.t0, 0.0) * 1e6,
                "args": dict(s.attrs, sid=s.sid, parent=s.parent),
            })
        meta = [
            {"ph": "M", "pid": 1, "tid": tid, "name": "thread_name",
             "args": {"name": name}}
            for name, tid in tids.items()
        ]
        doc = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc


#: Shared always-disabled tracer: the default for components constructed
#: outside a Cluster (standalone AnnaKVS in unit tests).  Never enable
#: it — build a real Tracer instead.
NULL_TRACER = Tracer(enabled=False)
