"""Process-wide host signals: Python's collector pauses and JAX's compiles.

Both stop the engine thread without any layer of the program asking for
it, so no phase can time them.  One ``gc.callbacks`` hook and one
``jax.monitoring`` listener per process count them into plain module
attributes; :func:`register` exposes those to a deployment's registry
as lazily read callback gauges (live views: a registry ``reset()``
leaves them alone, readers take window deltas):

* ``host.gc.pause_s``, ``host.gc.gen2.pause_s`` — seconds spent in
  collections (all generations; full collections only);
* ``host.gc.collections.gen0`` / ``gen1`` / ``gen2`` — collections run;
* ``host.jit.compiles``, ``host.jit.compile_s`` — backend compiles
  (``/jax/core/compile/backend_compile_duration``) and their seconds.

While a ``jax.profiler`` session captures, each collection also records
a ``cb.host.gc.gen<N>`` annotation, so its pause shows in the trace on
the device ops' clock.  :func:`install` is idempotent: building many
clusters leaves one hook in ``gc.callbacks``.
"""

from __future__ import annotations

import gc
import time
from typing import Dict

from .metrics import MetricsRegistry
from .trace import profiler_annotation

__all__ = ["COUNTS", "install", "register", "snapshot"]

_JIT_EVENT = "/jax/core/compile/backend_compile_duration"
_GC_LABELS = tuple(f"cb.host.gc.gen{g}" for g in range(3))


class _Counts:
    __slots__ = ("gc_pause_s", "gc_gen2_pause_s", "gc_collections",
                 "jit_compiles", "jit_compile_s")

    def __init__(self) -> None:
        self.gc_pause_s = 0.0
        self.gc_gen2_pause_s = 0.0
        self.gc_collections = [0, 0, 0]
        self.jit_compiles = 0
        self.jit_compile_s = 0.0


COUNTS = _Counts()
_gc_t0 = 0.0
_gc_ann = None
_jit_listening = False


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    global _gc_t0, _gc_ann
    gen = info["generation"]
    if phase == "start":
        ann = profiler_annotation()
        if ann is not None and ann.is_enabled():
            _gc_ann = ann(_GC_LABELS[gen])
            _gc_ann.__enter__()
        _gc_t0 = time.perf_counter()
        return
    pause = time.perf_counter() - _gc_t0
    COUNTS.gc_pause_s += pause
    COUNTS.gc_collections[gen] += 1
    if gen == 2:
        COUNTS.gc_gen2_pause_s += pause
    if _gc_ann is not None:
        _gc_ann.__exit__(None, None, None)
        _gc_ann = None


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == _JIT_EVENT:
        COUNTS.jit_compiles += 1
        COUNTS.jit_compile_s += duration


def install() -> None:
    """Hook the collector and JAX's compile events, once per process
    (compiles are counted only where JAX imports)."""
    global _jit_listening
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    if not _jit_listening:
        try:
            import jax.monitoring
        except ImportError:
            return
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _jit_listening = True


_READERS = {
    "host.gc.pause_s": lambda: COUNTS.gc_pause_s,
    "host.gc.gen2.pause_s": lambda: COUNTS.gc_gen2_pause_s,
    "host.gc.collections.gen0": lambda: COUNTS.gc_collections[0],
    "host.gc.collections.gen1": lambda: COUNTS.gc_collections[1],
    "host.gc.collections.gen2": lambda: COUNTS.gc_collections[2],
    "host.jit.compiles": lambda: COUNTS.jit_compiles,
    "host.jit.compile_s": lambda: COUNTS.jit_compile_s,
}


def snapshot() -> Dict[str, float]:
    """Every host signal by its registry name, as it stands now."""
    return {name: read() for name, read in _READERS.items()}


def register(metrics: MetricsRegistry) -> None:
    """:func:`install`, then expose the host signals in ``metrics``."""
    install()
    for name, read in _READERS.items():
        metrics.register_callback(name, read)
