"""What every driver shares: the device, compile accounting, host spans,
latency statistics and the result line."""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import sys
import time
from typing import Any, Dict, List

import jax


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int) -> Dict[str, Any]:
    """The device block of the result line; raises ``NoAccelerator``
    anywhere but on a TPU with at least ``chips`` devices."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoAccelerator(f"needs a TPU, JAX found {dev.platform}")
    if len(devices) < chips:
        raise NoAccelerator(f"cell needs {chips} chips, JAX found "
                            f"{len(devices)}")
    return describe_devices(chips)


def describe_devices(chips: int) -> Dict[str, Any]:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def peak_memory_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices,
    as the backend reports it (0 where it reports nothing)."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.devices()[:chips]]
    return max(peaks) if peaks else 0


class CompileLog:
    """Counts backend compiles and persistent-cache hits through JAX's
    monitoring hooks, with the compiled function's name."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.names: collections.Counter = collections.Counter()
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.names[kw.get("fun_name", "?")] += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return (self.seconds, sum(self.names.values()), self.cache_hits,
                collections.Counter(self.names))

    def since(self, mark) -> Dict[str, Any]:
        s0, n0, h0, names0 = mark
        return {"compile_s": self.seconds - s0,
                "compiles": sum(self.names.values()) - n0,
                "cache_hits": self.cache_hits - h0,
                "compiled": dict(self.names - names0)}


@contextlib.contextmanager
def span(name: str, on: bool):
    """A host span in the profiler's trace (``bench.<name>``) when
    tracing, nothing otherwise."""
    if not on:
        yield
        return
    with jax.profiler.TraceAnnotation(f"bench.{name}"):
        yield


def quantile(values: List[float], q: float) -> float:
    """The ``q`` quantile by linear interpolation between order
    statistics (numpy's default)."""
    if not values:
        raise ValueError("no values")
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to the command line."""

    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: List[List[Any]]  # [name, value, limit]: value <= limit
    window: Any = None  # layers.Window, for the per-layer readers
    memory_peak_bytes: int = 0


def check_lines(checks: List[List[Any]]) -> List[str]:
    return [f"check {name} = {value!r} (limit {limit!r}): "
            f"{'ok' if value <= limit else 'FAIL'}"
            for name, value, limit in checks]


def emit(line: Dict[str, Any], checks: List[List[Any]]) -> None:
    """The result: the compared numbers as the last lines of stderr, and
    the JSON object as the last line of stdout with them under
    ``checks``, last."""
    line = dict(line)
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, value, limit in checks}
    sys.stdout.flush()
    for text in check_lines(checks):
        print(text, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


class Stopwatch:
    """Monotonic seconds since construction."""

    def __init__(self) -> None:
        self.t0 = time.perf_counter()

    def __call__(self) -> float:
        return time.perf_counter() - self.t0

