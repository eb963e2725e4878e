"""From the profiler's trace to device busy time, kernel time and idle gaps.

Two steps, so that the second can be checked on a small recorded file:

1. ``reduce_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
   and keeps the device operations (name, XLA module, start, duration)
   of each chip and the harness's own host spans (``bench.*``).
2. ``summarize`` turns that record into busy seconds (the union of the
   operations' intervals inside the traced window, averaged over the
   chips), time per operation, and the idle gaps, each
   labelled with the innermost harness span that was open on the host at
   the gap's middle.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import glob
import os
from typing import Any, Dict, Iterable, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
OUTSIDE = "outside any bench span"


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace everything inside the block into ``log_dir``."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _device_index(plane_name: str) -> int:
    """``/device:TPU:3`` -> 3; -1 for planes that are not a TPU core."""
    head = "/device:TPU:"
    if not plane_name.startswith(head):
        return -1
    rest = plane_name[len(head):]
    return int(rest) if rest.isdigit() else -1


def reduce_xplane(path: str, chips: int, platform: str = "tpu"
                  ) -> Dict[str, Any]:
    """The record ``summarize`` reads: device ops of the first ``chips``
    TPU planes and every ``bench.*`` host span, times in ns.  On the CPU
    backend (rehearsals only) the XLA ops that the host threads ran stand
    in for device 0."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[List[Any]]] = {}
    host: List[List[Any]] = []
    for plane in data.planes:
        idx = _device_index(plane.name)
        if 0 <= idx < chips:
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in lines["XLA Modules"].events) if "XLA Modules" in lines else []
            starts = [m[0] for m in modules]
            ops = []
            op_line = lines.get("XLA Ops")
            for e in (op_line.events if op_line is not None else ()):
                k = bisect.bisect_right(starts, e.start_ns) - 1
                module = (modules[k][2] if k >= 0
                          and e.start_ns < modules[k][1] else "")
                ops.append([e.name, module, float(e.start_ns),
                            float(e.duration_ns)])
            devices[str(idx)] = ops
            continue
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
                    elif platform == "cpu":
                        stats = dict(e.stats)
                        if "hlo_module" in stats:
                            devices.setdefault("0", []).append(
                                [e.name, str(stats["hlo_module"]),
                                 float(e.start_ns), float(e.duration_ns)])
    return {"devices": devices, "host": host}


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # averaged over the chips
    op_s: Dict[str, float]  # "module/op" -> seconds, summed over chips
    idle_by_span: Dict[str, float]  # host span -> idle seconds (chip mean)
    chips: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds_matching(self, needles: Sequence[str]) -> float:
        """Device seconds of ops whose module or name holds a needle,
        per chip."""
        total = sum(s for name, s in self.op_s.items()
                    if any(n in name for n in needles))
        return total / self.chips

    def breakdown(self, top: int = 10) -> Dict[str, List[List[Any]]]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v / self.chips] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def summarize(record: Dict[str, Any], chips: int) -> TraceSummary:
    """Reduce ``reduce_xplane``'s record over the ``bench.window`` span."""
    windows = [h for h in record["host"] if h[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    w_lo = windows[0][1]
    w_hi = w_lo + windows[0][2]
    spans = sorted((s, s + d, name) for name, s, d in record["host"]
                   if name != WINDOW_SPAN)
    op_s: Dict[str, float] = collections.defaultdict(float)
    idle_by_span: Dict[str, float] = collections.defaultdict(float)
    busy_total = 0.0
    n_dev = 0
    for idx in range(chips):
        ops = record["devices"].get(str(idx))
        if ops is None:
            continue
        n_dev += 1
        clipped = []
        for name, module, start, dur in ops:
            lo, hi = max(start, w_lo), min(start + dur, w_hi)
            if hi <= lo:
                continue
            clipped.append((lo, hi))
            op_s[f"{module}/{name}" if module else name] += (hi - lo) * 1e-9
        busy = _union(clipped)
        busy_total += sum(hi - lo for lo, hi in busy)
        cursor = w_lo
        for lo, hi in busy + [(w_hi, w_hi)]:
            if lo > cursor:
                idle_by_span[_label(spans, (cursor + lo) / 2)] += (
                    (lo - cursor) * 1e-9)
            cursor = max(cursor, hi)
    if n_dev == 0:
        raise ValueError("the trace holds no device plane")
    return TraceSummary(
        window_s=(w_hi - w_lo) * 1e-9, busy_s=busy_total / n_dev * 1e-9,
        op_s=dict(op_s),
        idle_by_span={k: v / n_dev for k, v in idle_by_span.items()},
        chips=n_dev)


def _label(spans: List[Tuple[float, float, str]], t: float,
           lookback: int = 64) -> str:
    """The innermost host span open at ``t``: harness spans nest, so it
    is the latest-starting one that has not closed.  Looks back at most
    ``lookback`` spans."""
    k = bisect.bisect_right(spans, (t, float("inf"), "")) - 1
    for lo, hi, name in spans[max(k - lookback, -1) + 1:k + 1][::-1]:
        if hi >= t:
            return name
    return OUTSIDE
