"""What a per-layer reader sees of one measured window.

A reader (``bench/layers/<metric>.py``) is ``read(window) -> float | None``;
``None`` means the window held nothing for it to read, and the metric is
then left out of the result line.  Readers of a share of a roofline or of
a peak never turn "nothing" into 0.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

from .trace import TraceSummary


@dataclasses.dataclass
class Window:
    seconds: float  # host-clock length of the measured window
    chips: int
    peaks: Dict[str, Any]  # this device's row of bench/peaks.json
    counters: Dict[str, float]  # program counters, window deltas
    step_s: List[float]  # host seconds of each engine step() call
    work: Dict[str, float]  # operations, bytes and counts of the traffic
    trace: Optional[TraceSummary] = None
    # host-clock latency of every call due in the window, due to end
    latency_ms: List[float] = dataclasses.field(default_factory=list)

    def counter_sum(self, prefix: str, suffix: str) -> float:
        """Sum of the counters named ``<prefix>...<suffix>``."""
        return float(sum(v for k, v in self.counters.items()
                         if k.startswith(prefix) and k.endswith(suffix)))

    def device_seconds(self, needles) -> Optional[float]:
        """Traced device seconds of ops whose module or name holds one of
        ``needles``; ``None`` without a trace or where none ran."""
        if self.trace is None:
            return None
        s = self.trace.seconds_matching(needles)
        return s if s > 0 else None


def numeric_snapshot(registry) -> Dict[str, float]:
    """The registry's counters (plain numbers only)."""
    out: Dict[str, float] = {}
    for name, value in registry.snapshot().items():
        if isinstance(value, (int, float)) and not isinstance(value, bool):
            out[name] = float(value)
    return out


def delta(after: Dict[str, float], before: Dict[str, float]
          ) -> Dict[str, float]:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}
