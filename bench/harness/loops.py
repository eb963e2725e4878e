"""The open loop over ``Cluster.call_dag_async`` + ``Cluster.step()``.

A loop owns one row per call: when it was due (drawn before the window),
when it ended and what it returned.  Each engine turn is one ``step()``
and one ``tick()`` (cache flushes, gossip, pushes); a call has ended once
its run has.  Latency is
measured from when a call was due, so a stall delays every later call.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List

import numpy as np

from .common import Stopwatch, span

DRAIN_S = 60.0  # how long past the window's close a call may still end


class Loop:
    def __init__(self, cluster, due: np.ndarray,
                 submit: Callable[[int], Any], traced: bool):
        self.cluster = cluster
        self.submit_fn = submit
        self.traced = traced
        self.due = due
        n = len(due)
        self.done = np.full(n, np.nan)
        self.value: List[Any] = [None] * n
        self.submitted = np.zeros(n, bool)
        self.step_s: List[float] = []
        self.turn_s: List[float] = []  # step() and tick() together
        self.inflight: Dict[int, Any] = {}
        self.late_s = 0.0  # how far past its due time a call was sent, at most

    def __len__(self) -> int:
        return len(self.done)

    @property
    def ok(self) -> np.ndarray:
        return ~np.isnan(self.done)

    def submit(self, i: int, now: float) -> None:
        self.inflight[i] = self.submit_fn(i)
        self.late_s = max(self.late_s, now - self.due[i])
        self.submitted[i] = True

    def turn(self, clock: Stopwatch) -> List[int]:
        """One engine turn and one tick; returns the calls that ended."""
        t0 = time.perf_counter()
        with span("step", self.traced):
            self.cluster.step()
        self.step_s.append(time.perf_counter() - t0)
        with span("tick", self.traced):
            self.cluster.tick()
        self.turn_s.append(time.perf_counter() - t0)
        now = clock()
        ended = [i for i, f in self.inflight.items() if f.run.finished]
        for i in ended:
            run = self.inflight.pop(i).run
            if run.state == "done":
                self.done[i] = now
                self.value[i] = run.value
        return ended

    def latencies_ms(self) -> List[float]:
        ok = self.ok
        return list((self.done[ok] - self.due[ok]) * 1e3)


def run_open(loop: Loop, seconds: float) -> None:
    """Send each call when it is due, whatever is still in flight; then
    drain."""
    clock = Stopwatch()
    due = loop.due
    n = len(loop)
    i = 0
    while True:
        now = clock()
        if i < n and due[i] <= now:
            with span("submit", loop.traced):
                while i < n and due[i] <= now:
                    loop.submit(i, now)
                    i += 1
        if loop.inflight:
            loop.turn(clock)
        elif i < n:
            with span("wait", loop.traced):
                time.sleep(max(0.0, due[i] - clock()))
        else:
            return
        if clock() > seconds + DRAIN_S:
            return
