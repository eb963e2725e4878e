#!/usr/bin/env python3
"""Split a traced run's device idle time by the program's own spans.

Runs one cell traced, as ``bench/run.py --trace 1`` does, and prints its
result line with one more key, ``idle_split``: the idle gaps of the
device, each put down to the innermost host span open at its middle
among the harness's ``bench.*`` spans and the program's ``cb.*`` ones
(``Tracer.phase`` at the layer boundaries, ``cb.host.gc.gen<N>`` for
the collector's pauses), seconds per span, mean over the chips.  The
result line's own ``breakdown`` names the ``bench.*`` spans alone, as
the benchmark reports it.  Idle time that no program span covers keeps
its ``bench.*`` label: what is still unexplained.

  python3 bench/idle_split.py --workload ycsb-a.open --seed 7 --seconds 40
"""

import argparse
import contextlib
import json
import os
import sys
import tempfile
from pathlib import Path
from typing import Any, Dict, List, Optional

_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT / "src"))
sys.path.insert(0, str(_ROOT))

PROGRAM_PREFIX = "cb."


def program_spans(path: str) -> List[List[Any]]:
    """Every ``cb.*`` host event of the ``.xplane.pb`` at ``path``:
    [name, start ns, duration ns], as ``reduce_xplane`` keeps
    ``bench.*`` ones."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    out.append([e.name, float(e.start_ns),
                                float(e.duration_ns)])
    return out


def idle_split(record: Dict[str, Any], chips: int) -> Dict[str, float]:
    """Idle seconds of ``record`` (``reduce_xplane``'s, its ``host``
    holding ``cb.*`` spans too) per innermost open host span, mean over
    the chips.  The innermost span at an instant is the latest-starting
    one not yet closed, however many shorter ones closed before it."""
    from bench.harness.trace import OUTSIDE, WINDOW_SPAN, _union

    w = [h for h in record["host"] if h[0] == WINDOW_SPAN]
    if not w:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    lo, hi = w[0][1], w[0][1] + w[0][2]
    spans = sorted((s, s + d, name) for name, s, d in record["host"]
                   if name != WINDOW_SPAN)
    split: Dict[str, float] = {}
    n_dev = 0
    for idx in range(chips):
        ops = record["devices"].get(str(idx))
        if ops is None:
            continue
        n_dev += 1
        clipped = [(max(s, lo), min(s + d, hi)) for _n, _m, s, d in ops]
        gaps, cursor = [], lo
        for a, b in _union(c for c in clipped if c[1] > c[0]) + [(hi, hi)]:
            if a > cursor:
                gaps.append((cursor, a))
            cursor = max(cursor, b)
        stack: List[tuple] = []  # open spans, latest start on top
        k = 0
        for a, b in gaps:
            t = (a + b) / 2
            while k < len(spans) and spans[k][0] <= t:
                stack.append(spans[k])
                k += 1
            while stack and stack[-1][1] < t:
                stack.pop()
            name = stack[-1][2] if stack else OUTSIDE
            split[name] = split.get(name, 0.0) + (b - a) * 1e-9
    if n_dev == 0:
        raise ValueError("the trace holds no device plane")
    return {k: v / n_dev for k, v in sorted(split.items(),
                                            key=lambda kv: -kv[1])}


def run_split(cell, seed: int, seconds: float, setup_clock,
              device: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Drive ``cell`` once, traced; the result line with ``idle_split``.
    ``device`` as in ``cli.run_cell`` (tests pass a CPU description)."""
    from bench.harness import cli, common, flops
    from bench.harness import trace as tracemod
    from bench.harness.spec import load_driver

    device = dict(device or common.describe_devices(cell.chips))
    with tempfile.TemporaryDirectory(prefix="bench-split-") as tmp:
        @contextlib.contextmanager
        def window_ctx():
            with tracemod.capture(tmp):
                with common.span("window", True):
                    yield

        outcome = load_driver(cell.driver).run(
            cell, seed, seconds, True, window_ctx, setup_clock, cli.log,
            lambda: common.peak_memory_bytes(cell.chips))
        path = tracemod.find_xplane(tmp)
        record = tracemod.reduce_xplane(path, cell.chips, device["platform"])
        spans = program_spans(path)
    summary = tracemod.summarize(record, cell.chips)
    record["host"] += spans
    split = idle_split(record, cell.chips)
    window = outcome.window
    window.trace = summary
    window.peaks = (flops.peaks_for(device["kind"], cell.bench_dir)
                    if device["platform"] == "tpu" else {})
    device.update(memory_peak_bytes=int(outcome.memory_peak_bytes),
                  busy_s=summary.busy_s, window_s=summary.window_s)
    return {"correct": bool(outcome.correct),
            "attempted": int(outcome.attempted),
            "failed": int(outcome.failed),
            "metrics": cli.per_layer(cell, window),
            "device": device,
            "breakdown": summary.breakdown(),
            "idle_split": [[k, v] for k, v in split.items()],
            "program_spans": len(spans),
            "checks": outcome.checks}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    os.environ["REPRO_DEVICE_TIER"] = "1"
    from bench.harness import common
    from bench.harness.spec import load_cell

    cell = load_cell(args.workload)
    device = common.require_tpu(cell.chips)
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    line = run_split(cell, args.seed, args.seconds, common.Stopwatch(),
                     device)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
