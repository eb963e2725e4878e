"""``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

Runs one cell on the chips of this machine and prints, as the last line
of stdout, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device`` and, traced, ``breakdown``; then ``checks``,
each compared number beside its limit.  Anywhere but on a TPU with the
chips the cell asks for it prints no result and exits 1.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import os
import sys
import tempfile
from pathlib import Path
from typing import Any, Dict, List, Optional

from .spec import load_cell, load_driver, load_reader


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv: Optional[List[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dump", default=None,
                    help="also write the reduced trace record (JSON) here")
    return ap.parse_args(argv)


def per_layer(cell, window) -> Dict[str, Dict[str, Any]]:
    out = {}
    for metric in cell.per_layer:
        value = load_reader(metric["name"], cell.bench_dir)(window)
        if value is not None:
            if not math.isfinite(value):
                raise ValueError(f"{metric['name']} read {value}")
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def run_cell(cell, seed: int, seconds: float, traced: bool,
             setup_clock, trace_dump: Optional[str] = None,
             device: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Drive ``cell`` once; returns the result line (without printing).

    ``device`` is ``require_tpu``'s block; tests pass a CPU description
    to rehearse a driver."""
    from . import common, flops
    from . import trace as tracemod

    comp = common.CompileLog()
    driver = load_driver(cell.driver)
    device = dict(device or common.describe_devices(cell.chips))
    state: Dict[str, Any] = {}
    tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")

    @contextlib.contextmanager
    def window_ctx():
        mark = comp.mark()
        ctx = (tracemod.capture(tmp.name) if traced
               else contextlib.nullcontext())
        with ctx:
            with common.span("window", traced):
                yield
        state["compiles"] = comp.since(mark)

    outcome = driver.run(cell, seed, seconds, traced, window_ctx,
                         setup_clock, log,
                         lambda: common.peak_memory_bytes(cell.chips))
    comp.close()
    comp_w = state.get("compiles", {"compiles": -1, "cache_hits": -1,
                                     "compiled": {}})
    log(f"compiles in the window: {comp_w['compiles']} (expected 0), "
        f"{comp_w['cache_hits']} of them loaded from the persistent cache; "
        f"{comp_w['compiled']}")
    device["memory_peak_bytes"] = int(outcome.memory_peak_bytes)
    line: Dict[str, Any] = {"correct": bool(outcome.correct),
                            "attempted": int(outcome.attempted),
                            "failed": int(outcome.failed)}
    if traced:
        record = tracemod.reduce_xplane(tracemod.find_xplane(tmp.name),
                                        cell.chips, device["platform"])
        if trace_dump:
            Path(trace_dump).parent.mkdir(parents=True, exist_ok=True)
            with open(trace_dump, "w") as f:
                json.dump(record, f)
        summary = tracemod.summarize(record, cell.chips)
        del record
        window = outcome.window
        window.trace = summary
        window.peaks = (flops.peaks_for(device["kind"], cell.bench_dir)
                        if device["platform"] == "tpu" else {})
        line["metrics"] = per_layer(cell, window)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        line["breakdown"] = summary.breakdown()
    else:
        line["metrics"] = {
            m["name"]: {"value": float(outcome.end_to_end[m["name"]]),
                        "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in outcome.end_to_end}
    tmp.cleanup()
    line["device"] = device
    line["checks"] = outcome.checks
    del outcome
    gc.collect()
    return line


def main(argv: Optional[List[str]] = None, setup_clock=None) -> int:
    args = parse(argv)
    try:
        cell = load_cell(args.workload)
    except (KeyError, FileNotFoundError, ValueError) as e:
        log(f"no such cell: {e}")
        return 2
    os.environ["REPRO_DEVICE_TIER"] = "1"
    from . import common

    try:
        device = common.require_tpu(cell.chips)
    except common.NoAccelerator as e:
        log(str(e))
        return 1
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # every program, however small or quick to build, goes into the
    # cache, so that a second run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"device {device['kind']} ({device['platform']}) x "
        f"{len(jax.devices())} visible, cell asks {cell.chips}; jax "
        f"{jax.__version__}; compile cache {cache_dir}")
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                    setup_clock or common.Stopwatch(), args.trace_dump,
                    device)
    checks = line.pop("checks")
    common.emit(line, checks)
    return 0
