#!/usr/bin/env python3
"""Find a cell's knee: the highest open-loop rate it sustains.

Sets the cell up once, then offers each rate, lowest first, for
``--seconds`` and prints one JSON line per rate: calls sent and
completed, completions per second, p50 and p95 latency and the engine
turns taken.  A rate is sustained where the window completes at least
95% of the calls it sent and the 95th percentile stays under a second,
so that no backlog builds.  The sweep stops after two rates in a row
that are not, and prints last the knee, the highest rate sustained.
The benchmark's own runs never call this; its result fixes the rate
written into the cell's traffic file.

  python3 bench/sweep.py --workload ycsb-a.open --seed 3 --seconds 12 \\
      --rates 100 200 300 400
"""

import argparse
import json
import os
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT / "src"))
sys.path.insert(0, str(_ROOT))


def _stats(loop, seconds, rate):
    from bench.harness.common import quantile

    lat = loop.latencies_ms()
    return {"rate": rate, "sent": int(loop.submitted.sum()),
            "completed": int(loop.ok.sum()),
            "completed_per_s": float((loop.done <= seconds).sum() / seconds),
            "p50_ms": quantile(lat, 0.5) if lat else None,
            "p95_ms": quantile(lat, 0.95) if lat else None,
            "turns": len(loop.step_s)}


def sustained(stats, seconds) -> bool:
    return (stats["completed_per_s"] * seconds >= 0.95 * stats["sent"]
            and stats["p95_ms"] is not None and stats["p95_ms"] < 1000.0)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=12)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    os.environ["REPRO_DEVICE_TIER"] = "1"
    from bench.harness import common, loops, store
    from bench.harness.spec import load_cell

    cell = load_cell(args.workload)
    common.require_tpu(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    mix = dict(cell.traffic)
    cfg = cell.config
    n, floats = int(cfg["records"]), int(cfg["record_floats"])
    cluster = store.build_cluster(cfg, args.seed)
    store.load(cluster, store.loaded_records(args.seed, n, floats),
               cfg["load"])
    store.prefill_caches(cluster, args.seed, mix, n,
                         int(cfg["cache_prefill_keys"]))
    store.warm_wave_shapes(cluster, args.seed, mix, n, floats,
                           int(cell.params["shape_waves"]),
                           int(cell.params.get("backlog_waves", 0)))

    def make(rate, stream):
        mix["arrivals"] = {"process": "poisson", "rate_per_s": rate}
        return store.make_loop(cluster, args.seed, stream, mix, args.seconds,
                               n, floats, False)[1]

    loops.run_open(make(args.rates[0], "sweep-warmup"), args.seconds)
    knee, misses = None, 0
    for rate in sorted(args.rates):
        loop = make(rate, f"sweep-{rate}")
        loops.run_open(loop, args.seconds)
        stats = _stats(loop, args.seconds, rate)
        print(json.dumps(stats), flush=True)
        if sustained(stats, args.seconds):
            knee, misses = rate, 0
        else:
            misses += 1
            if misses == 2:
                break
    print(json.dumps({"knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
